"""Measuring tools for the kernels.

* ``autotune.py`` — the timer and the platform tag the tools share
  (what is left of the trace-time tile sweep: a kernel's tiles are a
  static rule, ``ops/pallas_attention.py:_block_sizes``).
* ``probe.py`` — segment-timing harness: per-kernel milliseconds at
  the rule's tiles or at given ones, and full-step fwd/bwd/remainder
  attribution (``python -m hetu_tpu.tune.probe``).
"""
from .autotune import get_table, platform_tag, timeit
from .probe import attribute_step, probe_attention

__all__ = ["get_table", "platform_tag", "timeit", "attribute_step",
           "probe_attention"]
