"""Kernel autotuning: sweep-once, cache-forever config selection.

* ``autotune.py`` — the generic engine: ``autotune(name, key,
  candidates, measure)`` with an env-controlled persistent JSON cache
  (``HETU_AUTOTUNE``, ``HETU_AUTOTUNE_CACHE``). Kernel-agnostic by
  design; flash-attention block sizes are the first consumer
  (``ops/pallas_attention.py``), scan-block sizes and pipeline tick
  fusing can ride the same cache later.
* ``probe.py`` — segment-timing harness: per-kernel tuned-vs-static
  milliseconds and full-step fwd/bwd/remainder attribution
  (``python -m hetu_tpu.tune.probe``).
"""
from .autotune import (AutotuneSweepError, AutotuneTable, autotune,
                       configure, default_cache_path, get_table,
                       platform_tag, reset, timeit, tuning_mode)
from .probe import attribute_step, probe_attention

__all__ = ["AutotuneSweepError", "AutotuneTable", "autotune", "configure",
           "default_cache_path", "get_table", "platform_tag", "reset",
           "timeit", "tuning_mode", "attribute_step", "probe_attention",
           "chosen_configs"]


def chosen_configs(prefix=None):
    """{key_string: config} of every cached decision in the
    process-global table — what ``bench.py`` stamps into each round's
    artifact so the chosen (bq, bk) per kernel is recorded."""
    return get_table().chosen(prefix=prefix)
