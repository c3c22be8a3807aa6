"""Device milliseconds of one prefill program (``gpt_paged_prefill``:
one group of admitted prompts at one prompt bucket): the median
duration of the ``jit_hetu_paged_prefill`` programs on the device's
``XLA Modules`` line inside the traced window.

layer: model step (hetu_tpu/models/gpt.py paged forwards) — source:
device_trace — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import program_spans


def reduce(trace, facts):
    prefills = program_spans.modules(
        trace, program_spans.names()["prefill_module"])
    if not prefills:
        return None
    return stats.median(program_spans.milliseconds(prefills))
