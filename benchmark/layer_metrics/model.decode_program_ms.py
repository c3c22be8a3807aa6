"""Device milliseconds of one decode program (all running sequences,
one token each): the median duration of the ``jit_hetu_paged_decode``
programs on the device's ``XLA Modules`` line inside the traced window,
found by NAME as ``model.prefill_device_ms`` finds the prefill.

``model.decode_device_ms`` takes the longest program wholly inside a
``bench.engine.decode`` host span; since the engine keeps a program in
flight across two calls of ``_decode_once`` only the steps it reads at
once lie inside one, so this is the decode program's length from now
on (PERF.md, Open questions).

layer: model step (the serving models' paged decode forwards) — source:
device_trace — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import program_spans


def reduce(trace, facts):
    decodes = program_spans.modules(
        trace, program_spans.names()["decode_module"])
    if not decodes:
        return None
    return stats.median(program_spans.milliseconds(decodes))
