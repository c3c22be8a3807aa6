"""Milliseconds per decode step in which the device is not running the
decode program: from the end of one ``jit_hetu_paged_decode`` program
on the device's ``XLA Modules`` line to the start of the next, where no
``hetu.serve.wait`` and no ``hetu.serve.prefill.*`` span lies between
the two (so the engine went straight from one decode to the next);
the median. It holds the host sync of the logits, the token choice,
retiring, admission, the slot grid, the puts and the dispatch.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import program_spans, xplane


def reduce(trace, facts):
    names = program_spans.names()
    waits = program_spans.spans(trace, name=names["wait_span"])
    if waits is None:
        return None
    other = xplane.union(waits + program_spans.spans(
        trace, prefix=names["prefill_span_prefix"]))
    decodes = program_spans.modules(trace, names["decode_module"])
    gaps = [(a[1], b[0]) for a, b in zip(decodes, decodes[1:])]
    straight = [g for g in gaps
                if xplane.total(xplane.clip(other, *g)) == 0]
    if not straight:
        return None
    return stats.median(program_spans.milliseconds(straight))
