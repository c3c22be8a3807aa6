"""Share of the decode programs, in percent, that the engine dispatched
while the program before was still unread (its ids stay on the device
and are the next step's ``tokens``; ``scheduler.py:_decode_once``): the
``hetu.serve.decode.ahead`` spans wholly inside the traced window over
the ``jit_hetu_paged_decode`` programs wholly inside it. What is left
are the steps built from host values: the first after an admission or a
prefill, one after a preemption, every step that holds a sampled row.
The two counts are taken on two clocks' events, so a span at the
window's edge can be counted without its program (a few tenths of a
percent at some hundreds of steps).

``None`` where the profile holds no such span (a program from before
it, or a window without one step dispatched ahead).

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import program_spans


def reduce(trace, facts):
    name = read_json(BENCH_DIR + "/layer_metrics/decode_ahead_names.json")[
        "decode_ahead_span"]
    ahead = program_spans.spans(trace, name=name)
    decodes = program_spans.modules(
        trace, program_spans.names()["decode_module"])
    if not ahead or not decodes:
        return None
    return 100.0 * len(ahead) / len(decodes)
