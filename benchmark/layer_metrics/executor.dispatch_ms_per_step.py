"""Host milliseconds per training step inside ``hetu.device_dispatch``
(``SubExecutor.run``: the argument tuple and the call of the compiled
step, which returns when the step is enqueued): the sum over the traced
window over its steps.

layer: step executor (hetu_tpu/executor.py) — source: program_span —
moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import program_spans


def reduce(trace, facts):
    dispatches = program_spans.spans(
        trace, name=program_spans.names()["dispatch_span"])
    if not dispatches or not facts.get("steps"):
        return None
    return sum(program_spans.milliseconds(dispatches)) / facts["steps"]
