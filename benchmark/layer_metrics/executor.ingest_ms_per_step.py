"""Host milliseconds per training step inside ``hetu.executor.ingest``
(``SubExecutor.run``'s feed loop and dataloader batches: host arrays to
device arrays): the sum over the traced window over its steps.

layer: step executor (hetu_tpu/executor.py) — source: program_span —
moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import program_spans


def reduce(trace, facts):
    ingests = program_spans.spans(
        trace, name=program_spans.names()["ingest_span"])
    if not ingests or not facts.get("steps"):
        return None
    return sum(program_spans.milliseconds(ingests)) / facts["steps"]
