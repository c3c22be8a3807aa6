"""How unevenly a training session's routed rows fell on the held
experts: the busiest (layer, held expert)'s rows over the mean of all
of them (1 is even), from the counter the compiled step accumulates on
the device (``Executor.moe_counters()``: every training step since the
session began). The loss has no load-balancing term, so nothing pulls
this towards 1 but the data.

layer: model step (hetu_tpu/models/sparse_decoder.py) — source:
program_counter — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import gqa_train_events as events


def reduce(trace, facts):
    counted = events.counters(facts)
    if counted is None:
        return None
    rows = [n for layer in counted["layers"]
            for n in layer["moe_rows_by_expert"]]
    if not rows or not sum(rows):
        return None
    return max(rows) / (sum(rows) / len(rows))
