"""How unevenly the window's routed rows fell on the held experts: the
busiest held expert's rows over the mean of all held experts' (1 is
even), from the counters the programs return
(``moe_rows_by_expert``, prefill and decode programs together, over
the window).

layer: model step (hetu_tpu/models/latent_moe.py) — source:
program_counter — moves: serve_request_p95_ms.
"""


def reduce(trace, facts):
    counted = facts.get("model_counters") or {}
    rows = [sum(pair) for pair in zip(
        counted.get("prefill_moe_rows_by_expert", ()),
        counted.get("decode_moe_rows_by_expert", ()))]
    if not rows or not sum(rows):
        return None
    return max(rows) / (sum(rows) / len(rows))
