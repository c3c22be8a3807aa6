"""Median time per output token after the first by the engine's own
clock: its ``serve_tpot_ms`` histogram ((retire - first token) /
(tokens - 1) per request), telemetry enabled, traced run only.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""


def reduce(trace, facts):
    return facts.get("serve_tpot_ms_p50")
