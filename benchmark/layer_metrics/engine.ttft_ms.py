"""Median time to first token by the engine's own clock: its
``serve_ttft_ms`` histogram (submit to the end of the prefill's host
sync, ``serving/scheduler.py:_finish_done``), which exists only with
the engine's telemetry enabled — so in the traced run only.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""


def reduce(trace, facts):
    return facts.get("serve_ttft_ms_p50")
