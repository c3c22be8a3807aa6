"""Median milliseconds a request waited between ``submit`` and its
admission to the running batch, by the engine's own clock: its
``serve_queue_wait_ms`` histogram (the ``queue`` episodes of each
retired request), telemetry enabled, traced run only.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""


def reduce(trace, facts):
    return facts.get("serve_queue_wait_ms_p50")
