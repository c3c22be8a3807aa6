"""Host milliseconds per step inside the un-synced ``Executor.run``
call: feed ingest, shape key, dispatch of the jitted step. The sum over
the traced window's steps (hundreds of milliseconds, so the host
clock's half millisecond does not matter) over their number.

layer: step executor (hetu_tpu/executor.py) — source: host_clock —
moves: train_tokens_per_s_per_chip.
"""


def reduce(trace, facts):
    samples = facts.get("host_ms_per_step")
    if not samples:
        return None
    return sum(samples) / len(samples)
