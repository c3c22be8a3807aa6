"""Peak share of the recurrent-state slots held by running sequences,
in percent: ``state_slots_used / state_slots`` as the engine's record
of each program of the traced window has them (``engine.program_log``:
the slots held when the program's result had been read). A sequence
holds ONE slot whatever its length, so this is the batch's width
against ``max_batch_size`` — the other half of this cache's pressure,
beside ``kvcache.used_pct`` for the rows of the attention layers.
``None`` for an engine whose records carry no slots (a model of rows
alone; the parent).

layer: KV cache (hetu_tpu/serving/kvcache.py) — source:
program_counter — moves: serve_request_p95_ms.
"""
from benchmark.trace import ssm_events


def reduce(trace, facts):
    found = ssm_events.slots_used_peak(facts)
    if found is None:
        return None
    used, slots = found
    return 100.0 * used / slots
