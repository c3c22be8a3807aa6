"""Peak share of the WINDOW layers' blocks held by running sequences,
in percent: ``window_blocks_used / window_blocks`` as the engine's
record of each program of the traced window has them
(``engine.program_log``: the blocks held when the program's result had
been read). A sequence holds at most a ring (``ceil(window /
block_size) + 1`` blocks) there whatever its length, so this is the
batch's width in long sequences against ``max_batch_size`` — beside
``kvcache.used_pct`` for the full layers' pool, which grows with every
token. ``None`` for an engine whose records carry no window blocks (a
model without a window layer; the parent).

layer: KV cache (hetu_tpu/serving/kvcache.py) — source:
program_counter — moves: serve_request_p95_ms.
"""
from benchmark.trace import window_events


def reduce(trace, facts):
    found = window_events.window_blocks_used_peak(facts)
    if found is None:
        return None
    used, blocks = found
    return 100.0 * used / blocks
