"""Peak share of the KV pool's blocks held by live sequences, in
percent: ``kv_blocks_used / kv_blocks`` polled from ``engine.stats()``
every 100 ms of the traced run.

layer: KV cache (hetu_tpu/serving/kvcache.py) — source:
program_counter — moves: serve_out_tokens_per_s.
"""


def reduce(trace, facts):
    peak, blocks = facts.get("kv_blocks_used_peak"), facts.get("kv_blocks")
    if peak is None or not blocks:
        return None
    return 100.0 * peak / blocks
