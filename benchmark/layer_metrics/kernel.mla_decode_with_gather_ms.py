"""Device milliseconds of the absorbed latent attention WITH the gather
that feeds it, inside ONE decode program: the ``hetu_mla_decode``
events (every layer) and the operations that gather each sequence's
latent rows out of the pool, block by block, into the array the kernel
reads; the median over the decode programs of the traced window.

The gather is the compiler's own operation and carries no name of the
program's: it is known by what it makes, an array of whole cache
blocks (``[blocks gathered, block size, row width]`` in the cache's
dtype) that is not the pool itself (``kv blocks + 1`` of them: the
scatter that writes the step's rows returns that). The shape comes
from the cell's configuration and traffic files and the engine's pool
size, the pattern from ``latent_moe_names.json``. ``None`` where the
trace shows no such operation beside the kernel's events.

layer: kernels (hetu_tpu/ops/pallas_mla.py, hetu_tpu/ops/attention.py
mla_decode_attention) — source: device_trace — moves:
serve_request_p95_ms.
"""
import re

from benchmark.harness import stats
from benchmark.trace import latent_moe_events as events


def gather_pattern(facts):
    """The gathered blocks' result, any count of blocks but the pool's."""
    c = facts["config"]
    row = -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128
    dtype = {"bfloat16": "bf16", "float32": "f32"}[c["serve_dtype"]]
    return events.names()["mla_gather_result"].format(
        not_blocks=facts["kv_blocks"] + 1, dtype=re.escape(dtype),
        block_size=facts["traffic"]["engine"]["block_size"], row=row)


def reduce(trace, facts):
    if not facts.get("kv_blocks") or "config" not in facts:
        return None
    kernel = events.per_program(trace, "decode", "mla_decode_kernel")
    gather = events.per_program(trace, "decode", gather_pattern(facts),
                                literal=True)
    if not kernel or not gather:
        return None
    return stats.median([(a[2] + b[2]) / 1e6
                         for a, b in zip(kernel, gather)])
