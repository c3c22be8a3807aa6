"""Every flash call the benchmark's six cells trace, with the tiles read
for it on the chip (PERF.md section 6, PR 46) — shared by
``test_flash_tiles.py`` (the rule answers them) and
``test_chip_compile.py`` (the chip's compiler takes them).

GPT-2 trains on packed ``[B, S, 3H]`` rows, BERT on three projections'
``[B, S, H]`` rows (both token-major); serving's prefill is head-major,
float32 in the GPT engine, bfloat16 in the latent-attention ones, a
call a prompt bucket (powers of two over the traffic's prompt range, a
program as wide as the token cap admits). ``jamba2-3b-serve-chat-r50``
traces no flash call: its two attention layers attend composed
(``ops/attention.py:grouped_prefill_attention``)."""
from typing import NamedTuple


class CellCall(NamedTuple):
    cell: str
    kind: str           # fwd | fwd_lse | bwd
    rows: str           # token-major "packed" / "three", or "" head-major
    batch: int
    heads: int
    seq: int
    head_dim: int
    dtype: str
    causal: bool
    has_mask: bool
    tiles: tuple

    @property
    def token_major(self):
        return bool(self.rows)


def call_id(call):
    return f"{call.cell}-{call.kind}-S{call.seq}-D{call.head_dim}"


def _prefill(cell, heads, head_dim, dtype, token_cap, max_batch, tiles):
    """A call a prompt bucket, at the widest batch bucket (a power of
    two) whose tokens stay under the engine's cap."""
    return [CellCall(cell, "fwd", "",
                     min(max_batch, 1 << (token_cap // s).bit_length() - 1),
                     heads, s, head_dim, dtype, True, False, t)
            for s, t in tiles.items()]


CELL_CALLS = [
    # the train window's two kernels, and the validate pass's forward
    CellCall("gpt2s-train-s1024", "fwd_lse", "packed", 16, 12, 1024, 64,
             "bfloat16", True, False, (512, 256)),
    CellCall("gpt2s-train-s1024", "bwd", "packed", 16, 12, 1024, 64,
             "bfloat16", True, False, (256, 256)),
    CellCall("gpt2s-train-s1024", "fwd", "packed", 16, 12, 1024, 64,
             "bfloat16", True, False, (512, 256)),
    CellCall("bert-base-train-s128", "fwd_lse", "three", 256, 12, 128, 64,
             "bfloat16", False, True, (128, 128)),
    CellCall("bert-base-train-s128", "bwd", "three", 256, 12, 128, 64,
             "bfloat16", False, True, (128, 128)),
    CellCall("bert-base-train-s128", "fwd", "three", 256, 12, 128, 64,
             "bfloat16", False, True, (128, 128)),
    *_prefill("gpt2s-serve-chat-r50", 12, 64, "float32", 32 * 512, 32,
              {64: (64, 64), 128: (128, 128), 256: (256, 256),
               512: (512, 256)}),
    *_prefill("sarvam105b-serve-docqa-r50", 64, 192, "bfloat16", 10731, 16,
              {1024: (256, 256), 2048: (256, 256), 4096: (256, 256),
               8192: (256, 256)}),
    *_prefill("xing29b-serve-reason-r50", 32, 192, "bfloat16", 10731, 32,
              {128: (128, 128), 256: (256, 256), 512: (256, 256),
               1024: (256, 256), 2048: (256, 256)}),
]
