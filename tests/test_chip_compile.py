"""The flash kernels of the main path compile for the chip — checked
without one: the TPU's compiler is installed beside JAX and compiles
for a *described* ``v5e:2x2`` topology (on-chip-measurement guide §2.3).
Interpret-mode tests cannot see what this sees: a tile the Mosaic
compiler refuses, or a block set that overflows VMEM.

Shapes are the three the chip paths run at real width — BERT-base
(b64, h12, S128, padding mask), GPT-2 small (b8, h12, S1024, causal)
and the long-sequence cell (b8, h8, S2048, padding mask) — in bf16,
each for the plain forward, the forward with logsumexp and the fused
backward; plus the smallest and the largest (bq, bk) the autotuner may
pick at S=2048. A compile that passes is a compile, not a run.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp

import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from hetu_tpu.ops import pallas_attention as pk  # noqa: E402

# name -> (batch, heads, seq, head_dim, causal, has_mask)
SHAPES = {
    "bert_base": (64, 12, 128, 64, False, True),
    "gpt2_small": (8, 12, 1024, 64, True, False),
    "s2048_mask": (8, 8, 2048, 64, False, True),
}
KINDS = ("fwd", "fwd_lse", "bwd")


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one described v5e chip. Around the module the
    persistent compilation cache is switched off (an entry written for
    a described device cannot be read back without a chip, and the next
    compile would warn) and the matmul precision goes back to the
    default the chip runs with — the harness's "highest" asks Mosaic
    for an fp32 contraction of bf16 operands, which it refuses."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


def _compile(kind, shape, blocks, sharding):
    b, h, s, d, causal, has_mask = shape
    bq, bk = blocks
    x = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=sharding)
    mask = jax.ShapeDtypeStruct((b, 1, 1, s), jnp.float32,
                                sharding=sharding) if has_mask else None
    if kind == "bwd":
        lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32,
                                   sharding=sharding)
        lowered = pk._flash_attention_bwd_jit.lower(
            x, x, x, mask, x, lse, x, 0.125, causal, False, bq, bk)
    else:
        lowered = pk._flash_attention_jit.lower(
            x, x, x, mask, 0.125, causal, False, bq, bk,
            kind == "fwd_lse")
    return lowered.compile().as_text()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", list(SHAPES))
def test_flash_kernel_compiles_at_static_tiles(one_chip, name, kind):
    shape = SHAPES[name]
    text = _compile(kind, shape, pk._block_sizes(shape[2], shape[3]),
                    one_chip)
    # the backward is two kernels (dK/dV, dQ)
    assert text.count("tpu_custom_call") >= (2 if kind == "bwd" else 1)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("edge", ["smallest", "largest"])
def test_autotune_candidate_edges_compile_s2048(one_chip, edge, kind):
    """Every (bq, bk) the autotuner may pick must be one the compiler
    takes; the corners of the candidate grid bound the VMEM demand."""
    shape = SHAPES["s2048_mask"]
    cands = pk._candidates(shape[2])
    block = min(cands) if edge == "smallest" else max(cands)
    assert "tpu_custom_call" in _compile(kind, shape, (block, block),
                                         one_chip)
