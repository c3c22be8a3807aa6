"""The flash kernels of the main path compile for the chip — checked
without one: the TPU's compiler is installed beside JAX and compiles
for a *described* ``v5e:2x2`` topology (on-chip-measurement guide §2.3).
Interpret-mode tests cannot see what this sees: a tile the Mosaic
compiler refuses, or a block set that overflows VMEM.

Shapes are the three the chip paths run at real width — BERT-base
(b64, h12, S128, padding mask), GPT-2 small (b8, h12, S1024, causal)
and the long-sequence cell (b8, h8, S2048, padding mask) — in bf16,
each for the plain forward, the forward with logsumexp and the fused
backward (one kernel since PR 36); plus every flash call the
benchmark's cells trace at the tiles the rule gives it
(``flash_cells.py``), the backward at the
GPT-2 train cell's own shape (b16) over the corners of the tile
grid, and the forward that walks regions (PR 40) at that cell's packed
rows, at BERT's cell (twelve heads a program) and head-major at
S = 8,192, D = 192; and both kernels token-major at BERT's cell, a
batch row's twelve heads a program (PR 45), alone and in BERT-base's
whole train step, which then holds no ``[256, 12, 128, 64]`` array at
all; and both train cells' steps at their FULL vocabularies, where the
sparse cross-entropy reads the logits as the head's matmul wrote them
(PR 48: no whole copy, no gather, five instructions that touch them in
BERT's step and four in GPT-2's); and in neither step a matmul that
reads a float32 weight: every one reads the bfloat16 working copy that
the update of the step before wrote beside its master, and with the
options the executor gives a training step on a TPU each update runs
beside its layer's backward (PR 49). A compile that passes is a compile,
not a run.

The optimizer's sparse row update (``ops/pallas_sparse_update.py``,
PR 51) compiles at the three tables the train cells run it at, and in
GPT-2's step and the sparse decoder's every table is an aliased operand
of that one call: no scatter with a table-shaped result, no whole copy
of a table, no sort of the ids beyond ``dedup``'s own.

LayerNorm's backward kernel (``ops/pallas_norm.py``) compiles at the
two shapes the train cells run it at, ``[16384, 768]`` (GPT-2 small,
batch 16 x S 1024) and ``[32768, 768]`` (BERT-base, 256 x 128), bf16;
and the gradient op compiles for four chips under a ``dp`` mesh, where
GSPMD partitions the step and would refuse the kernel.
"""
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp

import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from hetu_tpu.ops import pallas_attention as pk  # noqa: E402
from hetu_tpu.ops import pallas_norm  # noqa: E402

from flash_cells import CELL_CALLS, call_id  # noqa: E402
from hlo_matmuls import matmul_fusions, result_types  # noqa: E402

# name -> (batch, heads, seq, head_dim, causal, has_mask)
SHAPES = {
    "bert_base": (64, 12, 128, 64, False, True),
    "gpt2_small": (8, 12, 1024, 64, True, False),
    "s2048_mask": (8, 8, 2048, 64, False, True),
}
KINDS = ("fwd", "fwd_lse", "bwd")


@pytest.fixture(scope="module")
def v5e():
    """The described v5e:2x2's four devices. Around the module the
    persistent compilation cache is switched off (an entry written for
    a described device cannot be read back without a chip, and the next
    compile would warn) and the matmul precision goes back to the
    default the chip runs with — the harness's "highest" asks Mosaic
    for an fp32 contraction of bf16 operands, which it refuses."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
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
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    """Sharding on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e[0])


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
    # the backward is ONE kernel (dQ, dK and dV from one pass)
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("blocks", [(128, 128), (128, 1024), (1024, 128),
                                    (1024, 1024), (256, 256), (512, 512)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_one_pass_backward_compiles_at_the_train_cell(one_chip, blocks):
    """The GPT-2 train cell's backward (batch 16, causal) at the four
    corners of the tile grid and the two square tiles the walk
    skips most at: one custom call, under the name the trace's readers
    match, and the row residuals reach it as rows — no float32
    ``[B*H, S, 128]`` lane broadcast is left around it."""
    b, h, s, d = 16, 12, 1024, 64
    text = _compile("bwd", (b, h, s, d, True, False), blocks, one_chip)
    entry = text[text.index("ENTRY"):]
    calls = [ln for ln in entry.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].lstrip().startswith("%_flash_attention_bwd_jit")
    assert f"f32[{b * h},{s},{pk.LANES}]" not in text
    assert f"f32[{b * h},1,{s}]" in calls[0]


@pytest.mark.parametrize("call", CELL_CALLS, ids=call_id)
def test_every_tile_the_rule_returns_compiles(one_chip, call):
    """Every flash call the benchmark's cells trace, at the rule's
    tiles, in the call's own operand form and dtype, a program as wide
    as the cell's: one the chip's compiler takes (a tile it refuses, or
    a block set past VMEM, fails here and not in a cell)."""
    b, h, s, d = call.batch, call.heads, call.seq, call.head_dim
    blocks = pk._block_sizes(s, d, call.kind, call.causal, call.has_mask)
    dtype = jnp.dtype(call.dtype)

    def arr(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    mask = arr(b, 1, 1, s, dt=jnp.float32) if call.has_mask else None
    if call.token_major:
        packed = call.rows == "packed"
        layout = (pk.TokenMajor.packed if packed else pk.TokenMajor)(h, d)
        qkv, ctx = arr(b, s, (3 if packed else 1) * h * d), arr(b, s, h * d)
        lse, form = arr(b, h, 1, s, dt=jnp.float32), (layout,)
    else:
        qkv = ctx = arr(b, h, s, d)
        lse, form = arr(b, h, s, dt=jnp.float32), ()
    scale = 1.0 / float(d) ** 0.5
    if call.kind == "bwd":
        lowered = pk._flash_attention_bwd_jit.lower(
            qkv, qkv, qkv, mask, ctx, lse, ctx, scale, call.causal, False,
            *blocks, *form)
    else:
        lowered = pk._flash_attention_jit.lower(
            qkv, qkv, qkv, mask, scale, call.causal, False, *blocks,
            call.kind == "fwd_lse", *form)
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("shape,dtype", [
    ((16384, 768), jnp.bfloat16), ((16, 1024, 768), jnp.bfloat16),
    ((32768, 768), jnp.bfloat16), ((256, 128, 768), jnp.bfloat16),
    # the block rule at another width and dtype (BERT-large in float32),
    # and rows that are no multiple of the block (the masked tail)
    ((32768, 1024), jnp.float32), ((1000, 768), jnp.bfloat16)], ids=str)
def test_layer_norm_backward_kernel_compiles(one_chip, shape, dtype):
    """One custom call, named for the trace's readers, and no pass over
    the rows left to XLA: what it compiles around the kernel (the
    ``[1, D]`` sums' relayout to ``[D]``) touches kilobytes."""
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    scale = jax.ShapeDtypeStruct(shape[-1:], dtype, sharding=one_chip)
    compiled = pallas_norm.hetu_layer_norm_bwd.lower(
        x, x, scale, eps=1e-12).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count("tpu_custom_call") == 1
    assert f"%{pallas_norm.KERNEL_NAME}" in entry
    assert " fusion(" not in entry
    assert compiled.cost_analysis()["bytes accessed"] < 64 * 1024


def test_layer_norm_backward_compiles_under_a_dp_mesh(v5e, monkeypatch):
    """A step over a ``("dp", 4)`` mesh is a GSPMD program, and GSPMD
    cannot partition a Mosaic kernel (the first assertion pins that, at
    chip_smoke's dp shapes; interpret mode on the CPU cannot see it). So
    under a mesh the gradient op keeps the composed form, and compiles:
    rows sharded, the column sums all-reduced."""
    import numpy as np
    import types
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import hetu_tpu as ht
    from hetu_tpu.ops import attention

    mesh = Mesh(np.asarray(v5e), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    x = jax.ShapeDtypeStruct((64, 128, 768), jnp.float32, sharding=rows)
    scale = jax.ShapeDtypeStruct((768,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    with pytest.raises(NotImplementedError, match="Mosaic kernels"):
        pallas_norm.hetu_layer_norm_bwd.lower(
            x, x, scale, eps=1e-12).compile()

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    nodes = [ht.Variable(n, trainable=False) for n in ("dy", "x", "s")]
    op = ht.layer_normalization_gradient_op(*nodes, None, 1e-12)
    ectx = types.SimpleNamespace(config=types.SimpleNamespace(mesh=mesh))
    text = jax.jit(lambda dy, x, s: op.compute([dy, x, s], ectx)).lower(
        x, x, scale).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text


@pytest.mark.parametrize("shape", [(16, 1024, 768), (32768, 768),
                                   (256, 12, 128, 128), (32, 128)], ids=str)
def test_dropout_mask_kernel_compiles(one_chip, shape):
    """The generator's seed takes two words and no more, and the
    compare's result packs to bytes: what only Mosaic can refuse. One
    custom call, named for the trace's reader, nothing left to XLA."""
    from hetu_tpu.ops import pallas_dropout
    seed = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    text = pallas_dropout.hetu_dropout_mask.lower(
        seed, shape, 0.9).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count("tpu_custom_call") == 1
    assert f"%{pallas_dropout.KERNEL_NAME}" in entry
    assert " fusion(" not in entry


def _compile_token_major(kind, blocks, sharding, d=64):
    """The GPT-2 train cell's attention (batch 16, S 1024, 768 lanes of
    heads, causal) over the packed ``[B, S, 3H]`` rows."""
    b, s, lanes = 16, 1024, 768
    layout = pk.TokenMajor.packed(lanes // d, d)
    rows = jax.ShapeDtypeStruct((b, s, 3 * lanes), jnp.bfloat16,
                                sharding=sharding)
    ctx = jax.ShapeDtypeStruct((b, s, lanes), jnp.bfloat16,
                               sharding=sharding)
    if kind == "bwd":
        lse = jax.ShapeDtypeStruct((b, lanes // d, 1, s), jnp.float32,
                                   sharding=sharding)
        lowered = pk._flash_attention_bwd_jit.lower(
            rows, rows, rows, None, ctx, lse, ctx, 0.125, True, False,
            *blocks, layout)
    else:
        lowered = pk._flash_attention_jit.lower(
            rows, rows, rows, None, 0.125, True, False, *blocks,
            kind == "fwd_lse", layout)
    return lowered.compile().as_text()


@pytest.mark.parametrize("blocks", [(128, 128), (256, 256), (256, 512),
                                    (1024, 1024)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("kind", KINDS)
def test_token_major_kernels_compile_at_the_train_cell(one_chip, kind,
                                                       blocks):
    """Two heads a lane tile split by static lane windows (loads and
    stores at lane 64), the logsumexp written as ``[B, H, 1, S]`` rows:
    Mosaic takes it over the tile grid, as ONE custom call
    under the name the trace's readers match, and the operands are the
    packed rows themselves — no copy feeds the call."""
    text = _compile_token_major(kind, blocks, one_chip)
    entry = text[text.index("ENTRY"):]
    calls = [ln for ln in entry.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    name = "_flash_attention_bwd_jit" if kind == "bwd" \
        else "_flash_attention_jit"
    assert calls[0].lstrip().removeprefix("ROOT ").startswith("%" + name)
    assert "bf16[16,1024,768]" in calls[0]
    moved = [ln for ln in entry.splitlines()
             if " copy(" in ln or " transpose(" in ln]
    assert not any("[16,1024,2304]" in ln for ln in moved), moved
    if kind != "bwd":   # (the backward's D row sum may re-lay o and dO)
        assert not moved, moved


@pytest.mark.parametrize("blocks", [(512, 512), (512, 256), (128, 1024),
                                    (1024, 128)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_region_forward_compiles_at_the_train_cell(one_chip, blocks):
    """The forward that walks regions (PR 40) at the GPT-2 train cell's
    shape, at the rule's tiles, a square pair and the two lopsided
    corners of the grid (the square corners, (256, 512) and (256, 256)
    are in the test above): a lane block's whole triangle a program,
    one custom call under the name the trace's readers match, the
    residual written as the rows the backward takes."""
    text = _compile_token_major("fwd_lse", blocks, one_chip)
    entry = text[text.index("ENTRY"):]
    calls = [ln for ln in entry.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].lstrip().removeprefix("ROOT ").startswith(
        "%_flash_attention_jit")
    assert "f32[16,12,1,1024]" in text
    assert not [ln for ln in entry.splitlines()
                if " copy(" in ln or " transpose(" in ln]


@pytest.mark.parametrize("kind", ["fwd", "fwd_lse"])
def test_grouped_forward_compiles_at_berts_cell(one_chip, kind):
    """BERT-base's train cell, ``[256 x 12, 128, 64]`` with a padding
    mask: a head is ONE tile pair, so a program takes the twelve heads
    of a batch row (256 programs a layer where 3,072 ran) and Mosaic
    takes the ``[12, 128, 64]`` blocks."""
    shape = (256, 12, 128, 64, False, True)
    blocks = pk._block_sizes(128, 64)
    assert pk.heads_per_program(12, 128, *blocks) == 12
    text = _compile(kind, shape, blocks, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[3072,128,64]" in text


@pytest.mark.parametrize("blocks", [None, (128, 128), (1024, 1024)],
                         ids=["rule", "128x128", "1024x1024"])
def test_region_forward_compiles_at_the_longest_prefill(one_chip, blocks):
    """Head-major at S = 8,192, D = 192 (the latent-attention cells'
    largest prompt bucket: K and V of a head are 16.8 MB of VMEM,
    double-buffered, and the kernel asks for what it needs), causal, the
    regions walked by a loop: at the rule's tiles and at the two square
    corners of the tile grid."""
    s, d = 8192, 192
    blocks = blocks or pk._block_sizes(s, d, "fwd", True)
    assert pk._region_span(s, *blocks) < s
    assert pk.heads_per_program(64, s, *blocks) == 1
    text = _compile("fwd", (1, 4, s, d, True, False), blocks, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_token_major_kernels_compile_at_one_head_a_block(one_chip):
    """D = 128: a lane block is one head, no window inside it."""
    for kind in KINDS:
        text = _compile_token_major(kind, (256, 256), one_chip, d=128)
        assert text.count('custom_call_target="tpu_custom_call"') == 1


_STEP_TEXTS = {}        # a compile a (model, arguments), shared by tests


def _gpt2_step_text(v5e_device, monkeypatch, dropout, vocab=1024,
                    layers=1, as_the_executor=False):
    """The optimized HLO of a GPT-2 training step (the cell's widths,
    batch and context; one layer and, unless the test is about the
    logits, a vocabulary of 1024, to keep the compile short) for one
    described chip; ``as_the_executor`` compiles it as
    ``SubExecutor._jit`` does on a TPU: four donated trees and
    ``TPU_TRAIN_STEP_OPTIONS``."""
    key = ("gpt2", dropout, vocab, layers, as_the_executor)
    if key not in _STEP_TEXTS:
        _STEP_TEXTS[key] = _compile_gpt2_step(
            v5e_device, monkeypatch, dropout, vocab, layers,
            as_the_executor)
    return _STEP_TEXTS[key]


def _compile_gpt2_step(v5e_device, monkeypatch, dropout, vocab, layers,
                       as_the_executor):
    import numpy as np
    import hetu_tpu as ht
    from jax.sharding import SingleDeviceSharding
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.ops import attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    model = GPTLMHeadModel(GPTConfig(
        vocab_size=vocab, hidden_size=768, num_hidden_layers=layers,
        num_attention_heads=12, max_position_embeddings=1024,
        hidden_dropout_prob=dropout, use_flash_attention=True))
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    _, loss = model(ids, labels)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train_op = ht.optim.AdamOptimizer(1e-4).minimize(lm_loss)
    executor = ht.Executor([lm_loss, train_op], dtype=jnp.bfloat16,
                           ctx=ht.cpu(0))
    sub = executor.subexecutors["default"]
    feed = {ids: np.zeros((16, 1024), np.int32),
            labels: np.zeros((16, 1024), np.int32)}
    step = sub.prepare(executor, feed)
    sharding = SingleDeviceSharding(v5e_device)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype")
                                       else a.dtype, sharding=sharding),
        sub.trace_args(executor, feed))
    if as_the_executor:
        from hetu_tpu.executor import TPU_TRAIN_STEP_OPTIONS
        return jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(
            *shapes).compile(
                compiler_options=TPU_TRAIN_STEP_OPTIONS).as_text()
    return jax.jit(step).lower(*shapes).compile().as_text()


def test_gpt2_step_holds_the_dropout_mask_kernel(v5e, monkeypatch):
    """The step a train cell compiles for one chip draws its three
    dropout masks (the embedding's and two in the layer) in
    ``hetu_dropout_mask`` calls and no ``bernoulli`` is left in it; with
    dropout off it holds none."""
    text = _gpt2_step_text(v5e[0], monkeypatch, 0.1)
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "hetu_dropout_mask" in ln]
    # forward and backward: 6 where the backward draws again, 3 where
    # the compiler merged each pair
    print("hetu_dropout_mask calls in the step:", len(calls))
    assert len(calls) in (3, 6), len(calls)
    # the per-op fold_in of the key stays threefry; no draw does
    assert "bernoulli" not in text and "rng-bit-generator" not in text
    off = _gpt2_step_text(v5e[0], monkeypatch, 0.0)
    assert "hetu_dropout_mask" not in off


# an HLO instruction: its name, its result type(s), its opcode and what
# stands between the opcode's parentheses
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) "
                          r"([\w\-]+)\((.*?)\)(?:, |$)", re.MULTILINE)


def _flash_calls(text):
    """{call name without its number: [(result type, operand types)]}
    of the flash custom calls of a compiled step, every type without
    its layout."""
    bare = lambda t: re.sub(r"\{[^{}]*\}", "", t)      # noqa: E731
    defined = {m.group(1): bare(m.group(2))
               for m in _INSTRUCTION.finditer(text)}
    calls = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%_flash_attention\w*?)(?:\.\d+)? = "
                     r"(\(.*?\)|\S+) custom-call\((.*?)\), custom_call",
                     ln)
        if m:
            operands = [defined[o.split("*/")[-1].strip()]
                        for o in m.group(3).split(", ")]
            calls.setdefault(m.group(1), []).append(
                (bare(m.group(2)), operands))
    return calls


def test_gpt2_step_reads_the_qkv_rows_where_they_lie(v5e, monkeypatch):
    """The step a train cell compiles: both flash calls take the qkv
    projection's result (a bitcast of it) and write rows, so none of
    the relayouts that stood around the head-major calls is left in the
    HLO — no ``[B, H, S, D]`` array of q, k, v, the context or a
    gradient, no lane-tiled float32 logsumexp — and the two calls keep
    the names the trace's readers match."""
    text = _gpt2_step_text(v5e[0], monkeypatch, 0.1)
    for shape in ("bf16[1,16,12,1024,64]", "bf16[16,12,1024,64]",
                  "bf16[16,1024,3,12,64]", "bf16[3,16,12,1024,64]",
                  "bf16[192,1024,64]", "f32[192,1024,128]"):
        assert shape not in text, shape
    calls = [ln.strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "_flash_attention" in ln.split("=")[0]]
    assert sorted(c.split(" ")[0].rstrip(".0123456789") for c in calls) \
        == ["%_flash_attention_bwd_jit", "%_flash_attention_jit"]
    assert all("f32[16,12,1,1024]" in c for c in calls)   # residual rows
    # the operands and results the calls hold since PR 38, a lane block
    # of two heads a program (PR 45 widened BERT's programs, not these)
    rows, ctx = "bf16[16,1024,2304]", "bf16[16,1024,768]"
    lse = "f32[16,12,1,1024]"
    assert _flash_calls(text) == {
        "%_flash_attention_jit": [(f"({ctx}, {lse})", [rows] * 3)],
        "%_flash_attention_bwd_jit": [
            (f"({ctx}, {ctx}, {ctx})", [rows] * 3 + [ctx, lse, lse])]}


@pytest.mark.parametrize("kind", KINDS)
def test_token_major_kernels_compile_at_berts_cell(one_chip, kind):
    """BERT-base's train cell token-major (PR 45): three projections'
    ``[256, 128, 768]`` rows under a padding mask, a batch row's twelve
    heads a program in both directions (blocks ``[1, 128, 768]``, 256
    programs a layer), the backward summing D itself — ONE custom call
    under the name the trace's readers match, fed by the rows as they
    lie, and nothing around it but the mask's column."""
    b, s, heads, d = 256, 128, 12, 64
    layout = pk.TokenMajor(heads, d)
    blocks = pk._block_sizes(s, d)
    assert pk.heads_per_program(heads, s, *blocks, layout) == 12
    rows = jax.ShapeDtypeStruct((b, s, heads * d), jnp.bfloat16,
                                sharding=one_chip)
    mask = jax.ShapeDtypeStruct((b, 1, 1, s), jnp.float32,
                                sharding=one_chip)
    if kind == "bwd":
        lse = jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32,
                                   sharding=one_chip)
        lowered = pk._flash_attention_bwd_jit.lower(
            rows, rows, rows, mask, rows, lse, rows, 0.125, False, False,
            *blocks, layout)
    else:
        lowered = pk._flash_attention_jit.lower(
            rows, rows, rows, mask, 0.125, False, False, *blocks,
            kind == "fwd_lse", layout)
    text = lowered.compile().as_text()
    entry = text[text.index("ENTRY"):]
    calls = [ln for ln in entry.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    name = "_flash_attention_bwd_jit" if kind == "bwd" \
        else "_flash_attention_jit"
    assert calls[0].lstrip().removeprefix("ROOT ").startswith("%" + name)
    assert "bf16[256,128,768]" in calls[0]
    moved = [ln for ln in entry.splitlines()
             if " copy(" in ln or " transpose(" in ln]
    assert not any("[256,128,768]" in ln or "[256,12," in ln
                   for ln in moved), moved
    assert " fusion(" not in entry      # no pass over dO and O for D


def _bert_step_text(v5e_device, monkeypatch, vocab=1024):
    """The optimized HLO of BERT-base's pre-training step (the cell's
    widths, batch 256 and length 128; one layer and, unless the test is
    about the logits, a vocabulary of 1024, to keep the compile short)
    for one described chip."""
    if ("bert", vocab) not in _STEP_TEXTS:
        _STEP_TEXTS["bert", vocab] = _compile_bert_step(
            v5e_device, monkeypatch, vocab)
    return _STEP_TEXTS["bert", vocab]


def _compile_bert_step(v5e_device, monkeypatch, vocab):
    import numpy as np
    import hetu_tpu as ht
    from jax.sharding import SingleDeviceSharding
    from hetu_tpu.models import BertConfig, BertForPreTraining
    from hetu_tpu.ops import attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    batch, seq = 256, 128
    model = BertForPreTraining(BertConfig(
        vocab_size=vocab, hidden_size=768, num_hidden_layers=1,
        num_attention_heads=12, intermediate_size=3072,
        max_position_embeddings=seq, use_flash_attention=True))
    nodes = [ht.Variable(n, trainable=False) for n in (
        "input_ids", "token_type_ids", "attention_mask",
        "masked_lm_labels", "next_sentence_label")]
    _, _, mlm_loss, nsp_loss = model(*nodes)
    loss = ht.reduce_mean_op(mlm_loss, [0, 1]) \
        + ht.reduce_mean_op(nsp_loss, [0])
    train_op = ht.optim.AdamOptimizer(1e-4).minimize(loss)
    executor = ht.Executor([loss, train_op], dtype=jnp.bfloat16,
                           ctx=ht.cpu(0))
    sub = executor.subexecutors["default"]
    ids = np.zeros((batch, seq), np.int32)
    feed = dict(zip(nodes, (ids, ids, np.ones((batch, seq), np.float32),
                            ids, np.zeros((batch,), np.int32))))
    step = sub.prepare(executor, feed)
    sharding = SingleDeviceSharding(v5e_device)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding),
        sub.trace_args(executor, feed))
    return jax.jit(step).lower(*shapes).compile().as_text()


def test_bert_step_holds_no_head_major_relayout(v5e, monkeypatch):
    """BERT-base's train step (the cell's widths, batch and length; one
    layer and a vocabulary of 1024, to keep the compile short) for one
    described chip: the three projections' rows reach both flash calls
    as they lie and the context and the three gradients leave as rows,
    so no array of ``[256, 12, 128, 64]`` (or its transposes) is left
    anywhere in the text — the q / k / v relayouts and the composed
    attention backward are gone — and the backward call takes the
    forward's logsumexp as the forward leaves it."""
    text = _bert_step_text(v5e[0], monkeypatch)
    for shape in ("[256,12,128,64]", "[256,128,12,64]", "[3072,128,64]",
                  "[256,12,64,128]", "[256,12,128,128]"):
        assert shape not in text, shape
    rows, lse = "bf16[256,128,768]", "f32[256,12,1,128]"
    calls = _flash_calls(text)
    assert sorted(calls) == ["%_flash_attention_bwd_jit",
                             "%_flash_attention_jit"]
    (fwd,), (bwd,) = (calls["%_flash_attention_jit"],
                      calls["%_flash_attention_bwd_jit"])
    assert fwd == (f"({rows}, {lse})", [rows] * 3 + ["f32[256,1,128]"])
    # q, k, v, dO, the logsumexp and the mask's column: no D residual
    assert bwd == (f"({rows}, {rows}, {rows})",
                   [rows] * 4 + [lse, "f32[256,128,1]"])
    # ... and that logsumexp is the forward call's second result as it
    # left the kernel: nothing computes or re-lays an array of its shape
    assert not [ln for ln in text.splitlines()
                if ln.lstrip().split(" = ")[-1].startswith(lse)
                and any(op in ln for op in (" copy(", " transpose(",
                                            " fusion(", " reshape("))]


def _called_bodies(text):
    """{computation name: its text} of a compiled module."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(%[\w.\-]+) \(.*?\{\n(.*?)^\}", text, re.MULTILINE | re.DOTALL)}


def _touching(text, elements):
    """The ENTRY instructions of a compiled step that read or write an
    array of ``elements`` elements or more — bitcasts and tuple
    plumbing move nothing and are left out: ``[(name, opcode, the
    result is that large, the text of what it calls)]``."""
    sized = lambda t: max(                                  # noqa: E731
        [math.prod(int(d) for d in dims.split(",") if d)
         for dims in re.findall(r"\w+\[([\d,]*)\]", t)] or [0]) >= elements
    bodies = _called_bodies(text)
    entry = text[text.index("ENTRY"):]
    types, found = {}, []
    for m in _INSTRUCTION.finditer(entry):
        name, result, opcode, operands = m.groups()
        types[name] = result
        if opcode in ("bitcast", "get-tuple-element", "parameter", "tuple"):
            continue
        reads = any(sized(types.get(o, ""))
                    for o in re.findall(r"%[\w.\-]+", operands))
        if sized(result) or reads:
            line = entry[m.start():entry.find("\n", m.end())]
            called = re.search(r"calls=(%[\w.\-]+)", line)
            found.append((name, opcode, sized(result),
                          bodies.get(called.group(1), "") if called else ""))
    return found


@pytest.mark.parametrize("model,logits,most", [
    ("bert", 256 * 128 * 30522, 5), ("gpt2", 16 * 1024 * 50257, 4)])
def test_step_reads_the_logits_where_the_head_left_them(
        v5e, monkeypatch, model, logits, most):
    """A train cell's step at its batch, length and FULL vocabulary, one
    layer, for one described chip: the sparse cross-entropy reads the
    logits where the head's matmul wrote them (PR 48). ONE instruction
    has a result as large as the logits and it is that matmul's fusion
    (no ``copy``, no ``transpose``, no slice / bitcast fusion: no whole
    copy); nothing that reads them holds a ``gather``; and at most five
    instructions touch them in BERT's step (the matmul with the row
    maximum in its epilogue, ONE reduction pass for the sum of
    exponentials and the label's logit, the decoder bias's gradient,
    the dX and the dW fusions), four in GPT-2's (no bias).

    On the parent (c450c58) BERT's step fails on all three counts — the
    five, ``copy`` and ``slice_bitcast_fusion`` (two whole copies, 2 GB
    each way each) and the gather fusion that reads the second: eight
    in this compile — and GPT-2's on the gather alone: five, no copy."""
    text = (_bert_step_text(v5e[0], monkeypatch, vocab=30522)
            if model == "bert" else
            _gpt2_step_text(v5e[0], monkeypatch, 0.1, vocab=50257))
    found = _touching(text, logits)
    print("\n".join(f"{name} {opcode}" for name, opcode, _, _ in found))
    written = [(name, opcode, body) for name, opcode, large, body in found
               if large]
    assert len(written) == 1, [w[:2] for w in written]
    (name, opcode, body), = written
    assert opcode == "fusion" and " convolution(" in body, (name, opcode)
    assert not [name for name, _, _, body in found if " gather(" in body]
    assert 2 <= len(found) <= most, [f[:2] for f in found]


def _update_results(rows):
    """{matrix shape: the sorted dtypes of the results} of the fusions
    that hold a matmul and return one array a result, all of one shape:
    the optimizer's update with the matmul of its gradient inside."""
    updates = {}
    for row in rows:
        results = result_types(row)
        shapes = {shape for _, shape, _ in results}
        if len(results) >= 3 and len(shapes) == 1:
            updates[shapes.pop()] = sorted(d for d, _, _ in results)
    return updates


@pytest.mark.parametrize("model", ["bert", "gpt2"])
def test_no_matmul_of_the_step_reads_a_float32_weight(v5e, monkeypatch,
                                                      model):
    """A train cell's step at the cell's widths, one layer, for one
    described chip (PR 49): no operand of any ``convolution`` is a
    float32 operand of its fusion, moved or converted inside it — on the
    parent (e33ac3f) every weight was, 18 of them in BERT's compile —
    and the update of each of the block's matrices, which XLA fuses into
    the matmul that makes its gradient, returns FOUR arrays of the
    matrix's shape: the master and Adam's two moments in float32 and the
    next step's working copy in bfloat16."""
    text = (_bert_step_text(v5e[0], monkeypatch) if model == "bert" else
            _gpt2_step_text(v5e[0], monkeypatch, 0.1))
    rows = matmul_fusions(text)
    assert len(rows) >= (20 if model == "bert" else 12), len(rows)
    weights = [o for row in rows for o in row["operands"]
               if len(o["shape"]) == 2 and o["shape"][0] % 768 == 0]
    assert len(weights) >= (12 if model == "bert" else 8), weights
    read = [(row["name"], o) for row in rows for o in row["operands"]
            if o["dtype"] == "f32" or o["converted"]]
    assert not read, read
    updates = _update_results(rows)
    for shape in ((768, 768), (768, 3072), (3072, 768)) + (
            ((768, 2304),) if model == "gpt2" else ()):
        assert updates[shape] == ["bf16", "f32", "f32", "f32"], updates


def test_gpt2_step_runs_each_update_beside_its_layers_backward(
        v5e, monkeypatch):
    """XLA's default memory scheduler keeps one of several orders of the
    step, and for GPT-2 small's step with its 50,257-word head it kept
    one that runs the optimizer's updates after the WHOLE backward pass,
    with their operands gone from on-chip memory (110.9 ms a step for
    106.1 on the chip, PERF.md section 6, PR 49). Compiled as the
    executor compiles a training step on a TPU (four donated trees,
    ``TPU_TRAIN_STEP_OPTIONS``), four layers' worth of that step for one
    described chip: the update of every layer's second feed-forward
    matrix, with the matmul of its gradient inside, is scheduled before
    the flash backward of the SAME layer, which the backward pass
    reaches next."""
    text = _gpt2_step_text(v5e[0], monkeypatch, 0.1, vocab=50257,
                           layers=4, as_the_executor=True)
    entry = text[text.index("\nENTRY "):]
    order = []
    for line in entry.splitlines():
        if "_flash_attention_bwd" in line and " custom-call(" in line:
            order.append("backward")
        elif " fusion(" in line and \
                line.split(" fusion(")[0].count("f32[3072,768]") == 3:
            order.append("update")
    assert order == ["update", "backward"] * 4, order


def test_dropout_keeps_the_composed_draw_under_a_dp_mesh(v5e, monkeypatch):
    """Under a ``("dp", 4)`` mesh GSPMD would have to partition the
    kernel and cannot (the first assertion pins that); the ops keep
    ``jax.random.bernoulli`` there, forward and backward, and compile."""
    import numpy as np
    import types
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    import hetu_tpu as ht
    from hetu_tpu.ops import attention, pallas_dropout

    mesh = Mesh(np.asarray(v5e), ("dp",))
    seed = jax.ShapeDtypeStruct((2,), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    with pytest.raises(NotImplementedError, match="Mosaic kernels"):
        pallas_dropout.hetu_dropout_mask.lower(
            seed, (64, 128, 768), 0.9).compile()

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    x_node = ht.Variable("x", trainable=False)
    forward = ht.dropout_op(x_node, 0.9)
    backward = forward.gradient(x_node)[0]
    key = jax.random.PRNGKey(0)
    ectx = types.SimpleNamespace(
        config=types.SimpleNamespace(mesh=mesh), training=True,
        rng_for=lambda op: jax.random.fold_in(key, op.id))
    x = jax.ShapeDtypeStruct((64, 128, 768), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    text = jax.jit(lambda x: (forward.compute([x], ectx),
                              backward.compute([x], ectx))).lower(
        x).compile().as_text()
    assert "tpu_custom_call" not in text
    one = types.SimpleNamespace(config=None, training=True,
                                rng_for=ectx.rng_for)
    x1 = jax.ShapeDtypeStruct((64, 128, 768), jnp.bfloat16,
                              sharding=SingleDeviceSharding(v5e[0]))
    assert "tpu_custom_call" in jax.jit(
        lambda x: forward.compute([x], one)).lower(x1).compile().as_text()


# -- GPT-2 small's serving programs, a block's vectors stacked (PR 44) -------

@pytest.mark.parametrize("kind,inputs", [
    ("decode", ((8,), (8,), (8, 1024), (8,))),
    ("prefill", ((8, 512), (8, 512)))])
def test_gpt_serving_program_streams_its_matrices_and_aliases_its_pools(
        one_chip, monkeypatch, kind, inputs):
    """``hetu_paged_decode`` at (8, 1024) and ``hetu_paged_prefill`` at
    (8, 512), the serve cell's widths, depth and pool (1,536 + 1 blocks
    of 16), compiled for the described chip. The donated pools are
    updated in place (1.81e9 bytes aliased). The block's matrices are an
    array a layer so that the compiler streams each into VMEM under the
    layer before (``slice-start``, 4 slices a matrix in the decode
    program; with the matrices stacked ``[12, ...]`` beside the vectors
    it streamed none and the program ran a third slower on the chip:
    PERF.md, PR 44). The names below are how this jax spells an
    argument's path in the compiled text: after a jax upgrade that
    renames them, read the new spelling off ``compiled.as_text()``."""
    import re
    from hetu_tpu.models.gpt import GPTConfig
    from hetu_tpu.ops import attention
    from hetu_tpu.serving.kvcache import PagedKVCache
    from hetu_tpu.serving.scheduler import _named_program

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    cfg = GPTConfig(vocab_size=50257, hidden_size=768,
                    num_hidden_layers=12, num_attention_heads=12,
                    max_position_embeddings=1024)
    model = cfg.serving_model()

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    by_suffix = {"wte": (v, h), "wpe": (1024, h), "lm_head_weights": (h, v),
                 "qkv_weights": (h, 3 * h), "qkv_bias": (3 * h,),
                 "attn_proj_weights": (h, h), "fc_weights": (h, i),
                 "fc_bias": (i,), "mlp_proj_weights": (i, h)}

    def lookup(name):
        shape = next((s for suffix, s in by_suffix.items()
                      if name.endswith(suffix)), (h,))
        return jnp.zeros(shape, jnp.float32)

    params, pools = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: (model.params(lookup),
                 PagedKVCache(cfg, num_blocks=1536, block_size=16).pools)))
    assert len(jax.tree_util.tree_leaves(params)) == 5 + 8 + 4 * 12
    fn, static = model.program(kind)
    compiled = _named_program(fn, "hetu_paged_" + kind, **static).lower(
        params, pools, *(jax.ShapeDtypeStruct(s, jnp.int32,
                                              sharding=one_chip)
                         for s in inputs)).compile()
    pool_bytes = 2 * 12 * 1537 * 16 * 768 * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    if kind == "decode":
        streamed = re.findall(
            r" slice-start\(%?args_0___blocks____(\w+?)___0__\d+_",
            compiled.as_text())
        assert sorted(set(streamed)) == ["fc", "mlp_proj", "proj", "qkv"]
        assert len(streamed) >= 4 * 4 * 11


# -- the sparse-expert decoder's training kernels (PR 50) --------------------

def _gqa_call(kind, window, one_chip, s=8192, heads=28, groups=4, d=128):
    """The compiled text of one grouped-query flash call at the
    published widths of the train cell ``smallthinker21b-train-s8192``
    (28 query heads on 4 key/value heads of 128, one 8,192-token
    sequence), token-major, at the rule's tiles."""
    lay = pk.TokenMajor(heads, d, kv_heads=groups)
    assert lay.fits(s)

    def sds(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, s, width), dtype, sharding=one_chip)

    q, k = sds(heads * d), sds(groups * d)
    if kind == "bwd":
        lse = jax.ShapeDtypeStruct((1, heads, 1, s), jnp.float32,
                                   sharding=one_chip)
        fn = lambda q, k, v, o, lse, do: pk.flash_attention_bwd(  # noqa: E731
            q, k, v, None, o, lse, do, d ** -0.5, True, False, lay,
            window=window)
        return jax.jit(fn).lower(q, k, k, q, lse, q).compile().as_text()
    fn = lambda q, k, v: pk.flash_attention_with_lse(  # noqa: E731
        q, k, v, None, d ** -0.5, True, False, lay, window=window)
    return jax.jit(fn).lower(q, k, k).compile().as_text()


@pytest.mark.parametrize("window", [4096, None], ids=["window", "global"])
@pytest.mark.parametrize("kind", ["fwd_lse", "bwd"])
def test_grouped_query_kernels_compile_at_the_sparse_decoders_cell(
        one_chip, kind, window):
    """S = 8,192 is eight regions of 1,024 a side: both kernels walk
    them by their loops, the band's edge regions behind a branch, q,
    dO and the float32 dQ^T of a head resident in the backward (the
    VMEM it asks for is under the 100 MiB bound), a head a program."""
    blocks = pk._block_sizes(8192, 128, "bwd" if kind == "bwd" else
                             "fwd_lse", True)
    assert pk._region_span(8192, *blocks) == 1024
    assert pk._band_regions(8192, 1024, 4096) == (3, [4])
    text = _gqa_call(kind, window, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    name = "hetu_flash_gqa_" + ("window_" if window else "") \
        + ("bwd" if kind == "bwd" else "fwd")
    assert name in text
    if kind == "bwd":       # dk / dv of a group: summed over its 7 heads
        assert "bf16[1,8192,512]" in text


def test_the_banded_walk_leaves_out_a_quarter_of_the_pairs():
    """At S = 8,192 under a window of 4,096 the walk visits 408 of the
    diagonal's 528 tiles of 256 x 256 (25.2M of 33.6M pairs a head)."""
    whole = pk.tile_walk_counts(8192, 256, 256, True)
    band = pk.tile_walk_counts(8192, 256, 256, True, 4096)
    assert whole["tiles_visited"] == 32 * 33 // 2 == 528
    assert band["tiles_visited"] == 16 * 17 // 2 + 16 * 17 == 408
    assert band["tiles_masked"] == 32 + 16


# (sorted rows, hidden, gate|up, experts held) of the two sparse train
# cells: 8,192 x 6 picks at smallthinker's widths, 8,192 x 4 at lfm2's
EXPERT_CELLS = {"smallthinker": (8192 * 6, 2560, 1536, 16),
                "lfm2": (8192 * 4, 2048, 3584, 8)}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
@pytest.mark.parametrize("which", ["forward", "rows", "weights"])
def test_expert_products_compile_at_the_sparse_decoders_cell(one_chip,
                                                              which, cell):
    """The six grouped products of a held-expert layer's training step
    at a cell's widths, under the tiles the rule gives them
    (``moe._kernel_tiles``: a tile over the kernel's on-chip memory is
    refused here, off the chip): the forward kernel for gate|up and
    down, the same with its right side transposed
    (``hetu_moe_experts_dx``) for ``da`` and ``dxs``, and the repo's own
    kernel (``ops/pallas_grouped.py``, ``hetu_moe_experts_dw``) for the
    two weight gradients, at tiles whose blocks, two float32
    accumulators among them, the kernel asks for itself
    (``WEIGHTS_BLOCK_BYTES``). Each is ONE custom call under its stable
    name; the weight gradient's visit list is compare-and-sum fusions:
    no loop (``searchsorted``), no gather, no scatter beside the call."""
    from hetu_tpu.ops import moe
    rows, hidden, wide, held = EXPERT_CELLS[cell]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    sizes = sds((held + 1,), jnp.int32)
    xs, h, a = sds((rows, hidden)), sds((rows, wide)), \
        sds((rows, wide // 2))
    w_in, w_down = sds((held, hidden, wide)), sds((held, wide // 2, hidden))
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    # (lhs, the other operand, result columns, the result's type)
    if which == "forward":
        name = moe.KERNEL_NAME
        calls = [(xs, w_in, wide, f"bf16[{rows},{wide}]"),
                 (a, w_down, hidden, f"bf16[{rows},{hidden}]")]
    elif which == "rows":
        name = moe.ROWS_GRAD_KERNEL_NAME
        calls = [(xs, w_down, wide // 2, f"bf16[{rows},{wide // 2}]"),
                 (h, w_in, hidden, f"bf16[{rows},{hidden}]")]
    else:
        name = moe.WEIGHTS_GRAD_KERNEL_NAME
        calls = [(a, xs, hidden, f"f32[{held},{wide // 2},{hidden}]"),
                 (xs, h, wide, f"f32[{held},{hidden},{wide}]")]
    for lhs, other, n, result in calls:
        tiles = moe._kernel_tiles(which, *lhs.shape, n, 2,
                                  which != "weights")
        assert moe._block_bytes(which, *tiles, 2, which != "weights") <= (
            moe.WEIGHTS_BLOCK_BYTES if which == "weights"
            else moe.KERNEL_BLOCK_BYTES)
        if which == "forward":
            fn = moe._kernel(tiles, bf16, False)
            args = (lhs, other, sizes, sds((rows, n)))
        elif which == "rows":
            fn = moe._grad_kernel("rows", tiles, bf16, False)
            args = (lhs, other, sizes, sds((rows, n)))
        else:
            fn = moe._grad_kernel("weights", tiles, f32, False, held)
            args = (lhs, other, sizes)
        text = fn.lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1, tiles
        assert name in text and result in text
        if which == "weights":
            assert not re.search(r" (while|gather|scatter)\(", text)


def _sparse_decoder_step_text(v5e_device, monkeypatch):
    """One WINDOW layer of the sparse-expert decoder at the published
    widths and S = 8,192 (2 experts held and 1,024 rows of vocabulary,
    to keep the compile short), as the executor compiles a training
    step."""
    key = ("sparse_decoder",)
    if key in _STEP_TEXTS:
        return _STEP_TEXTS[key]
    import numpy as np
    import hetu_tpu as ht
    from jax.sharding import SingleDeviceSharding
    from hetu_tpu.executor import TPU_TRAIN_STEP_OPTIONS
    from hetu_tpu.models import SparseDecoderConfig, \
        SparseDecoderLMHeadModel
    from hetu_tpu.ops import attention, moe

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    # the row buffers' allocation as the chip runs it: a kernel
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    model = SparseDecoderLMHeadModel(SparseDecoderConfig(
        vocab_size=1024, hidden_size=2560, num_attention_heads=28,
        num_key_value_heads=4, head_dim=128, window_layout=[1],
        rope_layout=[1], sliding_window=4096, moe_ffn_hidden_size=768,
        num_experts=64, num_experts_per_tok=6, experts_held=(0, 2),
        rope_theta=1.5e6))
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    _, loss = model(ids, labels, seq_len=8192)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train_op = ht.optim.AdamOptimizer(1e-4).minimize(lm_loss)
    executor = ht.Executor([lm_loss, train_op], dtype=jnp.bfloat16,
                           ctx=ht.cpu(0))
    sub = executor.subexecutors["default"]
    feed = {ids: np.zeros((1, 8192), np.int32),
            labels: np.zeros((1, 8192), np.int32)}
    step = sub.prepare(executor, feed)
    sharding = SingleDeviceSharding(v5e_device)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), a.dtype if hasattr(a, "dtype")
            else np.asarray(a).dtype, sharding=sharding),
        sub.trace_args(executor, feed))
    text = jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(
        *shapes).compile(compiler_options=TPU_TRAIN_STEP_OPTIONS).as_text()
    _STEP_TEXTS[key] = text
    return text


def test_sparse_decoder_step_holds_the_banded_grouped_backward(
        v5e, monkeypatch):
    """The step holds the banded grouped-query forward and backward,
    the experts' three kinds of grouped product (two of each) and the
    token table's sparse row update (PR 51): nine kernels, and the ten
    allocations of the expert layer's row buffers (PRs 53 and 55)."""
    text = _sparse_decoder_step_text(v5e[0], monkeypatch)
    assert text.count('custom_call_target="tpu_custom_call"') == 9 + 10
    for name in ("hetu_flash_gqa_window_fwd", "hetu_flash_gqa_window_bwd",
                 "hetu_moe_experts_dx", "hetu_moe_experts_dw",
                 "hetu_sparse_rows_update", "hetu_moe_rows_buffer"):
        assert name in text, name
    # a group's dk / dv leave the backward summed over its 7 heads
    assert "bf16[1,8192,512]" in text


def test_sparse_decoder_step_runs_the_experts_passes_to_the_held_extent(
        v5e, monkeypatch):
    """The expert layer's composed passes as the chip's compiler leaves
    them (PRs 53 and 55). Six loops: the forward's ``flat[token]`` (also
    the backward's), ``dy[token]``, the activation, the backward's
    elementwise work, and the way back of each direction, whose body
    holds the loop over a token's picks (``_token_sums``). Ten
    ``hetu_moe_rows_buffer`` calls: one a loop, one for each of the four
    grouped products to write into: nothing fills a ``[T x k, ...]``
    buffer before a loop or a kernel does (no broadcast into one, and no
    whole-array select follows a ``hetu_moe_experts*`` call: the
    library's zero fill behind the held groups is gone). A tile's
    results are written in place: no ``dynamic-update-slice`` stands
    alone in a loop's body with a ``[T x k, width]`` result (one did
    while the tile's start lay behind a ``minimum``: a copy of every
    tile). Outside the loops no instruction with a ``[T x k, hidden]``
    result reads the ``[T, hidden]`` tokens, no gather has a ``[T x k,
    hidden]`` result or operand (the way back reads the products'
    outputs a token tile at a time, inside its loops), and no float32
    ``[T, k, hidden]`` array is left anywhere."""
    from hlo_matmuls import _computations, describe
    from hetu_tpu.ops import moe
    text = _sparse_decoder_step_text(v5e[0], monkeypatch)
    rows, tokens, hidden = 8192 * 6, 8192, 2560
    comps, entry = _computations(text)
    buffers = [name for name, v in comps[entry].items()
               if name.startswith("%hetu_moe_rows_buffer")
               and v[1] == "custom-call"]
    assert len(buffers) == 6 + 4
    for name, (result, opcode, _, line) in comps[entry].items():
        if result.startswith("("):
            continue
        dims = describe(result)[1]
        whole = len(dims) == 2 and dims[0] == rows
        if opcode == "broadcast":
            assert not whole, name
        if "jit(hetu_moe_experts" in line and opcode != "custom-call":
            assert not whole, name      # (megablox's where after its call)
    assert "f32[8192,6,2560]" not in text
    bodies = {re.search(r"body=(%[\w.\-]+)", v[3]).group(1)
              for v in comps[entry].values()
              if v[1] == "while" and "HeldExperts" in v[3]
              and "searchsorted" not in v[3]}     # the kernels' own
    assert len(bodies) == 6
    nested = [body for body in bodies
              if any(v[1] == "while" for v in comps[body].values())]
    assert len(nested) == 2                       # the way back, a direction
    for body in bodies:
        for name, (result, opcode, _, _) in comps[body].items():
            if opcode == "dynamic-update-slice":    # a [rows] vector may
                dims = describe(result)[1]
                assert not (len(dims) == 2 and dims[0] == rows), (body, name)
    for name, (result, opcode, operands, _) in comps[entry].items():
        if opcode not in ("fusion", "gather") or result.startswith("("):
            continue
        reads = [describe(comps[entry][o][0])[1] for o in operands
                 if o in comps[entry]
                 and not comps[entry][o][0].startswith("(")]
        if result.startswith("bf16[") \
                and describe(result)[1] == (rows, hidden):
            assert (tokens, hidden) not in reads, name
    # every gather of [T x k, hidden] rows is a token tile in a loop
    in_loops = set(bodies)
    for body in nested:
        in_loops |= {re.search(r"body=(%[\w.\-]+)", v[3]).group(1)
                     for v in comps[body].values() if v[1] == "while"}
    tile = min(moe.TOKEN_TILE, tokens)
    way_back = 0
    for comp, body in comps.items():
        for name, (result, opcode, operands, _) in body.items():
            if opcode != "gather":
                continue
            got = describe(result)[1]
            assert got != (rows, hidden), (comp, name)
            if describe(body[operands[0]][0])[1] == (rows, hidden):
                assert got == (tile, hidden), (comp, name)
                users = [c for c in in_loops if any(
                    f"calls={comp}" in v[3] for v in comps[c].values())]
                assert users, (comp, name)
                way_back += 1
    assert way_back == 2


# (rows, width, ids a step) of the tables the train cells update sparsely
SPARSE_TABLES = {"smallthinker": (37984, 2560, 8192),
                 "gpt2_wte": (50257, 768, 16384),
                 "gpt2_wpe": (1024, 768, 16384)}


@pytest.mark.parametrize("slots", [0, 1, 2, 3],
                         ids=["sgd", "adagrad", "adam", "amsgrad"])
@pytest.mark.parametrize("table", list(SPARSE_TABLES))
def test_sparse_update_kernel_compiles_at_the_train_cells_tables(
        one_chip, table, slots):
    """One custom call named for the trace's readers; the parameter and
    every slot are aliased to its results (donated, nothing copies a
    table), and what XLA compiles around it is the count of the real
    ids: it reads no table."""
    from hetu_tpu import optimizer as optim
    from hetu_tpu.ops import pallas_sparse_update as kernel
    rows, width, n = SPARSE_TABLES[table]
    rule, hyper = [(optim.sgd_rows, ()), (optim.adagrad_rows, (1e-7,)),
                   (optim.adam_rows, (0.9, 0.999, 1e-7)),
                   (optim.adam_rows, (0.9, 0.999, 1e-7))][slots]

    def update(ids, g, scale, *tables):
        return kernel.hetu_sparse_rows_update(
            rule, hyper, ids, g, [scale], list(tables), interpret=False)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(    # noqa: E731
        shape, dtype, sharding=one_chip)
    tables = [sds((rows, width), jnp.float32)] * (1 + slots)
    compiled = jax.jit(update, donate_argnums=tuple(
        range(3, 3 + len(tables)))).lower(
            sds((n,), jnp.int32), sds((n, width), jnp.float32),
            sds((), jnp.float32), *tables).compile()
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY"):]
    assert entry.count("tpu_custom_call") == 1
    assert f"%{kernel.KERNEL_NAME}" in entry
    aliased = re.search(r"output_to_operand_aliasing=\{(.*?)\}, ", entry)
    assert aliased and aliased.group(1).count("{})") == len(tables), entry
    assert not re.search(r"f32\[%d,%d\]\S* copy\(" % (rows, width), entry)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= len(tables) * rows * width * 4
    assert memory.temp_size_in_bytes < 1024 * 1024


def _table_sized(text, rows, width):
    """ENTRY instructions (but plumbing) with a float32 result of a
    table's shape: ``[(name, opcode, line, the text of what it
    calls)]``."""
    bodies = _called_bodies(text)
    entry = text[text.index("\nENTRY "):]
    found = []
    for m in _INSTRUCTION.finditer(entry):
        name, result, opcode, _ = m.groups()
        if opcode in ("bitcast", "get-tuple-element", "parameter", "tuple") \
                or f"f32[{rows},{width}]" not in result:
            continue
        line = entry[m.start():entry.find("\n", m.end())]
        called = re.search(r"calls=(%[\w.\-]+)", line)
        found.append((name, opcode, line,
                      bodies.get(called.group(1), "") if called else ""))
    return found


@pytest.mark.parametrize("model,ids,tables", [
    ("gpt2", 16384, [(50257, 768), (1024, 768)]),
    ("sparse_decoder", 8192, [(1024, 2560)])])
def test_step_updates_its_sparse_tables_in_one_call_each(
        v5e, monkeypatch, model, ids, tables):
    """GPT-2's four-layer step (the one the order test compiles) and a
    one-layer sparse-decoder step, as the executor compiles them: every
    sparsely updated table — master and both of Adam's moments — is an
    aliased operand of ONE ``hetu_sparse_rows_update`` call; no
    instruction with a table-shaped float32 result is a scatter or a
    copy (on the parent: three scatter fusions a table, each sorting
    the ids again); and the ids are sorted by ``dedup`` alone, twice a
    table at most (three times on the parent)."""
    text = (_gpt2_step_text(v5e[0], monkeypatch, 0.1, vocab=50257,
                            layers=4, as_the_executor=True)
            if model == "gpt2" else
            _sparse_decoder_step_text(v5e[0], monkeypatch))
    for rows, width in tables:
        found = _table_sized(text, rows, width)
        calls = [f for f in found if "hetu_sparse_rows_update" in f[0]]
        assert len(calls) == 1, [f[:2] for f in found]
        aliasing = re.search(r"output_to_operand_aliasing=\{(.*?)\}, ",
                             calls[0][2]).group(1)
        assert aliasing.count("{})") == 3, aliasing
        for name, opcode, _, body in found:
            assert opcode != "copy" and " scatter(" not in body \
                and " sort(" not in body, (name, opcode)
    # (the expert op sorts its 8,192 tokens too: ``moe._token_sums``)
    sorts = [line for line in text.split("\n") if "HeldExperts" not in line
             and re.search(r"= \(?s32\[%d\]\S*(?:, s32\[%d\]\S*)?\)? "
                           r"sort\(" % (ids, ids), line)]
    assert 1 <= len(sorts) <= 2 * len(tables), sorts


# ---------------------------------------------------------------------------
# the expert layer's bookkeeping: no scalar walked one by one (PR 62)
# ---------------------------------------------------------------------------

def _scalar_walks(text):
    """``[(instruction, scalars moved)]``: the ``gather`` and ``scatter``
    instructions of a compiled module whose slice / update is ONE scalar
    an index (no offset, no window dimension), which the chip walks
    element by element, with the elements of the gather's result or of
    the scatter's updates. A row gather (an embedding's) or a row update
    has such dimensions and is not one."""
    from hlo_matmuls import _computations, describe
    found = []
    for body in _computations(text)[0].values():
        for name, (result, opcode, operands, line) in body.items():
            if opcode == "gather" and "offset_dims={}" in line:
                moved = result
            elif opcode == "scatter" and "update_window_dims={}" in line:
                moved = body[operands[-1]][0]
            else:
                continue
            found.append((name, math.prod(describe(moved)[1])))
    return found


def _bookkeeping_text(one_chip, tokens, top_k, experts, held, walked=False):
    """``route`` + ``_sorted_pairs`` compiled for the described chip;
    ``walked``: the forms they replaced (a scatter-add of a one a pair,
    a gather of a score a pick), to show that the search finds them."""
    from hetu_tpu.ops import moe

    def fn(x, w, bias):
        picks, weights, scores = moe.route(x, w, bias, top_k, 2.5)
        order, sizes, _, _ = moe._sorted_pairs(
            picks, jnp.ones(tokens, bool), held, 0)
        if walked:
            sizes = jnp.zeros(held + 1, jnp.int32).at[
                jnp.minimum(picks, held).reshape(-1)].add(1)
            weights = jnp.take_along_axis(scores, picks, axis=-1)
        return weights, order, sizes

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dtype, sharding=one_chip)
    return jax.jit(fn).lower(
        sds((tokens, 256), jnp.bfloat16), sds((256, experts), jnp.float32),
        sds((experts,), jnp.float32)).compile().as_text()


@pytest.mark.parametrize("tokens,top_k,experts,held", [
    (8192, 6, 64, 16), (8192, 4, 32, 8), (4096, 8, 128, 32),
    (4096, 8, 512, 128)])
def test_the_expert_layers_bookkeeping_walks_no_scalar(
        one_chip, tokens, top_k, experts, held):
    """Counting the pairs of each held expert and reading a pick's score
    at both train cells' shapes, a sarvam pass's and a ling pass's
    (whole row tiles: no pad on either path): compare-and-reduce
    fusions, no ``scatter`` and no ``gather`` at all (the sort of the
    pairs stays a sort)."""
    text = _bookkeeping_text(one_chip, tokens, top_k, experts, held)
    assert " gather(" not in text and " scatter(" not in text
    assert " sort(" in text


def test_the_search_finds_the_scalar_walks_that_went(one_chip):
    found = _scalar_walks(_bookkeeping_text(one_chip, 8192, 4, 32, 8,
                                            walked=True))
    assert sorted(n for _, n in found) == [8192 * 4] * 2, found


def test_sparse_decoder_step_walks_no_scalar_a_pair(v5e, monkeypatch):
    """The sparse decoder's whole training step: its gathers and
    scatters move rows (the token table's lookup, the way back's tiles).
    What is left of scalars walked one by one is megablox's own group
    metadata before the forward's and the rows' grouped products
    (``make_group_metadata``: a ``searchsorted`` and two scatter-adds
    over the row tiles and the groups, 200 scalars at the cell's
    shapes); nothing walks a scalar a (token, pick) pair or a token. On
    PR 62's parent the count of the pairs was one: 49,152 scalars, 0.43
    ms a layer on the chip. The weight gradients' visit list is none
    (PR 64: compare-and-sum; with megablox's ``tgmm`` this one-layer
    step held ten such instructions, five of them its metadata)."""
    text = _sparse_decoder_step_text(v5e[0], monkeypatch)
    walks = _scalar_walks(text)
    assert walks                        # the search has something to read
    assert max(n for _, n in walks) < 1024, walks
    assert len(walks) <= 5, walks
    for line in text.splitlines():
        if "jit(hetu_moe_experts_dw)" in line:
            assert not re.search(r" (while|gather|scatter)\(", line), line


# ---------------------------------------------------------------------------
# a kernel's bytes under the chip entry points' locations (PR 52)
# ---------------------------------------------------------------------------

def _flash_text(sharding):
    x = jax.ShapeDtypeStruct((2, 12, 1024, 64), jnp.bfloat16,
                             sharding=sharding)
    jax.clear_caches()          # this call traces the kernel anew
    return pk._flash_attention_jit.lower(
        x, x, x, None, 0.125, True, False, 512, 512, False).as_text()


@pytest.mark.parametrize("frames,same", [(1, True), (10, False)],
                         ids=["one_frame", "jax_default"])
def test_a_kernels_bytes_do_not_depend_on_who_traced_it(one_chip, frames,
                                                        same):
    """``cachedir.enable_compile_cache`` keeps ONE frame of a location's
    traceback: the lowered text of a flash call, its serialized Mosaic
    body included (what the persistent cache's key is made of), is then
    the same from two call stacks. jax's own ten frames show that the
    comparison can fail."""
    names = ("jax_include_full_tracebacks_in_locations",
             "jax_traceback_in_locations_limit")
    was = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], True)
    jax.config.update(names[1], frames)
    try:
        direct = _flash_text(one_chip)
        nested = (lambda: (lambda: _flash_text(one_chip))())()
    finally:
        for n, v in was.items():
            jax.config.update(n, v)
    assert "tpu_custom_call" in direct
    assert (direct == nested) == same


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_short_conv_compiles_at_the_hybrid_cells_widths(one_chip, direction):
    """The gated short convolution (``ops/short_conv.py``, PR 56) is
    composed: at the cell's widths (one 8,192-token sequence, 2,048
    channels, 3 taps, bfloat16) each direction compiles for the
    described chip into fusions alone (no custom call), writes results
    of the widths the graph op promises, and keeps what it holds beside
    its operands and results under the float32 ``[T, 3C]`` that a form
    materialising every intermediate would pass."""
    from hetu_tpu.ops import short_conv
    rows, channels, taps = 8192, 2048, 3

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    proj, w = arr(1, rows, 3 * channels), arr(channels, taps,
                                               dtype=jnp.float32)
    if direction == "forward":
        compiled = short_conv._forward.lower(proj, w).compile()
        out = [(1, rows, channels)]
    else:
        compiled = short_conv._backward.lower(
            proj, w, arr(1, rows, channels)).compile()
        out = [(1, rows, 3 * channels), (channels, taps)]
    assert "custom-call" not in compiled.as_text()
    got = jax.tree_util.tree_leaves(compiled.out_info)
    assert [tuple(o.shape) for o in got] == out
    assert got[0].dtype == jnp.bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes \
        < rows * 3 * channels * 4
