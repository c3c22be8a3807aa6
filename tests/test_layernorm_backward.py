"""LayerNorm's backward in one pass over the rows
(``hetu_tpu/ops/pallas_norm.py``), in interpret mode on the CPU: against
``jax.vjp`` of a float32 LayerNorm, through the graph, where the rule
that picks the kernel draws its line (the width, and a step that a mesh
partitions), and the float32 accumulation."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.ops import attention, pallas_norm
from hetu_tpu.ops.norm import layer_norm_backward_reference

EPS = 1e-5


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def _inputs(shape, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (jax.random.normal(k[0], shape) * 2 + 0.5).astype(dtype)
    dy = jax.random.normal(k[1], shape).astype(dtype)
    scale = (1 + 0.1 * jax.random.normal(k[2], shape[-1:])).astype(dtype)
    return dy, x, scale


def _truth(dy, x, scale):
    f32 = [a.astype(jnp.float32) for a in (x, scale, jnp.zeros_like(scale))]
    return jax.vjp(_layer_norm, *f32)[1](dy.astype(jnp.float32))


# rows 16, 1000 (no multiple of any block) and 16384, as 2-D and 3-D
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("d", [128, 768, 1024])
@pytest.mark.parametrize("lead", [(16,), (1000,), (4, 250), (16384,),
                                  (16, 1024)], ids=str)
def test_kernel_agrees_with_the_float32_gradient(lead, d, dtype):
    dy, x, scale = _inputs(lead + (d,), dtype)
    got = pallas_norm.hetu_layer_norm_bwd(dy, x, scale, eps=EPS,
                                          interpret=True)
    assert [g.shape for g in got] == [x.shape, scale.shape, scale.shape]
    assert [g.dtype for g in got] == [x.dtype, scale.dtype, scale.dtype]
    # one rounding of the result to the dtype, nothing that grows with
    # the rows: bfloat16 keeps 8 bits
    tolerance = 2e-6 if dtype == jnp.float32 else 2.0 ** -8
    for g, want in zip(got, _truth(dy, x, scale)):
        error = jnp.abs(g.astype(jnp.float32) - want).max()
        assert float(error / jnp.abs(want).max()) <= tolerance


def test_the_row_block_is_a_function_of_width_and_dtype():
    for d in (128, 768, 1024, 4096):
        for itemsize, tile in ((4, 8), (2, 16)):
            block = pallas_norm.block_rows(10 ** 6, d, itemsize)
            assert block % tile == 0 and block >= tile
            held = block * d * (6 * itemsize
                                + 4 * pallas_norm.F32_TEMPORARIES)
            assert held <= pallas_norm.VMEM_BUDGET
            # the largest such multiple
            assert held + tile * d * (6 * itemsize + 20) \
                > pallas_norm.VMEM_BUDGET
    assert pallas_norm.block_rows(16384, 768, 2) == 336
    assert pallas_norm.block_rows(32768, 768, 2) == 336
    # never more rows than there are, rounded up to a whole tile
    assert pallas_norm.block_rows(16, 768, 4) == 16
    assert pallas_norm.block_rows(20, 768, 2) == 32


@pytest.mark.parametrize("d,kernel", [(128, True), (768, True),
                                      (1024, True), (64, False),
                                      (192, False), (200, False),
                                      (8, False)])
def test_where_the_line_between_kernel_and_composed_form_is(
        monkeypatch, d, kernel):
    """A last axis of whole 128-lane tiles takes the kernel on a TPU;
    any other takes the composed form, as everything does off it."""
    assert pallas_norm.supported(d, 4) is kernel
    called = []
    real = pallas_norm.hetu_layer_norm_bwd

    def spy(*args, **kwargs):
        called.append(kwargs)
        return real(*args, **kwargs)
    monkeypatch.setattr(pallas_norm, "hetu_layer_norm_bwd", spy)
    monkeypatch.setattr(pallas_norm, "INTERPRET", True)
    x_node, s_node, dy_node = [ht.Variable(n, trainable=False)
                               for n in ("x", "s", "dy")]
    op = ht.layer_normalization_gradient_op(dy_node, x_node, s_node, None,
                                            EPS)
    dy, x, scale = _inputs((24, d), jnp.float32)
    off_tpu = op.compute([dy, x, scale], None)
    assert not called
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    on_tpu = op.compute([dy, x, scale], None)
    assert bool(called) is kernel
    if kernel:
        assert called == [{"eps": EPS, "interpret": True}]
    for a, b in zip(on_tpu, off_tpu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d,itemsize,kernel", [
    (16384, 2, True), (16384 + 128, 2, False),
    (23808, 4, True), (23808 + 128, 4, False), (32768, 4, False)])
def test_a_row_too_wide_for_one_tile_takes_the_composed_form(
        d, itemsize, kernel):
    """The smallest block is one sublane tile of rows (16 of bfloat16, 8
    of float32); where that alone passes ``VMEM_BUDGET`` the kernel
    would not compile, and the width is not supported."""
    assert pallas_norm.supported(d, itemsize) is kernel
    if kernel:
        tile = pallas_norm.block_rows(10 ** 6, d, itemsize)
        assert tile == 8 * 4 // itemsize
        assert tile * d * (6 * itemsize + 4 * pallas_norm.F32_TEMPORARIES) \
            <= pallas_norm.VMEM_BUDGET


@pytest.mark.parametrize("devices,kernel", [(None, True), (1, True),
                                            (4, False)])
def test_a_step_that_a_mesh_partitions_takes_the_composed_form(
        monkeypatch, devices, kernel):
    """GSPMD cannot split a Mosaic kernel: under a mesh of more than
    one device (``ectx.config.mesh``, the Executor's and a pipeline
    stage's alike) the op stays with the composed form on a TPU too."""
    called = []
    monkeypatch.setattr(pallas_norm, "hetu_layer_norm_bwd",
                        lambda *a, **k: called.append(k) or (None,) * 3)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    mesh = devices and jax.sharding.Mesh(
        np.asarray(jax.devices()[:devices]), ("dp",))
    ectx = types.SimpleNamespace(config=types.SimpleNamespace(mesh=mesh))
    nodes = [ht.Variable(n, trainable=False) for n in ("dy", "x", "s")]
    op = ht.layer_normalization_gradient_op(nodes[0], nodes[1], nodes[2],
                                            None, EPS)
    op.compute(list(_inputs((16, 128), jnp.float32)), ectx)
    assert bool(called) is kernel


def test_a_data_parallel_step_runs_without_the_kernel(monkeypatch):
    """The Executor's dp route end to end with the TPU's rule in force:
    the same three gradients as one device gives, and no kernel call in
    the partitioned trace."""
    from hetu_tpu.executor import Executor, HetuConfig
    called = []
    real = pallas_norm.hetu_layer_norm_bwd
    monkeypatch.setattr(
        pallas_norm, "hetu_layer_norm_bwd",
        lambda *a, **k: called.append(k) or real(*a, **k))
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(pallas_norm, "INTERPRET", True)
    rng = np.random.RandomState(5)
    xv = rng.randn(8, 6, 128).astype(np.float32)
    wv = rng.randn(8, 6, 128).astype(np.float32)

    def run(mesh):
        x = ht.Variable("x", trainable=False)
        w = ht.Variable("w", trainable=False)
        scale = ht.Variable("scale", value=np.ones(128, np.float32))
        bias = ht.Variable("bias", value=np.zeros(128, np.float32))
        y = ht.layer_normalization_op(x, scale, bias, eps=EPS)
        loss = ht.reduce_sum_op(ht.mul_op(y, w), axes=[0, 1, 2])
        grads = ht.gradients(loss, [scale, bias])
        if mesh is None:
            exe = Executor(grads, ctx=ht.cpu(0))
        else:
            config = HetuConfig(eval_node_list=grads, mesh=mesh)
            config.nrank = mesh.size
            exe = Executor({"default": grads}, config=config)
        return [r.asnumpy() for r in exe.run(feed_dict={x: xv, w: wv})]

    one = run(None)
    assert len(called) == 1
    four = run(jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("dp",)))
    assert len(called) == 1
    for a, b in zip(four, one):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(6, 20, 128), (40, 256)], ids=str)
def test_graph_route_gives_the_three_gradients_it_gave(monkeypatch, shape):
    """``ht.gradients`` through ``layer_normalization_op`` in an
    Executor: the packed op and its three unpack ops, composed form
    against kernel."""
    rng = np.random.RandomState(3)
    xv = rng.randn(*shape).astype(np.float32) * 2 + 0.5
    sv = 1 + 0.1 * rng.randn(shape[-1]).astype(np.float32)
    bv = 0.1 * rng.randn(shape[-1]).astype(np.float32)
    wv = rng.randn(*shape).astype(np.float32)

    def run():
        x = ht.Variable("x", value=xv)
        scale = ht.Variable("scale", value=sv)
        bias = ht.Variable("bias", value=bv)
        w = ht.Variable("w", value=wv, trainable=False)
        y = ht.layer_normalization_op(x, scale, bias, eps=EPS)
        loss = ht.reduce_sum_op(ht.mul_op(y, w),
                                axes=list(range(len(shape))))
        grads = ht.gradients(loss, [x, scale, bias])
        assert [type(g).__name__ for g in grads] == [
            "LayerNormalizationGradientOfDataOp",
            "LayerNormalizationGradientOfScaleOp",
            "LayerNormalizationGradientOfBiasOp"]
        assert len({id(g.inputs[0]) for g in grads}) == 1   # one packed op
        exe = ht.Executor(grads, ctx=ht.cpu(0))
        return [r.asnumpy() for r in exe.run()]

    composed = run()
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(pallas_norm, "INTERPRET", True)
    kernel = run()
    truth = _truth(jnp.asarray(wv), jnp.asarray(xv), jnp.asarray(sv))
    for k, c, t in zip(kernel, composed, truth):
        assert k.shape == c.shape == t.shape
        np.testing.assert_allclose(k, c, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(k, np.asarray(t), rtol=1e-4, atol=1e-4)


def test_the_column_sums_accumulate_in_float32():
    """32,768 rows of a constant in bfloat16: dbias is 32768 in every
    column. An accumulator that is itself bfloat16 stops at 256 (256 + 1
    rounds back to 256), whatever the order of a row-by-row sum."""
    rows, d = 32768, 128
    dy = jnp.ones((rows, d), jnp.bfloat16)
    x = jnp.tile(jnp.asarray([1.0, -1.0] * (d // 2), jnp.bfloat16),
                 (rows, 1))                  # mean 0, variance 1: xhat = x
    scale = jnp.ones((d,), jnp.bfloat16)
    stuck, _ = jax.lax.scan(lambda acc, row: (acc + row, None),
                            jnp.zeros((d,), jnp.bfloat16), dy)
    assert np.all(np.asarray(stuck, np.float32) == 256.0)
    _, dscale, dbias = pallas_norm.hetu_layer_norm_bwd(
        dy, x, scale, eps=EPS, interpret=True)
    assert dbias.dtype == dscale.dtype == jnp.bfloat16
    assert np.all(np.asarray(dbias, np.float32) == 32768.0)
    np.testing.assert_array_equal(np.asarray(dscale, np.float32),
                                  32768.0 * np.asarray(x[0], np.float32))
    # and the composed form asks for the same sums
    _, ref_dscale, ref_dbias = layer_norm_backward_reference(
        dy.astype(jnp.float32), x.astype(jnp.float32),
        scale.astype(jnp.float32), EPS)
    np.testing.assert_allclose(np.asarray(ref_dbias), 32768.0)
    np.testing.assert_allclose(np.abs(np.asarray(ref_dscale)), 32768.0,
                               rtol=1e-4)
