"""The sparse softmax cross-entropy pair (``hetu_tpu/ops/losses.py``)
through ``ht.Executor`` against a float32 reference written out here
(``logsumexp`` and a one-hot): 2-D and 3-D logits, float32 and bfloat16,
class counts that are not whole lanes, labels that hold the ignored
index everywhere, nowhere and in places — and the gradient op with and
without the forward's log-sum-exp as its residual."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.graph.node import ExecContext
from hetu_tpu.ops import losses

# 1,000 + 2 classes: 30,522-like, seven whole lane blocks and a rest
SHAPES = {"2d": (24, 1002), "3d": (3, 8, 1002), "nsp": (16, 2)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _case(shape, dtype, ignored, index=-1, seed=0):
    """Logits and upstream gradient as the Executor will hold them
    (rounded to ``dtype``), and labels of which ``ignored`` (all, none,
    some) hold ``index``."""
    rng = np.random.default_rng(seed)
    logits = (4.0 * rng.standard_normal(shape) + 1.5).astype(np.float32)
    grad = rng.standard_normal(shape[:-1]).astype(np.float32)
    labels = rng.integers(0, shape[-1], shape[:-1]).astype(np.int32)
    if index >= 0:      # a class of its own: drawn labels may hit it too
        labels[labels == index] = (index + 1) % shape[-1]
    drop = {"all": np.ones(shape[:-1], bool),
            "none": np.zeros(shape[:-1], bool),
            "some": rng.random(shape[:-1]) < 0.4}[ignored]
    labels[drop] = index
    as_held = lambda a: np.asarray(                       # noqa: E731
        jnp.asarray(a).astype(dtype).astype(jnp.float32))
    return as_held(logits), labels, as_held(grad)


def _reference(logits, labels, grad, index=-1):
    """float32 loss ``[rows]`` and d(sum(loss * grad)) / d(logits)."""
    nclass = logits.shape[-1]
    keep = labels != index
    at = np.clip(labels, 0, nclass - 1)
    onehot = jax.nn.one_hot(at, nclass, dtype=jnp.float32)
    lse = jax.nn.logsumexp(jnp.asarray(logits), axis=-1)
    loss = jnp.where(keep, lse - jnp.sum(onehot * logits, axis=-1), 0.0)
    d = (jax.nn.softmax(jnp.asarray(logits), axis=-1) - onehot) \
        * jnp.where(keep, grad, 0.0)[..., None]
    return np.asarray(loss), np.asarray(d)


def _placeholders():
    return [ht.Variable(n, trainable=False) for n in ("x", "y", "g")]


def _close(got, want, dtype):
    got = np.asarray(got)
    assert got.dtype == np.dtype(dtype)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    else:
        # one rounding of the result to bfloat16's 8 bits, on float32
        # arithmetic over the rounded inputs
        np.testing.assert_allclose(got.astype(np.float32), want,
                                   rtol=2.0 ** -8, atol=1e-6)


@pytest.mark.parametrize("ignored", ["all", "none", "some"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_forward_and_gradient_agree_with_the_float32_reference(
        shape, dtype, ignored):
    dtype = DTYPES[dtype]
    logits, labels, grad = _case(SHAPES[shape], dtype, ignored)
    x, y, g = _placeholders()
    loss = ht.softmaxcrossentropy_sparse_op(x, y)
    total = ht.reduce_sum_op(ht.mul_op(loss, g),
                             list(range(logits.ndim - 1)))
    dx, = ht.gradients(total, [x])
    executor = ht.Executor(
        [loss, dx], ctx=ht.cpu(0),
        dtype=None if dtype == jnp.float32 else dtype)
    got_loss, got_dx = executor.run(
        feed_dict={x: logits, y: labels, g: grad},
        convert_to_numpy_ret_vals=True)
    want_loss, want_dx = _reference(logits, labels, grad)
    assert got_loss.shape == labels.shape and got_dx.shape == logits.shape
    _close(got_loss, want_loss, dtype)
    _close(got_dx, want_dx, dtype)
    if ignored == "all":
        assert not got_loss.any() and not got_dx.any()


@pytest.mark.parametrize("index", [-1, -100, 3])
def test_the_ignored_index_is_the_ops_own(index):
    """Whatever the index — one below the classes or a class itself —
    its rows score 0 and send nothing back; a label beyond the classes
    that is NOT ignored picks the last class, as the gather it replaces
    clipped it."""
    logits, labels, grad = _case(SHAPES["2d"], jnp.float32, "some", index)
    labels[0] = logits.shape[-1] + 5
    x, y, g = _placeholders()
    loss = ht.softmaxcrossentropy_sparse_op(x, y, ignored_index=index)
    dx, = ht.gradients(ht.reduce_sum_op(ht.mul_op(loss, g), [0]), [x])
    got = ht.Executor([loss, dx], ctx=ht.cpu(0)).run(
        feed_dict={x: logits, y: labels, g: grad},
        convert_to_numpy_ret_vals=True)
    for have, want in zip(got, _reference(logits, labels, grad, index)):
        _close(have, want, jnp.float32)
    assert not got[0][labels == index].any()
    assert got[0][0] > 0 and got[1][0].any()


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("shape", ["2d", "3d"])
def test_the_gradient_op_is_the_same_with_and_without_the_residual(
        monkeypatch, shape, dtype):
    """One graph holds the forward, its gradient op as autodiff builds
    it (``forward_op`` set: the forward's ``lse`` out of ``ectx.cache``)
    and a gradient op built alone (it computes ``lse`` by the same
    function): the same array to the bit, and the log-sum-exp traced
    twice for the three ops, not three times."""
    dtype = DTYPES[dtype]
    logits, labels, grad = _case(SHAPES[shape], dtype, "some", seed=3)
    traced = []
    real = losses._log_sum_exp
    monkeypatch.setattr(losses, "_log_sum_exp",
                        lambda a: traced.append(a.shape) or real(a))
    x, y, g = _placeholders()
    loss = ht.softmaxcrossentropy_sparse_op(x, y)
    with_residual, = loss.gradient(g)[:1]
    alone = ht.softmaxcrossentropy_sparse_gradient_op(x, y, g)
    assert with_residual.forward_op is loss and alone.forward_op is None
    executor = ht.Executor(
        [loss, with_residual, alone], ctx=ht.cpu(0),
        dtype=None if dtype == jnp.float32 else dtype)
    _, got_with, got_alone = executor.run(
        feed_dict={x: logits, y: labels, g: grad},
        convert_to_numpy_ret_vals=True)
    assert traced == [logits.shape] * 2
    np.testing.assert_array_equal(got_with, got_alone)
    _close(got_with, _reference(logits, labels, grad)[1], dtype)


def test_a_gradient_op_whose_forward_ran_in_no_trace_computes_lse_itself():
    """``forward_op`` names an op this trace never computed (a subgraph
    that evaluates the gradient alone): no residual, the same result."""
    logits, labels, grad = _case(SHAPES["3d"], jnp.float32, "some", seed=5)
    x, y, g = _placeholders()
    loss = ht.softmaxcrossentropy_sparse_op(x, y)
    dx = loss.gradient(g)[0]
    got, = ht.Executor([dx], ctx=ht.cpu(0)).run(
        feed_dict={x: logits, y: labels, g: grad},
        convert_to_numpy_ret_vals=True)
    _close(got, _reference(logits, labels, grad)[1], jnp.float32)


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_the_forward_differentiates_to_the_gradient_op(dtype):
    """A pipeline stage takes ``jax.vjp`` of the ops' ``compute``
    (``parallel/pipeline.py``): the forward's own derivative is what the
    gradient op returns."""
    dtype = DTYPES[dtype]
    logits, labels, grad = _case(SHAPES["3d"], dtype, "some", seed=7)
    x, y, g = _placeholders()
    loss = ht.softmaxcrossentropy_sparse_op(x, y)
    held = jnp.asarray(logits).astype(dtype)

    def scored(a):
        out = loss.compute([a, labels], ExecContext(training=True))
        return jnp.sum(out.astype(jnp.float32) * grad)
    got = jax.grad(scored)(held)
    want = loss.gradient(g)[0].compute(
        [held, labels, jnp.asarray(grad).astype(dtype)],
        ExecContext(training=True))
    assert got.dtype == want.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)),
        rtol=2e-5 if dtype == jnp.float32 else 2.0 ** -7, atol=1e-6)


def test_no_gather_and_one_exponential_pass_a_direction():
    """What the pair traces to: no ``gather`` (the label's logit is a
    masked reduction), and with the residual the gradient holds ONE
    ``exp`` and no reduction over the classes at all."""
    logits = jnp.zeros((4, 6, 1002), jnp.bfloat16)
    labels = jnp.zeros((4, 6), jnp.int32)
    x, y, g = _placeholders()
    loss = ht.softmaxcrossentropy_sparse_op(x, y)
    dx = loss.gradient(g)[0]
    ectx = ExecContext(training=True)
    forward = str(jax.make_jaxpr(
        lambda a, b: loss.compute([a, b], ectx))(logits, labels))
    assert "gather" not in forward and forward.count(" exp ") == 1
    lse = jnp.zeros((4, 6), jnp.float32)

    def backward(a, b, c, residual):
        ectx.cache[("sparse_ce_lse", loss.id)] = residual
        return dx.compute([a, b, c], ectx)
    text = str(jax.make_jaxpr(backward)(
        logits, labels, jnp.zeros((4, 6), jnp.bfloat16), lse))
    assert "gather" not in text and text.count(" exp ") == 1
    assert "reduce_" not in text and "argmax" not in text
