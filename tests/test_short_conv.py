"""The gated short convolution as a graph op (``ops/short_conv.py``),
the dense SwiGLU unit (``ops/activations.py:swiglu_op``) and an RMS norm
a head: every value and every gradient through ``ht.Executor`` against
``jax.grad`` of the composed float32 form written out here — among the
shapes the first two positions alone (the zeros before t = 0), an S that
no tile divides and a tap count other than the published 3."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.ops import short_conv
from test_sparse_decoder import check, close, op_and_grads


def _plain_short_conv(proj, taps):
    """``C * conv(B * u)`` one tap at a time, by explicit index."""
    channels, k = taps.shape
    s = proj.shape[1]
    gate_in, gate_out, u = (proj[..., i * channels:(i + 1) * channels]
                            for i in range(3))
    z = gate_in * u
    v = jnp.zeros_like(z)
    for j in range(k):
        back = k - 1 - j
        if back < s:
            v = v.at[:, back:].add(taps[:, j] * z[:, :s - back])
    return gate_out * v


def _case(b, s, channels, k, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randn(b, s, 3 * channels).astype(np.float32),
            rs.uniform(-0.5, 0.5, (channels, k)).astype(np.float32)]


@pytest.mark.parametrize("b,s,channels,k", [
    (2, 7, 8, 3), (1, 33, 16, 3), (3, 2, 4, 3), (2, 1, 4, 3), (2, 9, 8, 4),
    (1, 5, 8, 1)])
def test_short_conv_value_and_gradients(b, s, channels, k):
    check(ht.short_conv_op, _plain_short_conv, _case(b, s, channels, k))


def test_the_first_positions_see_zeros_before_the_sequence():
    proj, taps = _case(1, 4, 4, 3, seed=2)
    (got,), _ = op_and_grads(ht.short_conv_op, [proj, taps],
                             np.ones((1, 4, 4), np.float32))
    z = proj[0, :, :4] * proj[0, :, 8:]
    gate = proj[0, :, 4:8]
    close(got[0], gate[0] * taps[:, 2] * z[0])
    close(got[1], gate[1] * (taps[:, 2] * z[1] + taps[:, 1] * z[0]))
    close(got[2], gate[2] * (taps[:, 2] * z[2] + taps[:, 1] * z[1]
                             + taps[:, 0] * z[0]))


def test_the_projection_gets_one_gradient_of_its_own_width():
    proj, taps = _case(2, 6, 8, 3, seed=3)
    upstream = np.random.RandomState(4).randn(2, 6, 8).astype(np.float32)
    _, (dproj, dtaps) = op_and_grads(ht.short_conv_op, [proj, taps],
                                     upstream)
    assert dproj.shape == proj.shape and dtaps.shape == taps.shape
    assert dtaps.dtype == np.float32


def test_short_conv_in_bfloat16_is_float32_between_read_and_write():
    proj, taps = _case(2, 16, 8, 3, seed=5)
    low = jnp.asarray(proj, jnp.bfloat16)
    got = short_conv.short_conv(low, jnp.asarray(taps))
    want = _plain_short_conv(low.astype(jnp.float32), jnp.asarray(taps))
    assert got.dtype == jnp.bfloat16
    # one rounding, of the result
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.bfloat16)
                                             .astype(jnp.float32)))
    dproj, dtaps = short_conv.short_conv_grads(
        low, jnp.asarray(taps), jnp.ones((2, 16, 8), jnp.bfloat16))
    assert dproj.dtype == jnp.bfloat16 and dtaps.dtype == jnp.float32


def test_short_conv_refuses_rows_of_another_width():
    with pytest.raises(ValueError, match="reads"):
        short_conv.short_conv(jnp.zeros((1, 4, 10)), jnp.zeros((4, 3)))


def test_the_two_directions_carry_their_names():
    assert short_conv._forward.__wrapped__.__name__ == "hetu_short_conv_fwd"
    assert short_conv._backward.__wrapped__.__name__ == "hetu_short_conv_bwd"
    text = jax.jit(lambda p, t: short_conv._forward(p, t)).lower(
        *map(jnp.asarray, _case(1, 8, 4, 3))).as_text()
    assert "hetu_short_conv_fwd" in text


# -- the dense SwiGLU unit and a norm a head ---------------------------------

def _plain_swiglu(h):
    width = h.shape[-1] // 2
    return jax.nn.silu(h[..., :width]) * h[..., width:]


@pytest.mark.parametrize("shape", [(2, 5, 12), (7, 6), (1, 3, 2)])
def test_swiglu_value_and_gradient(shape):
    h = np.random.RandomState(6).randn(*shape).astype(np.float32) * 2
    check(ht.swiglu_op, _plain_swiglu, [h])


def test_a_dense_swiglu_layer_against_jax_grad():
    rs = np.random.RandomState(7)
    x = rs.randn(6, 8).astype(np.float32)
    w_in = rs.randn(8, 24).astype(np.float32) * 0.3
    w_out = rs.randn(12, 8).astype(np.float32) * 0.3
    check(lambda x, a, b: ht.matmul_op(ht.swiglu_op(ht.matmul_op(x, a)), b),
          lambda x, a, b: _plain_swiglu(x @ a) @ b, [x, w_in, w_out])


@pytest.mark.parametrize("heads,d", [(8, 16), (2, 16), (3, 8)])
def test_a_norm_a_head_value_and_gradients(heads, d):
    """What ``models/hybrid_decoder.py`` does to q and k: rows reshaped
    to heads, an RMS norm over a head under one gain of ``d``."""
    rs = np.random.RandomState(8)
    rows = rs.randn(2, 5, heads * d).astype(np.float32)
    gain = (1.0 + 0.1 * rs.randn(d)).astype(np.float32)

    def build(rows, gain):
        by_head = ht.array_reshape_op(rows, [-1, 5, heads, d])
        return ht.array_reshape_op(
            ht.rms_normalization_op(by_head, gain, eps=1e-5),
            [-1, 5, heads * d])

    def plain(rows, gain):
        x = rows.reshape(2, 5, heads, d)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        return (x * gain).reshape(2, 5, heads * d)

    check(build, plain, [rows, gain])
