"""Manifold-constrained hyper-connections (``hetu_tpu/ops/mhc.py``):
the maps, the two kernels (interpreted here; their real tiles are
compiled for the described chip in ``tests/test_latent_moe_serving.py``
and run on it by the benchmark's cell) and the composed form, against
the plain reference's own maps (``benchmark/reference/xing_mhc.py``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import xing_mhc as reference
from hetu_tpu import telemetry
from hetu_tpu.ops import mhc
from hetu_tpu.telemetry.check import check_args

N, C = 4, 128
CONFIG = {"hc_mult": N, "hc_eps": 1e-6, "hc_sinkhorn_iters": 20,
          "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
          "serve_dtype": "bfloat16"}
ARGS = (20, 1e-6, (-30.0, 30.0))


def seeded(seed=0, scale=(0.5, 0.5, 0.5), res_diag=2.0):
    rng = np.random.RandomState(seed)
    bias = np.concatenate([
        rng.randn(2 * N) * 0.5,
        (res_diag * np.eye(N) + rng.randn(N, N) * 0.5).reshape(-1)])
    maps = {"phi": jnp.asarray(rng.randn(N * C, 24) * 0.05, jnp.float32),
            "scale": jnp.asarray(scale, jnp.float32),
            "bias": jnp.asarray(bias, jnp.float32)}
    return dict(maps, kernel=mhc.prepare(**maps))


def rows(t, seed=1, dtype=jnp.bfloat16):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(t, N, C), dtype),
            jnp.asarray(rng.randn(t, C), dtype))


@pytest.fixture
def interpreted():
    mhc.INTERPRET = True
    yield
    mhc.INTERPRET = False


@pytest.fixture
def tel():
    """An enabled process-global telemetry, which sees the instants."""
    old = telemetry._default
    yield telemetry.configure(enabled=True, service="test-mhc-plan")
    telemetry._default = old


def plans(tel):
    return [e["args"] for e in tel.tracer.drain(clear=True)
            if e.get("name") == "mhc_plan"]


def unpack(carry):
    """``(Hpost, Hres)`` of either form's carry."""
    if isinstance(carry, tuple):
        return np.asarray(carry[0]), np.asarray(carry[1])
    carry = np.asarray(carry)
    return carry[:, N:2 * N], carry[:, 2 * N:2 * N + N * N].reshape(-1, N, N)


def test_hres_is_doubly_stochastic_after_20_iterations_and_not_after_1():
    w = seeded()
    x, _ = rows(64)
    _, _, res = mhc.maps(x, w["phi"], w["scale"], w["bias"], *ARGS)
    res = np.asarray(res)
    assert np.abs(res.sum(axis=1) - 1).max() < 1e-5
    assert np.abs(res.sum(axis=2) - 1).max() < 1e-4
    assert res.min() > 0 and res.max() < 1
    # far from uniform, so that a wrong projection shows
    assert np.abs(res - 0.25).max() > 0.2
    _, _, once = mhc.maps(x, w["phi"], w["scale"], w["bias"], 1, 1e-6,
                          ARGS[2])
    once = np.asarray(once)
    assert np.abs(once.sum(axis=1) - 1).max() < 1e-5      # columns last
    assert np.abs(once.sum(axis=2) - 1).max() > 1e-2


@pytest.mark.parametrize("form", ["composed", "kernel"])
def test_the_clamp_holds_where_b_res_is_large(form, request):
    if form == "kernel":
        request.getfixturevalue("interpreted")
    w = seeded()
    large = np.asarray(w["bias"]).copy()
    large[2 * N:] = (100.0 * (2 * np.eye(N) - 1)).reshape(-1)
    w = dict(w, bias=jnp.asarray(large))
    w["kernel"] = mhc.prepare(w["phi"], w["scale"], w["bias"])
    x, y = rows(16)
    u, carry = mhc.mhc_pre(x, w, *ARGS)
    _, res = unpack(carry)
    assert np.isfinite(res).all()
    # exp(30) on the diagonal against exp(-30) off it: the identity
    np.testing.assert_allclose(res, np.broadcast_to(np.eye(N), res.shape),
                               atol=1e-6)
    assert np.isfinite(np.asarray(
        mhc.mhc_post(x, y, carry).astype(jnp.float32))).all()
    # the reference with the clamp dropped overflows there
    bad = reference.hc_maps(x.astype(jnp.float32), w["phi"], w["scale"],
                            w["bias"], CONFIG, "no_clamp")[2]
    assert not np.isfinite(np.asarray(bad)).all()


@pytest.mark.parametrize("t", [1, 8, 13, 128, 200])
def test_kernel_equals_composed_equals_reference(t, interpreted):
    w = seeded(seed=t)
    x, y = rows(t, seed=t + 1)
    mhc.INTERPRET = False
    u_c, carry_c = mhc.mhc_pre(x, w, *ARGS)
    out_c = mhc.mhc_post(x, y, carry_c)
    mhc.INTERPRET = True
    u_k, carry_k = mhc.mhc_pre(x, w, *ARGS)
    out_k = mhc.mhc_post(x, y, carry_k)
    assert isinstance(carry_c, tuple) and not isinstance(carry_k, tuple)
    assert u_k.shape == (t, C) and out_k.shape == (t, N, C)
    with jax.default_matmul_precision("highest"):
        pre, post, res = reference.hc_maps(
            x.astype(jnp.float32), w["phi"], w["scale"], w["bias"], CONFIG)
        u_r = reference.read(x.astype(jnp.float32), pre)
        out_r = reference.write(x.astype(jnp.float32),
                                y.astype(jnp.float32), post, res,
                                jnp.bfloat16)
    for carry in (carry_c, carry_k):
        got_post, got_res = unpack(carry)
        np.testing.assert_allclose(got_post, np.asarray(post), atol=2e-6)
        np.testing.assert_allclose(got_res, np.asarray(res), atol=2e-6)

    def rms(a, b):
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        return np.sqrt(np.mean(np.square(a - b)) / np.mean(np.square(b)))

    # both round to bfloat16 what the reference holds in float32
    # (u) or rounds itself (X'): a rounding moved here and there
    for u, out in ((u_c, out_c), (u_k, out_k)):
        assert rms(u, u_r) < 3e-3
        assert rms(out, out_r) < 5e-4
    # maps rounded to bfloat16 would move nearly every rounding of X'
    lossy = reference.write(
        x.astype(jnp.float32), y.astype(jnp.float32),
        post.astype(jnp.bfloat16).astype(jnp.float32),
        res.astype(jnp.bfloat16).astype(jnp.float32), jnp.bfloat16)
    assert rms(lossy, out_r) > 1e-3


def test_padded_rows_change_nothing(interpreted):
    w = seeded()
    x, y = rows(24)
    u, carry = mhc.mhc_pre(x, w, *ARGS)
    out = mhc.mhc_post(x, y, carry)
    # the same rows with other rows (a batch bucket's padding) behind
    pad_x, pad_y = rows(40, seed=9)
    wide_x = jnp.concatenate([x, pad_x * 50])
    wide_y = jnp.concatenate([y, pad_y * 50])
    u2, carry2 = mhc.mhc_pre(wide_x, w, *ARGS)
    out2 = mhc.mhc_post(wide_x, wide_y, carry2)
    np.testing.assert_array_equal(np.asarray(u2[:24], np.float32),
                                  np.asarray(u, np.float32))
    np.testing.assert_array_equal(np.asarray(carry2)[:24, :24],
                                  np.asarray(carry)[:, :24])
    np.testing.assert_array_equal(np.asarray(out2[:24], np.float32),
                                  np.asarray(out, np.float32))


def test_the_split_phi_is_exact_to_float32():
    w = seeded()
    split, table = w["kernel"]
    assert split.shape == (N * C, 128) and split.dtype == jnp.bfloat16
    bands = np.asarray(split.astype(jnp.float32)).reshape(N * C, -1)
    back = bands[:, 0:24] + bands[:, 24:48] + bands[:, 48:72]
    np.testing.assert_array_equal(back, np.asarray(w["phi"]))
    assert not bands[:, 72:].any()
    table = np.asarray(table)
    assert np.ptp(table[:8]) == 0 and table[0, 0] == np.float32(0.5)
    assert np.ptp(table[8:24]) == 0 and table[8, 0] == np.float32(0.5)
    np.testing.assert_array_equal(table[128:152, 5], np.asarray(w["bias"]))
    assert mhc.prepared_bytes(N, C) == split.nbytes + table.nbytes


def test_supported_names_the_first_condition_that_failed():
    assert mhc.supported(4, 3584, jnp.bfloat16) is None
    assert mhc.supported(4, 3584, jnp.float32) == "dtype"
    assert mhc.supported(4, 100, jnp.bfloat16) == "lanes"
    assert mhc.supported(6, 128, jnp.bfloat16) == "streams"
    assert mhc.supported(1, 128, "bfloat16") is None
    assert mhc.map_width(4) == 24


@pytest.mark.parametrize("form,dtype,c,reason", [
    ("kernel", jnp.bfloat16, 128, None),
    ("composed", jnp.float32, 128, "dtype"),
    ("composed", jnp.bfloat16, 64, "lanes")])
def test_a_traced_call_says_which_form_it_runs_in(form, dtype, c, reason,
                                                  interpreted, tel):
    rng = np.random.RandomState(0)
    maps = {"phi": jnp.asarray(rng.randn(N * c, 24) * 0.05, jnp.float32),
            "scale": jnp.ones(3), "bias": jnp.zeros(24)}
    if form == "kernel":
        maps["kernel"] = mhc.prepare(**maps)
    x = jnp.asarray(rng.randn(8, N, c), dtype)
    jax.jit(lambda x, m: mhc.mhc_pre(x, m, *ARGS)[0])(x, maps)
    (args,) = plans(tel)
    assert args["form"] == form and args.get("reason") == reason
    assert args["streams"] == N and args["iters"] == 20
    assert check_args("mhc_plan", args) == []
    assert check_args("mhc_plan", {"streams": 4}) != []


def test_off_a_tpu_the_composed_form_runs_and_says_platform(tel):
    w = seeded()
    x, _ = rows(4)
    _, carry = mhc.mhc_pre(x, w, *ARGS)
    assert isinstance(carry, tuple)
    (args,) = plans(tel)
    assert args["form"] == "composed" and args["reason"] == "platform"
    assert not mhc.wants_prepared(N, C, jnp.bfloat16)
