"""``ops/kda.py``: the chunked delta rule and its one-token step against
a token-by-token NumPy spelling of the recurrence, composed and through
the (interpreted) kernels."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops import kda


def scan(q, k, v, g, beta, s0, lengths):
    """The recurrence a token at a time, float64: ``(o [B, T, H, dv],
    S^T [B, H, dv, dk])`` at each row's last real token."""
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    rows, t, heads, _ = k.shape
    out = np.zeros(v.shape)
    states = np.array(s0, np.float64).transpose(0, 1, 3, 2)    # [dk, dv]
    for r in range(rows):
        for h in range(heads):
            s = states[r, h]
            for i in range(int(lengths[r])):
                s = np.exp(g[r, i, h])[:, None] * s
                s = s + beta[r, i, h] * np.outer(
                    k[r, i, h], v[r, i, h] - s.T @ k[r, i, h])
                out[r, i, h] = s.T @ q[r, i, h]
            states[r, h] = s
    return out, states.transpose(0, 1, 3, 2)


def inputs(seed, rows, t, heads, dk, dv, strong=False):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(rows, t, heads, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(rows, t, heads, dk)) * dk ** -0.5
    v = rng.normal(size=(rows, t, heads, dv))
    # log-decays over the whole of [-5, 0]; ``strong``: every one at -5
    g = np.full((rows, t, heads, dk), -5.0) if strong \
        else -5.0 * rng.uniform(size=(rows, t, heads, dk)) ** 3
    beta = rng.uniform(size=(rows, t, heads))
    s0 = rng.normal(size=(rows, heads, dv, dk))
    return [np.asarray(a, np.float32) for a in (q, k, v, g, beta, s0)]


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
@pytest.mark.parametrize("lengths", [(40, 7), (96, 33), (1, 0)])
def test_chunk_form_is_the_token_scan_over_ragged_rows(monkeypatch, chunk,
                                                       lengths):
    monkeypatch.setattr(kda, "CHUNK", chunk)
    t = 96
    q, k, v, g, beta, s0 = inputs(chunk + lengths[0], 2, t, 2, 32, 16)
    lengths = np.asarray(lengths, np.int32)
    o, s = kda.kda_chunk(q, k, v, g, beta, s0, jnp.asarray(lengths))
    want_o, want_s = scan(q, k, v, g, beta, s0, lengths)
    assert close(s, want_s)
    for r, n in enumerate(lengths):
        if n:
            assert close(o[r, :n], want_o[r, :n])
    # a row with no real token keeps the state it came with
    if lengths[1] == 0:
        np.testing.assert_array_equal(np.asarray(s[1]), s0[1])


def test_chunk_form_holds_at_the_strongest_decay_and_equal_keys():
    """Every log-decay at the bound (e^-320 over a chunk) and every key
    the same (the triangular system at its worst)."""
    q, k, v, g, beta, s0 = inputs(3, 1, 64, 1, 32, 16, strong=True)
    lengths = np.asarray([64], np.int32)
    o, s = kda.kda_chunk(q, k, v, g, beta, s0, jnp.asarray(lengths))
    want_o, want_s = scan(q, k, v, g, beta, s0, lengths)
    assert close(o, want_o) and close(s, want_s)
    k[:] = k[:, :1]
    g[:] = -1e-3
    beta[:] = 1.0
    o, s = kda.kda_chunk(q, k, v, g, beta, s0, jnp.asarray(lengths))
    want_o, want_s = scan(q, k, v, g, beta, s0, lengths)
    assert close(o, want_o, 1e-4) and close(s, want_s, 1e-4)


@pytest.mark.parametrize("chunk", [64, 128])
def test_chunk_kernel_is_the_composed_form(monkeypatch, chunk):
    q, k, v, g, beta, s0 = inputs(5, 2, 256, 2, 128, 128)
    lengths = jnp.asarray([200, 129], jnp.int32)
    monkeypatch.setattr(kda, "CHUNK", chunk)
    composed = kda.kda_chunk(q, k, v, g, beta, s0, lengths)
    monkeypatch.setattr(kda, "INTERPRET", True)
    kernel = kda.kda_chunk(q, k, v, g, beta, s0, lengths)
    want_o, want_s = scan(q, k, v, g, beta, s0, np.asarray(lengths))
    for got in (composed, kernel):
        assert close(got[1], want_s)
        assert close(got[0][0, :200], want_o[0, :200])
        assert close(got[0][1, :129], want_o[1, :129])


@pytest.mark.parametrize("kernel", [False, True])
def test_step_is_one_token_of_the_scan_in_place(monkeypatch, kernel):
    heads, d = 2, 128
    q, k, v, g, beta, s0 = inputs(9, 3, 1, heads, d, d)
    monkeypatch.setattr(kda, "INTERPRET", kernel)
    pool = np.random.default_rng(1).normal(
        size=(5, 2, heads, d, d)).astype(np.float32)
    slots = np.asarray([3, 1, 0], np.int32)
    pool[slots, 1] = s0
    o, new = kda.kda_step(jnp.asarray(pool), jnp.asarray(slots), 1,
                          q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    want_o, want_s = scan(q, k, v, g, beta, s0, np.ones(3, np.int32))
    assert close(o, want_o[:, 0])
    assert close(np.asarray(new)[slots, 1], want_s)
    # the other layer and the other slots are as they were
    new = np.asarray(new)
    np.testing.assert_array_equal(new[:, 0], pool[:, 0])
    np.testing.assert_array_equal(new[[2, 4]], pool[[2, 4]])


def test_prefill_then_steps_continue_the_scan():
    """A prompt through the chunk form, then a token at a time through a
    slot: the whole is one scan."""
    heads, d, p, t = 1, 32, 21, 26
    q, k, v, g, beta, s0 = inputs(11, 1, t, heads, d, d)
    s0[:] = 0
    want_o, _ = scan(q, k, v, g, beta, s0, np.asarray([t]))
    pad = 32 - p
    padded = [np.concatenate([a[:, :p], np.repeat(a[:, p - 1:p], pad, 1)],
                             axis=1) for a in (q, k, v, g, beta)]
    o, s = kda.kda_chunk(*padded, s0, jnp.asarray([p], jnp.int32))
    assert close(o[0, :p], want_o[0, :p])
    pool = jnp.zeros((2, 1, heads, d, d), jnp.float32).at[1, 0].set(s[0])
    for i in range(p, t):
        o, pool = kda.kda_step(pool, jnp.asarray([1], jnp.int32), 0,
                               q[:, i], k[:, i], v[:, i], g[:, i],
                               beta[:, i])
        assert close(o[0], want_o[0, i])


def test_sub_block_follows_the_bound_on_the_decay():
    assert kda._sub_block(64) == 16
    assert kda._sub_block(64, -20.0) == 4
    assert kda._sub_block(8) == 8
    assert kda.supported(128, 128) is None
    assert kda.supported(64, 128)


def test_a_traced_call_says_which_form_it_runs_in():
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry.check import check_args
    old = telemetry._default
    tel = telemetry.configure(enabled=True, service="test-kda-plan")
    try:
        a = inputs(2, 1, 16, 1, 128, 128)
        lengths = jnp.asarray([16], jnp.int32)
        kda.kda_chunk(*a, lengths)
        kda.INTERPRET = True
        kda.kda_chunk(*a, lengths)
        b = inputs(2, 1, 16, 1, 32, 16)
        kda.kda_chunk(*b, lengths)
    finally:
        kda.INTERPRET = False
        telemetry._default = old
    plans = [e["args"] for e in tel.tracer.drain(clear=True)
             if e.get("name") == "kda_plan"]
    assert [p["form"] for p in plans] == ["composed", "kernel", "composed"]
    assert plans[0]["reason"] == "platform" and "reason" not in plans[1]
    assert "lane block" in plans[2]["reason"]
    assert all(check_args("kda_plan", p) == [] for p in plans)
    assert check_args("kda_plan", {"form": "kernel"}) != []
