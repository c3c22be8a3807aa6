"""The expert layer's integer bookkeeping (``hetu_tpu/ops/moe.py``)
counts and picks by compare-and-sum: the rows of each held expert
(``_sorted_pairs``' ``sizes``) and a pick's score (``route``'s
``picked``) must be what the scatter-add and the gather they replaced
gave, as integers and as float32 BITS. The scatter and the gather stay
here as the reference. The router's weights, its flipped-picks counter
and its packed gradient are held to the values the tree BEFORE the
change gave on one seeded input (``PINNED``: sha256 of the arrays'
bytes, taken with ``python tests/test_moe_bookkeeping.py`` under
``JAX_PLATFORMS=cpu`` on commit b7b4321; the input is dyadic, so the
logits are exact under any order of summation).
"""
import hashlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE)):     # run as a script too
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import hetu_tpu as ht  # noqa: E402
from hetu_tpu.ops import moe  # noqa: E402

# (T, k, E, held, first): both train cells, a sarvam pass, a decode step
SHAPES = {
    "smallthinker": (8192, 6, 64, 16, 0),
    "lfm2": (8192, 4, 32, 8, 0),
    "sarvam_pass": (4096, 8, 128, 32, 32),
    "decode_step": (16, 8, 128, 32, 96),
}


def _routing(seed, t, k, experts):
    """``[t, k]`` int32: ``k`` distinct experts a token, seeded."""
    keys = np.random.RandomState(seed).rand(t, experts)
    return np.argsort(keys, axis=-1)[:, :k].astype(np.int32)


def _scattered_sizes(experts, valid, held_n, first, pad=0):
    """The parent's count: a scatter-add of one a pair."""
    local = experts - first
    held = (local >= 0) & (local < held_n) & valid[:, None]
    group = jnp.where(held, local, held_n).reshape(-1)
    sizes = jnp.zeros(held_n + 1, jnp.int32).at[group].add(1)
    return sizes.at[held_n].add(pad), jnp.argsort(group, stable=True)


def _check_sizes(experts, valid, held_n, first):
    order, sizes, held, rows = moe._sorted_pairs(
        jnp.asarray(experts), jnp.asarray(valid), held_n, first)
    pad = order.shape[0] - rows
    want, want_order = _scattered_sizes(jnp.asarray(experts),
                                        jnp.asarray(valid), held_n, first,
                                        pad)
    assert sizes.dtype == jnp.int32 and sizes.shape == (held_n + 1,)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(order[:rows]),
                                  np.asarray(want_order))
    assert int(sizes.sum()) == rows + pad
    assert int(sizes[:-1].sum()) == int(np.asarray(held).sum())
    return np.asarray(sizes), pad


@pytest.mark.parametrize("name", list(SHAPES))
def test_sizes_are_the_scatter_adds_at_the_cells_shapes(name):
    t, k, e, held_n, first = SHAPES[name]
    for seed in (0, 1):
        _check_sizes(_routing(seed, t, k, e), np.ones(t, bool), held_n, first)


def test_an_expert_that_gets_no_row_counts_zero():
    t, k, e, held_n, first = 64, 2, 16, 4, 4
    experts = _routing(3, t, k, e)
    experts[experts == 6] = 12           # held expert 2 gets nothing
    sizes, _ = _check_sizes(experts, np.ones(t, bool), held_n, first)
    assert sizes[2] == 0 and sizes[:-1].sum() > 0


def test_every_pair_held_elsewhere_is_the_last_group():
    t, k, e, held_n, first = 32, 4, 32, 8, 24
    experts = _routing(4, t, k, first)   # every pick below ``first``
    sizes, _ = _check_sizes(experts, np.ones(t, bool), held_n, first)
    assert sizes[:-1].sum() == 0 and sizes[-1] == t * k


def test_padded_tokens_are_routed_nowhere():
    t, k, e, held_n, first = 48, 3, 8, 8, 0     # every expert held
    experts = _routing(5, t, k, e)
    valid = np.arange(t) < 29
    sizes, _ = _check_sizes(experts, valid, held_n, first)
    assert sizes[:-1].sum() == 29 * k and sizes[-1] == (t - 29) * k


def test_the_kernel_paths_pad_rows_join_the_last_bin(monkeypatch):
    monkeypatch.setattr(moe, "INTERPRET", True)
    t, k, e, held_n, first = 5, 3, 8, 4, 2
    experts = _routing(6, t, k, e)
    sizes, pad = _check_sizes(experts, np.ones(t, bool), held_n, first)
    assert pad == 128 - t * k
    elsewhere = int(((experts < first) | (experts >= first + held_n)).sum())
    assert sizes[-1] == elsewhere + pad


# -- a pick's score ----------------------------------------------------------

def _gathered(scores, experts):
    """The parent's read of the chosen scores: a gather."""
    return jnp.take_along_axis(scores, experts, axis=-1)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("name", list(SHAPES))
def test_picked_is_the_gathered_score_bit_for_bit(name):
    t, k, e, _, _ = SHAPES[name]
    rs = np.random.RandomState(7)
    scores = jnp.asarray(rs.rand(t, e).astype(np.float32))
    experts = jnp.asarray(_routing(8, t, k, e))
    got = moe._picked(scores, experts)
    assert got.dtype == jnp.float32 and got.shape == (t, k)
    np.testing.assert_array_equal(_bits(got),
                                  _bits(_gathered(scores, experts)))


@pytest.mark.parametrize("name,n_group,topk_group,norm_eps", [
    ("lfm2", 1, 1, 1e-20), ("sarvam_pass", 1, 1, 0.0),
    ("sarvam_pass", 8, 4, 0.0), ("decode_step", 1, 1, 0.0)])
def test_route_is_the_gathering_routers_bit_for_bit(
        monkeypatch, name, n_group, topk_group, norm_eps):
    """``route`` against itself with the pick read by the gather, which
    is the parent's ``route`` line for line."""
    t, k, e, _, _ = SHAPES[name]
    hidden = 32
    rs = np.random.RandomState(9)
    x = jnp.asarray(rs.randn(t, hidden).astype(np.float32))
    w = jnp.asarray((rs.randn(hidden, e) * 0.3).astype(np.float32))
    bias = jnp.asarray((rs.randn(e) * 0.1).astype(np.float32))

    def routed():
        return jax.jit(lambda *a: moe.route(
            *a, k, 2.5, n_group, topk_group, norm_eps))(x, w, bias)

    got = routed()
    monkeypatch.setattr(moe, "_picked", _gathered)
    want = routed()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(_bits(got[1]), _bits(want[1]))
    np.testing.assert_array_equal(_bits(got[2]), _bits(want[2]))


# -- the graph's router against the parent's values --------------------------

PINNED = {
    "weights":
        "ffc096ee42a071b234da59e167064c012b5129725cf6e1d4d056dacfb393afea",
    "picks":
        "1459fed00202b9d14aa3db81d94e1ab7de8949a09702c99b2fe79682bc278849",
    "dx":
        "2b33745016e62e555f80d85a7280994e840f6c73dc2c897f56854c5957f3f07f",
    "dw":
        "607fc208b09991cc2fd3acfdb402cd7d6a83aeb40f92eeaeb24b1b2ec36fc70a",
    "route_weights":
        "50105e90e70f3dd408dac73620293404684d0c96a4221d56c49fd0795237baa1",
    "flipped_picks": 222,
}

S, HIDDEN, EXPERTS, TOP_K = 32, 64, 16, 4


def _pinned_case():
    """Dyadic inputs: every product is a multiple of 1/128 and a sum of
    64 of them is exact in float32 whatever the order."""
    rs = np.random.RandomState(62)
    x = rs.randint(-8, 9, (2, S, HIDDEN)).astype(np.float32) / 8
    w = rs.randint(-8, 9, (HIDDEN, EXPERTS)).astype(np.float32) / 16
    bias = rs.randint(-8, 9, (EXPERTS,)).astype(np.float32) / 32
    upstream = rs.randint(-8, 9, (2, S, TOP_K)).astype(np.float32) / 8
    return x, w, bias, upstream


def _digest(a):
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(str((a.dtype, a.shape)).encode()
                          + a.tobytes()).hexdigest()


def pinned_outputs():
    """{name: digest} of the sigmoid router's weights, picks, packed
    gradient ``(dx, dw)`` and, after two training steps, its counter."""
    x, w, bias, upstream = _pinned_case()
    nodes = [ht.Variable(n, trainable=False) for n in ("x", "w", "bias")]
    weights = ht.router_op(nodes[0], nodes[1], TOP_K, scoring="sigmoid",
                           bias=nodes[2], scale=1.5, norm_eps=1e-20)
    seed = ht.Variable("seed", trainable=False)
    grads = ht.gradients(weights, nodes[:2], insert_grad=seed)
    ex = ht.Executor([weights, ht.router_picks_op(weights)] + grads)
    out = [np.asarray(o.asnumpy()) for o in ex.run(feed_dict={
        nodes[0]: x, nodes[1]: w, nodes[2]: bias, seed: upstream})]
    found = dict(zip(("weights", "picks", "dx", "dw"), map(_digest, out)))
    found["route_weights"] = _digest(moe.route(
        jnp.asarray(x.reshape(-1, HIDDEN)), jnp.asarray(w),
        jnp.asarray(bias), TOP_K, 1.5, norm_eps=1e-20)[1])

    x_n = ht.Variable("x", trainable=False)
    w_n = ht.Variable("w_r", value=w)
    b_n = ht.Variable("b_r", value=bias, trainable=False)
    counted = ht.router_op(x_n, w_n, TOP_K, scoring="sigmoid", bias=b_n)
    loss = ht.reduce_mean_op(counted, [0, 1, 2])
    train = ht.optim.SGDOptimizer(learning_rate=0.0).minimize(loss)
    ex = ht.Executor([loss, train], seed=1)
    for _ in range(2):
        ex.run(feed_dict={x_n: x})
    found["flipped_picks"] = int(
        ex.state[str(counted.id)]["moe_bias_flipped_picks"])
    return found


@pytest.fixture(scope="module")
def outputs():
    return pinned_outputs()


@pytest.mark.parametrize("name", ["weights", "picks", "dx", "dw",
                                  "route_weights", "flipped_picks"])
def test_the_graphs_router_gives_the_parents_values(outputs, name):
    assert outputs[name] == PINNED[name]


if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    import pprint
    pprint.pprint(pinned_outputs())
