"""The optimizer's sparse row update (PR 51): ``hetu_sparse_rows_update``
in interpret mode against the composed ``jax.numpy`` form, both against
a plain numpy lazy update; which tables take which path, and that a
whole executor trains the same on both.

The composed form is the parent's arithmetic (``optimizer.py``'s rules,
traced as they stand into both paths), so on the CPU it is bit-identical
to the parent's on every case but one: the parent folded ``dedup``'s
padding onto the table's LAST row, whose slots then kept their old
values whenever that row was looked up beside a duplicate id.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import optimizer as optim
from hetu_tpu.ndarray import IndexedSlices
from hetu_tpu.ops import attention, pallas_sparse_update as kernel
from hetu_tpu.telemetry import Telemetry
from hetu_tpu.telemetry.check import check_args

V = 50          # not whole row groups: the last group is ragged
LR, STEP = 0.01, 3

RULES = {
    "sgd": (optim.SGDOptimizer, {}),
    "adagrad": (optim.AdaGradOptimizer, {"initial_accumulator_value": 0.1}),
    "adam": (optim.AdamOptimizer, {}),
    "amsgrad": (optim.AdamOptimizer, {"amsgrad": True}),
    "adamw": (optim.AdamWOptimizer, {}),
}

IDS = {
    "distinct": np.random.RandomState(1).permutation(V)[:16].reshape(2, 8),
    "duplicates": np.random.RandomState(2).randint(0, 5, (2, 8)),
    # 3 distinct ids in 16 slots: 13 padding sentinels
    "padding": np.array([[7, 7, 7, 7, 9, 9, 9, 9], [7, 7, 7, 7, 8, 8, 8, 8]]),
    "last_row": np.array([[V - 1, 3, 3, 3, 7, V - 1, 0, 1]]),
    "out_of_range": np.array([[V + 5, 3, V, 3, 7, V - 2, 0, 1]]),
}


class _Step:
    """What ``sparse_update_path`` reads of a step's context."""
    def __init__(self, mesh=None):
        self.config = type("Config", (), {"mesh": mesh})()


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The kernel's path without a TPU: the platform rule answers yes
    and the kernel is interpreted."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(kernel, "INTERPRET", True)


def _problem(rule, width, ids, seed=0):
    cls, kw = RULES[rule]
    opt = cls(LR, **kw)
    rng = np.random.RandomState(seed)
    param = rng.randn(V, width).astype(np.float32)
    node = type("Node", (), {"id": 1, "name": "table"})
    slots = {name: np.abs(rng.randn(V, width)).astype(np.float32)
             for name in opt.init_state({node: param}).get(1, {})}
    values = rng.randn(*ids.shape, width).astype(np.float32)
    return opt, param, slots, values


def _update(opt, param, slots, ids, values, site):
    grad = IndexedSlices(jnp.asarray(ids.astype(np.int32)),
                         jnp.asarray(values), param.shape)
    new, out = opt.update_one(
        jnp.asarray(param), grad,
        {k: jnp.asarray(v) for k, v in slots.items()}, LR, STEP, site)
    return np.asarray(new), {k: np.asarray(v) for k, v in out.items()}


def _lazy_numpy(rule, opt, param, slots, ids, values):
    """The dense rule on the touched rows only, in float64."""
    g = np.zeros(param.shape, np.float64)
    touched = np.zeros(len(param), bool)
    for i, v in zip(ids.reshape(-1), values.reshape(-1, param.shape[1])):
        if 0 <= i < len(param):
            g[i] += v
            touched[i] = True
    p = param.astype(np.float64)
    s = {k: v.astype(np.float64) for k, v in slots.items()}
    if rule == "sgd":
        p = p - LR * g
    elif rule == "adagrad":
        s["accum"] = s["accum"] + g * g
        p = p - LR * g / (np.sqrt(s["accum"]) + opt.eps)
    else:
        s["m"] = opt.beta1 * s["m"] + (1 - opt.beta1) * g
        s["v"] = opt.beta2 * s["v"] + (1 - opt.beta2) * g * g
        vhat = s["v"]
        if "vmax" in s:
            vhat = s["vmax"] = np.maximum(s["vmax"], s["v"])
        t = STEP + 1
        scale = LR * np.sqrt(1 - opt.beta2 ** t) / (1 - opt.beta1 ** t)
        p = p - scale * s["m"] / (np.sqrt(vhat) + opt.epsilon)
    keep = ~touched[:, None]
    p = np.where(keep, param, p)
    s = {k: np.where(keep, slots[k], v) for k, v in s.items()}
    return p, s, touched


@pytest.mark.parametrize("ids", list(IDS))
@pytest.mark.parametrize("width", [128, 768, 2560])
@pytest.mark.parametrize("rule", list(RULES))
def test_the_kernel_updates_the_looked_up_rows_and_no_other(
        on_a_tpu, rule, width, ids):
    """New parameter and every slot: the kernel's equal the composed
    form's to 1e-6 on the rows a step looked up and are the OLD bits
    everywhere else; both equal the numpy lazy update."""
    ids = IDS[ids]
    opt, param, slots, values = _problem(rule, width, ids)
    assert optim.sparse_update_path(
        jnp.asarray(param), slots, ("table", _Step())) == ("kernel", None)
    got_p, got_s = _update(opt, param, slots, ids, values,
                           ("table", _Step()))
    want_p, want_s = _update(opt, param, slots, ids, values, None)
    ref_p, ref_s, touched = _lazy_numpy(rule, opt, param, slots, ids,
                                        values)
    assert got_s.keys() == want_s.keys() == slots.keys()
    pairs = [(got_p, want_p, ref_p, param)] + [
        (got_s[k], want_s[k], ref_s[k], slots[k]) for k in slots]
    # composed, SGD adds duplicates up inside its scatter, each scaled
    # by the rate first: another order of additions than dedup's
    rtol = 5e-6 if rule == "sgd" else 1e-6
    for got, want, ref, old in pairs:
        np.testing.assert_array_equal(got[~touched], old[~touched])
        np.testing.assert_array_equal(want[~touched], old[~touched])
        np.testing.assert_allclose(got[touched], want[touched],
                                   rtol=rtol, atol=1e-6 * np.abs(want).max())
        np.testing.assert_allclose(want[touched], ref[touched],
                                   rtol=2e-5, atol=1e-6 * np.abs(ref).max())
        assert not np.array_equal(got[touched], old[touched])


@pytest.mark.parametrize("rule", ["adagrad", "adam"])
def test_the_last_row_keeps_its_update_beside_padding(rule):
    """The composed form drops ``dedup``'s padding ids; folded onto the
    last row (the parent), they wrote that row's OLD slots over its new
    ones whenever it was looked up in a batch with a duplicate id."""
    ids = IDS["last_row"]
    opt, param, slots, values = _problem(rule, 16, ids)
    _, new = _update(opt, param, slots, ids, values, None)
    for name in slots:
        assert not np.array_equal(new[name][V - 1], slots[name][V - 1])


@pytest.mark.parametrize("width,rows,mesh,dtype,reason", [
    (4, V, None, np.float32, "lanes"), (16, V, None, np.float32, "lanes"),
    (100, V, None, np.float32, "lanes"), (768, 2, None, np.float32, "rows"),
    (128, V, "dp", np.float32, "mesh"), (128, V, None, jnp.bfloat16, "dtype"),
])
def test_a_table_the_kernel_does_not_take_is_composed_and_says_why(
        on_a_tpu, width, rows, mesh, dtype, reason):
    if mesh:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("dp",))
    param = jnp.zeros((rows, width), dtype)
    slots = {"m": param, "v": param}
    site = ("table", _Step(mesh))
    assert optim.sparse_update_path(param, slots, site) == \
        ("composed", reason)
    # and the update runs, composed: no row but the looked-up ones moves
    ids = np.array([[1, 1, 0]])
    new, _ = _update(optim.AdamOptimizer(LR), np.asarray(param),
                     {k: np.asarray(v) for k, v in slots.items()}, ids,
                     np.ones((1, 3, width), np.asarray(param).dtype), site)
    assert np.all(new[:2] != 0) and not np.any(new[2:])


def test_off_a_tpu_or_without_a_step_every_table_is_composed():
    param = jnp.zeros((V, 128), jnp.float32)
    assert optim.sparse_update_path(param, {}, ("t", _Step())) == \
        ("composed", "platform")
    assert optim.sparse_update_path(param, {}, None) == \
        ("composed", "caller")


def _embedding_model(width):
    """Two embedding layers (a token table and a position table, as
    GPT-2's wte and wpe) under a linear head."""
    ids = ht.Variable("ids", trainable=False)
    pos = ht.Variable("pos", trainable=False)
    y_ = ht.Variable("y", trainable=False)
    rng = np.random.RandomState(3)
    wte = ht.Variable("wte", value=rng.randn(V, width).astype(np.float32))
    wpe = ht.Variable("wpe", value=rng.randn(8, width).astype(np.float32))
    head = ht.Variable("head", value=(
        rng.randn(width, 4) * 0.1).astype(np.float32))
    h = ht.embedding_lookup_op(wte, ids) + ht.embedding_lookup_op(wpe, pos)
    logits = ht.matmul_op(ht.array_reshape_op(h, (-1, width)), head)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(logits, y_), [0])
    return (ids, pos, y_), (wte, wpe, head), loss


def _train(width, steps=3, telemetry=None):
    (ids, pos, y_), tables, loss = _embedding_model(width)
    train = ht.optim.AdamOptimizer(0.05).minimize(loss)
    exe = ht.Executor([loss, train], dtype=jnp.bfloat16,
                      telemetry=telemetry)
    rng = np.random.RandomState(4)
    losses = []
    for _ in range(steps):
        feed = {ids: rng.randint(0, V, (4, 8)).astype(np.int32),
                pos: np.tile(np.arange(8, dtype=np.int32), (4, 1)),
                y_: np.eye(4, dtype=np.float32)[rng.randint(0, 4, 32)]}
        losses.append(float(exe.run(feed_dict=feed)[0].asnumpy()))
    masters = [np.asarray(exe.params[str(t.id)]) for t in tables]
    copies = [np.asarray(exe.work[str(t.id)].astype(jnp.float32))
              for t in tables]
    exe.close()
    return losses, masters, copies


def test_an_executor_trains_the_same_on_both_paths(monkeypatch):
    """Three steps of a two-table embedding model through
    ``ht.Executor(dtype=bfloat16)``: the same losses, float32 tables and
    bfloat16 working copies whether its tables take the kernel or the
    composed form."""
    composed = _train(128)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(kernel, "INTERPRET", True)
    tel = Telemetry(enabled=True, rank=0)
    with_kernel = _train(128, telemetry=tel)
    paths = [e["args"]["path"] for e in tel.tracer.drain(clear=True)
             if e.get("name") == "sparse_update"]
    assert paths == ["kernel", "kernel"]
    np.testing.assert_allclose(with_kernel[0], composed[0], rtol=1e-5)
    for got, want in zip(with_kernel[1] + with_kernel[2],
                         composed[1] + composed[2]):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_a_compiled_step_says_which_path_each_table_took():
    """A tiny GPT-2 step: one ``sparse_update`` instant a table (wte,
    wpe), each naming its path and, composed, the reason."""
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    tel = Telemetry(enabled=True, rank=0)
    model = GPTLMHeadModel(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=16,
        hidden_dropout_prob=0.0))
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    _, loss = model(ids, labels)
    train = ht.optim.AdamOptimizer(1e-3).minimize(
        ht.reduce_mean_op(loss, [0, 1]))
    exe = ht.Executor([train], telemetry=tel)
    feed = np.zeros((2, 16), np.int32)
    exe.run(feed_dict={ids: feed, labels: feed})
    exe.close()
    events = [e["args"] for e in tel.tracer.drain(clear=True)
              if e.get("name") == "sparse_update"]
    assert len(events) == 2, events
    for event in events:
        assert check_args("sparse_update", event) == []
        assert event["path"] == "composed"
        assert event["reason"] == "platform"
        assert event["width"] == 32 and event["slots"] == 2
    assert sorted(e["rows"] for e in events) == [16, 64]
    assert all("wte" in e["table"] or "wpe" in e["table"] for e in events), \
        events


def test_block_rows_fit_the_budget_at_every_table_of_the_cells():
    """A program's row groups (8 rows a table an id) and its gradient
    block, twice, stay inside half the default VMEM."""
    for n, width, tables in ((8192, 2560, 3), (8192, 2560, 4),
                             (16384, 768, 3), (16384, 768, 1), (4, 128, 2)):
        block = kernel.block_rows(n, width, tables)
        assert block % 8 == 0 and 8 <= block <= kernel.MAX_BLOCK_ROWS
        assert block * width * 4 * (8 * tables + 2) <= kernel.VMEM_BUDGET \
            or block == 8
