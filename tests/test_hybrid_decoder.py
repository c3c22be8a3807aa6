"""The convolution-hybrid sparse-expert decoder
(``hetu_tpu/models/hybrid_decoder.py``) and what it needed of the graph:
the sigmoid router that selects by a bias (``router_op(scoring="sigmoid",
bias=)``) against ``benchmark/reference/lfm2_moe.py`` — picks, weights,
a bias that provably changes a pick, zero gradient to the bias, the
logits' gradient against ``jax.grad`` with the picks held —; the whole
graph's loss, scores and EVERY parameter's gradient against the
reference, the tied table's among them (the lookup's rows plus the
head's matrix); two Adam steps; the device counters, with the picks the
bias changed; the four EP4 shares adding up to the uncut layer; a table
that is both a lookup's and a dense product's never taking the sparse
in-place row update; and the smallthinker step lowering to the text the
parent lowered it to (``tests/data/sparse_decoder_step_text.json``).

To regenerate that file after a JAX upgrade, in a checkout of the
commit whose text is to be kept: ``python -c "import json, sys;
sys.path.insert(0, 'tests'); import test_hybrid_decoder as t;
json.dump(t.smallthinker_step_fingerprints(), open(t.FINGERPRINTS, 'w'),
indent=1)"`` under ``JAX_PLATFORMS=cpu`` with
``jax_default_matmul_precision=highest`` (what ``tests/conftest.py``
sets).
"""
import hashlib
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import optimizer
from hetu_tpu.models import (HybridDecoderLMHeadModel,
                             SparseDecoderLMHeadModel)
from hetu_tpu.models.hybrid_decoder import hybrid_decoder_param_shapes

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.families import lfm2_moe as family  # noqa: E402
from benchmark.families import smallthinker_moe  # noqa: E402
from benchmark.reference import lfm2_moe as reference  # noqa: E402
from test_sparse_decoder import (close,  # noqa: E402
                                 tiny_config as smallthinker_tiny)

S, VOCAB, HIDDEN = 32, 96, 64
EXPERTS, TOP_K = 8, 3
FINGERPRINTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "sparse_decoder_step_text.json")


# -- the sigmoid router ------------------------------------------------------

def _router_case(seed=0, bias_std=0.2):
    rs = np.random.RandomState(seed)
    return (rs.randn(2, S, HIDDEN).astype(np.float32),
            (rs.randn(HIDDEN, EXPERTS) * 0.3).astype(np.float32),
            (rs.randn(EXPERTS) * bias_std).astype(np.float32))


def _run_router(x, w, bias, upstream=None, scale=1.0):
    """``(weights, picks, dx, dw)`` of the graph's router."""
    nodes = [ht.Variable(n, trainable=False) for n in ("x", "w", "bias")]
    weights = ht.router_op(nodes[0], nodes[1], TOP_K, scoring="sigmoid",
                           bias=nodes[2], scale=scale,
                           norm_eps=reference.NORM_TOPK_EPS)
    picks = ht.router_picks_op(weights)
    seed = ht.Variable("seed", trainable=False)
    grads = ht.gradients(weights, nodes[:2], insert_grad=seed)
    if upstream is None:
        upstream = np.ones(x.shape[:-1] + (TOP_K,), np.float32)
    ex = ht.Executor([weights, picks] + grads)
    return [np.asarray(o.asnumpy()) for o in ex.run(feed_dict={
        nodes[0]: x, nodes[1]: w, nodes[2]: bias, seed: upstream})]


def test_sigmoid_router_picks_and_weights_agree_with_the_reference():
    x, w, bias = _router_case()
    weights, picks, _, _ = _run_router(x, w, bias, scale=2.5)
    with jax.default_matmul_precision("highest"):
        want_picks, want_weights, _, _ = reference.route(
            jnp.asarray(x.reshape(-1, HIDDEN)), jnp.asarray(w),
            jnp.asarray(bias), TOP_K, 2.5, None)
    np.testing.assert_array_equal(picks.reshape(-1, TOP_K),
                                  np.asarray(want_picks))
    close(weights.reshape(-1, TOP_K), want_weights)
    assert picks.dtype == np.int32 and weights.dtype == np.float32


def test_the_bias_changes_a_pick_and_never_a_weight():
    x, w, bias = _router_case()
    weights, picks, _, _ = _run_router(x, w, bias)
    _, unbiased, _, _ = _run_router(x, w, np.zeros_like(bias))
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    brought_in = [(b, t, j) for b in range(2) for t in range(S)
                  for j in range(TOP_K)
                  if picks[b, t, j] not in unbiased[b, t]]
    assert brought_in, "the seeded bias changed no pick"
    for b, t, j in brought_in:
        chosen = scores[b, t, picks[b, t]]
        # the weight is the UN-BIASED score's share of the chosen scores
        np.testing.assert_allclose(
            weights[b, t, j],
            chosen[j] / (chosen.sum() + reference.NORM_TOPK_EPS), rtol=1e-5)
    # and the flips are what the device counter would count
    kept = (picks[..., :, None] == unbiased[..., None, :]).any(-1)
    assert int((~kept).sum()) == len(brought_in)


@pytest.mark.parametrize("tied", [False, True])
def test_the_router_counts_the_flipped_picks_as_a_top_k_of_the_scores_would(
        tied):
    """The device counter ranks each pick among the scores alone; it
    must read what a second ``top_k`` would, ties (two experts with one
    column) to the lower index included."""
    x, w, bias = _router_case(seed=4)
    if tied:
        w[:, 5] = w[:, 2]
        bias[5] = bias[2] + 0.01
    x_n = ht.Variable("x", trainable=False)
    w_n = ht.Variable("w_r", value=w)
    b_n = ht.Variable("b_r", value=bias, trainable=False)
    weights = ht.router_op(x_n, w_n, TOP_K, scoring="sigmoid", bias=b_n)
    loss = ht.reduce_mean_op(weights, [0, 1, 2])
    train = ht.optim.SGDOptimizer(learning_rate=0.0).minimize(loss)
    ex = ht.Executor({"default": [loss, train],
                      "validate": [ht.router_picks_op(weights)]}, seed=1)
    picks = np.asarray(ex.run("validate", feed_dict={x_n: x})[0].asnumpy())
    scores = jax.nn.sigmoid(jnp.dot(x, w,
                                    precision=jax.lax.Precision.HIGHEST))
    own = np.asarray(jax.lax.top_k(scores, TOP_K)[1])
    want = int((~(picks[..., :, None] == own[..., None, :]).any(-1)).sum())
    assert want > 0
    for steps in (1, 2):
        ex.run(feed_dict={x_n: x})
        got = int(ex.state[str(weights.id)]["moe_bias_flipped_picks"])
        assert got == steps * want     # the validate pass counted none


def test_no_gradient_reaches_the_bias():
    nodes = [ht.Variable(n, trainable=False) for n in ("x", "w", "bias")]
    weights = ht.router_op(nodes[0], nodes[1], TOP_K, scoring="sigmoid",
                           bias=nodes[2])
    assert weights.gradient(weights)[2] is None
    assert ht.router_picks_op(weights).gradient(weights) == [None] * 3
    with pytest.raises(AssertionError, match="no gradient path"):
        ht.gradients(weights, [nodes[2]])


def test_sigmoid_router_gradients_against_jax_grad_with_the_picks_held():
    x, w, bias = _router_case(seed=1)
    upstream = np.random.RandomState(2).randn(2, S, TOP_K).astype(np.float32)
    _, picks, dx, dw = _run_router(x, w, bias, upstream, scale=1.5)

    def held(x, w):
        scores = jax.nn.sigmoid(jnp.dot(
            x, w, precision=jax.lax.Precision.HIGHEST))
        chosen = jnp.take_along_axis(scores, jnp.asarray(picks), axis=-1)
        weights = 1.5 * chosen / (jnp.sum(chosen, -1, keepdims=True)
                                  + reference.NORM_TOPK_EPS)
        return jnp.sum(weights * upstream)

    want_dx, want_dw = jax.grad(held, argnums=(0, 1))(jnp.asarray(x),
                                                      jnp.asarray(w))
    close(dx, want_dx)
    close(dw, want_dw)


def test_the_softmax_router_takes_none_of_the_sigmoid_routers_arguments():
    x, w = ht.Variable("x", trainable=False), ht.Variable("w", trainable=False)
    bias = ht.Variable("b", trainable=False)
    for kw in ({"bias": bias}, {"scale": 2.0}, {"norm_eps": 1e-6}):
        with pytest.raises(ValueError, match="sigmoid"):
            ht.router_op(x, w, TOP_K, **kw)
    with pytest.raises(ValueError, match="scoring"):
        ht.router_op(x, w, TOP_K, scoring="tanh")
    assert len(ht.router_op(x, w, TOP_K).inputs) == 2


def test_the_router_reads_the_bias_and_weights_as_float32_masters():
    """Under bfloat16 the picks are those of the float32 weights and
    bias: a bias that separates two near-ties survives."""
    x, w, bias = _router_case(seed=3, bias_std=0.01)
    params = [ht.Variable("router_w", value=w),
              ht.Variable("router_bias", value=bias, trainable=False)]
    feed = ht.Variable("x", trainable=False)
    weights = ht.router_op(feed, params[0], TOP_K, scoring="sigmoid",
                           bias=params[1])
    ex = ht.Executor([ht.router_picks_op(weights)], dtype=jnp.bfloat16)
    low = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(ex.run(feed_dict={feed: x})[0].asnumpy())
    _, want, _, _ = _run_router(low, w, bias)
    np.testing.assert_array_equal(got, want)


# -- the whole graph against the reference -----------------------------------

def tiny_config(first=2, held=4, vocab=VOCAB, bias_std=0.05):
    """A configuration file's content at the test's widths: a dense
    convolution layer, then attention, convolution, convolution under
    experts (8 query heads on 2 key/value heads of 8)."""
    return {"vocab_size": vocab, "hidden_size": HIDDEN,
            "layer_types": ["conv", "full_attention", "conv", "conv"],
            "num_hidden_layers": 4, "num_dense_layers": 1,
            "intermediate_size": 48, "moe_intermediate_size": 32,
            "num_routed_experts": EXPERTS, "num_experts": held,
            "first_expert": first, "num_experts_per_tok": TOP_K,
            "num_attention_heads": 8, "num_key_value_heads": 2,
            "conv_L_cache": 3, "rope_theta": 1e6, "norm_eps": 1e-5,
            "routed_scaling_factor": 1.0,
            "assumed": {"weights": {
                "initializer_std": 0.3, "embedding_std": 0.3,
                "expert_bias_std": bias_std, "conv_taps_halfwidth": 0.5}}}


def batch(b=2, seed=10, vocab=VOCAB):
    ids = np.random.RandomState(seed).randint(0, vocab, (b, S)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.full((b, 1), -1, np.int32)], 1)
    return ids, labels


def _value(o):
    held = getattr(o, "jax_array", o)
    return np.asarray(held.to_dense() if hasattr(held, "to_dense")
                      else o.asnumpy())


class Graph:
    def __init__(self, config, with_gradients=False, **executor_kw):
        self.config = config
        self.model = HybridDecoderLMHeadModel(family.model_config(config))
        self.ids = ht.Variable("input_ids", trainable=False)
        self.labels = ht.Variable("labels", trainable=False)
        self.logits, loss = self.model(self.ids, self.labels, seq_len=S)
        self.loss = ht.reduce_mean_op(loss, [0, 1])
        groups = {"validate": [self.loss, self.logits]
                  + list(self.model.picks)}
        if with_gradients:
            by_name = {n.name: n for n in
                       ht.executor.find_topo_sort([self.loss])
                       if getattr(n, "trainable", False)}
            self.grad_names = sorted(by_name)
            groups["grads"] = ht.gradients(
                self.loss, [by_name[n] for n in self.grad_names])
        self.executor = ht.Executor(groups, seed=3, **executor_kw)

    def params(self):
        return {node.name: np.asarray(arr) for node, arr in
                self.executor.config.placeholder_to_arr_map.items()}

    def run(self, group, ids, labels):
        return [_value(o) for o in self.executor.run(
            group, feed_dict={self.ids: ids, self.labels: labels})]


@pytest.fixture(scope="module")
def graph():
    return Graph(tiny_config(), with_gradients=True)


def test_every_parameter_and_buffer_is_named_by_the_shapes_function(graph):
    shapes = hybrid_decoder_param_shapes(family.model_config(graph.config))
    buffers = sorted(n for n in shapes if n.endswith("_expert_bias"))
    assert buffers == [f"hybrid_h{i}_expert_bias" for i in (1, 2, 3)]
    assert sorted(set(shapes) - set(buffers)) == graph.grad_names
    assert {k: v.shape for k, v in graph.params().items()} == shapes
    assert "hybrid_lm_head" not in shapes      # the head is the table
    assert shapes["hybrid_h0_conv_in"] == (HIDDEN, 3 * HIDDEN)
    assert shapes["hybrid_h0_ffn_gate_up"] == (HIDDEN, 96)
    assert shapes["hybrid_h1_attn_q_norm_scale"] == (8,)
    assert shapes["hybrid_h2_experts_gate_up"] == (4, HIDDEN, 64)
    trained = sum(int(np.prod(shapes[n])) for n in graph.grad_names)
    assert family.param_count(graph.config) == trained


def test_the_seeded_bias_is_centred_within_a_chips_experts():
    """N(0, std) with the mean of every chip's group of experts taken
    off, so the bias favours no chip."""
    from hetu_tpu.initializers import NormalInit
    from hetu_tpu.models.hybrid_decoder import _GroupCentredNormal
    model = HybridDecoderLMHeadModel(family.model_config(
        tiny_config(bias_std=0.2)))
    for block in model.decoder.blocks[1:]:
        bias = block.expert_bias.initial_value(seed=3)
        seed = 3 + zlib.crc32(block.expert_bias.name.encode())
        drawn = NormalInit((EXPERTS,), 0.0, 0.2).init_numpy(seed).reshape(
            2, 4)
        assert abs(drawn.mean(axis=1)).max() > 1e-3
        assert bias.shape == (EXPERTS,) and bias.dtype == np.float32
        np.testing.assert_allclose(bias.reshape(2, 4).mean(axis=1), 0.0,
                                   atol=1e-7)
        # the plain draw, less its groups' means
        np.testing.assert_allclose(
            bias, (drawn - drawn.mean(axis=1, keepdims=True)).reshape(-1),
            atol=1e-7)
        assert not block.expert_bias.trainable
    with pytest.raises(ValueError, match="groups of 3"):
        _GroupCentredNormal((8,), 0.1, 3)


def test_loss_and_scores_agree_with_the_reference(graph):
    ids, labels = batch()
    loss, logits, *picks = graph.run("validate", ids, labels)
    assert len(picks) == 3 and picks[0].shape == (2, S, TOP_K)
    log = []
    want_loss, (want_logits,) = reference.loss_and_scores(
        graph.params(), graph.config, ids, labels, forced=picks,
        log=log.append)
    assert log[0]["rows_differing_by_layer"] == [0, 0, 0]
    assert logits.dtype == np.float32
    assert abs(loss - want_loss) <= 1e-5 * abs(want_loss)
    close(logits, want_logits, 2e-5)
    free_loss, _ = reference.loss_and_scores(graph.params(), graph.config,
                                             ids, labels)
    assert free_loss == want_loss


def test_every_parameters_gradient_agrees_with_the_reference(graph):
    ids, labels = batch()
    grads = dict(zip(graph.grad_names, graph.run("grads", ids, labels)))
    params = {k: jnp.asarray(v) for k, v in graph.params().items()}
    want = jax.grad(reference.loss_fn)(params, graph.config, ids, labels)
    for name in graph.grad_names:
        assert np.abs(want[name]).max() > 0, name
        close(grads[name], want[name], 1e-4)
    # the bias only selects: the reference's gradient to it is zero too
    for name in want:
        if name.endswith("_expert_bias"):
            assert not np.abs(want[name]).any()


def test_the_tied_tables_gradient_is_the_lookups_rows_plus_the_heads_matrix(
        graph):
    """Each half alone, from the reference with the other use of the
    table cut off, and the graph's one array against their sum."""
    ids, labels = batch()
    grads = dict(zip(graph.grad_names, graph.run("grads", ids, labels)))
    params = {k: jnp.asarray(v) for k, v in graph.params().items()}

    def split_loss(lookup_table, head_table):
        """The reference's loss with the two uses of the table apart."""
        config = graph.config
        total = 0.0
        with jax.default_matmul_precision("highest"):
            for b in range(ids.shape[0]):
                x = lookup_table[ids[b]]
                for i in range(config["num_hidden_layers"]):
                    w = {role: params[f"hybrid_h{i}_{role}"]
                         for role in reference.layer_roles(config, i)}
                    x = reference.layer(x, w, None,
                                        **reference.layer_statics(config,
                                                                  i))[0]
                part, _ = reference.head(
                    x, params["hybrid_ln_f_scale"], head_table,
                    jnp.asarray(labels[b]), eps=config["norm_eps"])
                total = total + part
        return total / ids.size

    rows, matrix = jax.grad(split_loss, argnums=(0, 1))(
        params["hybrid_embed"], params["hybrid_embed"])
    assert np.abs(rows).max() > 0 and np.abs(matrix).max() > 0
    # a half alone would be off by the other half
    assert np.abs(grads["hybrid_embed"] - np.asarray(matrix)).max() \
        > 1e-3 * np.abs(matrix).max()
    close(grads["hybrid_embed"], np.asarray(rows + matrix), 1e-4)


def test_a_flipped_pick_beyond_the_margin_fails_the_comparison(graph):
    ids, labels = batch()
    picks = graph.run("validate", ids, labels)[2:]
    params = graph.params()
    # the pick furthest under the cut: the expert with the least
    # score + bias at that row
    own = set(picks[1][0, 5].tolist())
    log = []
    worst = None
    for e in range(EXPERTS):
        if e in own:
            continue
        trial = [p.copy() for p in picks]
        trial[1][0, 5, 0] = e
        log.clear()
        loss, _ = reference.loss_and_scores(params, graph.config, ids,
                                            labels, forced=trial,
                                            log=log.append)
        margin = log[0]["worst_margin_by_layer"][1]
        if worst is None or margin > worst[0]:
            worst = (margin, loss, list(log[0]["rows_differing_by_layer"]))
    assert worst[2] == [0, 1, 0]
    assert worst[0] > reference.PICK_MARGIN and np.isnan(worst[1])


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_each_control_moves_what_it_should(graph, control):
    """The 8-bit control and the short convolution fault move the
    scores; the bias left out of the selection moves no score (the
    weights never held it) but every flip it causes shows as a margin."""
    ids, labels = batch()
    _, logits, *picks = graph.run("validate", ids, labels)
    log = []
    _, (got,) = reference.loss_and_scores(
        graph.params(), graph.config, ids, labels, forced=picks,
        control=control, log=log.append)
    moved = float(np.abs(got - logits).max() / logits.std())
    if control == "no_bias":
        assert moved < 1e-4
        assert sum(log[0]["rows_differing_by_layer"]) > 0
        assert max(log[0]["worst_margin_by_layer"]) > 0.01
    else:
        assert moved > 0.05
    with pytest.raises(ValueError, match="control"):
        reference.forward({}, graph.config, ids, labels, control="other")


def test_two_adam_steps_reproduce_the_references_losses():
    config = tiny_config()
    model = HybridDecoderLMHeadModel(family.model_config(config))
    ids_n = ht.Variable("input_ids", trainable=False)
    labels_n = ht.Variable("labels", trainable=False)
    _, loss = model(ids_n, labels_n, seq_len=S)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train = ht.optim.AdamOptimizer(learning_rate=1e-2).minimize(lm_loss)
    ex = ht.Executor([lm_loss, train], seed=3)
    # (copies: the step donates the executor's own arrays)
    params = {n.name: jnp.asarray(np.array(a)) for n, a in
              ex.config.placeholder_to_arr_map.items()}
    trained = [n for n in params if not n.endswith("_expert_bias")]
    ids, labels = batch()
    m = {n: jnp.zeros_like(params[n]) for n in trained}
    v = {n: jnp.zeros_like(params[n]) for n in trained}
    for step in (1, 2):
        got = float(ex.run(feed_dict={ids_n: ids, labels_n: labels})[0]
                    .asnumpy())
        want, grads = jax.value_and_grad(reference.loss_fn)(
            params, config, ids, labels)
        assert abs(got - float(want)) <= 2e-5 * abs(float(want)), step
        scale = 1e-2 * np.sqrt(1 - 0.999 ** step) / (1 - 0.9 ** step)
        for n in trained:
            m[n] = 0.9 * m[n] + 0.1 * grads[n]
            v[n] = 0.999 * v[n] + 0.001 * grads[n] ** 2
            params[n] = params[n] - scale * m[n] / (jnp.sqrt(v[n]) + 1e-7)
    # the buffer is where it started: no update rule holds it
    for node in ex.config.placeholder_to_arr_map:
        if node.name.endswith("_expert_bias"):
            np.testing.assert_array_equal(
                np.asarray(ex.params[str(node.id)]),
                np.asarray(params[node.name]))


def test_the_step_counts_the_picks_the_bias_changed_on_the_device():
    config = tiny_config(bias_std=0.2)
    model = HybridDecoderLMHeadModel(family.model_config(config))
    ids_n = ht.Variable("input_ids", trainable=False)
    labels_n = ht.Variable("labels", trainable=False)
    logits, loss = model(ids_n, labels_n, seq_len=S)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train = ht.optim.SGDOptimizer(learning_rate=0.0).minimize(lm_loss)
    ex = ht.Executor({"default": [lm_loss, train],
                      "validate": [lm_loss, logits] + list(model.picks)},
                     seed=3)
    ids, labels = batch()
    params = {n.name: np.asarray(a) for n, a in
              ex.config.placeholder_to_arr_map.items()}
    picks = [np.asarray(p.asnumpy()) for p in ex.run(
        "validate", feed_dict={ids_n: ids, labels_n: labels})[2:]]
    unbiased = dict(params)
    for name in params:
        if name.endswith("_expert_bias"):
            unbiased[name] = np.zeros_like(params[name])
    # the reference's own top-k without the bias, on the SAME stream:
    # forced onto the program's picks, so the layers below see the same
    _, _, routing = reference.forward(
        {k: jnp.asarray(v) for k, v in unbiased.items()}, config,
        jnp.asarray(ids), jnp.asarray(labels),
        forced=[jnp.asarray(p) for p in picks])
    for _ in range(2):
        ex.run(feed_dict={ids_n: ids, labels_n: labels})
    counted = ex.moe_counters()
    assert len(counted) == 3
    for layer, p in zip(counted, picks):
        assert layer["steps"] == 2
        held = (p >= 2) & (p < 6)
        assert layer["moe_routed_rows"] == 2 * int(held.sum())
        assert "moe_bias_flipped_picks" in layer
    # two identical steps (learning rate 0): each counted the same flips
    assert all(c["moe_bias_flipped_picks"] % 2 == 0 for c in counted)
    flips = [c["moe_bias_flipped_picks"] // 2 for c in counted]
    assert sum(flips) > 0
    want = [int(np.asarray(r["differing"]).sum()) for r in routing]
    # a row that differs holds at least one flipped pick, at most k
    for got, rows in zip(flips, want):
        assert rows <= got <= TOP_K * rows
    family._SESSION["executor"] = ex
    try:
        calls = family.flash_calls_per_step(
            dict(config, num_hidden_layers=4), {"seq_len": S}, 2)
    finally:
        family._SESSION.clear()
    entry = calls[-1]
    assert entry["kind"] == "moe_counters"
    assert entry["moe_bias_flipped_picks"] == 2 * sum(flips)
    assert entry["moe_picks"] == 3 * 2 * 2 * S * TOP_K
    assert calls[-2] == {"kind": "short_conv", "rows": 2 * S,
                         "channels": HIDDEN, "taps": 3, "itemsize": 2,
                         "calls": 3}


def test_a_router_without_a_bias_keeps_the_counters_it_had():
    x, w = (ht.Variable(n, trainable=False) for n in ("x", "w"))
    g, d = (ht.Variable(n, trainable=False) for n in ("g", "d"))
    weights = ht.router_op(x, w, TOP_K)
    op = ht.held_experts_op(x, weights, ht.router_picks_op(weights), g, d)
    shapes = op.state_shapes([(2, S, 8), (2, S, 3), (2, S, 3), (4, 8, 16),
                              (4, 8, 8)])
    assert not getattr(weights, "stateful", False)
    biased = ht.router_op(x, w, TOP_K, scoring="sigmoid",
                          bias=ht.Variable("b", trainable=False))
    assert biased.stateful and biased.state_shapes([]) == {
        "moe_bias_flipped_picks": ()}
    assert sorted(shapes) == sorted([
        "moe_rows_by_expert", "moe_expert_visits", "moe_row_tiles",
        "moe_row_tiles_of", "moe_kernel_rows", "moe_back_rows",
        "moe_back_rows_of", "moe_dw_tiles", "moe_dw_cut_tiles", "steps"])


# -- the share ties to the model ---------------------------------------------

def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """One expert layer's sum over ALL eight experts, by the reference
    with every expert held, against the four EP4 shares' held sums
    through the GRAPH's ops: nothing counted twice, nothing left out."""
    rs = np.random.RandomState(20)
    n = rs.randn(2, S, HIDDEN).astype(np.float32)
    w_router = (rs.randn(HIDDEN, EXPERTS) * 0.3).astype(np.float32)
    bias = (rs.randn(EXPERTS) * 0.1).astype(np.float32)
    gate_up = (rs.randn(EXPERTS, HIDDEN, 64) * 0.3).astype(np.float32)
    down = (rs.randn(EXPERTS, 32, HIDDEN) * 0.3).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        flat = jnp.asarray(n.reshape(-1, HIDDEN))
        picks, weights, _, _ = reference.route(
            flat, jnp.asarray(w_router), jnp.asarray(bias), TOP_K, 1.0, None)
        whole = reference.held_experts(flat, picks, weights,
                                       jnp.asarray(gate_up),
                                       jnp.asarray(down), 0)
    total = np.zeros((2, S, HIDDEN), np.float32)
    for share in range(4):
        first = 2 * share

        def build(x, w, b, g, d):
            weights = ht.router_op(x, w, TOP_K, scoring="sigmoid", bias=b,
                                   norm_eps=reference.NORM_TOPK_EPS)
            return ht.held_experts_op(x, weights,
                                      ht.router_picks_op(weights), g, d,
                                      first=first, activation="silu")

        nodes = [ht.Variable(f"in{i}", trainable=False) for i in range(5)]
        ex = ht.Executor([build(*nodes)])
        part = np.asarray(ex.run(feed_dict=dict(zip(nodes, (
            n, w_router, bias, gate_up[first:first + 2],
            down[first:first + 2]))))[0].asnumpy())
        assert np.abs(part).max() > 0
        total += part
    close(total.reshape(-1, HIDDEN), whole, 1e-4)


def test_four_slices_of_the_vocabulary_concatenate_to_the_whole_head():
    rs = np.random.RandomState(21)
    hidden = rs.randn(S, HIDDEN).astype(np.float32)
    table = (rs.randn(VOCAB, HIDDEN) * 0.3).astype(np.float32)
    scale = np.ones(HIDDEN, np.float32)
    labels = np.full(S, -1, np.int32)
    with jax.default_matmul_precision("highest"):
        whole = reference.head(jnp.asarray(hidden), scale, jnp.asarray(table),
                               labels, eps=1e-5)[1]
        parts = [reference.head(jnp.asarray(hidden), scale,
                                jnp.asarray(table[i:i + VOCAB // 4]),
                                labels, eps=1e-5)[1]
                 for i in range(0, VOCAB, VOCAB // 4)]
    close(np.concatenate(parts, axis=-1), whole)


# -- a tied table and the sparse row update ----------------------------------

def _tied_graph(make_optimizer, monkeypatch=None):
    rs = np.random.RandomState(30)
    table = (rs.randn(24, 8) * 0.3).astype(np.float32)
    ids = rs.randint(0, 24, (2, 6)).astype(np.int32)
    target = rs.randn(12, 24).astype(np.float32)
    embed = ht.Variable("tied_table", value=table)
    ids_n = ht.Variable("ids", trainable=False)
    target_n = ht.Variable("target", trainable=False)
    rows = ht.array_reshape_op(ht.embedding_lookup_op(embed, ids_n), [-1, 8])
    logits = ht.matmul_op(rows, embed, trans_B=True)
    loss = ht.reduce_mean_op(ht.mul_op(logits, target_n), [0, 1])
    train = make_optimizer().minimize(loss)
    ex = ht.Executor([loss, train], seed=0)

    def plain(table):
        return jnp.mean((table[ids.reshape(-1)] @ table.T) * target)

    return ex, {ids_n: ids, target_n: target}, table, plain


@pytest.mark.parametrize("rule", ["sgd", "adam"])
def test_a_tied_table_gets_the_sum_of_both_gradients_applied_once(
        rule, monkeypatch):
    taken = []
    monkeypatch.setattr(optimizer, "_update_rows",
                        lambda *a, **k: taken.append(a) or (_ for _ in ())
                        .throw(AssertionError("sparse row update taken")))
    make = {"sgd": lambda: ht.optim.SGDOptimizer(learning_rate=0.5),
            "adam": lambda: ht.optim.AdamOptimizer(learning_rate=0.1)}[rule]
    ex, feeds, table, plain = _tied_graph(make)
    ex.run(feed_dict=feeds)
    node = next(n for n in ex.config.placeholder_to_arr_map
                if n.name == "tied_table")
    after = np.asarray(ex.params[str(node.id)])
    grad = np.asarray(jax.grad(plain)(jnp.asarray(table)))
    # both halves are there: rows no id names moved too (the head's),
    # and the named rows moved by more than the head's half alone
    head_only = np.asarray(jax.grad(
        lambda t: jnp.mean((jax.lax.stop_gradient(t)[feeds[
            next(k for k in feeds if k.name == "ids")].reshape(-1)] @ t.T)
            * feeds[next(k for k in feeds if k.name == "target")]))(
                jnp.asarray(table)))
    assert np.abs(grad - head_only).max() > 1e-3
    if rule == "sgd":
        close(after, table - 0.5 * grad, 1e-5)
    else:
        # Adam's first step: m = 0.1 g, v = 0.001 g^2
        scale = 0.1 * np.sqrt(0.001) / 0.1
        close(after, table - scale * 0.1 * grad
              / (np.sqrt(0.001 * grad ** 2) + 1e-7), 1e-4)
    assert not taken


def test_rows_of_one_id_add_up_in_the_wider_dtype():
    """600 bfloat16 rows of ones for one id into a float32 matrix: the
    sum is 600, which a bfloat16 accumulator cannot hold (it stops at
    256); with one dtype on both sides the scatter into zeros and the
    add are what they were."""
    from hetu_tpu.ndarray import IndexedSlices
    from hetu_tpu.ops.basic import AddOp
    op = AddOp(ht.Variable("a", trainable=False),
               ht.Variable("b", trainable=False))
    rows = IndexedSlices(indices=jnp.full((600,), 3, jnp.int32),
                         values=jnp.ones((600, 4), jnp.bfloat16),
                         dense_shape=(8, 4))
    dense = jnp.full((8, 4), 0.5, jnp.float32)
    for args in ((rows, dense), (dense, rows)):
        got = op.compute(list(args), None)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got[3]), 600.5)
        np.testing.assert_array_equal(np.asarray(got[2]), 0.5)
    same = op.compute([rows, dense.astype(jnp.bfloat16)], None)
    assert same.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(same, np.float32),
        np.asarray(rows.to_dense() + dense.astype(jnp.bfloat16),
                   np.float32))


def test_two_lookups_of_one_table_stay_sparse():
    """What the rule must not break: a table looked up twice and met by
    no dense product keeps its rows sparse."""
    table = ht.Variable("twice", value=np.ones((8, 4), np.float32))
    a, b = (ht.Variable(n, trainable=False) for n in "ab")
    out = ht.embedding_lookup_op(table, a) + ht.embedding_lookup_op(table, b)
    loss = ht.reduce_mean_op(out, [0, 1])
    (grad,) = ht.gradients(loss, [table])
    ex = ht.Executor([grad])
    got = ex.run(feed_dict={a: np.array([1, 2], np.int32),
                            b: np.array([2, 3], np.int32)})[0]
    assert hasattr(getattr(got, "jax_array", got), "to_dense")


# -- the smallthinker step's text --------------------------------------------

FIRST_NODE_ID = 5_000_000


def _step_fingerprints(dtype):
    config = smallthinker_tiny()
    model = SparseDecoderLMHeadModel(smallthinker_moe.model_config(config))
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    logits, loss = model(ids, labels, seq_len=S)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    train = ht.optim.AdamOptimizer(learning_rate=1e-4).minimize(lm_loss)
    ex = ht.Executor({"default": [lm_loss, train],
                      "validate": [lm_loss, logits] + list(model.picks)},
                     seed=3, **({} if dtype is None else {"dtype": dtype}))
    feed = dict(zip((ids, labels), batch()))
    found = {}
    for group in ("default", "validate"):
        sub = ex.subexecutors[group]
        step = sub.prepare(ex, feed)
        text = jax.jit(step).lower(*sub.trace_args(ex, feed)).as_text()
        found[f"{group}.{'float32' if dtype is None else 'bfloat16'}"] \
            = hashlib.sha256(text.encode()).hexdigest()
    return found


def smallthinker_step_fingerprints():
    """``{program: sha256 of its lowered StableHLO text}`` of the
    smallthinker decoder's training step and validate program at the
    tiny widths of ``tests/test_sparse_decoder.py``, float32 and
    bfloat16 (``Lowered.as_text()`` carries no locations). A step's
    arguments are keyed by node id, so each graph is minted from the
    same first id, whatever this process built before (and past any id
    it did)."""
    from hetu_tpu.graph import node as graph_node
    found = {}
    for dtype in (None, jnp.bfloat16):
        minted = graph_node.G_NODE_ID
        graph_node.G_NODE_ID = FIRST_NODE_ID
        try:
            found.update(_step_fingerprints(dtype))
        finally:
            graph_node.G_NODE_ID = max(minted, graph_node.G_NODE_ID)
    return found


def test_the_smallthinker_step_lowers_to_the_text_the_parent_lowered():
    with open(FINGERPRINTS) as f:
        stored = json.load(f)
    assert smallthinker_step_fingerprints() == stored["fingerprints"], (
        "router_op's / held_experts_op's defaults or SparseDecoderBlock "
        "changed the smallthinker step (or JAX was upgraded: the "
        "docstring says how to regenerate)")
