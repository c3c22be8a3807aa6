"""Model-zoo smoke + convergence tests (reference strategy: loss decreases
over steps, examples/runner/parallel/validate_results.py style)."""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import models
from hetu_tpu.executor import Executor


def _onehot(y, n):
    return np.eye(n, dtype=np.float32)[y]


def _run_steps(exe, feeds, n=3):
    out = []
    for _ in range(n):
        res = exe.run(feed_dict=feeds)
        out.append(np.asarray(res[0].asnumpy()).reshape(()).item())
    return out


def _train(model_fn, xshape, num_classes=10, lr=0.1, steps=4):
    rng = np.random.RandomState(0)
    x = ht.Variable("x", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    loss, y = model_fn(x, y_)
    opt = ht.optim.SGDOptimizer(learning_rate=lr)
    train_op = opt.minimize(loss)
    exe = Executor([loss, train_op], ctx=ht.cpu(0))
    xv = rng.randn(*xshape).astype(np.float32)
    yv = _onehot(rng.randint(0, num_classes, xshape[0]), num_classes)
    return _run_steps(exe, {x: xv, y_: yv}, steps)


def test_logreg():
    losses = _train(models.logreg, (8, 784))
    assert losses[-1] < losses[0]


def test_mlp():
    losses = _train(models.mlp, (8, 3072), lr=0.01)
    assert losses[-1] < losses[0]


def test_cnn_3_layers():
    losses = _train(models.cnn_3_layers, (4, 784), lr=0.01)
    assert losses[-1] < losses[0]


def test_lenet():
    losses = _train(models.lenet, (4, 784), lr=0.01)
    assert losses[-1] < losses[0]


def test_alexnet():
    losses = _train(lambda x, y: models.alexnet(x, y), (2, 3, 32, 32),
                    lr=0.001, steps=2)
    assert np.isfinite(losses).all()


def test_vgg16():
    losses = _train(models.vgg16, (2, 3, 32, 32), lr=0.001, steps=2)
    assert np.isfinite(losses).all()


def test_resnet18():
    losses = _train(models.resnet18, (2, 3, 32, 32), lr=0.01, steps=2)
    assert np.isfinite(losses).all()


def test_rnn():
    losses = _train(models.rnn, (4, 784), lr=0.05)
    assert losses[-1] < losses[0]


def test_lstm():
    losses = _train(models.lstm, (4, 784), lr=0.05, steps=3)
    assert np.isfinite(losses).all()


# ---------------------------------------------------------------------------
# BERT
# ---------------------------------------------------------------------------

def _tiny_bert_config(**kw):
    return models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, **kw)


def test_bert_pretraining_converges():
    rng = np.random.RandomState(0)
    config = _tiny_bert_config()
    model = models.BertForPreTraining(config)
    bs, sl = 4, 16
    input_ids = ht.Variable("input_ids", trainable=False)
    token_type_ids = ht.Variable("token_type_ids", trainable=False)
    attention_mask = ht.Variable("attention_mask", trainable=False)
    mlm_labels = ht.Variable("masked_lm_labels", trainable=False)
    nsp_label = ht.Variable("next_sentence_label", trainable=False)

    _, _, mlm_loss, nsp_loss = model(input_ids, token_type_ids,
                                     attention_mask, mlm_labels, nsp_label)
    loss = ht.reduce_mean_op(mlm_loss, [0, 1]) + \
        ht.reduce_mean_op(nsp_loss, [0])
    opt = ht.optim.AdamOptimizer(learning_rate=1e-2)
    train_op = opt.minimize(loss)
    exe = Executor([loss, train_op], ctx=ht.cpu(0))

    feeds = {
        input_ids: rng.randint(0, 64, (bs, sl)),
        token_type_ids: rng.randint(0, 2, (bs, sl)),
        attention_mask: np.ones((bs, sl), np.float32),
        mlm_labels: rng.randint(0, 64, (bs, sl)),
        nsp_label: rng.randint(0, 2, (bs,)),
    }
    losses = _run_steps(exe, feeds, 8)
    assert losses[-1] < losses[0], losses


def test_bert_mlm_head_keeps_the_rows_flat():
    """The transform hands ``[B * S, H]`` to its LayerNorm and on to
    the decoder's matmul (no ``[B, S, H]`` round trip between the two
    matmuls, whose relayouts of the logits' gradient the TPU paid for:
    PERF.md §6, PR 28); the logits are what the 3-D route gives."""
    from hetu_tpu.models import bert
    rng = np.random.RandomState(2)
    config = _tiny_bert_config()
    bs, sl, hidden = 3, 16, config.hidden_size
    table = ht.Variable("word_embeddings", value=rng.randn(
        config.vocab_size, hidden).astype(np.float32))
    head = bert.BertLMPredictionHead(config, table)
    states = ht.Variable("states", trainable=False)
    flat = head.transform(states)
    logits = head(states)
    transform = head.transform
    round_trip = transform.LayerNorm(
        transform.dense_act(states, [-1, sl, hidden]))
    exe = Executor([flat, logits, round_trip], ctx=ht.cpu(0))
    value = rng.randn(bs, sl, hidden).astype(np.float32)
    flat_v, logits_v, round_trip_v = [
        r.asnumpy() for r in exe.run(feed_dict={states: value})]
    assert flat_v.shape == (bs * sl, hidden)
    assert logits_v.shape == (bs, sl, config.vocab_size)
    np.testing.assert_allclose(flat_v.reshape(bs, sl, hidden),
                               round_trip_v, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        logits_v, round_trip_v @ np.asarray(table.tensor_value).T,
        rtol=1e-4, atol=1e-4)


def test_bert_classification():
    rng = np.random.RandomState(1)
    config = _tiny_bert_config()
    model = models.BertForSequenceClassification(config, num_labels=3)
    bs, sl = 2, 16
    input_ids = ht.Variable("input_ids", trainable=False)
    token_type_ids = ht.Variable("token_type_ids", trainable=False)
    attention_mask = ht.Variable("attention_mask", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    logits, loss = model(input_ids, token_type_ids, attention_mask, labels)
    sloss = ht.reduce_mean_op(loss, [0])
    opt = ht.optim.SGDOptimizer(learning_rate=0.1)
    train_op = opt.minimize(sloss)
    exe = Executor([sloss, logits, train_op], ctx=ht.cpu(0))
    feeds = {
        input_ids: rng.randint(0, 64, (bs, sl)),
        token_type_ids: np.zeros((bs, sl), np.int32),
        attention_mask: np.ones((bs, sl), np.float32),
        labels: rng.randint(0, 3, (bs,)),
    }
    losses = _run_steps(exe, feeds, 5)
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# CTR
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("builder", [models.wdl_criteo,
                                     models.deepfm_criteo,
                                     models.dcn_criteo,
                                     models.dc_criteo])
def test_ctr_models(builder):
    rng = np.random.RandomState(2)
    dense = ht.Variable("dense", trainable=False)
    sparse = ht.Variable("sparse", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    loss, y, _, train_op = builder(dense, sparse, y_,
                                   feature_dimension=1000,
                                   embedding_size=8)
    exe = Executor([loss, train_op], ctx=ht.cpu(0))
    feeds = {
        dense: rng.randn(16, 13).astype(np.float32),
        sparse: rng.randint(0, 1000, (16, 26)),
        y_: rng.randint(0, 2, (16, 1)).astype(np.float32),
    }
    losses = _run_steps(exe, feeds, 4)
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# GNN
# ---------------------------------------------------------------------------

def _random_norm_adj(n, avg_deg=4, seed=3):
    import scipy.sparse as sp
    rng = np.random.RandomState(seed)
    rows = np.repeat(np.arange(n), avg_deg)
    cols = rng.randint(0, n, n * avg_deg)
    m = sp.coo_matrix((np.ones(n * avg_deg), (rows, cols)),
                      shape=(n, n)).tocsr()
    m = m + sp.eye(n, format="csr")
    deg = np.asarray(m.sum(1)).ravel()
    dinv = sp.diags(1.0 / np.sqrt(deg))
    return (dinv @ m @ dinv).tocsr()


@pytest.mark.parametrize("model_fn", [models.gcn, models.graphsage])
def test_gnn_models(model_fn):
    rng = np.random.RandomState(4)
    n, fdim, ncls = 40, 12, 3
    adj = _random_norm_adj(n)
    feat = ht.Variable("feat", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    mask_ = ht.Variable("mask_", trainable=False)
    norm_adj = ht.Variable("norm_adj", trainable=False)
    loss, y, train_op = model_fn(feat, y_, mask_, norm_adj, fdim, 16, ncls)
    exe = Executor([ht.reduce_mean_op(loss, [0]), train_op], ctx=ht.cpu(0))
    sp_adj = ht.ND_Sparse_Array(
        adj.data.astype(np.float32), adj.indptr.astype(np.int32),
        adj.indices.astype(np.int32), nrow=n, ncol=n)
    feeds = {
        feat: rng.randn(n, fdim).astype(np.float32),
        y_: _onehot(rng.randint(0, ncls, n), ncls),
        mask_: np.ones(n, np.float32),
        norm_adj: sp_adj,
    }
    losses = _run_steps(exe, feeds, 4)
    assert losses[-1] < losses[0], losses
