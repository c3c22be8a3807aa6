"""GPT decoder family (models/gpt.py): causal LM training, causality of
the mask, and causal sequence-parallel equivalence — the user-reachable
surface of the zigzag ring / causal Ulysses paths."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import hetu_tpu as ht
from hetu_tpu.executor import Executor, HetuConfig
import hetu_tpu.models as M

VOCAB, SEQ, BATCH = 64, 32, 4


def _build(sp=None, flash=False):
    cfg = M.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=8, max_position_embeddings=SEQ,
        hidden_dropout_prob=0.0, sequence_parallel=sp,
        use_flash_attention=flash)
    model = M.GPTLMHeadModel(cfg)
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    logits, loss = model(ids, labels)
    lm = ht.reduce_mean_op(loss, [0, 1])
    train = ht.optim.AdamOptimizer(1e-3).minimize(lm)
    return ids, labels, logits, lm, train


def _shifted(x):
    # final position: no next token -> the sparse-CE ignored_index
    return np.concatenate(
        [x[:, 1:], np.full((len(x), 1), -1, np.int64)], axis=1)


def test_gpt_learns_periodic_sequence():
    """Next-token loss on a deterministic periodic sequence falls far
    below the ln(V)=4.16 uniform floor — the decoder actually models
    token order, not just marginals."""
    ids, labels, _, lm, train = _build()
    exe = Executor([lm, train])
    # period-4 sequence: the next token is a function of the current one
    base = np.arange(SEQ) % 4 + 10
    x = np.stack([np.roll(base, s) for s in range(BATCH)])
    y = _shifted(x)
    losses = [float(exe.run(feed_dict={ids: x, labels: y},
                            convert_to_numpy_ret_vals=True)[0])
              for _ in range(80)]
    assert losses[-1] < losses[0]
    assert losses[-1] < 1.0, losses[-5:]


@pytest.mark.parametrize("flash", [False, True])
def test_gpt_logits_are_causal(flash):
    """Changing ONLY the last input token must not change any earlier
    position's logits — direct probe of the causal masking, on BOTH
    the composed-mask path and the flash-op path (the one every
    use_flash_attention=True user runs)."""
    ids, labels, logits, lm, train = _build(flash=flash)
    exe = Executor([logits])
    rng = np.random.RandomState(0)
    x1 = rng.randint(0, VOCAB, (1, SEQ))
    x2 = x1.copy()
    x2[0, -1] = (x1[0, -1] + 7) % VOCAB
    y = _shifted(x1)
    l1 = np.asarray(exe.run(feed_dict={ids: x1, labels: y},
                            convert_to_numpy_ret_vals=True)[0])
    l2 = np.asarray(exe.run(feed_dict={ids: x2, labels: y},
                            convert_to_numpy_ret_vals=True)[0])
    np.testing.assert_allclose(l1[:, :-1], l2[:, :-1], atol=1e-5)
    assert np.abs(l1[:, -1] - l2[:, -1]).max() > 1e-3


def test_gpt_flash_matches_composed():
    """use_flash_attention=True and False build different graphs but
    the same math: identical losses over a few training steps."""
    rng = np.random.RandomState(2)
    x = rng.randint(0, VOCAB, (BATCH, SEQ))
    y = _shifted(x)
    ids, labels, _, lm, train = _build(flash=False)
    ref = Executor([lm, train])
    want = [float(ref.run(feed_dict={ids: x, labels: y},
                          convert_to_numpy_ret_vals=True)[0])
            for _ in range(3)]
    ids2, labels2, _, lm2, train2 = _build(flash=True)
    exe = Executor([lm2, train2])
    got = [float(exe.run(feed_dict={ids2: x, labels2: y},
                         convert_to_numpy_ret_vals=True)[0])
           for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("sp", ["ring", "ulysses"])
def test_gpt_causal_sequence_parallel_matches(sp):
    """GPTConfig(sequence_parallel=...) on the 8-way sp mesh trains
    bit-comparably to the fused single-device decoder (zigzag causal
    ring / causal Ulysses under the hood)."""
    ids, labels, _, lm, train = _build()
    ref = Executor([lm, train])
    rng = np.random.RandomState(1)
    x = rng.randint(0, VOCAB, (BATCH, SEQ))
    y = _shifted(x)
    want = [float(ref.run(feed_dict={ids: x, labels: y},
                          convert_to_numpy_ret_vals=True)[0])
            for _ in range(3)]

    ids2, labels2, _, lm2, train2 = _build(sp=sp)
    conf = HetuConfig(eval_node_list=[lm2, train2],
                      mesh=Mesh(np.asarray(jax.devices()[:8]), ("sp",)))
    exe = Executor({"default": [lm2, train2]}, config=conf)
    got = [float(exe.run(feed_dict={ids2: x, labels2: y},
                         convert_to_numpy_ret_vals=True)[0])
           for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=1e-4)
