"""GPT causal-LM trainer — the decoder-only counterpart of the BERT
example (no reference equivalent: the reference's NLP zoo stops at
encoders; this family exists for the causal long-context path).

Data: a token-id corpus from ``HETU_DATA_DIR/lm/corpus.npy`` when
present ([N] int array, chunked into sequences); otherwise a synthetic
Markov corpus (each token is a deterministic function of the previous
two) that a working decoder drives far below the uniform-loss floor —
the hermetic stand-in for text.

    python examples/nlp/train_hetu_gpt.py --timing
    python examples/nlp/train_hetu_gpt.py --sequence-parallel ring
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import hetu_tpu as ht                                   # noqa: E402
from hetu_tpu.models import GPTConfig, GPTLMHeadModel   # noqa: E402


def load_corpus(args):
    path = os.path.join(os.environ.get("HETU_DATA_DIR", "datasets"),
                        "lm", "corpus.npy")
    if os.path.exists(path):
        flat = np.load(path).astype(np.int64)
        assert flat.max() < args.vocab_size and flat.min() >= 0, (
            f"corpus ids span [{flat.min()}, {flat.max()}] but "
            f"--vocab-size is {args.vocab_size}; the embedding gather "
            "would silently clamp out-of-range ids")
    else:
        rng = np.random.RandomState(0)
        n = args.nsamples * args.seq_len
        flat = np.empty(n, np.int64)
        flat[0], flat[1] = rng.randint(0, args.vocab_size, 2)
        # order-2 Markov rule: learnable, not memorizable marginals
        for i in range(2, n):
            flat[i] = (3 * flat[i - 1] + 5 * flat[i - 2] + 7) \
                % args.vocab_size
    nseq = len(flat) // args.seq_len
    return flat[:nseq * args.seq_len].reshape(nseq, args.seq_len)


def build_graph(args):
    """The causal-LM training graph for the parsed arguments:
    ``(config, ids, labels, lm_loss, train_op)``."""
    cfg = GPTConfig(
        vocab_size=args.vocab_size, hidden_size=args.hidden_size,
        num_hidden_layers=args.num_layers,
        num_attention_heads=args.num_heads,
        max_position_embeddings=args.seq_len,
        hidden_dropout_prob=args.dropout,
        use_flash_attention=True,
        sequence_parallel=args.sequence_parallel)
    model = GPTLMHeadModel(cfg)
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    _, loss = model(ids, labels)
    lm_loss = ht.reduce_mean_op(loss, [0, 1])
    opt = ht.optim.AdamOptimizer(learning_rate=args.learning_rate)
    train_op = opt.minimize(lm_loss)
    return cfg, ids, labels, lm_loss, train_op


def batches(args, data):
    """(input, next-token label) batches of one epoch. Labels are the
    input shifted by one; the final position has no next token — pad
    with the sparse-CE op's ignored_index so it trains nothing."""
    for b in range(max(1, len(data) // args.batch_size)):
        x = data[b * args.batch_size:(b + 1) * args.batch_size]
        y = np.concatenate(
            [x[:, 1:], np.full((len(x), 1), -1, np.int64)], axis=1)
        yield x, y


def main(args):
    data = load_corpus(args)
    _, ids, labels, lm_loss, train_op = build_graph(args)
    executor = ht.Executor([lm_loss, train_op])

    results = {}
    for epoch in range(args.nepoch):
        t0 = time.time()
        losses = []
        for x, y in batches(args, data):
            out = executor.run(feed_dict={ids: x, labels: y},
                               convert_to_numpy_ret_vals=True)
            losses.append(float(out[0]))
        msg = f"epoch {epoch}: loss {np.mean(losses):.4f}"
        if args.timing:
            msg += f", {time.time() - t0:.2f}s"
        print(msg, flush=True)
        results["loss"] = float(np.mean(losses))
    return results


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--hidden-size", type=int, default=128)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--nsamples", type=int, default=256)
    p.add_argument("--nepoch", type=int, default=2)
    p.add_argument("--learning-rate", type=float, default=1e-3)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--timing", action="store_true")
    p.add_argument("--sequence-parallel", default=None,
                   choices=[None, "ring", "ulysses"])
    return p.parse_args(argv)


if __name__ == "__main__":
    main(parse_args())
