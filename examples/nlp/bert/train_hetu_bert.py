"""BERT pre-training entrypoint (reference parity:
examples/nlp/bert/train_hetu_bert.py — MLM+NSP joint loss, Adam, per-step
loss/time printing). TPU-native: bf16 mixed precision and the Pallas
flash-attention kernel are on by default; data falls back to synthetic
token streams when no corpus is prepared (the reference requires a
preprocessed wikicorpus).

    python examples/nlp/bert/train_hetu_bert.py --timing --num-steps 50
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                ".."))

import hetu_tpu as ht                       # noqa: E402
import hetu_tpu.models as M                 # noqa: E402


def synthetic_batch(rng, batch, seq_len, vocab):
    input_ids = rng.randint(0, vocab, (batch, seq_len))
    token_type_ids = np.zeros((batch, seq_len), np.int64)
    token_type_ids[:, seq_len // 2:] = 1
    attention_mask = np.ones((batch, seq_len), np.float32)
    masked_lm_labels = np.where(rng.rand(batch, seq_len) < 0.15,
                                input_ids, -1)
    next_sentence_label = rng.randint(0, 2, (batch,))
    return (input_ids, token_type_ids, attention_mask, masked_lm_labels,
            next_sentence_label)


def build(args):
    """Graph and session for the parsed arguments:
    ``(executor, feed_nodes)``."""
    import jax.numpy as jnp

    cfg = M.BertConfig(
        vocab_size=args.vocab_size, hidden_size=args.hidden_size,
        num_hidden_layers=args.num_layers,
        num_attention_heads=args.num_heads,
        intermediate_size=args.hidden_size * 4,
        max_position_embeddings=args.seq_length,
        use_flash_attention=not args.no_flash)
    model = M.BertForPreTraining(cfg)

    input_ids = ht.Variable("input_ids", trainable=False)
    token_type_ids = ht.Variable("token_type_ids", trainable=False)
    attention_mask = ht.Variable("attention_mask", trainable=False)
    mlm_labels = ht.Variable("masked_lm_labels", trainable=False)
    nsp_label = ht.Variable("next_sentence_label", trainable=False)
    _, _, mlm_loss, nsp_loss = model(input_ids, token_type_ids,
                                     attention_mask, mlm_labels, nsp_label)
    loss = ht.reduce_mean_op(mlm_loss, [0, 1]) + \
        ht.reduce_mean_op(nsp_loss, [0])
    opt = ht.optim.AdamOptimizer(learning_rate=args.lr)
    train_op = opt.minimize(loss)

    executor = ht.Executor(
        [loss, train_op], comm_mode=args.comm_mode,
        dtype=None if args.fp32 else jnp.bfloat16)
    feed_nodes = (input_ids, token_type_ids, attention_mask, mlm_labels,
                  nsp_label)
    return executor, feed_nodes


def run(args, session=None):
    """Train for ``args.num_steps``; ``session`` is a :func:`build`
    result to train (callers that inspect the executor afterwards).
    Returns the last logged loss and throughput plus every logged
    window's ``losses`` and ``window_seconds``."""
    executor, feed_nodes = session or build(args)

    rng = np.random.RandomState(0)
    results = {"losses": [], "window_seconds": []}
    t0 = time.perf_counter()
    window_tokens = 0
    for step in range(args.num_steps):
        values = synthetic_batch(rng, args.batch_size, args.seq_length,
                                 args.vocab_size)
        out = executor.run(
            feed_dict=dict(zip(feed_nodes, values)))
        window_tokens += args.batch_size * args.seq_length
        if (step + 1) % args.log_every == 0:
            loss_val = float(np.asarray(out[0].asnumpy()))
            dt = time.perf_counter() - t0
            tps = window_tokens / dt
            msg = f"step {step + 1}: loss {loss_val:.4f}"
            if args.timing:
                msg += f", {tps:.0f} tokens/sec"
            print(msg, flush=True)
            results.update(loss=loss_val, tokens_per_sec=tps)
            results["losses"].append(loss_val)
            results["window_seconds"].append(dt)
            t0 = time.perf_counter()
            window_tokens = 0
    return results


def parse_args(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--seq-length", type=int, default=128)
    parser.add_argument("--vocab-size", type=int, default=30522)
    parser.add_argument("--hidden-size", type=int, default=768)
    parser.add_argument("--num-layers", type=int, default=12)
    parser.add_argument("--num-heads", type=int, default=12)
    parser.add_argument("--lr", type=float, default=1e-4)
    parser.add_argument("--num-steps", type=int, default=100)
    parser.add_argument("--log-every", type=int, default=10)
    parser.add_argument("--timing", action="store_true")
    parser.add_argument("--fp32", action="store_true",
                        help="disable bf16 mixed precision")
    parser.add_argument("--no-flash", action="store_true",
                        help="disable the Pallas flash-attention kernel")
    parser.add_argument("--comm-mode", default=None)
    return parser.parse_args(argv)


if __name__ == "__main__":
    run(parse_args())
