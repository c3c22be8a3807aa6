"""The serving tests' independent reference: a toy ``GPTLMHeadModel``
GRAPH behind an ``InferenceSession``. Its full-sequence forward shares
no code with the pure-JAX serving block of models/gpt.py, so what the
paged programs and the engine compute is held against it. One
definition, so every serving test pins to the same reference."""
import numpy as np

import hetu_tpu as ht
import hetu_tpu.models as M
from hetu_tpu.serving import InferenceSession

VOCAB = 64


def gpt_session(seed=0, seq=32, hidden_act="gelu"):
    """``(cfg, sess)``: a 2-layer, 4-head, 32-wide GPT over ``seq``
    learned positions, weights drawn from ``seed``."""
    cfg = M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=seq, hidden_act=hidden_act,
                      hidden_dropout_prob=0.0)
    ids = ht.Variable("input_ids", trainable=False)
    sess = InferenceSession([M.GPTLMHeadModel(cfg)(ids)],
                            seq_buckets=(seq,), seed=seed)
    return cfg, sess


def full_forward(sess, x):
    """Logits ``[B, S, VOCAB]`` of the graph's forward over ``x``
    ``[B, S]`` (the session pads to its bucket and trims back)."""
    return sess.predict({"input_ids": np.asarray(x)})[0]


def greedy_chain(sess, prompt, n):
    """The ``n`` tokens greedy decoding appends to one ``prompt``, by
    the slow definition: a full forward per token, argmax of its last
    row."""
    cur = np.asarray(prompt)[None, :]
    for _ in range(n):
        nxt = np.argmax(full_forward(sess, cur)[:, -1], axis=-1)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    return cur[0, len(prompt):]
