"""Operations a training step REQUIRES per token, from shapes alone.

Copied from ``bench.py:bert_train_flops`` / ``gpt_train_flops`` (sound
arithmetic; listed in PERF.md for deletion there), restated per token.
Forward per token: the four attention projections 8h^2, scores and
context 4sh (2sh when causal: only ~s/2 keys per query are real work),
the feed-forward pair 4hi, and the output head over every position
2hV. Embedding gathers, LayerNorm, softmax and GELU are O(h) and left
out, so a utilisation from these counts is on the low side. Backward
counts twice the forward; recomputed operations do not count.
"""


def _per_token_forward(seq_len, hidden, layers, intermediate, vocab,
                       causal):
    attention = (2 if causal else 4) * seq_len * hidden
    return layers * (8 * hidden * hidden + attention
                     + 4 * hidden * intermediate) + 2 * hidden * vocab


def gpt_train_flops_per_token(seq_len, hidden, layers, intermediate,
                              vocab):
    return 3.0 * _per_token_forward(seq_len, hidden, layers,
                                    intermediate, vocab, causal=True)


def bert_train_flops_per_token(seq_len, hidden, layers, intermediate,
                               vocab):
    return 3.0 * _per_token_forward(seq_len, hidden, layers,
                                    intermediate, vocab, causal=False)
