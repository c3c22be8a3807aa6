"""Operations and bytes ONE training call of grouped-query flash
attention needs, forward or backward, causal, with a band or without
(``hetu_tpu/ops/pallas_attention.py``: ``hetu_flash_gqa[_window]_fwd``
/ ``_bwd``), from its shapes alone: ``b`` sequences of ``s`` tokens,
``h`` query heads on ``kv_heads`` key/value heads of ``d``.

**Pairs.** Only the (query, key) pairs a row may see are counted: under
the diagonal ``s (s + 1) / 2``, inside a band of ``window`` keys
``w (w + 1) / 2 + (s - w) w``. What a tile computes beyond them (the
half of a tile an edge cuts) is the kernel's own cost, so a share of
the roofline cannot pass 100.

**Operations** are the algorithm's, as ``flops/flash.py`` has them: the
forward's scores and context are ``4 x d`` a pair a query head, the
backward's dV, dP, dQ, dK and the one rebuild of the scores ``10 x d``.

**Bytes** are what must cross HBM once: q, the context, dO and dQ a
QUERY head; k, v, dK and dV a KEY/VALUE head (a group's seven query
heads read the same rows once); the float32 logsumexp a query head.
"""


def pairs(s, window=None):
    """(query, key) pairs one head of one sequence scores."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def forward(b, h, kv_heads, s, d, itemsize, window=None):
    flops = 4.0 * b * h * pairs(s, window) * d
    nbytes = (2.0 * h + 2.0 * kv_heads) * b * s * d * itemsize \
        + 4.0 * b * h * s
    return flops, nbytes


def backward(b, h, kv_heads, s, d, itemsize, window=None):
    flops = 10.0 * b * h * pairs(s, window) * d
    nbytes = (4.0 * h + 4.0 * kv_heads) * b * s * d * itemsize \
        + 4.0 * b * h * s
    return flops, nbytes
