"""Operations and bytes ONE call of a flash-attention kernel needs,
from its shapes alone (``[B, H, S, D]`` q/k/v, ``itemsize`` bytes an
element).

Operations are the algorithm's, not the implementation's: the forward
needs the scores and the context (2 matmuls, 4*S*S*D per head); the
backward needs dV, dP, dQ, dK and one rebuild of the scores from the
saved logsumexp (5 matmuls, 10*S*S*D per head) — the convention of the
FlashAttention papers. Whatever a kernel recomputes beyond that (the
program's two-kernel backward rebuilds scores and dP in both) is its
own cost and is NOT counted, so the share cannot be flattered by extra
work. Causal attention needs half of each.

Bytes are what must cross HBM once: the forward reads q, k, v and
writes o (and the float32 logsumexp when it is kept); the backward
reads q, k, v, o, do and the logsumexp and writes dq, dk, dv.
"""


def _pairs(b, h, s, causal):
    return b * h * s * s * (0.5 if causal else 1.0)


def forward(b, h, s, d, itemsize, causal, with_lse):
    flops = 4.0 * _pairs(b, h, s, causal) * d
    nbytes = 4.0 * b * h * s * d * itemsize \
        + (4.0 * b * h * s if with_lse else 0.0)
    return flops, nbytes


def backward(b, h, s, d, itemsize, causal):
    flops = 10.0 * _pairs(b, h, s, causal) * d
    nbytes = 8.0 * b * h * s * d * itemsize + 4.0 * b * h * s
    return flops, nbytes


def least_seconds(flops, nbytes, peaks):
    """(the least time the chip could take, which bound applies)."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_compute, t_memory), \
        "compute" if t_compute >= t_memory else "memory"
