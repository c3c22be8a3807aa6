"""Bytes and operations of the selective state-space recurrence
(``hetu_tpu/ops/ssm.py``) for ONE token in ONE Mamba layer, from
shapes: ``d`` channels, ``n`` state values a channel, ``r`` the rank of
``dt``.

**A prompt token** (the scan). Bytes are the LEAST any implementation
must move through HBM for a (token, layer): the convolved ``x`` in and
``y`` out (``d`` each, the model's dtype) and the low-rank inputs the
step size and the two projections are made of (``r + 2 n`` float32
values): ``2 d itemsize + 4 (r + 2 n)`` — 21,248 at d = 5120, n = 16,
r = 160 in bfloat16. An implementation that takes ``delta`` expanded
(``d`` float32: this program's kernel does, with ``B`` and ``C``
broadcast along 128 lanes beside it) moves more; one that fused the
``dt`` projection and the softplus into the scan would move this. The
state itself never leaves the chip between a prompt's tokens. The share
this count gives reads LOW whatever the implementation: a token's
update is ``d n`` exponentials and about ``7 d n`` multiplies and adds
on the vector unit (573,440 at these widths) for those 21 KB — 27
vector operations a byte, where the chip moves a byte in the time of
about one — and ``benchmark/peaks.json`` publishes no vector peak to
hold them against. No fusing can push it past 100.

**A decode token** (the step). The state is read once and written once,
``2 x 4 d n`` bytes (655,360 at these widths); ``x``, ``delta``, ``y``
and the rest are 8% of that and left out, so the share cannot pass 100.
"""


def scan_bytes_per_row(d, n, r, itemsize):
    return float(2 * d * itemsize + 4 * (r + 2 * n))


def scan_vector_ops_per_row(d, n):
    """The update's elementwise work: the exponential's argument, the
    decay, ``delta x B``, the sum, ``S C`` and its reduction (about 7 a
    state value); the exponential itself is counted apart."""
    return float(7 * d * n)


def step_bytes_per_row(d, n):
    return float(2 * 4 * d * n)
