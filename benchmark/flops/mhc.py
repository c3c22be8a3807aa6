"""Operations and bytes of the hyper-connections' residual path
(``hetu_tpu/ops/mhc.py``) around ONE sublayer for ONE token, from
shapes: ``n`` streams of ``c`` lanes.

Bytes are what a (token, sublayer) MUST move through HBM whatever
implements it: the stream read once (``n c``) and written once (``n
c``), the sublayer's output ``y`` read (``c``) and the mix ``u`` it
reads written (``c``): ``(2 n c + 2 c) x itemsize`` — 71,680 at n = 4,
c = 3584 in bfloat16 (ISSUE 39 prints 57,344, which is the stream's two
passes alone: its own formula gives this). The maps' parameters (``phi``, read once a
program and shared by its tokens) and the few hundred bytes of maps a
token are left out. An implementation in two kernels (``mhc_pre``,
then ``mhc_post`` after the sublayer) reads the stream TWICE, so where
the stream lives in HBM it moves ``3 n c + 2 c`` and cannot pass ``(2 n
+ 2) / (3 n + 2)`` of the bandwidth by this count: 10 / 14 = 71% at n =
4. XLA keeps a SHORT prompt's stream in on-chip memory between the two
kernels (the compiled programs mark 15 of 16 ``hetu_mhc_post`` results
so up to 512 prompt tokens, 9 of 16 at 1,024, none from 2,048): none of
those bytes crosses HBM, and what bounds the reading there is the
kernels' vector work, about 77% by the chip's readings (PERF.md
section 6, PR 39) — still under 100.

Operations are the algorithm's: the product with ``phi`` (``2 n c (2 n
+ n^2)``), the norm's sum of squares and the mix-in (``2 n c`` each)
and the mix-out (``2 n^2 c`` for ``Hres X`` and ``2 n c`` for ``Hpost
y``, which ISSUE 39's formula leaves out): ``2 n c (2 n + n^2) + 4 n c
+ 2 n^2 c + 2 n c``; the Sinkhorn iterations' few hundred are left
out. No metric divides by them: the path is bound by bytes (12
operations a byte against the chip's 240).
"""


def bytes_per_row(n, c, itemsize):
    return float((2 * n * c + 2 * c) * itemsize)


def flops_per_row(n, c):
    return float(2 * n * c * (2 * n + n * n) + 4 * n * c + 2 * n * n * c
                 + 2 * n * c)


def two_kernel_ceiling(n):
    """The largest share of the bandwidth a form that reads the stream
    twice OUT OF HBM can show by :func:`bytes_per_row`."""
    return (2.0 * n + 2) / (3.0 * n + 2)
