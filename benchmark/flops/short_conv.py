"""Bytes and operations ONE training step's gated short convolutions
need (``hetu_tpu/ops/short_conv.py``: ``hetu_short_conv_fwd`` /
``hetu_short_conv_bwd``), from their shapes alone: ``rows`` tokens of
``channels`` channels, ``taps`` taps.

**Bytes** are what must cross HBM once. Forward: the projection's
``[rows, 3 channels]`` read, ``[rows, channels]`` written. Backward:
the projection and ``dy`` read, ``[rows, 3 channels]`` written. The taps
(``channels x taps`` numbers) are negligible and left out, as is any
intermediate a fused form keeps on the chip: a form that writes ``B *
u``, its shifted copies or their sum to HBM moves more than this, which
is what the share is there to show.

**Operations**: a multiply for ``B * u``, a multiply-add a tap, a
multiply for ``C * v`` forward; the backward about three times that. A
few operations a byte: the op is bound by memory on any chip.
"""


def forward(rows, channels, taps, itemsize):
    flops = rows * channels * (2.0 + 2.0 * taps)
    nbytes = 4.0 * rows * channels * itemsize
    return flops, nbytes


def backward(rows, channels, taps, itemsize):
    flops = rows * channels * (5.0 + 6.0 * taps)
    nbytes = 7.0 * rows * channels * itemsize
    return flops, nbytes
