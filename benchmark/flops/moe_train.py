"""Operations and bytes of the held experts' grouped products in a
TRAINING step (``hetu_tpu/ops/moe.py``: ``hetu_moe_experts`` forward,
``hetu_moe_experts_dx`` and ``hetu_moe_experts_dw`` backward), from
COUNTED work: the (token, pick) rows that landed on a held expert and
the held experts that got at least one row, as the step itself counted
them on the device (``Executor.moe_counters()``).

**Operations.** A routed row goes through three ``hidden x width``
matrices (gate, up, down) forward, ``6 x hidden x width``; backward
each of them is met twice, once for the row's gradient and once for
the weight's, ``12 x hidden x width``. What a row tile's padding
computes is the kernel's own cost and is not counted.

**Bytes.** An expert that got a row has its three matrices read in
both directions (``itemsize`` bytes an element) and its three
gradients written (float32); one that got none is not read. The rows
themselves are left out, as ``flops/moe.py`` leaves them.
"""
FORWARD, BACKWARD = 6.0, 12.0


def flops(routed_rows, hidden, width, share=FORWARD + BACKWARD):
    return share * routed_rows * hidden * width


def weight_bytes(expert_visits, hidden, width, itemsize):
    return 3.0 * expert_visits * hidden * width * (2.0 * itemsize + 4.0)
