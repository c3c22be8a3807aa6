"""Operations and bytes of the routed experts' grouped matmuls
(``hetu_tpu/ops/moe.py``: gate|up, then down, over the held experts),
from COUNTED work: the rows that landed on a held expert and the held
experts that got at least one row, as the program's own counters report
them (``moe_routed_rows``, ``moe_expert_visits``).

Operations are the algorithm's: a routed row goes through three
``hidden x width`` matrices (gate, up, down), 2 operations a
multiply-add: ``6 x hidden x width`` a row. Whatever the kernel computes
beyond the rows that are there (a row tile's padding) is its own cost
and is not counted.

Bytes are the weights that must cross HBM once a visit: an expert that
got a row has its three matrices read, ``3 x hidden x width x
itemsize``; an expert that got none is not read. The rows themselves
(a few kilobytes each) are left out: a decode step's bytes are the
weights' to within a percent.
"""


def flops(routed_rows, hidden, width):
    return 6.0 * routed_rows * hidden * width


def weight_bytes(expert_visits, hidden, width, itemsize):
    return 3.0 * expert_visits * hidden * width * itemsize
