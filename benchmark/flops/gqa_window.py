"""Operations and bytes of grouped-query attention over a window or
over everything (``hetu_tpu/models/window_moe.py``), from COUNTED work:
the (query, key) pairs a prefill's real tokens score and the cached
rows a decode step's real tokens read, as the program's own counters
report them (``attn_window_rows`` / ``attn_full_rows``: a token's
``min(context, window)`` / ``context``, which is both, each already
times the layers of its kind).

**A scored pair** (the flash forward). ``q k`` and ``p v`` over one
head's ``head_dim``, 2 operations a multiply-add: ``4 x head_dim`` a
head, ``4 x head_dim x heads`` a (query, key) pair of a layer. Only the
pairs INSIDE the band (or under the diagonal) of REAL tokens are
counted; what a tile computes beyond them (the half of a tile an edge
cuts, a padded prompt's tail) is the kernel's own cost, so the share
cannot pass 100.

**A cached row read** (a decode step). One ``k`` and one ``v`` row of
the key/value heads alone, ``2 x kv_heads x head_dim x itemsize``
bytes; the six query heads of a group read the same row once. The
composed form gathers the rows into a buffer and reads that again: the
second pass is the implementation's cost and is not counted, so the
share reads at most half of what a paged kernel could.
"""


def score_pair_flops(heads, head_dim):
    return 4.0 * heads * head_dim


def cached_row_bytes(kv_heads, head_dim, itemsize):
    return 2.0 * kv_heads * head_dim * itemsize
