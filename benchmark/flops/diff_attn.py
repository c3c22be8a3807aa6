"""Operations and bytes of differential attention
(``hetu_tpu/ops/attention.py``: ``diff_prefill_attention``,
``diff_rows_attention``) from COUNTED work, as the program's own
counters report it (``hetu_tpu/models/shared_cache_decoder.py``:
``attn_window_rows``, a token's ``min(context, window)`` times the
window layers; ``attn_full_rows``, a row's ``context`` times the layers
that read the one shared cache).

**A scored pair** (the banded prefill call). A pair of heads scores a
(query, key) pair in TWO maps, each ``q k`` over ``head_dim`` and ``p
U`` over the value's ``2 x head_dim``, 2 operations a multiply-add: ``2
x (2 x head_dim + 4 x head_dim) = 12 x head_dim`` a pair of heads, ``6
x head_dim x heads`` a (query, key) pair of a layer (15,360 at 40 heads
of 64). The kernel pads each map's query and key to the value's width
and computes whole tiles: what it multiplies beyond this count (the
zeros, the half of a tile an edge cuts, a padded prompt's tail) is its
own cost, so the share cannot pass 100.

**A shared row read** (a decode step). One ``k`` and one ``v`` row of
the key/value heads, ``2 x kv_heads x head_dim x itemsize`` bytes
(5,120 at 20 heads of 64 in bfloat16), once for each layer that reads
it: a layer's query depends on the layer before, so eight reads cannot
be one, and the rows do not fit on the chip between layers. The gather
that brings the rows in position order and the context bucket's padding
are the implementation's cost and are not counted, so the share cannot
pass 100.
"""


def score_pair_flops(heads, head_dim):
    return 6.0 * heads * head_dim


def shared_row_bytes(kv_heads, head_dim, itemsize):
    return 2.0 * kv_heads * head_dim * itemsize
