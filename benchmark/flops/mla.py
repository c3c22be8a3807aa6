"""Operations and bytes of latent (MLA) attention, from COUNTED work.

*Absorbed* (a decode step, ``hetu_tpu/ops/pallas_mla.py``): every head
scores against the same cache rows ``[c ; k_r]`` (``latent + rope``
wide) and sums the latent part of them. A (sequence, layer, step) that
attends ``n`` context rows must read those rows once, ``n x (latent +
rope) x itemsize`` bytes, and do ``2 x heads x (latent + rope)``
operations a row for the scores and ``2 x heads x latent`` for the
context: ``2 x heads x (2 x latent + rope)`` a row. ``context_rows`` is
the program's ``mla_context_rows`` counter (rows attended, summed over
sequences, layers and steps).

*Expanded* (a whole-prompt prefill, the flash kernel at query/key heads
of ``nope + rope`` and value heads of ``v``): a causal pair costs ``2 x
(nope + rope)`` operations for its score and ``2 x v`` for its share of
the context, per head. ``score_pairs`` is the program's
``mla_score_pairs`` counter: query-key pairs of the REAL prompt lengths,
``p (p + 1) / 2`` a prompt, summed over layers. What the kernel computes
beyond them (the bucket's padding, the values padded to the keys' width)
is its own cost and is not counted.
"""


def absorbed_bytes(context_rows, latent, rope, itemsize):
    return float(context_rows) * (latent + rope) * itemsize


def absorbed_flops(context_rows, heads, latent, rope):
    return 2.0 * context_rows * heads * (2 * latent + rope)


def expanded_flops(score_pairs, heads, nope, rope, v):
    return 2.0 * score_pairs * heads * (nope + rope + v)
