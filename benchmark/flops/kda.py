"""Operations and bytes of the delta rule with a per-channel decay
(``hetu_tpu/ops/kda.py``) for ONE token in ONE such layer, from shapes:
``heads`` heads of ``d`` key and ``d`` value channels.

**A prompt token** (the chunked form, chunk ``C``). Operations are the
matmul FLOPs the chunked form NEEDS a (token, head), a multiply-add two:
the two triangles ``A_b`` and ``A_q`` (``2 C d``), the unit-triangular
solve against ``[K~ ; V]`` (``2 C d``), the chunk's reads of the state
``W S`` and ``Q S`` (``4 d^2``), ``A_q U`` (``C d``) and the state's
update (``2 d^2``): ``6 d^2 + 5 C d`` — 180,224 at ``d = C = 128``,
5.77e6 a (token, layer) over 32 heads. The program's kernel does more
(it pads the triangles to squares and inverts the triangular matrix by
products of nilpotent factors, twelve ``C^3`` matmuls a chunk): that is
its own cost. Bytes are the LEAST any implementation must move through
HBM for a (token, layer): ``q``, ``k``, ``v`` and the decay's
projection in, the output out (``5 heads d`` values in the model's
dtype) and the write strength (``heads`` float32): 41,088 in bfloat16.
The state never leaves the chip between a prompt's tokens. **Which
bound binds**: at the chip's published peaks (``peaks.json``: 197e12
bfloat16 FLOP/s, 819e9 bytes/s) the operations take 29 ns and the bytes
50 ns a (token, layer), so the MEMORY bound binds and the share is
taken against it; the recurrence is float32 (six bfloat16 passes a
matmul), for which ``peaks.json`` publishes no peak, so the share reads
LOW whatever the implementation and cannot pass 100.

**A decode token** (the step). The state is read once and written once,
``2 x 4 heads d^2`` bytes (4,194,304 at these widths: 12.58e6 each way
over six layers); ``q``, ``k``, ``v``, ``g`` and the output are 2% of
that and left out, so the share cannot pass 100.
"""


def chunk_flops_per_row(heads, d, chunk):
    return float(heads * (6 * d * d + 5 * chunk * d))


def chunk_bytes_per_row(heads, d, itemsize):
    return float(5 * heads * d * itemsize + 4 * heads)


def chunk_least_seconds_per_row(heads, d, chunk, itemsize, peaks):
    """``(seconds, "memory" or "compute")``: the larger of the two least
    times a (token, layer) takes at the chip's peaks, and which it is."""
    by_flops = chunk_flops_per_row(heads, d, chunk) \
        / peaks["bf16_flops_per_s"]
    by_bytes = chunk_bytes_per_row(heads, d, itemsize) \
        / peaks["hbm_bytes_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_flops \
        else (by_flops, "compute")


def step_bytes_per_row(heads, d):
    return float(2 * 4 * heads * d * d)
