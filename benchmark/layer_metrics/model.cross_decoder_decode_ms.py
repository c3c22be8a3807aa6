"""Device milliseconds of the cross-decoder inside a decode program
(events ``hetu_cross_decoder_in`` .. ``_out``: the gated memory units
and the cross-attention layers behind the one full-attention layer,
their feed-forwards included): the median over the decode programs of
the traced window. The brackets are in a profiled engine's programs
alone. ``None`` for a program without them.

layer: model step (the serving models' paged decode forwards) — source:
device_trace — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import diff_events


def reduce(trace, facts):
    programs = diff_events.per_program(trace, "decode", "cross_decoder")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
