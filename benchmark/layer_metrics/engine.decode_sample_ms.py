"""Median milliseconds of ``hetu.serve.decode.sample`` (the token choice
per sequence on the host, appends, timeline notes) plus the
``hetu.serve.finish`` that follows it (freeing blocks, histograms,
``future.set_result`` and the callbacks it runs).

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import program_spans


def reduce(trace, facts):
    names = program_spans.names()
    samples = program_spans.spans(trace, name=names["decode_sample_span"])
    if not samples:
        return None
    finishes = program_spans.spans(trace, name=names["finish_span"])
    # in time order: a sample opens a total, the first finish after it
    # (and before the next sample) is added to it
    events = sorted([(s, e - s, True) for s, e in samples]
                    + [(s, e - s, False) for s, e in finishes])
    totals, wants_finish = [], False
    for _, duration, is_sample in events:
        if is_sample:
            totals.append(duration / 1e6)
        elif wants_finish:
            totals[-1] += duration / 1e6
        wants_finish = is_sample
    return stats.median(totals)
