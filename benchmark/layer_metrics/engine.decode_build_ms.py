"""Median milliseconds of ``hetu.serve.decode.build``: lazy capacity and
copy-on-write, the slot grid in numpy, the four host arrays of a decode
step and their puts to the device.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import program_spans


def reduce(trace, facts):
    builds = program_spans.spans(
        trace, name=program_spans.names()["decode_build_span"])
    if not builds:
        return None
    return stats.median(program_spans.milliseconds(builds))
