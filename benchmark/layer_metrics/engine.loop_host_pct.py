"""Of the scheduler thread's time inside the traced window and outside
``hetu.serve.wait`` (it has something to serve), the share in percent
that is NOT a host sync on the device: the host's own work — admission,
the builds, the dispatches, the samples, the finishes — against the
time it stands blocked on a result. A sync is a
``hetu.serve.decode.device`` span less the ``hetu.device_dispatch`` /
``hetu.jit_compile`` spans inside it (a step that is not dispatched
ahead holds its dispatch), and a ``hetu.serve.prefill.sync`` span (the
wait inside ``serve.prefill.device``). Near 100: the host sets the
loop's pace and the chip waits for it; low: the chip does.

``None`` for a program from before the phase clock (no prefill sync
leaf to tell a prefill's wait from its dispatch), and where the thread
only waited.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.trace import stall_spans


def reduce(trace, facts):
    split = stall_spans.loop_split(trace)
    if split is None:
        return None
    sync, serving = split
    return 100.0 * (1.0 - sync / serving)
