"""The device a run is on: refusal without it, its stamp, its peaks."""
import os

from benchmark.harness.spec import BENCH_DIR, read_json


class NoAccelerator(SystemExit):
    """Raised (exit code 3) when the chips the cell asks for are not
    there; no result line is printed."""

    def __init__(self, message):
        print(f"benchmark: {message}", flush=True)
        super().__init__(3)


def require_tpu(chips):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{devices[0].platform!r} ({devices[0].device_kind}). "
            f"There is no CPU fallback (--rehearse runs tiny files and "
            f"never passes).")
    if len(devices) != chips:
        raise NoAccelerator(
            f"this cell needs {chips} chip(s); JAX found {len(devices)}")
    return devices


def stamp(devices):
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def committed_bytes(devices):
    """Bytes the fullest device has committed right now: live buffers
    (``bytes_in_use``) plus the arena XLA keeps reserved for the running
    programs' temporaries (``bytes_reserved``). On this TPU runtime a
    program's temporaries never show in ``peak_bytes_in_use``: a BERT
    step whose compiled program needs 13.5 GB of them read 2.7 GB there
    and 13.5 GB under ``bytes_reserved`` (my chip run, PR 23)."""
    out = 0
    for d in devices:
        s = d.memory_stats() or {}
        out = max(out, s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))
    return int(out)


def memory_peak_bytes(devices, committed_at_window_end=0):
    """Peak bytes on the fullest device, as JAX reports them: the
    larger of ``peak_bytes_in_use`` and what the driver found committed
    (:func:`committed_bytes`) when the window closed — an instant that
    happened, so never an overstatement. 0 where the backend reports
    nothing (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks + [committed_at_window_end]))


def peaks(device_kind):
    """{"bf16_flops_per_s", "hbm_bytes_per_s", ...} of one chip. A kind
    that ``peaks.json`` does not hold is an error, never a default."""
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]
