"""From the profiler's trace to the numbers the per-layer metrics read.

``load(path)`` turns an ``.xplane.pb`` (read with nothing but JAX) into
plain data: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}`` — the form the recorded
trace under ``tests/data/`` is kept in. Everything else here is
arithmetic on that form:

* device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops``
  line holds one event per executed operation and ``XLA Modules`` one
  per executed program;
* the traced window is the host's ``bench.window`` annotation (host
  and device events share one clock); with none, the extent of the
  device events;
* busy time is the union of the op intervals inside the window, idle
  the rest; each idle gap is attributed to the ``bench.*`` host
  annotation that overlaps it most, the innermost among equals
  (``host:other`` where none does).
"""
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


_HLO = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = \(?([a-z0-9]+\[[\d,]*\])")


def short_name(raw):
    """A device event is named by its whole HLO instruction
    (``%fusion.1499 = bf16[16,1024,768]{...} fusion(...)``). Keep the
    instruction's name without its number, and the type and shape of
    its first result: ``fusion:bf16[16,1024,768]``. Renumbering between
    two compiles then changes nothing, and the layers' copies of one
    operation fall together."""
    m = _HLO.match(raw)
    return f"{m.group(1)}:{m.group(2)}" if m else raw[:96]


def start(trace_dir):
    """Start ``jax.profiler`` into ``trace_dir`` (the drivers stop it
    with ``jax.profiler.stop_trace``). The Python tracer is off: it
    records every Python call of every thread (38,638 of the 44,422
    host events in one second of the tiny serve rehearsal), which the
    reduction drops anyway, and it slows the engine's scheduler thread
    that the traced run measures. The ``bench.*`` annotations are
    TraceMe events of the host tracer and stay."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path, keep_lines=None):
    """Read an xplane file into the plain form. ``keep_lines(plane,
    line) -> bool`` drops lines while reading (host planes hold many
    threads)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, short = [], {}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_lines and not keep_lines(plane.name, line.name):
                continue
            shorten = line.name in (OPS_LINE, "Async XLA Ops")
            events = []
            for ev in line.events:
                raw = ev.name
                name = short.get(raw) if shorten else raw
                if name is None:    # a program's ops come again each run
                    name = short[raw] = short_name(raw)
                events.append([name, int(ev.start_ns), int(ev.duration_ns)])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------------
# interval arithmetic (nanoseconds, half-open)
# ---------------------------------------------------------------------------

def union(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(i) for i in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """Parts of the (disjoint, sorted) ``intervals`` outside the
    (disjoint, sorted) ``cover``."""
    out = []
    for s, e in intervals:
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a, b):
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


# ---------------------------------------------------------------------------
# reading the plain form
# ---------------------------------------------------------------------------

def device_planes(trace):
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane, line_name):
    """``[(name, start, end)]`` of one named line of a plane."""
    return [(n, s, s + d) for line in plane["lines"]
            if line["name"] == line_name for n, s, d in line["events"]]


def host_spans(trace, prefix="bench."):
    """``[(name, start, end)]`` of the host annotations the benchmark
    wrote, from every non-device plane."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"]
                    if n.startswith(prefix)]
    return out


def window(trace):
    """(start, end) of the traced window."""
    spans = [(s, e) for n, s, e in host_spans(trace) if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ops = [(s, e) for p in device_planes(trace)
           for _, s, e in line_events(p, OPS_LINE)]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in ops), max(e for _, e in ops)


def busy(trace):
    """(busy seconds averaged over the device planes, window seconds,
    per-plane busy unions clipped to the window)."""
    lo, hi = window(trace)
    unions = []
    for plane in device_planes(trace):
        unions.append(clip(union(
            (s, e) for _, s, e in line_events(plane, OPS_LINE)), lo, hi))
    if not unions:
        raise ValueError("the trace holds no device plane")
    mean = sum(total(u) for u in unions) / len(unions)
    return mean / 1e9, (hi - lo) / 1e9, unions


def op_seconds(trace, line_name=OPS_LINE):
    """{op name: seconds inside the window}, averaged over the device
    planes."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    out = {}
    for plane in planes:
        for n, s, e in line_events(plane, line_name):
            d = overlap((s, e), (lo, hi))
            if d:
                out[n] = out.get(n, 0.0) + d / 1e9 / len(planes)
    return out


def idle_percent(trace):
    """Share of the window, in percent, in which no operation ran on
    the device (mean over the device planes); None without a device
    plane."""
    if trace is None or not device_planes(trace):
        return None
    busy_s, window_s, _ = busy(trace)
    return 100.0 * (1.0 - busy_s / window_s)


def idle_gaps(trace):
    """{host span name: idle seconds attributed to it}, averaged over
    the device planes. One sweep over the gaps and the spans, both in
    time order: a serving trace holds some 300,000 gaps (one between
    every two operations of a program) and some 1,500 spans, and
    holding every gap against every span took longer than the run."""
    lo, hi = window(trace)
    _, _, unions = busy(trace)
    spans = sorted((s, e, n) for n, s, e in host_spans(trace)
                   if n != WINDOW_SPAN)
    out = {}
    for u in unions:
        live, upcoming = [], 0      # spans that reach past the last gap
        for gap in subtract([(lo, hi)], u):
            while upcoming < len(spans) and spans[upcoming][0] < gap[1]:
                live.append(spans[upcoming])
                upcoming += 1
            live = [span for span in live if span[1] > gap[0]]
            # the largest overlap wins; among equals the shortest span
            # (the innermost of nested annotations)
            best, best_key = "host:other", (0, 0)
            for s, e, n in live:
                key = (overlap(gap, (s, e)), s - e)
                if key[0] and key > best_key:
                    best, best_key = n, key
            out[best] = out.get(best, 0.0) \
                + (gap[1] - gap[0]) / 1e9 / len(unions)
    return out


def top(seconds_by_name, n=10):
    return [[k, v] for k, v in sorted(
        seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]
