"""Print what a trace holds: planes, lines, and the names that took
most time on each line. ``python -m benchmark.trace.inspect <dir>``.
Look at a trace with this before writing a reader against it."""
import sys

from benchmark.trace import xplane


def describe(trace, top_n=25):
    rows = []
    for plane in trace["planes"]:
        rows.append(f"plane {plane['name']!r}")
        for line in plane["lines"]:
            by = {}
            for n, _, d in line["events"]:
                c = by.setdefault(n, [0, 0])
                c[0] += 1
                c[1] += d
            rows.append(f"  line {line['name']!r}: "
                        f"{len(line['events'])} events")
            for n, (count, ns) in sorted(
                    by.items(), key=lambda kv: -kv[1][1])[:top_n]:
                rows.append(f"    {ns / 1e6:10.3f} ms {count:6d} x {n}")
    return "\n".join(rows)


def event_stats(path, pattern, limit=8):
    """Raw stats of the first ``limit`` device events whose name
    matches ``pattern`` — where a kernel's own name hides when the
    event is called ``custom-call.N``."""
    import re
    from jax.profiler import ProfileData
    pat, rows = re.compile(pattern), []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if pat.search(ev.name) and len(rows) < limit:
                    rows.append(f"{plane.name} | {line.name} | {ev.name} "
                                f"| {ev.duration_ns} ns | "
                                f"{dict(ev.stats)}")
    return "\n".join(rows)


if __name__ == "__main__":
    path = xplane.find_xplane(sys.argv[1])
    print(describe(xplane.load(path)))
    if len(sys.argv) > 2:
        print(event_stats(path, sys.argv[2]))
