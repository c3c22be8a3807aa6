"""Where a training step's device time goes, by graph op and by role,
from a kept profile: the account the five ``step.*`` metrics are made
of (``trace/step_account.py``), printed whole.

    python benchmark/run.py --workload <train cell> --trace 1 --keep-trace   # prints {"trace_dir": ...}
    python -m benchmark.tools.step_account <trace_dir> [--by role|op_type|node|parameter] [--top N]

``<trace_dir>`` is any directory ``jax.profiler`` wrote into (a kept
benchmark trace, or ``with ht.profiler.trace(dir):`` around a training
loop on a TPU). Without ``--by``: every instruction of the step that
ran, most time first, with its kind (``forward``, ``backward``,
``optimizer``, ``mixed``, ``step``, ``unscoped``, ``unjoined``), the
roles found in it, the graph op (``op_type/node[/parameter]``) most of
its operations were traced under, calls a step and milliseconds a step.
With ``--by``: the same time grouped by role, by ``(role, op_type)``,
by ``(role, op_type, node)`` or by the parameter an instruction updates
(``optimizer`` alone, ``mixed`` where the update is fused into an
instruction of another role: a weight gradient's matmul).
The totals line first, as one JSON object. Needs no TPU: it reads the
file.
"""
import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark.trace import step_account, xplane        # noqa: E402

GROUPS = {
    "role": lambda a: a.whose,
    "op_type": lambda a: f"{a.whose} {a.op_type}",
    "node": lambda a: f"{a.whose} {a.op_type}/{a.node}",
    "parameter": lambda a: f"{a.kind:9s} {a.parameter}"
    if a.parameter else None,
}


def table(account, by=None, top=None):
    """The account as lines of text."""
    if by is None:
        rows = [(f"{name:44s} {att.kind:9s} {att.roles:11s} "
                 + "/".join(p for p in (att.op_type, att.node,
                                        att.parameter) if p),
                 calls / account.steps, ns / 1e6 / account.steps)
                for name, att, calls, ns in account.rows]
    else:
        rows = [r for r in account.grouped(GROUPS[by]) if r[0] is not None]
    return [f"{ms:10.4f} ms {calls:8.1f} x  {what}"
            for what, calls, ms in rows[:top]]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace_dir")
    p.add_argument("--by", choices=sorted(GROUPS))
    p.add_argument("--top", type=int, default=None)
    args = p.parse_args(argv)
    path = xplane.find_xplane(args.trace_dir)
    trace = xplane.load(path, keep_lines=lambda plane, line:
                        plane.startswith("/host:")
                        or line == xplane.OPS_LINE)
    account = step_account.account(path, xplane.window(trace))
    if account is None:         # the reason is already printed
        return 1
    print("\n".join(table(account, args.by, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
