"""Per-request serving lifecycle: ids, phase timelines, in-flight dumps.

The serving plane used to be observable only at engine granularity
(``step`` spans + counters); this module is the request-level layer the
whole plane shares:

* **request ids** — :func:`mint_request_id` mints a process-unique id;
  ``ServingHTTPServer`` honors/echoes ``x-request-id`` at ingress,
  ``ReplicaRouter`` propagates it, and
  ``ContinuousBatchingEngine.submit`` / ``MicroBatcher.submit`` accept
  it (minting one themselves when the caller didn't).
* **phase accounts** — every request carries a :class:`RequestTimeline`
  (telemetry on or off): a handful of MARKS, each a reading of the
  engine's phase clock (``scheduler.py``: the time, and the cumulative
  nanoseconds the scheduler thread has spent ``stalled`` behind other
  requests' prefills and blocked on a decode program) taken when the
  request changes state — submit, admission, first token, preemption,
  the end of a replay, retirement. Its ACCOUNT is differences of marks:
  ``queue`` -> ``prefill`` -> (``stalled`` / ``decode_device`` /
  ``decode_host``), with everything between a preemption and the token
  count it had before as ``replay``. The marks tile ``[submit,
  retire]``, so the account sums to the request's latency with no
  residual; it costs O(1) a request and nothing a row a step, lands on
  ``Future.account`` (ms) and feeds ``engine.stats()
  ["request_account"]``. With telemetry on, :func:`emit_request`
  exports it as the ``serve_request`` span (the account in its args)
  and ONE ``serve_phase`` span a contiguous episode — a run of decode
  steps between two stalls is one episode, a stall is one with
  ``blocked_by`` = the requests whose prompts ran — which
  ``merge_traces`` interleaves with the engine's own step spans and
  ``python -m hetu_tpu.telemetry.doctor --serving`` sums into
  conserving buckets.
* **in-flight dumps** — serving components :func:`register` themselves
  in a process-wide WeakSet; :func:`dump_inflight` (called from
  ``Telemetry.flush``, which the PR 4 crash handlers already invoke)
  writes ``requests_rank<r>.json`` beside the flight rings so a
  crashed/watchdogged engine names its stuck requests (id, phase,
  tokens, blocks held, age) in the black-box report.
"""
from __future__ import annotations

import itertools
import json
import os
import time
import weakref

import numpy as np

__all__ = ["mint_request_id", "RequestTimeline", "emit_request",
           "summarize_accounts", "register", "dump_inflight", "PHASES"]

# the disjoint phases of a request's account; they sum to its latency
PHASES = ("queue", "prefill", "stalled", "decode_device", "decode_host",
          "replay")
# a request's states between two marks are "queue", "prefill", "replay"
# (each its phase whole) and "run", which the engine's clock splits
# into stalled / decode_device / decode_host

_RID = itertools.count(1)


def mint_request_id():
    """Process-unique request id (``req-<pid>-<n>``, both hex)."""
    return f"req-{os.getpid():x}-{next(_RID):x}"


class RequestTimeline:
    """The marks of ONE request (see the module docstring). A mark is
    ``(t_ns, stalled_ns, decode_device_ns, state)``: the engine's clock
    reading and the state the request is in FROM there. Every writer
    but ``__init__`` is the scheduler thread; no locks."""

    __slots__ = ("rid", "t_submit", "marks", "cached_tokens",
                 "computed_tokens")

    def __init__(self, rid, now_ns):
        self.rid = rid
        self.t_submit = now_ns
        # the queue needs no reading of the clock: it is queue whole
        self.marks = [(now_ns, 0, 0, "queue")]
        # prompt tokens the first admission found cached / had to run
        self.cached_tokens = self.computed_tokens = 0

    @property
    def state(self):
        return self.marks[-1][3]

    def mark(self, reading, state):
        self.marks.append(reading + (state,))

    def skip_stall(self, stalled_ns):
        """Its ``run`` mark was taken inside a stall, the one its own
        prompt ran in, which stands at ``stalled_ns`` on the clock now
        that it is over (or that the request retires in it): the
        request runs from that reading, so none of that stall is its
        ``stalled``. The rest of it, its own sample and the finish, is
        its ``decode_host``."""
        t, _, device, state = self.marks[-1]
        if state == "run":      # not preempted since
            self.marks[-1] = (t, stalled_ns, device, state)

    def account_ns(self, reading):
        """Nanoseconds by phase up to ``reading`` (the retirement's):
        sums to ``reading[0] - t_submit`` exactly."""
        out = dict.fromkeys(PHASES, 0)
        ends = self.marks[1:] + [reading]
        for (t0, st0, dd0, state), end in zip(self.marks, ends):
            span = end[0] - t0
            if state == "run":
                stalled, device = end[1] - st0, end[2] - dd0
                out["stalled"] += stalled
                out["decode_device"] += device
                out["decode_host"] += span - stalled - device
            else:
                out[state] += span
        return out

    def episodes(self, t_retire_ns, stalls):
        """``[(phase, t0_ns, t1_ns, attrs)]`` tiling ``[submit,
        retire]``: one a state, a running state cut at the engine's
        ``stalls`` (``(t0, t1, request ids)``, in time order) into
        ``stalled`` episodes and the ``decode`` runs between them. The
        first ``prefill`` episode carries the prompt's token split."""
        ends = [m[0] for m in self.marks[1:]] + [t_retire_ns]
        recent = []         # the stalls that end inside this request
        for stall in reversed(stalls):
            if stall[1] <= ends[0]:
                break
            recent.append(stall)
        recent.reverse()
        out = []
        split = {"cached_tokens": self.cached_tokens,
                 "computed_tokens": self.computed_tokens}
        for (t0, _, _, state), t1 in zip(self.marks, ends):
            if state != "run":
                out.append((state, t0, t1,
                            split if state == "prefill" else None))
                if state == "prefill":
                    split = None
                continue
            cur = t0
            for s0, s1, rids in recent:
                s0, s1 = max(s0, cur), min(s1, t1)
                if s1 <= s0 or self.rid in rids:    # not its own
                    continue
                if s0 > cur:
                    out.append(("decode", cur, s0, None))
                out.append(("stalled", s0, s1,
                            {"blocked_by": ",".join(rids)}))
                cur = s1
            if t1 > cur:
                out.append(("decode", cur, t1, None))
        return [e for e in out if e[2] > e[1]]


def emit_request(tel, tl, t_retire_ns, tokens, preempts, account_ms,
                 stalls):
    """Export one retired request: the enclosing ``serve_request`` span
    with its account (``<phase>_ms`` args) and one ``serve_phase`` span
    an episode (attrs typed in ``telemetry.check.SPAN_SCHEMA``)."""
    for phase, t0, t1, attrs in tl.episodes(t_retire_ns, stalls):
        args = {"request_id": tl.rid, "phase": phase}
        if attrs:
            args.update(attrs)
        tel.complete("serve_phase", t0, t1, args)
    args = {"request_id": tl.rid, "phase": "retired",
            "tokens": int(tokens), "preempts": int(preempts)}
    args.update((f"{p}_ms", round(v, 4)) for p, v in account_ms.items())
    tel.complete("serve_request", tl.t_submit, t_retire_ns, args)


def summarize_accounts(accounts):
    """``engine.stats()["request_account"]`` from the last retired
    requests' accounts (tuples of ns in the order of ``PHASES``): in
    ms, the median of each phase and of the latency (``total``), and
    each one's mean over the p95 COHORT — the requests whose latency is
    at or above the 95th percentile, the ones a tail metric is made
    of."""
    if not accounts:
        return {"requests": 0}
    ms = np.asarray(accounts, np.float64) / 1e6
    ms = np.concatenate([ms, ms.sum(axis=1, keepdims=True)], axis=1)
    cohort = ms[ms[:, -1] >= np.percentile(ms[:, -1], 95)]

    def by_phase(values):
        return {k: round(float(v), 3)
                for k, v in zip(PHASES + ("total",), values)}

    return {"requests": len(ms),
            "p50_ms": by_phase(np.percentile(ms, 50, axis=0)),
            "p95_cohort_requests": len(cohort),
            "p95_cohort_mean_ms": by_phase(cohort.mean(axis=0))}


# ---------------------------------------------------------------------------
# in-flight registry: the crash-dump view of the serving plane
# ---------------------------------------------------------------------------

# live serving components exposing inflight_requests() (engines,
# batchers, routers); weak so a closed engine never pins itself here
_COMPONENTS = weakref.WeakSet()


def register(component):
    """Track a serving component for crash-time in-flight dumps."""
    _COMPONENTS.add(component)


def dump_inflight(out_dir, rank):
    """Write ``requests_rank<rank>.json`` — every registered
    component's in-flight request table (+ its ``stats()`` snapshot) —
    atomically (tmp+rename, flight-ring discipline). Returns the path,
    or None when no component is registered or the write failed; never
    raises (this runs inside crash handlers)."""
    entries = []
    for comp in list(_COMPONENTS):
        try:
            entry = {"name": getattr(comp, "name", None)
                     or type(comp).__name__,
                     "kind": type(comp).__name__,
                     "requests": comp.inflight_requests()}
            stats = getattr(comp, "stats", None)
            if callable(stats):
                entry["stats"] = stats()
            entries.append(entry)
        except Exception:       # noqa: BLE001 — never mask the crash
            continue
    if not entries:
        return None
    try:
        doc = {"rank": int(rank), "pid": os.getpid(),
               "wall": time.time(), "components": entries}
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"requests_rank{int(rank)}.json")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path
    except OSError:
        return None
