"""Static concurrency verifier for the threaded host runtime (HT6xx).

PRs 2-10 made the host side a genuinely concurrent program: the ingest
engine's worker, the micro-batcher's condition loop, p2p accept/
connection readers, three HTTP servers, the PS push pool,
signal/atexit crash handlers. The preflight stack
(HT1xx-HT5xx) statically refuses to launch broken *fleets*; this pass
extends the same philosophy to broken *threads* — the classic lockset
(Eraser, Savage et al. 1997) and lock-order-graph (GoodLock)
algorithms, implemented over our small, idiomatic threading surface.

Per module, the pass models:

* **thread entry points** — ``threading.Thread(target=f)`` /
  ``Timer(..., f)`` targets, ``pool.submit(f, ...)`` callees,
  ``signal.signal(sig, f)`` handlers, and ``do_*``/``handle`` methods
  of ``BaseHTTPRequestHandler`` subclasses (each HTTP request runs on
  its own server thread). Everything reachable from an entry through
  the intra-module call graph runs in that entry's context; a function
  with no in-module callers is assumed main/API context.
* **shared mutable state** — ``self.attr`` and module-global writes
  (assignments, augmented assigns, subscript stores, and mutating
  method calls like ``.append``/``.update``), excluding ``__init__``
  (pre-thread-start construction).
* **locks** — attributes/globals assigned ``threading.Lock`` /
  ``RLock`` / ``Condition`` / ``Semaphore``, with ``Condition(lock)``
  aliased to the lock it wraps; per-statement locksets from ``with``
  regions, plus locks a helper's *every* in-module call site holds
  (so a helper that is only ever called under the lock counts as
  guarded).

and emits:

=====  =====  ==============================================================
HT601  error  shared-state write from >=2 thread contexts with an empty
              common lockset (the Eraser condition)
HT602  error  lock-order inversion: opposite acquisition orders of a lock
              pair; names both locks and their ``defined_at`` lines
HT603  warn   blocking call while holding a lock: ``Condition.wait`` with
              no timeout (while other locks are held), ``queue.get``,
              ``join``, ``Future.result``, socket ops, ``time.sleep``
HT604  warn   thread/pool lifecycle leak: non-daemon thread that is never
              joined, executor pool with no ``shutdown``/``with`` path
HT605  warn   unguarded lazy-init check-then-create (``if x is None: x =
              ...``) on shared state in a threaded module
HT606  warn   async-signal-unsafe work — lock acquisition or file IO —
              inside an installed signal handler
=====  =====  ==============================================================

A line containing ``# ht-ok`` (or the historical ``# lock-ok`` alias)
suppresses its findings; the annotated form ``# ht-ok: HT603 <reason>``
suppresses only that code and is the house style (the reason is the
review artifact — the shared :func:`~.findings.suppressed` helper makes
every pass's waivers one grep surface). For multi-site findings
(HT601/HT602) the annotation may sit on any involved line.

CLI: ``python -m hetu_tpu.analysis.concurrency [paths...] [--json]``
(default: the ``hetu_tpu`` package) — exit 1 when any unsuppressed
finding exists; wired into CI as the ``concurrency-lint`` job. The
dynamic twin — instrumented locks measuring the *observed* acquisition
graph under real load — is ``hetu_tpu/analysis/racecheck.py``.

Scope limitation, by design: the pass is per-module and name-based.
A lock passed across modules, attribute aliasing, and data handed
between threads through containers are invisible; cycles longer than
two locks are not searched. The racecheck harness is the net under
those — and, like jit_purity, the direct layer is where our bugs have
actually lived.
"""
from __future__ import annotations

import ast
import os
import re
import sys

from .findings import Finding, Report, suppressed

__all__ = ["check_source", "check_paths", "main"]

_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore"}
_POOL_FACTORIES = {"ThreadPoolExecutor", "ProcessPoolExecutor"}
_MUTATORS = {"append", "appendleft", "extend", "extendleft", "insert",
             "add", "discard", "remove", "update", "clear", "pop",
             "popleft", "setdefault", "put", "put_nowait"}
_SOCKET_BLOCKING = {"accept", "recv", "recv_into", "recvfrom", "sendall",
                    "create_connection"}
_QUEUE_HINTS = re.compile(r"(queue|inbox|jobs|mailbox|^_?q$)", re.I)
_JOIN_EXEMPT_ROOTS = {"os", "posixpath", "ntpath", "str", "shutil"}
_INIT_METHODS = {"__init__", "__new__", "__post_init__", "__set_name__"}
_HTTP_HANDLER_BASES = {"BaseHTTPRequestHandler",
                       "SimpleHTTPRequestHandler", "BaseRequestHandler",
                       "StreamRequestHandler"}
_EVENT_HINTS = {"event", "ev", "done", "stop", "ready"}
_MAIN = "main"


def _dotted(node):
    """Attribute/Name chain -> tuple of names, ('self','_cond') etc."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


class _Fn:
    """Everything the fixpoints need about one function body."""

    __slots__ = ("qual", "node", "cls", "calls", "acquires", "writes",
                 "blocking", "lazy", "sigwork", "contexts",
                 "callee_held", "is_entry", "globals_decl")

    def __init__(self, qual, node, cls):
        self.qual = qual
        self.node = node
        self.cls = cls                  # enclosing class qualname or None
        self.calls = []                 # (callee_qual, locks, lineno)
        self.acquires = []              # (lock_key, lineno, held_before)
        self.writes = []                # (state_key, lineno, locks)
        self.blocking = []              # (desc, lineno, locks, waited)
        self.lazy = []                  # (state_key, lineno, locks)
        self.sigwork = []               # (desc, lineno) for HT606
        self.contexts = set()
        self.callee_held = None         # fixpoint: locks held at entry
        self.is_entry = False
        self.globals_decl = set()


class _Module:
    """One module's collected model (built by two AST passes)."""

    def __init__(self, path):
        self.path = path
        self.fns = {}                   # qual -> _Fn
        self.methods = {}               # class qual -> {name: fn qual}
        self.scope_defs = {}            # scope qual ('' = module) -> {name: qual}
        self.locks = {}                 # lock_key -> defined lineno
        self.lock_alias = {}            # lock_key -> canonical lock_key
        self.entries = {}               # fn qual -> set of context labels
        self.signal_handlers = set()    # quals registered via signal.signal
        self.threads = []               # thread/pool creations (HT604)
        self.joins = set()              # receiver chains .join()ed
        self.shutdowns = set()          # receiver chains .shutdown()ed
        self.has_threading = False

    def canon(self, key):
        seen = set()
        while key in self.lock_alias and key not in seen:
            seen.add(key)
            key = self.lock_alias[key]
        return key

    def lock_line(self, key):
        return self.locks.get(key) or self.locks.get(self.canon(key))


def _lock_name(key):
    if key[0] == "attr":
        return f"{key[1].rsplit('.', 1)[-1]}.{key[2]}"
    return key[-1]


def _state_name(key):
    if key[0] == "attr":
        return f"{key[1].rsplit('.', 1)[-1]}.{key[2]}"
    return f"global {key[1]}"


# ---------------------------------------------------------------------------
# pass 1: scopes, locks, thread/pool creations
# ---------------------------------------------------------------------------

class _Collector(ast.NodeVisitor):
    def __init__(self, mod):
        self.mod = mod
        self.cls_stack = []             # fully qualified class names
        self.fn_stack = []              # _Fn objects
        self.http_classes = set()

    def _scope(self):
        if self.fn_stack:
            return self.fn_stack[-1].qual
        if self.cls_stack:
            return self.cls_stack[-1]
        return ""

    def _qual(self, name):
        prefix = self._scope()
        return f"{prefix}.{name}" if prefix else name

    def visit_ClassDef(self, node):
        qual = self._qual(node.name)
        bases = {b[-1] for b in map(_dotted, node.bases) if b}
        if bases & _HTTP_HANDLER_BASES:
            self.http_classes.add(qual)
            self.mod.has_threading = True
        self.cls_stack.append(qual)
        self.mod.methods.setdefault(qual, {})
        self.generic_visit(node)
        self.cls_stack.pop()

    def _visit_fn(self, node):
        qual = self._qual(node.name)
        cls = self.cls_stack[-1] if self.cls_stack else None
        fn = _Fn(qual, node, cls)
        self.mod.fns[qual] = fn
        self.mod.scope_defs.setdefault(self._scope(), {})[node.name] = qual
        if cls is not None:
            self.mod.methods.setdefault(cls, {})[node.name] = qual
            if cls in self.http_classes and (
                    node.name.startswith("do_") or node.name == "handle"):
                # each HTTP request runs this on its own server thread
                self.mod.entries.setdefault(qual, set()).add(f"http:{qual}")
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Global):
                fn.globals_decl.update(stmt.names)
        self.fn_stack.append(fn)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # -- lock / thread / pool creation sites -----------------------------
    def _state_key_of_target(self, tgt):
        if isinstance(tgt, ast.Attribute) and \
                isinstance(tgt.value, ast.Name) and tgt.value.id == "self":
            cls = self.cls_stack[-1] if self.cls_stack else "?"
            return ("attr", cls, tgt.attr)
        if isinstance(tgt, ast.Name):
            if not self.fn_stack:
                return ("global", tgt.id)
            if tgt.id in self.fn_stack[-1].globals_decl:
                return ("global", tgt.id)
            return ("local", self.fn_stack[-1].qual, tgt.id)
        return None

    def visit_Assign(self, node):
        value = node.value
        chain = _dotted(value.func) if isinstance(value, ast.Call) else None
        if chain and chain[-1] in _LOCK_FACTORIES:
            for tgt in node.targets:
                key = self._state_key_of_target(tgt)
                if key is None:
                    continue
                self.mod.locks[key] = node.lineno
                self.mod.has_threading = True
                if chain[-1] == "Condition" and value.args:
                    wrapped = self._state_key_of_target(value.args[0]) \
                        if isinstance(value.args[0],
                                      (ast.Name, ast.Attribute)) else None
                    if wrapped is not None:
                        self.mod.lock_alias[key] = wrapped
        if chain and chain[-1] in _POOL_FACTORIES | {"Thread", "Timer"}:
            self._note_spawn(node.lineno, value, chain,
                             [k for k in (self._state_key_of_target(t)
                                          for t in node.targets) if k])
        self.generic_visit(node)

    def _note_spawn(self, lineno, call, chain, targets, in_with=False):
        self.mod.has_threading = True
        kind = "pool" if chain[-1] in _POOL_FACTORIES else "thread"
        daemon = None
        for kw in call.keywords:
            if kw.arg == "daemon":
                daemon = getattr(kw.value, "value", None)
        self.mod.threads.append({"kind": kind, "lineno": lineno,
                                 "daemon": daemon, "targets": targets,
                                 "in_with": in_with, "node": call})

    def visit_With(self, node):
        for item in node.items:
            expr = item.context_expr
            chain = _dotted(expr.func) if isinstance(expr, ast.Call) \
                else None
            if chain and chain[-1] in _POOL_FACTORIES:
                self._note_spawn(node.lineno, expr, chain, [],
                                 in_with=True)
        self.generic_visit(node)

    def visit_Call(self, node):
        chain = _dotted(node.func)
        if chain:
            if chain[-1] == "join" and chain[0] not in _JOIN_EXEMPT_ROOTS:
                self.mod.joins.add(chain[:-1])
            if chain[-1] in ("shutdown", "close", "cancel"):
                self.mod.shutdowns.add(chain[:-1])
            if chain[0] in ("threading", "concurrent") or \
                    chain[-1] in ("Thread", "Timer", "submit",
                                  "serve_forever"):
                self.mod.has_threading = True
            # bare threading.Thread(...).start() never passes an Assign
            if chain[-1] in ("Thread", "Timer") and not any(
                    t["node"] is node for t in self.mod.threads):
                self._note_spawn(node.lineno, node, chain, [])
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# pass 2: per-function body analysis with lockset tracking
# ---------------------------------------------------------------------------

class _BodyWalker:
    """Walks one function body carrying the set of held locks; records
    writes, acquisitions, calls, blocking ops, and lazy-init shapes."""

    def __init__(self, mod, fn):
        self.mod = mod
        self.fn = fn

    # -- name resolution -------------------------------------------------
    def _resolve_callable(self, expr):
        """fn qualname for a Name / self.attr reference, or None."""
        chain = _dotted(expr)
        if chain is None:
            return None
        if chain[0] == "self" and len(chain) == 2 and self.fn.cls:
            return self.mod.methods.get(self.fn.cls, {}).get(chain[1])
        if len(chain) == 1:
            scope = self.fn.qual
            while True:
                # class scopes are not on the name-resolution path of
                # function bodies (Python scoping) — skip them
                if scope not in self.mod.methods:
                    hit = self.mod.scope_defs.get(scope, {}).get(chain[0])
                    if hit:
                        return hit
                if "." not in scope:
                    break
                scope = scope.rsplit(".", 1)[0]
            return self.mod.scope_defs.get("", {}).get(chain[0])
        return None

    def _lock_key(self, expr):
        chain = _dotted(expr)
        if chain is None:
            return None
        if chain[0] == "self" and len(chain) == 2 and self.fn.cls:
            key = ("attr", self.fn.cls, chain[1])
        elif len(chain) == 1:
            key = ("local", self.fn.qual, chain[0])
            if key not in self.mod.locks:
                key = ("global", chain[0])
        else:
            return None
        if key not in self.mod.locks and key not in self.mod.lock_alias:
            return None
        return self.mod.canon(key)

    def _state_key(self, tgt):
        if isinstance(tgt, ast.Attribute) and \
                isinstance(tgt.value, ast.Name) and tgt.value.id == "self":
            return ("attr", self.fn.cls or "?", tgt.attr)
        if isinstance(tgt, ast.Name) and tgt.id in self.fn.globals_decl:
            return ("global", tgt.id)
        return None

    # -- traversal -------------------------------------------------------
    def walk(self):
        for stmt in self.fn.node.body:
            self._stmt(stmt, frozenset())

    def _stmt(self, node, held):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return                      # nested defs analyzed on their own
        if isinstance(node, ast.With):
            inner = set(held)
            for item in node.items:
                self._exprs(item.context_expr, frozenset(inner))
                lk = self._lock_key(item.context_expr)
                if lk is not None:
                    self.fn.acquires.append((lk, node.lineno,
                                             frozenset(inner)))
                    inner.add(lk)
            for child in node.body:
                self._stmt(child, frozenset(inner))
            return
        if isinstance(node, ast.If):
            self._maybe_lazy_init(node, held)
        # expressions attached directly to THIS statement
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                self._note_write_target(tgt, node.lineno, held)
        elif isinstance(node, ast.AugAssign):
            self._note_write_target(node.target, node.lineno, held)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._exprs(child, held)
        # nested statements (If/For/While/Try bodies)
        for field in ("body", "orelse", "finalbody"):
            for child in getattr(node, field, []) or []:
                if isinstance(child, ast.stmt):
                    self._stmt(child, held)
        for handler in getattr(node, "handlers", []) or []:
            for child in handler.body:
                self._stmt(child, held)

    def _exprs(self, expr, held):
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                self._note_call(node, held)

    # -- lazy init (HT605) -----------------------------------------------
    def _maybe_lazy_init(self, node, held):
        """``if X is None: X = Call(...)`` / ``if not X: X = ...`` with
        no lock held — the check-then-create race. The double-checked
        form records nothing: the assignment's lockset is non-empty."""
        test = node.test
        name = None
        if isinstance(test, ast.Compare) and len(test.ops) == 1 and \
                isinstance(test.ops[0], ast.Is) and \
                isinstance(test.comparators[0], ast.Constant) and \
                test.comparators[0].value is None:
            name = test.left
        elif isinstance(test, ast.UnaryOp) and \
                isinstance(test.op, ast.Not):
            name = test.operand
        if name is None:
            return
        key = self._state_key(name)
        if key is None:
            return
        if self.fn.node.name in _INIT_METHODS:
            return                      # construction precedes threads
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Assign):
                for tgt in stmt.targets:
                    if self._state_key(tgt) == key:
                        locks = set(held) | self._locks_between(node, stmt)
                        self.fn.lazy.append((key, stmt.lineno,
                                             frozenset(locks)))

    def _locks_between(self, root, assign):
        """Locks acquired by With statements between root and assign
        (the inner ``with`` of double-checked locking)."""
        out = set()

        def scan(node, held):
            if node is assign:
                out.update(held)
                return True
            if isinstance(node, ast.With):
                inner = set(held)
                for item in node.items:
                    lk = self._lock_key(item.context_expr)
                    if lk is not None:
                        inner.add(lk)
                return any(scan(c, inner) for c in node.body)
            return any(scan(c, held) for c in ast.iter_child_nodes(node)
                       if isinstance(c, ast.stmt))

        scan(root, set())
        return out

    # -- writes / calls / blocking ----------------------------------------
    def _note_write_target(self, tgt, lineno, held):
        while isinstance(tgt, ast.Subscript):
            tgt = tgt.value             # self.x[k] = v mutates self.x
        if isinstance(tgt, ast.Tuple):
            for el in tgt.elts:
                self._note_write_target(el, lineno, held)
            return
        key = self._state_key(tgt)
        if key is not None:
            self.fn.writes.append((key, lineno, held))

    def _note_call(self, node, held):
        callee = self._resolve_callable(node.func)
        if callee is not None:
            self.fn.calls.append((callee, held, node.lineno))
        chain = _dotted(node.func)
        if chain is None:
            return
        last = chain[-1]
        # mutating method call on shared state: self.x.append(...)
        if len(chain) >= 3 and chain[0] == "self" and \
                last in _MUTATORS and self.fn.cls:
            self.fn.writes.append((("attr", self.fn.cls, chain[1]),
                                   node.lineno, held))
        elif len(chain) == 2 and last in _MUTATORS and \
                chain[0] in self.fn.globals_decl:
            self.fn.writes.append((("global", chain[0]), node.lineno,
                                   held))
        # entry registrations
        if last in ("Thread", "Timer"):
            target = None
            for kw in node.keywords:
                if kw.arg == "target":
                    target = kw.value
            if last == "Timer" and len(node.args) >= 2:
                target = node.args[1]
            self._register_entry(target, "thread")
        elif last == "submit" and node.args:
            self._register_entry(node.args[0], "pool")
        elif chain == ("signal", "signal") and len(node.args) >= 2:
            q = self._register_entry(node.args[1], "signal")
            if q:
                self.mod.signal_handlers.add(q)
        self._note_blocking(node, chain, last, held)
        if last == "acquire":
            lk = self._lock_key(node.func.value)
            if lk is not None:
                self.fn.sigwork.append(
                    (f"lock acquire on {_lock_name(lk)}", node.lineno))
        if chain == ("open",):
            self.fn.sigwork.append(("file IO (open)", node.lineno))

    def _register_entry(self, expr, kind):
        if expr is None:
            return None
        qual = self._resolve_callable(expr)
        if qual is None:
            return None
        self.mod.entries.setdefault(qual, set()).add(f"{kind}:{qual}")
        self.mod.has_threading = True
        return qual

    def _has_timeout(self, node):
        if any(kw.arg in ("timeout", "block") and
               not (isinstance(kw.value, ast.Constant)
                    and kw.value.value is None)
               for kw in node.keywords):
            return True
        return bool(node.args)          # wait(t) / join(t) / result(t)

    def _note_blocking(self, node, chain, last, held):
        waited = None
        desc = None
        recv = chain[:-1]
        if last in ("wait", "wait_for") and recv:
            if recv[-1].lower().lstrip("_") in _EVENT_HINTS:
                return                  # Event.wait: no lock to order
            if last == "wait_for" and len(node.args) > 1:
                return                  # wait_for(pred, timeout)
            if last == "wait" and self._has_timeout(node):
                return
            if any(kw.arg == "timeout" and
                   not (isinstance(kw.value, ast.Constant)
                        and kw.value.value is None)
                   for kw in node.keywords):
                return
            waited = self._lock_key(node.func.value)
            desc = f"{'.'.join(chain)}() with no timeout"
        elif last == "join" and chain[0] not in _JOIN_EXEMPT_ROOTS \
                and not self._has_timeout(node):
            desc = f"{'.'.join(chain)}()"
        elif last == "result" and not self._has_timeout(node):
            desc = f"{'.'.join(chain)}()"
        elif last == "get" and recv and _QUEUE_HINTS.search(recv[-1]) \
                and not node.args:
            # zero positional args: Queue.get() blocks; dict.get(k) is
            # a lookup and never does
            desc = f"blocking {'.'.join(chain)}()"
        elif last in _SOCKET_BLOCKING:
            desc = f"socket {'.'.join(chain)}()"
        elif chain == ("time", "sleep"):
            desc = "time.sleep()"
        if desc is not None:
            self.fn.blocking.append((desc, node.lineno, held, waited))


# ---------------------------------------------------------------------------
# fixpoints
# ---------------------------------------------------------------------------

def _propagate(mod):
    """Contexts flow entry -> callee; ``callee_held`` is the meet (set
    intersection) of locks held at every in-module call site."""
    for qual, labels in mod.entries.items():
        fn = mod.fns.get(qual)
        if fn is not None:
            fn.is_entry = True
            fn.contexts |= labels
    callers = {q: [] for q in mod.fns}
    for fn in mod.fns.values():
        for callee, locks, _ln in fn.calls:
            if callee in callers:
                callers[callee].append((fn.qual, locks))
    for fn in mod.fns.values():
        if callers[fn.qual] or fn.is_entry:
            continue
        parent = fn.qual.rsplit(".", 1)[0] if "." in fn.qual else ""
        if parent in mod.fns:
            continue                    # uncalled nested helper: no ctx
        fn.contexts.add(_MAIN)          # uncalled top-level: API surface
    for _ in range(len(mod.fns) + 2):
        changed = False
        for fn in mod.fns.values():
            for caller, _locks in callers[fn.qual]:
                add = mod.fns[caller].contexts - fn.contexts
                if add:
                    fn.contexts |= add
                    changed = True
        if not changed:
            break
    for _ in range(len(mod.fns) + 2):
        changed = False
        for fn in mod.fns.values():
            sites = callers[fn.qual]
            if not sites:
                new = frozenset()
            else:
                metas = []
                for caller, locks in sites:
                    ch = mod.fns[caller].callee_held
                    metas.append(set(locks) | (set(ch) if ch else set()))
                new = frozenset(set.intersection(*metas))
            if new != fn.callee_held:
                fn.callee_held = new
                changed = True
        if not changed:
            break
    for fn in mod.fns.values():
        if fn.callee_held is None:
            fn.callee_held = frozenset()


def _transitive_acquires(mod):
    """What calling f (transitively, in-module) acquires."""
    out = {q: {(lk, ln) for lk, ln, _h in fn.acquires}
           for q, fn in mod.fns.items()}
    for _ in range(len(mod.fns) + 2):
        changed = False
        for fn in mod.fns.values():
            for callee, _locks, _ln in fn.calls:
                if callee in out and not out[callee] <= out[fn.qual]:
                    out[fn.qual] |= out[callee]
                    changed = True
        if not changed:
            break
    return out


# ---------------------------------------------------------------------------
# finding emission
# ---------------------------------------------------------------------------

def _suppressed(lines, lineno, code):
    # shared helper (findings.suppressed): canonical ``# ht-ok`` plus
    # the historical ``# lock-ok`` alias this pass introduced
    return suppressed(lines, lineno, code, markers=("ht-ok", "lock-ok"))


def _emit(mod, lines, report):
    path = mod.path

    def add(code, sev, msg, lineno, anchors=(), **data):
        for ln in (lineno, *anchors):
            if _suppressed(lines, ln, code):
                return
        report.findings.append(Finding(code, sev, msg,
                                       where=f"{path}:{lineno}", **data))

    # -- HT601: unsynchronized shared-state writes -----------------------
    by_state = {}
    for fn in mod.fns.values():
        if fn.node.name in _INIT_METHODS:
            continue                    # pre-thread-start construction
        for key, lineno, locks in fn.writes:
            if key[0] == "local":
                continue
            eff = frozenset(set(locks) | set(fn.callee_held))
            by_state.setdefault(key, []).append((fn, lineno, eff))
    for key, sites in sorted(by_state.items(), key=str):
        ctxs = set()
        for fn, _ln, _locks in sites:
            ctxs |= fn.contexts
        if len(ctxs) < 2 or not any(c != _MAIN for c in ctxs):
            continue
        if frozenset.intersection(*(lk for _f, _l, lk in sites)):
            continue                    # a common lock guards every site
        anchor = next((s for s in sites if not s[2]), sites[0])
        where = sorted({f"{fn.node.name}():{ln}" for fn, ln, _lk in sites})
        add("HT601", "error",
            f"shared state {_state_name(key)} written from "
            f"{len(ctxs)} thread contexts ({', '.join(sorted(ctxs))}) "
            f"with an empty common lockset — write sites "
            f"{', '.join(where)}; hold one lock across all of them or "
            f"annotate '# lock-ok: HT601 <reason>'",
            anchor[1], anchors=[ln for _f, ln, _lk in sites],
            state=_state_name(key), contexts=sorted(ctxs), sites=where)

    # -- HT602: lock-order inversion -------------------------------------
    acq_all = _transitive_acquires(mod)
    edges = {}                          # (a, b) -> example lineno
    for fn in mod.fns.values():
        for lk, lineno, held in fn.acquires:
            for h in set(held) | set(fn.callee_held):
                if h != lk:
                    edges.setdefault((h, lk), lineno)
        for callee, held, lineno in fn.calls:
            hold = set(held) | set(fn.callee_held)
            if not hold:
                continue
            for lk, _ln in acq_all.get(callee, ()):
                for h in hold:
                    if h != lk:
                        edges.setdefault((h, lk), lineno)
    reported = set()
    for (a, b) in sorted(edges, key=str):
        if (b, a) not in edges or (b, a) in reported:
            continue
        reported.add((a, b))
        la, lb = mod.lock_line(a), mod.lock_line(b)
        add("HT602", "error",
            f"lock-order inversion between {_lock_name(a)} (defined "
            f"{path}:{la}) and {_lock_name(b)} (defined {path}:{lb}): "
            f"order {_lock_name(a)} -> {_lock_name(b)} at line "
            f"{edges[(a, b)]} but {_lock_name(b)} -> {_lock_name(a)} "
            f"at line {edges[(b, a)]} — two threads taking opposite "
            f"orders deadlock",
            edges[(a, b)], anchors=[edges[(b, a)]],
            locks=[_lock_name(a), _lock_name(b)],
            defined_at=[f"{path}:{la}", f"{path}:{lb}"])

    # -- HT603: blocking while holding a lock ----------------------------
    for fn in mod.fns.values():
        for desc, lineno, held, waited in fn.blocking:
            eff = set(held) | set(fn.callee_held)
            eff.discard(waited)         # cond.wait releases its own lock
            if not eff:
                continue
            add("HT603", "warn",
                f"blocking {desc} in {fn.node.name}() while holding "
                f"{', '.join(sorted(_lock_name(k) for k in eff))} — "
                f"every thread needing the lock stalls behind this "
                f"wait and teardown can deadlock; move the wait "
                f"outside the region or bound it with a timeout",
                lineno, locks=sorted(_lock_name(k) for k in eff))
        for callee, held, lineno in fn.calls:
            eff = set(held) | set(fn.callee_held)
            cfn = mod.fns.get(callee)
            if not eff or cfn is None:
                continue
            for desc, bln, bheld, waited in cfn.blocking:
                ceff = set(bheld) | set(cfn.callee_held)
                ceff.discard(waited)
                if ceff:
                    continue            # already reported in the callee
                if set(eff) == {waited}:
                    continue
                add("HT603", "warn",
                    f"{fn.node.name}() holds "
                    f"{', '.join(sorted(_lock_name(k) for k in eff))} "
                    f"across a call to {callee.rsplit('.', 1)[-1]}(), "
                    f"which does blocking {desc} (line {bln})",
                    lineno, locks=sorted(_lock_name(k) for k in eff))

    # -- HT604: thread/pool lifecycle ------------------------------------
    for th in mod.threads:
        if th["in_with"]:
            continue
        if th["kind"] == "thread" and th["daemon"] is True:
            continue
        names = set()
        for key in th["targets"]:
            if key[0] == "attr":
                names.add(("self", key[2]))
                names.add((key[2],))
            else:
                names.add((key[-1],))
        joined = any(not names or any(recv[-len(n):] == n for n in names)
                     for recv in mod.joins)
        closed = any(names and any(recv[-len(n):] == n for n in names)
                     for recv in mod.shutdowns)
        if th["kind"] == "pool" and not (closed or joined):
            add("HT604", "warn",
                "worker pool is never shut down — its non-daemon "
                "threads outlive the owner and interpreter exit hangs "
                "while a worker is wedged in a job; call .shutdown() "
                "on every teardown path (or use a with-block)",
                th["lineno"])
        elif th["kind"] == "thread" and not joined:
            add("HT604", "warn",
                "non-daemon thread with no join/close registration — "
                "it outlives its owner and hangs interpreter exit if "
                "its loop never returns; join it on close() or mark "
                "it daemon=True with a cooperative stop flag",
                th["lineno"])

    # -- HT605: unguarded lazy init --------------------------------------
    if mod.has_threading:
        for fn in mod.fns.values():
            for key, lineno, locks in fn.lazy:
                if set(locks) | set(fn.callee_held):
                    continue
                add("HT605", "warn",
                    f"unguarded lazy-init of {_state_name(key)} in "
                    f"{fn.node.name}(): two threads can both observe "
                    f"it unset and both construct (check-then-create "
                    f"race); guard with a lock (double-checked is "
                    f"fine)", lineno, state=_state_name(key))

    # -- HT606: async-signal-unsafe signal handlers ----------------------
    for qual in sorted(mod.signal_handlers):
        fn = mod.fns.get(qual)
        if fn is None:
            continue
        work = list(fn.sigwork)
        work += [(f"blocking {d}", ln) for d, ln, _h, _w in fn.blocking]
        work += [(f"lock acquisition of {_lock_name(lk)}", ln)
                 for lk, ln, _h in fn.acquires]
        for callee, _h, ln in fn.calls:
            cfn = mod.fns.get(callee)
            if cfn is not None and (cfn.acquires or cfn.sigwork):
                work.append((f"a call into {callee.rsplit('.', 1)[-1]}()"
                             f" which acquires locks / does IO", ln))
        for desc, lineno in sorted(set(work), key=lambda x: x[1]):
            add("HT606", "warn",
                f"signal handler {fn.node.name}() does {desc} — a "
                f"handler interrupting the lock's own holder "
                f"self-deadlocks and buffered IO is not reentrant; "
                f"set a flag and do the work on the main loop",
                lineno, handler=qual)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def check_source(src, path="<string>"):
    """Lint one module's source for HT6xx findings; returns a Report."""
    report = Report()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        report.add("HT600", "error", f"unparseable module: {e}",
                   where=path)
        return report
    mod = _Module(path)
    _Collector(mod).visit(tree)
    for fn in list(mod.fns.values()):
        _BodyWalker(mod, fn).walk()
    _propagate(mod)
    _emit(mod, src.splitlines(), report)
    return report


def check_paths(paths):
    """Lint every ``.py`` under the given files/directories."""
    report = Report()
    files = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                if "__pycache__" in root:
                    continue
                files.extend(os.path.join(root, n)
                             for n in sorted(names) if n.endswith(".py"))
        else:
            files.append(p)
    for f in files:
        with open(f, encoding="utf-8") as fh:
            report.extend(check_source(fh.read(), path=f).findings)
    return report


def main(argv=None):
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m hetu_tpu.analysis.concurrency",
        description="static lockset / lock-order / thread-lifecycle "
                    "verifier for the threaded host runtime (HT6xx)")
    parser.add_argument("paths", nargs="*",
                        help="files or directories (default: the "
                             "hetu_tpu package)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable output")
    args = parser.parse_args(argv)
    paths = args.paths or [os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))]
    report = check_paths(paths)
    print(report.to_json() if args.json else report.to_text())
    # ANY unsuppressed finding gates: a warn here is a deadlock in
    # waiting, not style — by-design sites carry explicit lock-ok
    # reasons instead
    return 1 if len(report) else 0


if __name__ == "__main__":
    sys.exit(main())
