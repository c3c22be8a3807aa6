"""Graph node base class.

Reference parity: python/hetu/gpu_ops/Node.py — an ``Op`` is a DAG node with
inputs, a device context, operator-overloading sugar, and per-op
``compute / gradient / infer_shape / deduce_states`` methods.

TPU-native difference: ``compute`` is a *pure function* of jax values
(input_vals -> output value) instead of an in-place kernel launch on a CUDA
stream. The executor traces the whole topological order through these
compute functions once, producing a single XLA program per subgraph — the
per-op Python dispatch loop of the reference (executor.py:1761-1843)
disappears at run time.
"""
from __future__ import annotations

import os
import sys

from ..context import get_current_context, DeviceGroup

G_NODE_ID = 0

# package root for construction-provenance capture: the first stack
# frame OUTSIDE this directory is the *user's* model line (trailing
# separator so a sibling like .../hetu_tpu_models.py doesn't match)
_PKG_DIR = os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))) + os.sep
# co_filename is whatever string the module was imported under — a
# sys.path entry like "tests/../examples/.." leaks into it verbatim, so
# paths must normalize before the prefix check (memoized: the set of
# distinct co_filenames on any stack is tiny)
_NORM_CACHE = {}

# op/initializer/optimizer plumbing never counts as a construction
# site: when a graph is built entirely inside the package (the zoo
# registry, spliced comm subgraphs), the provenance falls back to the
# first frame outside these directories — the models/ (or parallel/)
# line that composed the op — so findings still carry a real file:line
# a reviewer can annotate with `# ht-ok: <CODE>` waivers
_INTERNAL_PREFIXES = tuple(
    os.path.join(_PKG_DIR, p) for p in ("graph", "ops")) + tuple(
    os.path.join(_PKG_DIR, p) for p in ("initializers.py",
                                        "optimizer.py"))


def _norm(fn):
    n = _NORM_CACHE.get(fn)
    if n is None:
        n = fn if fn.startswith("<") else os.path.normpath(
            os.path.abspath(fn))
        _NORM_CACHE[fn] = n
    return n


def _construction_site():
    """((filename, lineno) or None, (filename, lineno) or None) — the
    nearest caller outside hetu_tpu (the *user* line that built this
    op: findings report it so a shape mismatch ten layers deep names
    the model code, not the framework) and the nearest frame outside
    the op/initializer plumbing (the line that *composed* the op —
    a ``hetu_tpu/models/`` line when the package built its own graph,
    where ``# ht-ok`` waiver comments anchor). One cheap frame walk
    per op; either element may be None."""
    try:
        f = sys._getframe(1)
    except Exception:       # noqa: BLE001 — provenance is best effort
        return None, None
    composed = None
    while f is not None:
        fn = _norm(f.f_code.co_filename)
        if not fn.startswith(_PKG_DIR) and not fn.startswith("<frozen") \
                and not fn.endswith(os.sep + "runpy.py"):
            # runpy is `python -m`'s launcher, not a construction site
            return (fn, f.f_lineno), composed
        if composed is None and fn.startswith(_PKG_DIR) \
                and not fn.startswith(_INTERNAL_PREFIXES):
            composed = (fn, f.f_lineno)
        f = f.f_back
    return composed, composed


def reset_node_ids():
    global G_NODE_ID
    G_NODE_ID = 0


class ExecContext:
    """Per-trace execution context threaded through Op.compute.

    Carries everything that is not a graph edge:
      * ``training``   — train vs inference behavior (dropout, batchnorm)
      * ``rng_for(op)``— deterministic per-op PRNG key for this step
      * ``params``     — current values of trainable placeholders
      * ``new_params`` — functional parameter updates (written by OptimizerOp)
      * ``state`` / ``new_state`` — non-trainable op state (BN running stats)
      * ``cache``      — intra-trace saved activations (dropout masks, softmax
                         outputs) shared between forward and gradient ops
      * ``opt_state`` / ``new_opt_state`` — optimizer slot variables
      * ``master_params`` — under mixed precision the float32 masters
                         (``params`` then holds the compute-dtype values)
      * ``work`` / ``new_work`` — the masters' compute-dtype working copies
                         that came into the step, by parameter, and the
                         ones OptimizerOp writes beside each new master
    """

    def __init__(self, training=True, base_rng=None, params=None, state=None,
                 opt_state=None, config=None, step=0):
        import jax
        self.training = training
        self.base_rng = (base_rng if base_rng is not None
                         else jax.random.PRNGKey(0))
        self.params = params or {}
        self.new_params = {}
        self.master_params = None
        self.work = {}
        self.new_work = {}
        self.state = state or {}
        self.new_state = {}
        self.cache = {}
        self.opt_state = opt_state
        self.new_opt_state = None
        self.config = config
        self.step = step

    def rng_for(self, op):
        import jax
        return jax.random.fold_in(self.base_rng, op.id)

    def get_state(self, key, default=None):
        return self.state.get(key, default)

    def put_state(self, key, value):
        self.new_state[key] = value


class Op:
    """A node in the dataflow graph (reference Node.py:9)."""

    # who made the node, for the scope its compute is traced under
    # (``scope()``): ``fwd`` unless ``autodiff.gradients`` minted it
    # (``bwd``) or it is an OptimizerOp (``opt``)
    role = "fwd"

    def __init__(self, op_type, inputs, ctx=None):
        global G_NODE_ID
        self.inputs = list(inputs)
        self.raw_ctx = (get_current_context() if ctx is None
                        else DeviceGroup(ctx))
        self.ctx = ctx
        self.const_attr = None
        self.dtype = None
        self.inplace = False
        self.event = None
        self.op_type = (op_type if isinstance(op_type, str)
                        else op_type.__name__)
        self.id = G_NODE_ID
        G_NODE_ID += 1
        # defined_at: the user line (analysis findings report it);
        # composed_at: the in-package model line that composed the op
        # (None when they coincide or no such frame exists) — waiver
        # comments on either line suppress a finding
        self.defined_at, self.composed_at = _construction_site()
        self.name = self.op_type + str(self.id)
        self.desc = self.name + "(" + ", ".join(
            inp.name for inp in self.inputs) + ")"

    # ------------------------------------------------------------------ core
    def scope(self):
        """What a compiled step calls everything this node traces:
        ``hetu.<role>/<op_type>/<node>``, in the ``op_name`` of each
        instruction (docs/tools.md; a profile's reader joins device
        time to graph ops by it)."""
        return f"hetu.{self.role}/{self.op_type}/" \
            + self.name.replace("/", ".")

    def compute(self, input_vals, ectx):
        """Pure computation: list of jax values -> output jax value."""
        raise NotImplementedError

    def gradient(self, output_grad):
        """Given the summed adjoint, build gradient ops per input."""
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        raise NotImplementedError

    def infer_range(self, input_ranges, input_shapes=None):
        """Interval semantics for the HT8xx numerics verifier
        (analysis/numerics.py): given per-input ``(lo, hi)`` bounds
        (None = unknown), return a ``(lo, hi)`` bounding every element
        of the output, or None for no claim. Ops with known value
        semantics override (ops/*.py); shape-aware cases (matmul,
        reductions, conv) are handled centrally by the pass."""
        return None

    # ------------------------------------------------------------ scheduling
    def forward_hook(self, config):
        """Called in topo order during executor configuration
        (reference Node.py / executor.py topo_sort_with_hook)."""
        if self.ctx is None:
            self.ctx = config.context

    def backward_hook(self, config):
        """Called in reverse topo order during executor configuration."""
        pass

    # --------------------------------------------------------- parallel (TP)
    def deduce_states(self, input_statuses, status, deduce_order):
        """Propagate NodeStatus through this op. Default: elementwise — all
        inputs and the output share one partition state (reference
        Node.py:160-190)."""
        if deduce_order:
            for st in input_statuses:
                if st is not None and st.order is not None:
                    status.set_attr(st.duplicate, st.order)
                    break
        else:
            for st in input_statuses:
                if st is not None and st.state is not None:
                    status.set_state(st.state)
                    if st.duplicate is not None and st.order is not None:
                        status.set_attr(st.duplicate, st.order)
                    break
            for st in input_statuses:
                if st is not None and st.state is None and status.state is not None:
                    st.set_state(status.state)

    def naive_infer_shape(self, input_shapes):
        return self.infer_shape(input_shapes)

    # ------------------------------------------------------------- operators
    def __add__(self, other):
        from ..ops.basic import add_op, addbyconst_op
        if isinstance(other, Op):
            return add_op(self, other)
        return addbyconst_op(self, other)

    def __mul__(self, other):
        from ..ops.basic import mul_op, mul_byconst_op
        if isinstance(other, Op):
            return mul_op(self, other)
        return mul_byconst_op(self, other)

    def __sub__(self, other):
        from ..ops.basic import add_op, addbyconst_op, opposite_op
        if isinstance(other, Op):
            return add_op(self, opposite_op(other))
        return addbyconst_op(self, -other)

    def __rsub__(self, other):
        from ..ops.basic import addbyconst_op, opposite_op
        return addbyconst_op(opposite_op(self), other)

    def __neg__(self):
        from ..ops.basic import opposite_op
        return opposite_op(self)

    def __truediv__(self, other):
        from ..ops.basic import div_op, div_const_op, mul_byconst_op
        if isinstance(other, Op):
            return div_op(self, other)
        return mul_byconst_op(self, 1.0 / other)

    def __rtruediv__(self, other):
        from ..ops.basic import div_const_op
        return div_const_op(other, self)

    __radd__ = __add__
    __rmul__ = __mul__

    def __str__(self):
        return self.name

    def __repr__(self):
        return self.desc
