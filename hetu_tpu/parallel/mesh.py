"""Device-mesh construction helpers.

Reference counterpart: DeviceGroup device lists + NodeStatus device-order
algebra (context.py:7-193). On TPU the physical topology is expressed once
as a named ``jax.sharding.Mesh``; every parallelism axis (dp/tp/pp/sp) is a
mesh axis and all communication lowers to XLA collectives over ICI.
"""
from __future__ import annotations

import numpy as np

__all__ = ["build_mesh", "factorized_axes", "mesh_for_statuses",
           "shard_map_unchecked"]


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off. The
    callers branch per device inside the body (``lax.cond`` per ring
    hop, ``lax.switch`` per pipeline stage), and the checker rejects
    branches whose outputs disagree on which mesh axes they vary over
    even when every branch is device-varying. Numerics are unaffected —
    the flag gates a static check and a transpose optimization, not the
    computation."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def build_mesh(axis_sizes, devices=None):
    """Mesh from an {axis_name: size} dict (insertion order = major→minor).

    >>> build_mesh({"dp": 2, "tp": 4})   # 8 devices
    """
    import jax
    from jax.sharding import Mesh
    names = list(axis_sizes)
    sizes = [axis_sizes[n] for n in names]
    need = int(np.prod(sizes)) if sizes else 1
    if devices is None:
        devices = jax.devices()
    assert len(devices) >= need, \
        f"mesh {axis_sizes} needs {need} devices, have {len(devices)}"
    arr = np.asarray(devices[:need]).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def factorized_axes(n, prefix="tp"):
    """Factor n into prime-power axes, largest first — a mesh that can
    express any split whose per-dim factors multiply subsets of these.

    >>> factorized_axes(8) -> {"tp0": 2, "tp1": 2, "tp2": 2}
    """
    axes = {}
    i = 0
    d = 2
    while n > 1:
        while n % d == 0:
            axes[f"{prefix}{i}"] = d
            n //= d
            i += 1
        d += 1 if d == 2 else 2
    return axes


def mesh_for_statuses(statuses, dp=1, devices=None):
    """Build a mesh able to express every NodeStatus in ``statuses``.

    The model axes come from prime-factorizing the max TP degree; an
    optional leading "dp" axis carries data parallelism. Returns
    (mesh, model_axes) where model_axes is the {name: size} dict of the
    TP axes (used by the planner's spec assignment).
    """
    tp_degree = 1
    for st in statuses:
        if st is not None and st.state is not None:
            tp_degree = max(tp_degree,
                            int(np.prod([s for s in st.state])))
    model_axes = factorized_axes(tp_degree)
    axes = {}
    if dp > 1:
        axes["dp"] = dp
    axes.update(model_axes)
    return build_mesh(axes, devices), model_axes
