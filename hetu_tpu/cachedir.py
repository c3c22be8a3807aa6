"""Where a run keeps what the next run may reuse.

Two kinds of state outlive a process: XLA's persistent compilation
cache, and the package's small JSON stores (CostDB, RangeDB). Both default to one fixed directory inside the checkout,
derived from this file's location — never from ``~``, a temp name, a
pid or the time — so that a run is a function of the committed tree
plus that directory, and a second run from the same checkout finds
what the first one compiled (the path is part of the cache key).

The compile cache can be placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
:func:`enable_compile_cache` sets nothing. The JSON stores keep their
own overrides (``HETU_COSTDB``, ``HETU_RANGEDB``).

Only the chip entry points call :func:`enable_compile_cache`
(``heturun`` exports the directory to its workers) — the CPU test
harness compiles for described devices
whose cache entries cannot be read back without a chip.
"""
from __future__ import annotations

import os

__all__ = ["STATE_ROOT", "store_path", "enable_compile_cache"]

_COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

STATE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def store_path(filename):
    """Default path of one JSON store under the in-checkout root."""
    return os.path.join(STATE_ROOT, "hetu_tpu", filename)


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory: the externally set one untouched, else the fixed
    in-checkout root.

    Also keeps cache keys a function of the program: a Pallas kernel's
    serialized body carries the location it was traced at, and with
    whole tracebacks those bytes — hence the key of every program that
    holds the kernel — depend on the Python call stack that FIRST traced
    it, so a second process that reaches the kernel by another path
    never hits what the first wrote (measured on the chip: GPT-2's step
    recompiled, 28 s, in the second process). So a location keeps ONE
    frame, the innermost of the program's own. It stays a traceback of
    one frame and not the bare file and line that
    ``jax_include_full_tracebacks_in_locations=False`` gives: under that
    flag this jax names an operation lowered outside a nested jit by its
    primitive alone (``op_name="add"``), and the scopes a compiled step
    runs its graph ops under (``Op.scope``: what a profile's device time
    is joined to graph ops by, docs/tools.md) never reach the compiled
    program's metadata."""
    import jax
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    external = os.environ.get(_COMPILE_CACHE_ENV)
    if external:
        return external
    jax.config.update("jax_compilation_cache_dir", STATE_ROOT)
    return STATE_ROOT
