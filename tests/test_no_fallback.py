"""No quiet fallback hides the device (ISSUE 21 §3-5): each place that
used to carry on — a wrapped device index, an accelerator context that
landed on the CPU, a swallowed kernel import, a missing ``bytes_limit``,
N workers on one chip — now says so."""
import importlib
import os
import sys
import types

import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import cachedir, executor, launcher, ndarray
from hetu_tpu.analysis import memory
from hetu_tpu.ops import attention

autotune = importlib.import_module("hetu_tpu.tune.autotune")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- device contexts ---------------------------------------------------------

def test_tpu_context_is_ith_virtual_device_when_cpu_pinned():
    assert ndarray.cpu_pinned()
    assert ht.tpu(3).jax_device() == jax.local_devices()[3]
    assert ht.cpu(2).jax_device() == jax.local_devices()[2]


@pytest.mark.parametrize("ctx", [ht.tpu(9), ht.gpu(8), ht.cpu(8)])
def test_device_index_beyond_devices_raises(ctx):
    """8 devices on the harness: index 8 and 9 used to wrap onto
    devices 0 and 1."""
    with pytest.raises(RuntimeError, match="out of range"):
        ctx.jax_device()


def test_tpu_context_without_accelerator_raises_unless_pinned(monkeypatch):
    monkeypatch.setattr(ndarray, "cpu_pinned", lambda: False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        ht.tpu(0).jax_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        ht.array([1.0], ctx=ht.tpu(0))


def test_default_ctx_propagates_backend_failure(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        executor._default_ctx()


# -- kernel dispatch ---------------------------------------------------------

def test_use_pallas_follows_the_platform_only(monkeypatch):
    assert attention._use_pallas() is False         # the CPU harness
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._use_pallas() is True


def test_kernel_import_error_surfaces_on_tpu(monkeypatch):
    """On a TPU backend a kernel module that cannot be imported used to
    mean "train on the composed reference"; now it raises."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(sys.modules, "hetu_tpu.ops.pallas_attention", None)
    q = jnp.zeros((1, 1, 8, 8), jnp.float32)
    with pytest.raises(ImportError):
        attention.prefill_attention(q, q, q, sm_scale=1.0)


# -- the platform tag ---------------------------------------------------------

def test_platform_tag_has_no_unknown(monkeypatch):
    def boom():
        raise RuntimeError("no backend")
    monkeypatch.setattr(autotune, "_PLATFORM", None)
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        autotune.platform_tag()


# -- memory budget -----------------------------------------------------------

def _fake_device(platform, stats):
    return types.SimpleNamespace(platform=platform,
                                 memory_stats=lambda: stats)


def test_budget_is_min_bytes_limit_of_accelerators(monkeypatch):
    monkeypatch.delenv("HETU_HBM_BUDGET", raising=False)
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _fake_device("tpu", {"bytes_limit": 16 << 30}),
        _fake_device("tpu", {"bytes_limit": 15 << 30})])
    assert memory.resolve_budget() == 15 << 30


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 1}])
def test_missing_bytes_limit_on_accelerator_raises(monkeypatch, stats):
    monkeypatch.delenv("HETU_HBM_BUDGET", raising=False)
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_fake_device("tpu", stats)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.resolve_budget()


def test_cpu_backend_has_no_budget(monkeypatch):
    monkeypatch.delenv("HETU_HBM_BUDGET", raising=False)
    assert memory.resolve_budget() is None
    assert memory.resolve_budget("2G") == 2 << 30


# -- compile cache and stores ------------------------------------------------

@pytest.fixture
def cache_config():
    """Restore the jax config enable_compile_cache() touches."""
    names = ("jax_compilation_cache_dir",
             "jax_include_full_tracebacks_in_locations",
             "jax_traceback_in_locations_limit")
    was = {n: getattr(jax.config, n) for n in names}
    yield was
    for n, v in was.items():
        jax.config.update(n, v)


def test_compile_cache_defaults_to_fixed_in_checkout_path(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cachedir.enable_compile_cache() == cachedir.STATE_ROOT
    assert jax.config.jax_compilation_cache_dir == cachedir.STATE_ROOT
    assert cachedir.STATE_ROOT == os.path.join(REPO, ".jax_cache")
    # kernel bodies must not carry the tracing call stack into the key:
    # one frame, as a traceback (the bare file and line of the other
    # flag loses the scopes of a step's graph ops: test_step_account.py)
    assert jax.config.jax_traceback_in_locations_limit == 1
    assert jax.config.jax_include_full_tracebacks_in_locations


def test_external_compile_cache_dir_is_left_untouched(
        monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cachedir.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == \
        cache_config["jax_compilation_cache_dir"]      # no path set


def test_json_stores_default_inside_the_checkout(monkeypatch):
    from hetu_tpu.analysis import rangecheck
    from hetu_tpu.telemetry import costdb
    for var in ("HETU_COSTDB", "HETU_RANGEDB"):
        monkeypatch.delenv(var, raising=False)
    root = os.path.join(REPO, ".jax_cache", "hetu_tpu")
    assert costdb.default_db_path() == os.path.join(root, "costdb.json")
    assert rangecheck.default_db_path() == os.path.join(
        root, "ranges.json")


# -- one process per chip ----------------------------------------------------

def _cluster(workers):
    return launcher.ClusterConfig([{"host": "localhost", "chief": True,
                                    "servers": 1, "workers": workers}])


def test_launcher_refuses_workers_sharing_a_tpu_host(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(launcher, "_local_tpu_chips", lambda: 4)
    with pytest.raises(RuntimeError, match="belongs to one"):
        launcher._refuse_shared_chip(_cluster(workers=2))
    launcher._refuse_shared_chip(_cluster(workers=1))     # the SPMD form


def test_launcher_allows_cpu_pinned_or_chipless_workers(monkeypatch):
    monkeypatch.setattr(launcher, "_local_tpu_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launcher._refuse_shared_chip(_cluster(workers=4))
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(launcher, "_local_tpu_chips", lambda: 0)
    launcher._refuse_shared_chip(_cluster(workers=4))


def test_tpu_chip_count_needs_no_backend(monkeypatch):
    """The launcher counts chips from the PCI bus; it must never
    initialise the backend its workers need."""
    def boom():
        raise AssertionError("the launcher touched the backend")
    monkeypatch.setattr(jax, "devices", boom)
    monkeypatch.setattr(jax, "local_devices", boom)
    assert launcher._local_tpu_chips() >= 0
