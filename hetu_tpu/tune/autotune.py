"""What the measuring tools share: a timer that waits for the device and
the platform's name. Nothing here picks a kernel's tiles
(``ops/pallas_attention.py:_block_sizes`` does, from what a call can
see); ``get_table`` is a shim for the benchmark's train driver, which
logs ``get_table().chosen("flash")`` (ROADMAP.md, Queue 3 §4)."""
import time

__all__ = ["get_table", "platform_tag", "timeit"]

_PLATFORM = None
# the kernels' names in the keys of the swept store the driver logged
_STORE_NAMES = {"fwd": "fwd_regions", "fwd_lse": "fwd_lse_regions",
                "bwd": "bwd_onepass"}


def platform_tag():
    """The attached accelerator's kind, memoized. Raises where no
    backend initialises: there is no tag for "no device"."""
    global _PLATFORM
    if _PLATFORM is None:
        import jax
        dev = jax.devices()[0]
        kind = dev.device_kind or dev.platform
        _PLATFORM = "".join(              # lock-ok: HT605 idempotent memo: racing writers compute identical values, swap is atomic
            c if c.isalnum() else "_" for c in str(kind).strip().lower())
    return _PLATFORM


def timeit(run, sync=None, reps=3, windows=2):
    """Seconds per ``run()`` call: one warmup (compile), then the best
    of ``windows`` windows of ``reps`` back-to-back dispatches ended by
    ``sync(out)``, which must WAIT for the device (a scalar readback or
    ``block_until_ready``): dispatch alone returns before the work."""
    sync = sync or (lambda out: None)
    sync(run())
    best = float("inf")
    for _ in range(max(1, windows)):
        t0 = time.perf_counter()
        for _ in range(max(1, reps)):
            out = run()
        sync(out)
        best = min(best, (time.perf_counter() - t0) / max(1, reps))
    return best


class get_table:
    def chosen(self, prefix=None):
        """{the swept store's key string: tiles} of the flash calls
        this process has planned."""
        from ..ops.pallas_attention import RESOLVED_TILES
        out = {}
        for (kind, rows, s, d, dtype, causal, has_mask), tiles in sorted(
                RESOLVED_TILES.items()):
            name = f"flash_{_STORE_NAMES[kind]}{'_token_major' * rows}"
            if prefix is None or name.startswith(prefix):
                out["|".join((platform_tag(), name, f"S{s}", f"D{d}", dtype,
                              "causal" if causal else "full",
                              "mask" if has_mask else "nomask"))] = tiles
        return out
