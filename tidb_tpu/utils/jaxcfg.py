"""JAX configuration for the engine. int64 semantics are load-bearing
(scaled-decimal arithmetic, date micros, row handles), so x64 must be on
before any jax array is created. Float columns still lower to float32 on
TPU via the copr layer's dtype policy when profitable.

Also owns two whole-query-dispatch concerns (docs/PERFORMANCE.md):

* the PERSISTENT XLA compilation cache — warmup compiles are the
  dominant cold-start cost of a fresh process; caching them on disk
  amortizes across processes and bench invocations. ONE directory,
  placeable from outside: JAX_COMPILATION_CACHE_DIR where it is set
  (jax reads it itself; nothing here overrides it), else
  <checkout>/.cache/jax (utils.resolve_jax_cache_dir). Lookup
  hits/misses land in the metrics registry (tidb_tpu_xla_cache_total).

* input-buffer DONATION for per-dispatch scratch arrays (validity
  masks): donate_argnums lets XLA reuse the input's HBM for outputs
  instead of allocating fresh — SNIPPETS.md [1]'s pjit donation applied
  to the kernel seam. Donation is only legal for buffers built fresh
  per dispatch; device-resident pool buffers must NEVER ride a donated
  position (guard_donation enforces at dispatch time). CPU's PJRT has
  no donation, so "auto" enables it only on real accelerators.
"""
import os
import threading

import jax

jax.config.update("jax_enable_x64", True)


def _setup_persistent_cache():
    """Point XLA's compilation cache at THE cache directory
    (utils.resolve_jax_cache_dir) and hook lookup hit/miss counters
    into the metrics registry. Where JAX_COMPILATION_CACHE_DIR is set
    jax has already taken the directory from it and no other is set in
    code. Never fatal: an unwritable directory degrades to an uncached
    (but working) engine."""
    from . import resolve_jax_cache_dir
    cache_dir = resolve_jax_cache_dir()
    try:
        os.makedirs(cache_dir, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
    except Exception:                   # noqa: BLE001
        return None
    # the threshold update must not fail the whole setup: once the
    # cache dir is active above, returning None here would make SHOW
    # VARIABLES report the cache disabled while XLA is reading/writing
    # it — a bad env value just leaves jax's default threshold
    try:
        # tiny CPU-test kernels compile in ms — writing them would
        # churn disk for nothing
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ.get(
                "TIDB_TPU_JAX_CACHE_MIN_COMPILE_SECS", "0.5")))
    except Exception:                   # noqa: BLE001
        pass
    from jax._src import compilation_cache as _cc
    if not getattr(_cc, "_tidb_cache_metered", False):
        orig = _cc.get_executable_and_time

        def metered(cache_key, *a, **kw):
            out = orig(cache_key, *a, **kw)
            try:
                from . import metrics as _metrics
                hit = out is not None and out[0] is not None
                _metrics.XLA_CACHE.labels(
                    "hit" if hit else "miss").inc()
            except Exception:       # noqa: BLE001
                pass
            return out

        _cc.get_executable_and_time = metered
        _cc._tidb_cache_metered = True
    return cache_dir


persistent_cache_dir = _setup_persistent_cache()


def _publish_cache_sysvar():
    """Reflect the ACTUAL cache outcome into the global sysvar
    tidb_tpu_jax_cache_dir ('' = degraded, e.g. an unwritable
    directory): SHOW VARIABLES must report the directory in force.
    Via sys.modules only — never triggers an import, so no cycle with
    the session package; sysvars' own default handles the
    registry-imported-second order the same way."""
    import sys
    sv = sys.modules.get("tidb_tpu.session.sysvars")
    if sv is None:
        return
    try:
        sv.get_sysvar("tidb_tpu_jax_cache_dir").default = \
            persistent_cache_dir or ""
    except Exception:                   # noqa: BLE001
        pass


_publish_cache_sysvar()


def name_program(fn, family: str):
    """Give `fn` the stable name `tidb_<family>` before it is jitted:
    jit calls the program `jit_tidb_<family>`, which is how a profile's
    `XLA Modules` line and the compile cache know it. The name is part
    of the persistent cache's key, so `family` comes only from what
    already distinguishes programs (a kernel-cache key's kind), never
    from a table, a literal, a shape or a seed. Where the family is
    fixed, the `def` itself carries the name. -> fn"""
    fn.__name__ = fn.__qualname__ = "tidb_" + family
    return fn


def donation_enabled() -> bool:
    """Donate per-dispatch scratch buffers? auto = real accelerators
    only (CPU PJRT ignores donation and warns per compile)."""
    mode = os.environ.get("TIDB_TPU_DONATE", "auto").lower()
    if mode in ("1", "on", "true"):
        return True
    if mode in ("0", "off", "false"):
        return False
    try:
        return jax.default_backend() != "cpu"
    except Exception:                   # noqa: BLE001
        return False


def donation_argnums(*argnums):
    """-> argnums tuple for jax.jit(donate_argnums=...) when donation
    is enabled, else () (a no-op donate spec)."""
    return tuple(argnums) if donation_enabled() else ()


_DONATED_MU = threading.Lock()
_DONATED: dict = {}        # id(buffer) -> weakref(buffer), bounded FIFO
_DONATED_ORDER: list = []  # (id, ref) pairs — the trim only removes an
#                            entry still holding ITS ref, so a recycled
#                            id re-registered for a live buffer can't be
#                            unregistered by its predecessor's trim
_DONATED_CAP = 4096


def guard_donation(fn, argnums):
    """Wrap a jitted kernel whose `argnums` positions are donated:
    after each call the donated buffers are dead, so a second dispatch
    handing any of them back is a use-after-free the backend may only
    catch asynchronously. Record donated buffers (weakly — a recycled
    id() of a collected array must not read as reuse) and fail FAST on
    a live match — the invariant tests/test_device_residency.py pins.
    With an empty argnums (donation disabled) the kernel passes
    through untouched."""
    if not argnums:
        return fn
    import weakref

    def guarded(*args, **kw):
        with _DONATED_MU:
            for i in argnums:
                if i < len(args):
                    ref = _DONATED.get(id(args[i]))
                    if ref is not None and ref() is args[i]:
                        raise RuntimeError(
                            f"donated buffer reused in dispatch arg "
                            f"{i}: per-dispatch scratch must be "
                            "rebuilt, never taken from a cache")
        out = fn(*args, **kw)
        with _DONATED_MU:
            for i in argnums:
                if i < len(args):
                    try:
                        ref = weakref.ref(args[i])
                    except TypeError:
                        continue        # not weakref-able: skip
                    _DONATED[id(args[i])] = ref
                    _DONATED_ORDER.append((id(args[i]), ref))
            while len(_DONATED_ORDER) > _DONATED_CAP:
                bid, bref = _DONATED_ORDER.pop(0)
                if _DONATED.get(bid) is bref:
                    _DONATED.pop(bid)
        return out

    guarded.__wrapped__ = fn
    return guarded
