"""Shared small helpers for the utils package."""
from __future__ import annotations

import os


def env_int(name: str, default: int) -> int:
    """Integer from the environment, falling back on missing OR
    malformed values — a bad harness env must never kill an import.
    Shared by the sysvar registry defaults and the storage lock
    knobs so the two parses can't drift."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class LRUCache:
    """Small thread-safe LRU over an insertion-ordered dict (the
    residency-store idiom: O(1) hit touch + O(1) eviction, no list
    scans). Shared by the domain's plan/AST/digest/point-template
    caches so each one is bounded the same way."""

    __slots__ = ("cap", "_d", "_mu", "_hits")

    def __init__(self, cap: int):
        import threading
        self.cap = int(cap)
        self._d: dict = {}
        self._mu = threading.Lock()
        self._hits = 0

    def get(self, key, default=None):
        # lock-free hit path: dict reads are GIL-atomic, and a thread
        # preempted while HOLDING the lock would convoy every other
        # session behind it (64-thread point-op serving hits this cache
        # once per statement). The MRU touch is amortized: every 32nd
        # hit takes the lock and re-inserts at the tail — approximate
        # LRU is plenty for plan/AST caches where a wrong eviction
        # costs one rebuild, not correctness.
        v = self._d.get(key, _LRU_MISS)
        if v is _LRU_MISS:
            return default
        n = self._hits + 1
        self._hits = n              # benign race: lost counts are fine
        if not (n & 31):
            with self._mu:
                if self._d.get(key) is v:
                    del self._d[key]
                    self._d[key] = v
        return v

    def put(self, key, value):
        with self._mu:
            if key in self._d:
                del self._d[key]
            self._d[key] = value
            while len(self._d) > self.cap:
                del self._d[next(iter(self._d))]

    def clear(self):
        with self._mu:
            self._d.clear()

    def pop(self, key, default=None):
        with self._mu:
            return self._d.pop(key, default)

    def __len__(self):
        return len(self._d)

    def __contains__(self, key):
        return key in self._d

    __setitem__ = put


_LRU_MISS = object()


def resolve_jax_cache_dir() -> str:
    """THE persistent XLA compile-cache directory (jax-import free —
    shared by jaxcfg's setup and the sysvar registry so the two
    resolutions can't drift): JAX_COMPILATION_CACHE_DIR where it is
    set, else <checkout>/.cache/jax computed from this package's
    location. The path is part of nothing process-local (no $HOME, pid
    or time), so every process of one checkout shares one cache."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".cache", "jax")
