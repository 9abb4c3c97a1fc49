"""One-round-trip device->host result fetching.

Every *synchronized* host fetch pays a fixed device->host latency (not
measured on this chip), while transfers issued with
``copy_to_host_async()`` overlap: N results prefetched together cost one
round trip instead of N.  Every query-result collection point must call
:func:`prefetch` on the whole result tree before the first
``np.asarray`` — sequential materialization of a 17-array aggregate
result otherwise pays that latency 17 times.

Reference analog: pkg/store/copr/coprocessor.go's copIterator overlaps
region responses the same way (streamed, not lock-step).
"""


def host_array(x):
    """THE designated device->host materialization seam (tpulint rule
    host-sync-in-device-path): turn a (prefetched) device array into
    numpy through ``__array__`` — one overlapped bulk transfer — never
    through the scalar dunders (``__int__``/``__bool__``/``.item()``),
    each of which is its own blocking device round trip."""
    import numpy as np
    return np.asarray(x)


def host_scalar(x):
    """Fetch-seam scalar read: materialize through the bulk-transfer
    path and hand back a numpy scalar. Call prefetch() on the enclosing
    result tree first so every scalar of a result rides ONE round
    trip."""
    return host_array(x)[()]


def host_int(x) -> int:
    """Fetch-seam int read (sizes, group counts, miss counters):
    ``int(device_array)`` is a per-value blocking sync; this routes
    through the prefetched bulk copy instead."""
    return int(host_array(x))


def prefetch(*trees):
    """Issue async device->host copies for every jax array found in the
    given pytrees (dict/list/tuple nests, scalars pass through).  After
    this, ``np.asarray()`` on each array materializes from the already
    overlapped transfer instead of paying its own link round trip."""
    stack = list(trees)
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
        else:
            start = getattr(x, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()
                except Exception:       # noqa: BLE001 - committed arrays only
                    pass
    return trees[0] if len(trees) == 1 else trees
