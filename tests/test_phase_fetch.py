"""Device->host materialization accounting (utils/phase.py fetch timer):
scalar conversions (each a blocking device sync) are counted as syncs; bulk np.asarray fetches count
as fetches on backends without zero-copy host aliasing (TPU). On the
CPU backend numpy may alias the buffer via __array_interface__ without
calling __array__, so only the sync counters are asserted exactly."""
import numpy as np

import tidb_tpu.utils.phase as ph


def test_scalar_sync_and_fetch_counters():
    import jax.numpy as jnp
    ph.reset()
    x = jnp.arange(1024)
    assert bool(x[0] == 0)
    assert int(x.sum()) == 1024 * 1023 // 2
    np.asarray(x)
    s = ph.current()
    assert s.get("syncs") == 2 and s.get("sync_s", 0) >= 0
    assert s.get("fetches", 0) in (0, 1)    # 0: zero-copy cpu alias
    ph.reset()
    assert ph.current() == {}


def test_nested_statements_accumulate():
    ph.reset()
    ph.stmt_enter()
    ph.add("dispatch_s", 0.5)
    ph.stmt_enter()          # internal SQL must not clobber
    ph.add("dispatch_s", 0.25)
    ph.stmt_leave()
    ph.stmt_leave()
    assert ph.current()["dispatch_s"] == 0.75
