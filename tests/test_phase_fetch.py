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


def test_fetch_and_dispatch_spans_at_the_central_wrappers(monkeypatch):
    """The two wrappers every kernel and every device->host
    materialisation pass through open the `dispatch` and `fetch` spans,
    so new operators are traced for free; the kernel's kind is a span
    attribute, no longer a per-kind phase key. `fetch` is the host
    blocked on the device: an array whose program has finished gets no
    span, only the counters."""
    import jax
    import jax.numpy as jnp
    from tidb_tpu.utils.tracing import FlightRecorder, Tracer

    def tidb_probe(x):
        return x.sum()

    tracer = Tracer(FlightRecorder())
    kern = ph.timed_kernel("probe", jax.jit(tidb_probe))
    x = jnp.arange(1024)
    ph.reset()
    monkeypatch.setattr(ph, "_awaits_device", lambda arr: True)
    with tracer.span("statement", sampled=True):
        a = kern(x)
        b = kern(x)
        assert int(a) == int(b) == 1024 * 1023 // 2
    evs = tracer.recorder.events()
    disp = [e for e in evs if e.name == "dispatch"]
    assert [e.attrs for e in disp] == ["kind=probe;first=1", "kind=probe"]
    fetch = [e for e in evs if e.name == "fetch"]
    assert [e.attrs for e in fetch] == ["bytes=8", "bytes=8"]
    root = next(e for e in evs if e.name == "statement")
    assert all(e.parent_id == root.span_id and e.trace_id == root.trace_id
               for e in disp + fetch)
    s = ph.current()
    assert s["dispatches"] == 2 and s["kernel_builds"] == 1 and \
        s["syncs"] == 2
    assert set(s) <= {"dispatches", "kernel_builds", "compile_s",
                      "dispatch_s", "sync_s", "syncs"}, s
    # a finished program's array: counted, not a span
    monkeypatch.undo()
    tracer.recorder.clear()
    with tracer.span("statement", sampled=True):
        c = kern(x)
        c.block_until_ready()
        assert ph._awaits_device(c) is False
        assert int(c) == 1024 * 1023 // 2
    assert [e.name for e in tracer.recorder.events()] == \
        ["dispatch", "statement"]
    assert ph.current()["syncs"] == 3
    # outside any span both wrappers only count
    tracer.recorder.clear()
    assert int(kern(x)) == 1024 * 1023 // 2
    assert tracer.recorder.events() == []
