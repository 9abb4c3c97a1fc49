"""Records the small device trace kept beside the trace reduction's
check (`small_trace_1chip.xplane.pb`): three runs of one jitted scan on
one chip, inside host spans named as the harness names them. Run on the chip:
`chiprun -- python3 benchmark/tests/record_trace.py`; the trace comes
back under chiprun_out/small_trace/."""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main():
    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    devs = jax.devices()
    print("devices", devs, file=sys.stderr)
    scan = jax.jit(lambda x, y: jnp.sum(jnp.where(x > 3, x * y, 0)))
    x = jnp.arange(1 << 20, dtype=jnp.int32)
    y = jnp.ones(1 << 20, dtype=jnp.int32)
    scan(x, y).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:traced_window"):
        for name in ("q6", "q1", "q6"):
            with jax.profiler.TraceAnnotation(f"stmt:{name}"):
                scan(x, y).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    print("trace", pb, os.path.getsize(pb), "bytes", file=sys.stderr)
    pd = jax.profiler.ProfileData.from_file(pb)
    for pl in pd.planes:
        print("PLANE", repr(pl.name))
        for ln in pl.lines:
            evs = list(ln.events)
            print("  LINE", repr(ln.name), len(evs),
                  [(e.name[:60], int(e.start_ns), int(e.duration_ns))
                   for e in evs[:4]])


if __name__ == "__main__":
    main()
