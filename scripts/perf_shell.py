"""Interactive perf harness: load SF once, then exec commands from stdin lines.
Usage: python scripts/perf_shell.py <sf>  — then feed python statements, one
compound block per '---' separated chunk, via a FIFO or here-doc."""
import os, sys, time
os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tests.conftest  # noqa: F401,E402  (CPU backend, 8 virtual devices)
from tidb_tpu.testkit import TestKit
from tidb_tpu.bench.tpch import load_tpch, ALL_QUERIES
sf = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
tk = TestKit()
t0 = time.time(); load_tpch(tk, sf=sf, seed=42)
print(f"READY load={time.time()-t0:.1f}s sf={sf}", flush=True)
buf = []
for line in sys.stdin:
    if line.rstrip() == "---":
        src = "".join(buf); buf = []
        try:
            exec(compile(src, "<cmd>", "exec"), globals())
        except Exception:
            import traceback; traceback.print_exc()
        print("DONE", flush=True)
    else:
        buf.append(line)
