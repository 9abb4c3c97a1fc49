#!/usr/bin/env python3
"""CPU rehearsal, run by hand before a chip call (never a measurement):

  JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--scale 0.01]

1. Loads TPC-H at a small scale through the harness's own path and
   checks engine rows == numpy reference rows for all six queries.
2. Drives every cell of BENCHMARK.json through `run_cell` for a few
   seconds with the look for a chip switched off, traced and untraced,
   and prints each result line. Its numbers are the CPU backend's and
   are never written as device metrics.
"""
import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def six_queries(scale, seed):
    import run
    from wire import Wire
    ds = run.load_module("datasets", "tpch", "data set")
    sys.path.insert(0, run.ROOT)
    data_dir = os.path.join(run.ROOT, ".cache", "bench", "rehearsal")
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    system = run.System(data_dir)
    ok = True
    try:
        wire = Wire(system.port)
        tables = ds.generate(scale, seed)
        ds.load(tables, wire.query, system.bulk_table)
        for name, sql in ds.STATEMENTS.items():
            got, want = wire.rows(sql), ds.reference(tables, name)
            bad = ds.answer_wrong(got, want)
            print(f"# {name}: {len(got)} rows "
                  f"{'WRONG' if bad else 'equal the reference'}")
            if bad:
                print("  got ", got[:3], "\n  want", want[:3])
                ok = False
        wire.close()
    finally:
        system.close()
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=2_400_000_011)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import run
    ok = six_queries(args.scale, args.seed)
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"), "b")
    for cell in bench["workloads"]:
        for trace in (False, True):
            result = run.run_cell(cell["name"], args.seed, args.seconds,
                                  trace, need_chips=False, scale=args.scale)
            print(f"# {cell['name']} trace={int(trace)} (CPU rehearsal)")
            print(json.dumps(result))
            ok = ok and result["correct"]
    print("rehearsal", "passed" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
