#!/usr/bin/env python3
"""What a profile's names can be joined to (PR 36, step 0): one traced
run of a benchmark cell, with the stage catalogue
(tidb_tpu/utils/kernel_stages.py) watched from inside the process.

  chiprun -- python3 benchmarks/stage_probe_tpu.py \
      --workload tpch-sf1.power --seed <n> [--seconds 51]

Prints, on stderr:
  1. every distinct `XLA Modules` event name of the traced window with
     its stats, beside what the program can compute of each catalogued
     program (the compiled text's header, the executable's fingerprint,
     the module proto's id), and which of those, if any, equals the
     number in the event's name or one of its stats;
  2. how a `while`'s body operations lie on the `XLA Ops` line: events
     that lie whole inside another event of the same line, by the
     container's name;
  3. how many catalogued programs have an instruction `fusion.6`.
Keeps under chiprun_out/stage_probe_<seed>/: the trace (gzip), the metrics
snapshot taken after the window (the catalogue as the harness reads
it), each program's compiled text, and the result line.
"""
import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)


def log(msg):
    print(f"stage_probe: {msg}", file=sys.stderr, flush=True)


def proto_id(blob):
    """Field 5 (`id`, a varint) of a serialized HloModuleProto."""
    i, n = 0, len(blob)

    def varint(i):
        v, shift = 0, 0
        while True:
            b = blob[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                return v, i
    while i < n:
        key, i = varint(i)
        field, wt = key >> 3, key & 7
        if wt == 0:
            v, i = varint(i)
            if field == 5:
                return v
        elif wt == 2:
            ln, i = varint(i)
            i += ln
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            return None
    return None


def candidates(compiled):
    """{what: int} the program can compute of its executable."""
    out = {}
    exe = compiled.runtime_executable()
    fp = exe.fingerprint
    out["fingerprint_raw"] = repr(fp)[:120]
    if isinstance(fp, (bytes, str)):
        raw = fp if isinstance(fp, bytes) else fp.encode()
        try:
            d = out["fingerprint_decimal"] = int(raw)
            for name, half in (("low64", d & (2 ** 64 - 1)),
                               ("high64", d >> 64)):
                out[f"fingerprint_{name}_u"] = half
                out[f"fingerprint_{name}_s"] = half - 2 ** 64 \
                    if half >= 2 ** 63 else half
        except ValueError:
            pass
        for name, chunk in (("first8", raw[:8]), ("last8", raw[-8:])):
            for order in ("little", "big"):
                for signed in (False, True):
                    out[f"fingerprint_{name}_{order}_"
                        f"{'s' if signed else 'u'}"] = int.from_bytes(
                            chunk, order, signed=signed)
    try:
        mod = exe.hlo_modules()[0]
        out["module_name"] = mod.name
        out["proto_id"] = proto_id(mod.as_serialized_hlo_module_proto())
    except Exception as e:                          # noqa: BLE001
        out["module_error"] = repr(e)[:100]
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="tpch-sf1.power")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal on the CPU backend at scale 0.01")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", f"stage_probe_{args.seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    import counters
    import run
    from tidb_tpu.utils import kernel_stages as ks

    programs = []
    orig_compile = ks._compile

    def compile_and_keep(jitted, a, kw):
        t = time.perf_counter()
        compiled, cost = orig_compile(jitted, a, kw)
        text = compiled.as_text()
        rec = {"seconds": time.perf_counter() - t, "compiled": cost,
               "header": text.split("\n", 1)[0][:300],
               "text_bytes": len(text)}
        try:
            rec["candidates"] = candidates(compiled)
        except Exception as e:                      # noqa: BLE001
            rec["candidates"] = {"error": repr(e)[:200]}
        family, stages = ks.parse_stages(text)
        rec["family"], rec["stages"] = family, stages
        with open(os.path.join(
                out_dir, f"hlo_{len(programs)}_{family}.txt"), "w") as f:
            f.write(text)
        programs.append(rec)
        return compiled, cost
    ks._compile = compile_and_keep

    snaps = []
    orig_snapshot = counters.snapshot

    def timed_snapshot(wire):
        pending = ks.noted()
        t = time.perf_counter()
        snap = orig_snapshot(wire)
        snaps.append(snap)
        log(f"snapshot {len(snaps)} took {time.perf_counter() - t:.3f} s "
            f"with {pending} programs noted and uncatalogued")
        return snap
    counters.snapshot = timed_snapshot

    kw = dict(need_chips=False, scale=0.01) if args.cpu else {}
    result = run.run_cell(args.workload, args.seed, args.seconds, True, **kw)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(result, f)
    print(json.dumps(result))

    after = snaps[-1]["metrics"]
    kept = {f"{k[0]}|{k[1]}": v for k, v in after.items()
            if "kernel_stage" in k[0] or "xla_cache" in k[0]
            or "prefix_select" in k[0]}
    with open(os.path.join(out_dir, "catalogue.json"), "w") as f:
        json.dump(kept, f, indent=0)
    # how each program built since the process started inverts its prefix
    # counts (PR 44): {site, form} -> programs; absent on an older tree
    log("prefix_select: " + str({k[1]: v for k, v in after.items()
                                 if k[0] == "tidb_tpu_prefix_select_total"}))
    labels = [k[1] for k in after if k[0] == "tidb_tpu_kernel_stage_ops"]
    log(f"catalogue: {len(labels)} samples, longest label "
        f"{max(map(len, labels), default=0)} bytes; outcomes "
        + str({k[1]: v for k, v in after.items()
               if k[0] == "tidb_tpu_kernel_stage_catalogue_total"}))
    for i, p in enumerate(programs):
        log(f"program {i} {p['family']}: look-up {p['seconds']:.3f} s, "
            f"backend compiled: {p['compiled']}, text {p['text_bytes']} "
            f"bytes, {len(p['stages'])} instructions; header {p['header']}")
        log(f"program {i} candidates {p['candidates']}")

    trace_dir = os.path.join(ROOT, ".cache", "bench", args.workload, "trace")
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        log("no trace file")
        return 1
    with open(found[0], "rb") as f, gzip.open(
            os.path.join(out_dir, "trace.xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    log(f"trace {os.path.getsize(found[0])} bytes")

    import jax
    data = jax.profiler.ProfileData.from_file(found[0])
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            events = sorted(((int(e.start_ns),
                              int(e.start_ns + e.duration_ns), e)
                             for e in line.events), key=lambda x: x[0])
            log(f"{plane.name} line {line.name!r}: {len(events)} events")
            if line.name == "XLA Modules":
                seen = {}
                for s, e, ev in events:
                    seen.setdefault(ev.name, []).append(ev)
                for name, evs in seen.items():
                    stats = {k: str(v) for k, v in evs[0].stats}
                    stats2 = {k: str(v) for k, v in evs[-1].stats}
                    varying = sorted(k for k in stats
                                     if stats[k] != stats2.get(k))
                    log(f"  module {name}: {len(evs)} runs; stats "
                        f"{stats}; differ between runs: {varying}")
                    numbers = {"name": name.split("(")[-1].rstrip(")")}
                    numbers.update(stats)
                    for i, p in enumerate(programs):
                        for what, v in p["candidates"].items():
                            for field, text in numbers.items():
                                if str(v) == text and field not in (
                                        "replica_id", "queue_id",
                                        "core_type"):
                                    log(f"    JOIN KEY: {field} of the "
                                        f"event equals {what} of "
                                        f"program {i} ({p['family']})")
            if line.name == "XLA Ops":
                inside, stack = {}, []
                for s, e, ev in events:
                    while stack and stack[-1][1] <= s:
                        stack.pop()
                    if stack and e <= stack[-1][1]:
                        key = (stack[-1][2].split(" = ")[0],
                               len(stack))
                        inside[key] = inside.get(key, 0) + 1
                    elif stack:
                        log(f"  PARTIAL OVERLAP {ev.name[:60]} with "
                            f"{stack[-1][2][:60]}")
                    stack.append((s, e, ev.name))
                for (name, depth), n in sorted(
                        inside.items(), key=lambda kv: -kv[1])[:20]:
                    log(f"  {n} events lie whole inside events called "
                        f"{name} (nesting depth {depth})")
        break                                        # one device is enough
    with_f6 = [i for i, p in enumerate(programs) if "fusion.6" in p["stages"]]
    log(f"programs with an instruction fusion.6: {len(with_f6)} of "
        f"{len(programs)}: " + ", ".join(
            f"{i} {programs[i]['family']} "
            f"({programs[i]['stages']['fusion.6']})" for i in with_f6))
    return 0


if __name__ == "__main__":
    sys.exit(main())
