#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, and trace tools; run on the chip.

    python3 benchmarks/chip/probe.py readings --workload <cell> --seeds 1,2,3 \
        [--control] [--faults] [--out file.jsonl]
        For each seed: one run of the cell with a one-round window; the
        program's numbers against the reference, and with --control the
        reference computed in bfloat16 put in the program's place, and with
        --faults each fault of reference.FAULTS planted in the reference
        put in the program's place. One JSON line per seed.
    python3 benchmarks/chip/probe.py tiny-trace --out DIR
        A small trace of two jitted programs inside "round" steps (the
        fixture of the reduction's tests).
    python3 benchmarks/chip/probe.py dump-trace PATH
        Planes, lines, event counts and sample events of a trace.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def readings(args) -> int:
    import jax
    import jax.numpy as jnp

    import reference
    import run

    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        keep = {}
        t0 = time.perf_counter()
        res = run.run_cell(args.workload, seed, 0.0, False, keep=keep, log=lambda s: None)
        if res is None:
            return 2
        row = {"seed": seed, "program": keep["numbers"], "correct": res["correct"],
               "device": res["device"], "run_s": time.perf_counter() - t0}
        variants = []
        if args.control:
            variants.append(("control_bf16", jnp.bfloat16, None))
        if args.faults:
            variants += [(f, jnp.float32, f) for f in reference.FAULTS]
        for name, dtype, fault in variants:
            t1 = time.perf_counter()
            with jax.default_matmul_precision("highest"):
                ws = reference.follow(
                    keep["mod"], keep["sizes"], keep["task"], keep["base"], keep["w0"],
                    keep["rounds"],
                    server_hist=keep["hists"][0], client_hists=keep["hists"][1],
                    public_y=keep["public_y"], steps=keep["fft"]["local_steps"],
                    batch=keep["fft"]["batch_size"], dtype=dtype, fault=fault)
            row[name] = reference.compare(keep["w0"], ws, keep["ref_ws"])
            row[name]["s"] = time.perf_counter() - t1
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


def tiny_trace(args) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def local_update(x):
        return jnp.tanh(x @ x) @ x

    @jax.jit
    def solve_weights(x):
        return jnp.sort(x.sum(0))

    x = jnp.ones((512, 512), jnp.float32)
    local_update(x).block_until_ready()
    solve_weights(x).block_until_ready()
    jax.profiler.start_trace(args.out)
    for r in range(3):
        with jax.profiler.StepTraceAnnotation("round", step_num=r):
            local_update(x).block_until_ready()
            time.sleep(0.002)
            solve_weights(x).block_until_ready()
    jax.profiler.stop_trace()
    print(sorted(str(p) for p in Path(args.out).rglob("*.xplane.pb")))
    return 0


def dump_trace(args) -> int:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(args.path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            for n, c in sorted(names.items(), key=lambda kv: -kv[1])[:args.top]:
                print(f"     {c:6d} x {n!r}")
            for e in evs[:2]:
                print(f"     sample {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                      f"stats {dict(list(e.stats)[:8])}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--control", action="store_true")
    r.add_argument("--faults", action="store_true")
    r.add_argument("--out")
    t = sub.add_parser("tiny-trace")
    t.add_argument("--out", required=True)
    d = sub.add_parser("dump-trace")
    d.add_argument("path")
    d.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    return {"readings": readings, "tiny-trace": tiny_trace, "dump-trace": dump_trace}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
