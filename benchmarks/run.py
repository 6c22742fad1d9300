"""Benchmark harness entrypoint — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes a schema-versioned
``BENCH_<name>.json`` baseline per bench (``--json-dir``, default the
working directory) carrying typed metrics, per-phase profiler seconds, and
an environment fingerprint — the inputs ``benchmarks.report diff`` gates
regressions on.  ``--full`` approaches the paper's scale; default quick
mode finishes on CPU.  Exits nonzero when any bench raises (the failure is
still printed as an ERROR CSV row, but never silently swallowed).
"""
import argparse
import sys
import time


def get_benches():
    from benchmarks import (bench_adaptive, bench_aggregation, bench_async,
                            bench_comm, bench_convergence, bench_fidelity,
                            bench_kernels, bench_population,
                            bench_resourceopt, bench_scenarios,
                            bench_stream, bench_table1, bench_table2,
                            bench_table3, bench_table4, bench_table5,
                            roofline)
    return {
        "kernels": bench_kernels,
        "aggregation": bench_aggregation,
        "stream": bench_stream,
        "convergence": bench_convergence,
        "table1": bench_table1,
        "table2": bench_table2,
        "table3": bench_table3,
        "table4": bench_table4,
        "table5": bench_table5,
        "resourceopt": bench_resourceopt,
        "scenarios": bench_scenarios,
        "population": bench_population,
        "async": bench_async,
        "comm": bench_comm,
        "adaptive": bench_adaptive,
        "fidelity": bench_fidelity,
        "roofline": roofline,
    }


def run_benches(benches, *, quick: bool, json_dir=None, out=print) -> int:
    """Run ``benches`` (name → module), stream CSV rows through ``out``,
    persist per-bench JSON baselines under ``json_dir``, and return the
    process exit code: 0 when every bench completed, 1 when any raised.
    A failing bench still emits an ERROR row (and fails the run) but never
    stops the benches after it."""
    import os

    from benchmarks.common import (BenchResult, env_fingerprint,
                                   write_bench_json)
    failures = []
    out("name,us_per_call,derived")
    for name, mod in benches.items():
        t0 = time.perf_counter()
        try:
            rows = mod.run(quick=quick)
            failed = False
        except Exception as e:  # noqa: BLE001
            rows = [f"{name}/ERROR,0,{type(e).__name__}:{e}"]
            failed = True
            failures.append(name)
        elapsed = time.perf_counter() - t0
        results = [r if isinstance(r, BenchResult)
                   else BenchResult.from_csv_row(r) for r in rows]
        for r in results:
            out(r.csv_row())
        print(f"# {name} took {elapsed:.1f}s", file=sys.stderr)
        if json_dir is not None and not failed:
            write_bench_json(os.path.join(json_dir, f"BENCH_{name}.json"),
                             name, results, elapsed_s=elapsed,
                             env=env_fingerprint(quick))
    if failures:
        print(f"# FAILED: {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. table2,kernels)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<name>.json baselines "
                         "(default: cwd; 'none' disables)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    benches = get_benches()
    if args.only:
        only = args.only.split(",")
        unknown = sorted(set(only) - set(benches))
        if unknown:
            print(f"unknown benches: {', '.join(unknown)} "
                  f"(known: {', '.join(benches)})", file=sys.stderr)
            return 2
        benches = {n: benches[n] for n in benches if n in only}
    json_dir = None if args.json_dir == "none" else args.json_dir
    return run_benches(benches, quick=not args.full, json_dir=json_dir)


if __name__ == "__main__":
    sys.exit(main())
