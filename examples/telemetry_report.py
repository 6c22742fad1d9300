"""Run telemetry end to end: instrumented run → NDJSON log → Markdown report.

Turns on the flight recorder (``FFTConfig.telemetry``) for a short
scenario run, writes the schema-versioned NDJSON event log, reloads it,
cross-checks the reloaded report against the run's own accounting
(``repro.obs.reconcile``), and renders the Markdown run report — the same
tables ``python -m benchmarks.report run-report <log.ndjson>`` prints.

    PYTHONPATH=src python examples/telemetry_report.py
    PYTHONPATH=src python examples/telemetry_report.py --mode buffered \\
        --codec adaptive:sign1-fp16 --out /tmp/telemetry.ndjson

``--telemetry sketch`` records the same run through the bounded-memory
sketch sink (PR 8) — byte totals stay bit-equal, distributions become
ε-approximate quantiles; ``--trace DIR`` runs the job under the JAX
profiler and writes its trace there: the ``phase.*`` spans (with round and
client ids) beside the device's ops, as a Perfetto JSON.
"""
from __future__ import annotations

import argparse
from pathlib import Path

from repro.core.strategies import STRATEGIES
from repro.fl.runtime import FFTConfig
from repro.fl.toy import make_toy_runner
from repro.obs import load_report, reconcile, render_markdown


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", default="bursty_handover")
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "async", "buffered"])
    ap.add_argument("--strategy", default=None,
                    help="default: fedauto (sync) / fedauto_async (async)")
    ap.add_argument("--codec", default="adaptive:sign1-fp16")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--out", default="telemetry.ndjson",
                    help="NDJSON event-log path")
    ap.add_argument("--report-out", default=None,
                    help="also write the Markdown report here")
    ap.add_argument("--telemetry", default="full",
                    choices=["full", "sketch"],
                    help="flight-recorder mode (sketch = bounded memory)")
    ap.add_argument("--trace", default=None,
                    help="also write a profiler trace to this directory")
    args = ap.parse_args()

    strategy = args.strategy or ("fedauto" if args.mode == "sync"
                                 else "fedauto_async")
    cfg = FFTConfig(n_clients=8, k_selected=6, local_steps=2, batch_size=16,
                    failure_mode=f"scenario:{args.world}", deadline_s=5.0,
                    model_bytes=4e6, server_mode=args.mode, tau_max=3,
                    buffer_k=3, codec=args.codec, eval_every=2, seed=0,
                    telemetry=args.telemetry, telemetry_log=args.out,
                    telemetry_console=True, telemetry_trace=args.trace)
    runner = make_toy_runner(cfg, n_samples=600, public_per_class=10,
                             pretrain_steps=15)
    hist = runner.run(STRATEGIES[strategy](), rounds=args.rounds)
    print(f"\naccuracy history: {[round(a, 4) for a in hist]}")

    # the NDJSON log round-trips to the same flight record the run held in
    # memory, and both agree with CommState's byte totals and the loop's
    # participant counts (load_report picks RunReport or SketchReport by
    # the log's recorded telemetry mode)
    reloaded = load_report(args.out)
    nums = reconcile(reloaded, runner)
    assert (reloaded.drop_cause_counts()
            == runner.report.drop_cause_counts())
    print(f"reconciled: {nums}")

    # per-phase profiler (PR 7): where each round's wall time actually
    # went — exclusive timers, so shares sum to 100%
    print("\nphase table (hottest first):")
    for row in reloaded.phase_table():
        print(f"  {row['phase']:<14s} {row['total_s']:8.3f} s total"
              f"  {row['s_per_round'] * 1e3:8.2f} ms/round"
              f"  {row['share'] * 100:5.1f}%")

    if args.trace:
        path = next(Path(args.trace).glob(
            "plugins/profile/*/perfetto_trace.json.gz"))
        print(f"\nprofiler trace: {path} → open in https://ui.perfetto.dev")

    md = render_markdown([reloaded])
    print("\n" + md)
    if args.report_out:
        with open(args.report_out, "w") as fh:
            fh.write(md + "\n")
        print(f"\nwrote {args.report_out}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
