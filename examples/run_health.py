"""Health-monitor gate: run a world under sketch telemetry, assert the verdict.

The online detectors (``repro.obs.HealthMonitors``) are only trustworthy if
they fire on known-bad runs AND stay silent on known-good ones.  This
script is that contract as an executable check — CI runs it twice:

    # seeded fault injection: the blackout world must trip alarms
    PYTHONPATH=src python examples/run_health.py --world blackout \\
        --expect alarms --out /tmp/blackout.ndjson

    # committed healthy baseline: the same detectors must stay silent
    PYTHONPATH=src python examples/run_health.py --world bursty_handover \\
        --expect healthy

Two run profiles, selected by ``--expect`` (override with ``--profile``):

* ``baseline`` — the committed healthy-baseline settings (6 clients,
  30 s deadline, default model size; the configuration the
  ``HealthConfig`` thresholds are calibrated to stay silent on for
  ``bursty_handover`` and ``correlated_wifi``);
* ``stress`` — tight 5 s deadline against a 4 MB model, which gives
  fault-injection worlds like ``blackout`` something to break.

Exit code 0 when the verdict matches ``--expect``, 1 when it does not.
``--trace DIR`` also writes a profiler trace of the run there (phase spans
and device ops, as a Perfetto JSON).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

from repro.core.strategies import STRATEGIES
from repro.fl.runtime import FFTConfig
from repro.fl.toy import make_toy_runner
from repro.obs import load_report, reconcile

PROFILES = {
    "baseline": dict(n_clients=6, k_selected=4, deadline_s=30.0,
                     model_bytes=None, tau_max=3, buffer_k=2, seed=3),
    "stress": dict(n_clients=8, k_selected=6, deadline_s=5.0,
                   model_bytes=4e6, tau_max=2, buffer_k=3, seed=0),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", default="blackout")
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "async", "buffered"])
    ap.add_argument("--codec", default="adaptive:sign1-fp16")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--expect", required=True,
                    choices=["healthy", "alarms"])
    ap.add_argument("--profile", default=None,
                    choices=sorted(PROFILES),
                    help="default: baseline for --expect healthy, "
                         "stress for --expect alarms")
    ap.add_argument("--out", default=None, help="NDJSON event-log path")
    ap.add_argument("--trace", default=None,
                    help="also write a profiler trace to this directory")
    args = ap.parse_args()

    profile = args.profile or ("healthy" == args.expect and "baseline"
                               or "stress")
    prof = PROFILES[profile]
    strategy = "fedauto" if args.mode == "sync" else "fedauto_async"
    cfg = FFTConfig(local_steps=2, batch_size=8, lr=0.05, eval_every=2,
                    failure_mode=f"scenario:{args.world}",
                    server_mode=args.mode, codec=args.codec,
                    telemetry="sketch", telemetry_console=True,
                    telemetry_log=args.out, telemetry_trace=args.trace,
                    **prof)
    runner = make_toy_runner(cfg, n_samples=300, n_classes=4, image_size=8,
                             public_per_class=10, pretrain_steps=0,
                             seed=prof["seed"])
    runner.run(STRATEGIES[strategy](), rounds=args.rounds)

    report = runner.report
    reconcile(report, runner)
    if args.out:
        reloaded = load_report(args.out)
        assert reloaded.health_verdict() == report.health_verdict()
        reconcile(reloaded, runner)
    if args.trace:
        path = next(Path(args.trace).glob(
            "plugins/profile/*/perfetto_trace.json.gz"))
        print(f"profiler trace: {path}")

    verdict = report.health_verdict()
    print(f"profile: {profile}  verdict: {verdict}")
    got = "healthy" if verdict["healthy"] else "alarms"
    if got != args.expect:
        print(f"FAIL: expected {args.expect!r}, run was {got!r}",
              file=sys.stderr)
        return 1
    print(f"OK: run is {got!r} as expected")
    return 0


if __name__ == "__main__":
    sys.exit(main())
