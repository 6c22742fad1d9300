"""Population-scale telemetry (PR 8): sketch sinks, health monitors,
dashboard, and the crash-durability satellites.

Deterministic variants of the sketch-accuracy properties live here (the
hypothesis sweeps are in ``test_hypothesis_properties.py``); the heavy
claims are structural: sketch-mode totals bit-equal to full mode on the
same seeded run, resident telemetry state O(rounds + K) at 50k clients,
and health monitors firing on the seeded blackout world while staying
silent on the healthy baselines.
"""
import io
import json
import math
import warnings
from bisect import bisect_left, bisect_right
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.strategies import STRATEGIES
from repro.fl.runtime import FFTConfig
from repro.fl.toy import make_toy_runner
from repro.obs import (AGGREGATED, EVICTED, LINK_DOWN, NOT_SELECTED,
                       ExactSum, GKQuantiles, HealthConfig, HealthMonitors,
                       NdjsonSink, Reservoir, RunReport, SketchReport,
                       SketchState, Telemetry, beta_row, load_report,
                       reconcile, render_dashboard, render_markdown, watch)

BASE = dict(n_clients=6, k_selected=4, local_steps=2, batch_size=8, lr=0.05,
            seed=3, eval_every=2, deadline_s=30.0, tau_max=3, buffer_k=2,
            failure_mode="scenario:bursty_handover")
TOY = dict(n_samples=300, n_classes=4, image_size=8, public_per_class=10,
           pretrain_steps=0, seed=3)
ROUNDS = 5


@pytest.fixture(scope="module")
def mode_runs(tmp_path_factory):
    """The same seeded buffered-adaptive run recorded twice: once in full
    mode, once in sketch mode (each with an NDJSON log)."""
    tmp = tmp_path_factory.mktemp("obs_scale")
    out = {}
    for mode in ("full", "sketch"):
        cfg = FFTConfig(**BASE, server_mode="buffered",
                        codec="adaptive:sign1-fp16", telemetry=mode,
                        telemetry_log=str(tmp / f"{mode}.ndjson"))
        runner = make_toy_runner(cfg, **TOY)
        hist = runner.run(STRATEGIES["fedauto_async"](), rounds=ROUNDS)
        out[mode] = (runner, hist)
    return out


# ---------------------------------------------------------------------------
# sketch primitives (deterministic sweeps; hypothesis versions elsewhere)
# ---------------------------------------------------------------------------
def test_exactsum_bit_equal_to_fsum():
    rng = np.random.default_rng(0)
    for trial in range(20):
        # mixed magnitudes where naive summation visibly loses bits
        vals = list(np.exp(rng.normal(10.0, 8.0, 500)))
        rng.shuffle(vals)
        acc = ExactSum()
        for v in vals:
            acc.add(v)
        assert acc.value() == math.fsum(vals)
        # order independence: a different fold order, same bits
        acc2 = ExactSum()
        for v in reversed(vals):
            acc2.add(v)
        assert acc2.value() == acc.value()
        # serialization round-trip preserves exactness
        assert ExactSum(acc.to_json()).value() == acc.value()


def _check_rank_error(values, eps):
    gk = GKQuantiles(eps)
    for v in values:
        gk.add(v)
    srt = sorted(values)
    n = len(srt)
    for q in (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
        got = gk.query(q)
        target = max(1, math.ceil(q * n))
        lo = bisect_left(srt, got) + 1        # 1-based rank range of `got`
        hi = bisect_right(srt, got)
        slack = eps * n + 1
        assert lo - slack <= target <= hi + slack, \
            f"q={q}: value {got} has ranks [{lo},{hi}] vs target {target}"
    return gk


def test_gk_rank_error_bound_deterministic():
    rng = np.random.default_rng(1)
    for dist in (rng.normal(0, 1, 5000), rng.exponential(1.0, 5000),
                 rng.integers(0, 10, 5000).astype(float),
                 np.sort(rng.uniform(0, 1, 5000))):
        gk = _check_rank_error(list(dist), eps=0.01)
        # size is sketch-like, not list-like
        assert len(gk.entries) < len(dist) / 4
        # serialization round-trips queries exactly
        gk2 = GKQuantiles.from_json(json.loads(json.dumps(gk.to_json())))
        assert all(gk2.query(q) == gk.query(q)
                   for q in (0.1, 0.5, 0.9, 0.99))


def test_reservoir_seeded_and_bounded():
    r1 = Reservoir(16, seed=7)
    r2 = Reservoir(16, seed=7)
    for i in range(1000):
        r1.offer({"i": i})
        r2.offer({"i": i})
    assert len(r1.rows) == 16 and r1.n == 1000
    assert r1.rows == r2.rows          # same seed → same sample
    r3 = Reservoir(16, seed=8)
    for i in range(1000):
        r3.offer({"i": i})
    assert r3.rows != r1.rows          # different seed → different sample


# ---------------------------------------------------------------------------
# sketch mode vs full mode on the same seeded run
# ---------------------------------------------------------------------------
def test_sketch_run_matches_full_bit_for_bit(mode_runs):
    full, hist_full = mode_runs["full"]
    sk, hist_sk = mode_runs["sketch"]
    # telemetry is observational in either mode: identical training
    assert hist_full == hist_sk
    # additive accounting is bit-equal, not approximately equal
    assert (sk.report.total_upload_bytes()
            == full.report.total_upload_bytes())
    assert (sk.report.total_download_bytes()
            == full.report.total_download_bytes())
    assert sk.report.drop_cause_counts() == full.report.drop_cause_counts()
    assert sk.report.rung_histogram() == full.report.rung_histogram()
    assert (sk.report.participants_per_round()
            == full.report.participants_per_round())
    # and both reconcile against their run's own accounting
    reconcile(full.report, full)
    reconcile(sk.report, sk)
    # β masses are exact additive group sums in both modes
    for key in ("staleness", "rung", "role"):
        a, b = full.report.beta_mass_by(key), sk.report.beta_mass_by(key)
        assert set(a) == set(b)
        assert all(a[g] == pytest.approx(b[g]) for g in a)
    assert sk.report.mean_distortion() == \
        pytest.approx(full.report.mean_distortion())


def test_sketch_quantiles_within_rank_error_of_full(mode_runs):
    full, _ = mode_runs["full"]
    sk, _ = mode_runs["sketch"]
    finals = full.report.final_outcomes()
    exact = {
        "upload_bytes": sorted(float(r["upload_bytes"])
                               for r in finals.values()
                               if r.get("upload_bytes") is not None),
        "distortion": sorted(float(r["distortion"]) for r in finals.values()
                             if r.get("distortion") is not None),
        "beta": sorted(float(row["beta"])
                       for row in full.report.beta_rows()
                       if row.get("role", "client") == "client")}
    qdocs = sk.report.quantiles(qs=(0.25, 0.5, 0.9))
    eps = sk.report.summary["sketch"]["eps"]
    for metric, srt in exact.items():
        assert srt, f"fixture recorded no {metric} values"
        n = len(srt)
        for q, got in qdocs[metric].items():
            target = max(1, math.ceil(q * n))
            lo = bisect_left(srt, got) + 1
            hi = bisect_right(srt, got)
            slack = eps * n + 1
            assert lo - slack <= target <= hi + slack, \
                f"{metric} q={q}: {got} ranks [{lo},{hi}] vs {target}"


def test_sketch_ndjson_roundtrip(mode_runs):
    sk, _ = mode_runs["sketch"]
    rep = load_report(sk.cfg.telemetry_log)
    assert isinstance(rep, SketchReport)
    assert rep.total_upload_bytes() == sk.report.total_upload_bytes()
    assert rep.drop_cause_counts() == sk.report.drop_cause_counts()
    assert rep.rung_histogram() == sk.report.rung_histogram()
    assert rep.beta_mass_by("staleness").keys() \
        == sk.report.beta_mass_by("staleness").keys()
    assert set(rep.quantiles()) == set(sk.report.quantiles())
    assert len(rep.sample_rows()) == len(sk.report.sample_rows())
    reconcile(rep, sk)                    # reloaded sketch still reconciles
    # full-mode logs resolve to RunReport through the same entry point
    full, _ = mode_runs["full"]
    assert isinstance(load_report(full.cfg.telemetry_log), RunReport)
    # and the renderer produces the same table set from either mode
    md = render_markdown([rep], labels=["sketch"])
    for section in ("## Runs", "## Drop-cause breakdown",
                    "## β-mass by staleness", "## Phase timings",
                    "## Distribution quantiles", "## Health"):
        assert section in md, section


def test_sketch_beta_ess_gauge(mode_runs):
    for mode in ("full", "sketch"):
        runner, _ = mode_runs[mode]
        ess = [r["gauges"]["beta_ess"] for r in runner.report.rounds
               if "beta_ess" in r["gauges"]]
        assert ess, f"{mode}: no beta_ess gauges recorded"
        assert all(1.0 <= e <= BASE["n_clients"] + 1e-9 for e in ess)
    f = {r["round"]: r["gauges"]["beta_ess"] for r in mode_runs["full"][0]
         .report.rounds if "beta_ess" in r["gauges"]}
    s = {r["round"]: r["gauges"]["beta_ess"] for r in mode_runs["sketch"][0]
         .report.rounds if "beta_ess" in r["gauges"]}
    assert f == pytest.approx(s)


def test_rung_churn_gauge_emitted(mode_runs):
    runner, _ = mode_runs["full"]
    churn = {r["round"]: r["gauges"]["rung_churn"]
             for r in runner.report.rounds if "rung_churn" in r["gauges"]}
    # round 1 has no previous assignment; every later round reports churn
    assert set(churn) == set(range(2, ROUNDS + 1))
    assert all(0.0 <= c <= 1.0 for c in churn.values())


# ---------------------------------------------------------------------------
# population scale: 50k simulated clients, O(rounds + K) resident state
# ---------------------------------------------------------------------------
def _feed_population(n_clients, rounds, k=64, seed=0):
    """Drive the hub protocol directly at population scale (no training —
    the telemetry path is the thing under test) and return the sketch
    report plus a stub runner carrying the ground-truth accounting."""
    rep = SketchReport()
    tel = Telemetry(sinks=[rep],
                    sketch=SketchState(n_clients, k=k, seed=seed))
    tel.start_run({"scenario": "synthetic", "n_clients": n_clients,
                   "rounds": rounds})
    rng = np.random.default_rng(seed)
    uploads = []
    participants = []
    downlink = 0.0
    for r in range(1, rounds + 1):
        tel.begin_round(r)
        sel = rng.random(n_clients) < 0.5
        up = rng.random(n_clients) < 0.9
        n_agg = 0
        for i in range(n_clients):
            if not sel[i]:
                tel.client_outcome(r, i, NOT_SELECTED)
            elif not up[i]:
                tel.client_outcome(r, i, LINK_DOWN, detail="outage")
            else:
                ub = float(rng.integers(10_000, 100_000))
                uploads.append(ub)
                tel.client_outcome(r, i, AGGREGATED, rung="qsgd:4",
                                   upload_bytes=ub,
                                   distortion=float(rng.random()))
                n_agg += 1
        betas = rng.dirichlet(np.ones(min(n_agg, 32)))
        tel.betas(r, [beta_row(b, client=j, rung="qsgd:4")
                      for j, b in enumerate(betas)])
        tel.gauge(r, "participants", float(n_agg))
        tel.gauge(r, "downlink_bytes", 1e6)
        downlink += 1e6
        participants.append(n_agg)
        tel.end_round(r)
    tel.end_run()
    runner = SimpleNamespace(
        comm=SimpleNamespace(total_uplink_bytes=math.fsum(uploads),
                             total_downlink_bytes=downlink),
        loop=SimpleNamespace(participants_per_round=participants))
    return rep, runner


def test_population_scale_sketch_smoke():
    small, _ = _feed_population(2_000, rounds=3, seed=5)
    big, runner = _feed_population(50_000, rounds=3, seed=5)
    # exact closure + bit-equal byte totals against the feed's accounting
    nums = reconcile(big, runner)
    assert nums["outcomes_total"] == 50_000 * 3
    assert big.total_upload_bytes() == runner.comm.total_uplink_bytes

    # resident state is O(rounds + K): no per-client rows anywhere,
    # per-round records of constant size (independent of n_clients),
    # reservoir capped at K, sketches at their ε-bound
    for rec in big.rounds:
        assert "clients" not in rec and "betas" not in rec
    est_small, est_big = small.resident_estimate(), big.resident_estimate()
    assert est_big["reservoir_rows"] == 64
    assert est_big["round_record_bytes"] < 16_000
    # 25× the clients must not grow the per-round record (same structure;
    # allow slack for longer digit strings in the counts)
    assert (est_big["round_record_bytes"]
            < est_small["round_record_bytes"] * 2)
    assert est_big["summary_bytes"] < est_small["summary_bytes"] * 4
    for name, doc in big.summary["sketch"]["sketches"].items():
        assert len(doc["entries"]) < 4_000, name

    # the sketches still answer sensible quantiles at this scale
    q = big.quantiles()["upload_bytes"]
    assert 10_000 <= q[0.5] <= 100_000

    # duplicate-outcome enforcement survives the sketch path
    tel = Telemetry(sinks=[SketchReport()], sketch=SketchState(10))
    tel.start_run({"n_clients": 10})
    tel.begin_round(1)
    tel.client_outcome(1, 3, NOT_SELECTED)
    with pytest.raises(ValueError, match="exactly one terminal outcome"):
        tel.client_outcome(1, 3, AGGREGATED)


# ---------------------------------------------------------------------------
# crash durability (satellite)
# ---------------------------------------------------------------------------
def test_truncated_final_line_tolerated(mode_runs, tmp_path):
    for mode, loader in (("full", RunReport.from_ndjson),
                         ("sketch", SketchReport.from_ndjson)):
        runner, _ = mode_runs[mode]
        lines = open(runner.cfg.telemetry_log).read().splitlines()
        cut = tmp_path / f"killed_{mode}.ndjson"
        # a kill mid-write: the final record is half a JSON object
        cut.write_text("\n".join(lines[:-1]) + "\n"
                       + lines[-1][:len(lines[-1]) // 2])
        with pytest.warns(RuntimeWarning, match="truncated final record"):
            rep = loader(str(cut))
        assert rep.n_rounds == ROUNDS       # run_end was the casualty
        assert rep.drop_cause_counts() == \
            runner.report.drop_cause_counts()
        # load_report dispatches on the surviving prefix too
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            assert type(load_report(str(cut))) is type(runner.report)

    # corruption that is NOT the final line is a damaged log: still raises
    bad = tmp_path / "damaged.ndjson"
    bad.write_text(lines[0] + "\n{half a record\n" + lines[-1] + "\n")
    with pytest.raises(json.JSONDecodeError):
        RunReport.from_ndjson(str(bad))


def test_ndjson_flushes_every_record(tmp_path):
    path = tmp_path / "flush.ndjson"
    sink = NdjsonSink(str(path))
    sink.on_run_start({"n_clients": 2})
    sink.on_round({"round": 1, "clients": {0: {"client": 0,
                                               "outcome": AGGREGATED}},
                   "gauges": {}, "betas": []})
    sink.on_resolution({"origin_round": 1, "client": 0,
                        "outcome": AGGREGATED})
    sink.on_health({"round": 1, "monitor": "empty_cohort",
                    "severity": "alarm", "value": 3, "threshold": 3,
                    "message": "x"})
    # nothing closed or crashed — every record is already on disk
    kinds = [json.loads(ln)["record"]
             for ln in open(path).read().splitlines()]
    assert kinds == ["run_start", "round", "resolution", "health"]


# ---------------------------------------------------------------------------
# final_outcomes caching (satellite)
# ---------------------------------------------------------------------------
def test_final_outcomes_cached_and_invalidated(mode_runs):
    runner, _ = mode_runs["full"]
    import copy
    rep = copy.deepcopy(runner.report)
    first = rep.final_outcomes()
    assert rep.final_outcomes() is first           # cache hit
    counts = rep.drop_cause_counts()
    # a new round record invalidates
    rep.on_round({"round": ROUNDS + 1,
                  "clients": {0: {"client": 0, "outcome": NOT_SELECTED}},
                  "gauges": {}, "betas": []})
    second = rep.final_outcomes()
    assert second is not first
    assert len(second) == len(first) + 1
    # in-place tampering that changes row counts (what the reconcile tamper
    # tests do) is seen by the cache key; pick a non-buffered row so no
    # resolution record is orphaned by the removal
    some_client = next(c for c, row in rep.rounds[0]["clients"].items()
                       if row["outcome"] != "buffered")
    rep.rounds[0]["clients"].pop(some_client)
    third = rep.final_outcomes()
    assert len(third) == len(second) - 1
    # a resolution record also invalidates (fresh copy: resolutions must
    # target a still-buffered record)
    rep2 = copy.deepcopy(runner.report)
    cached = rep2.final_outcomes()
    buffered_key = next((k for k, v in cached.items()
                         if v["outcome"] == "buffered"), None)
    if buffered_key is not None:
        rep2.on_resolution({"origin_round": buffered_key[0],
                            "client": buffered_key[1],
                            "outcome": EVICTED})
        assert rep2.final_outcomes() is not cached
    assert counts == copy.deepcopy(runner.report).drop_cause_counts()


# ---------------------------------------------------------------------------
# health monitors
# ---------------------------------------------------------------------------
def _digest(r, **kw):
    d = dict(round=r, n_clients=10, counts={}, participants=5,
             eval_acc=None, beta_n=0, beta_ess=None, distortion_mean=None,
             gauges={})
    d.update(kw)
    return d


def test_health_monitors_unit():
    cfg = HealthConfig()
    hm = HealthMonitors(cfg)
    recs = []
    # healthy warmup evals, then a crash below the drawdown threshold
    for r, acc in enumerate([0.5, 0.6, 0.62, 0.3], start=1):
        recs += hm.observe_round(_digest(r, eval_acc=acc))
    assert [x["monitor"] for x in recs] == ["acc_drawdown"]
    # staying collapsed does not re-fire (edge-triggered) …
    recs += hm.observe_round(_digest(5, eval_acc=0.3))
    assert len(recs) == 1
    # … but a recovery re-arms the detector
    hm.observe_round(_digest(6, eval_acc=0.62))
    recs += hm.observe_round(_digest(7, eval_acc=0.3))
    assert [x["monitor"] for x in recs] == ["acc_drawdown"] * 2
    for rec in recs:                      # schema'd records
        assert set(rec) == {"round", "monitor", "severity", "value",
                            "threshold", "message"}
        assert rec["severity"] == "alarm"

    hm = HealthMonitors(cfg)
    out = []
    for r in range(1, 5):
        out += hm.observe_round(_digest(r, participants=0,
                                        counts={"evicted": 1}))
    monitors = [x["monitor"] for x in out]
    assert monitors.count("empty_cohort") == 1
    assert monitors.count("eviction_streak") == 1
    assert out[0]["round"] == cfg.empty_streak

    hm = HealthMonitors(cfg)
    out = []
    for r in range(1, 4):
        out += hm.observe_round(_digest(r, beta_n=10, beta_ess=1.0))
    assert [x["monitor"] for x in out] == ["beta_collapse"]

    hm = HealthMonitors(cfg)
    out = []
    for r in range(1, 5):
        out += hm.observe_round(_digest(r, gauges={"rung_churn": 0.8}))
    assert [x["monitor"] for x in out] == ["rung_thrash"]

    hm = HealthMonitors(cfg)
    out = []
    for r, cap in enumerate([1e7, 1.1e7, 0.9e7, 1e7, 1e6], start=1):
        out += hm.observe_round(
            _digest(r, gauges={"cap_hat_mean_bps": cap}))
    assert [x["monitor"] for x in out] == ["cap_drift"]

    hm = HealthMonitors(cfg)
    out = []
    for r, d in enumerate([0.1, 0.11, 0.09, 0.6], start=1):
        out += hm.observe_round(_digest(r, distortion_mean=d))
    assert [x["monitor"] for x in out] == ["distortion_spike"]
    v = hm.verdict()
    assert not v["healthy"] and v["n_alarms"] == 1
    assert v["by_monitor"] == {"distortion_spike": 1}
    assert v["first_alarm_round"] == 4 and v["rounds_seen"] == 4


@pytest.fixture(scope="module")
def blackout_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("blackout")
    cfg = FFTConfig(n_clients=8, k_selected=6, local_steps=2, batch_size=8,
                    lr=0.05, seed=0, eval_every=2, deadline_s=5.0,
                    tau_max=2, buffer_k=3, model_bytes=4e6,
                    failure_mode="scenario:blackout", server_mode="sync",
                    codec="adaptive:sign1-fp16", telemetry="sketch",
                    telemetry_console=True,
                    telemetry_log=str(tmp / "blackout.ndjson"))
    runner = make_toy_runner(cfg, n_samples=300, n_classes=4, image_size=8,
                             public_per_class=10, pretrain_steps=0, seed=0)
    runner.run(STRATEGIES["fedauto"](), rounds=12)
    return runner


def test_health_fires_on_blackout(blackout_run):
    rep = blackout_run.report
    v = rep.health_verdict()
    assert v is not None and not v["healthy"]
    # the outage must trip the cohort detector at minimum, and the alarms
    # must postdate the blackout onset (round 6)
    assert "empty_cohort" in v["by_monitor"]
    assert v["first_alarm_round"] > 6
    assert len(rep.health) == v["n_alarms"]
    # alarm records and verdict survive the NDJSON round-trip
    rep2 = load_report(blackout_run.cfg.telemetry_log)
    assert [a["monitor"] for a in rep2.health] \
        == [a["monitor"] for a in rep.health]
    assert rep2.health_verdict() == v
    # … and the reloaded report still reconciles
    reconcile(rep2, blackout_run)


def test_console_sink_surfaces_health(capsys):
    from repro.obs import ConsoleSink
    sink = ConsoleSink()
    sink.on_health({"round": 9, "monitor": "empty_cohort",
                    "severity": "alarm", "value": 3.0, "threshold": 3.0,
                    "message": "3 consecutive rounds aggregated nothing"})
    sink.on_run_end({"health": {"healthy": False, "n_alarms": 1,
                                "by_monitor": {"empty_cohort": 1},
                                "first_alarm_round": 9, "rounds_seen": 12}})
    out = capsys.readouterr().out
    assert "[health] ALARM r=  9 empty_cohort" in out
    assert "verdict: 1 ALARMS [empty_cohort=1] first at r=9" in out
    sink.on_run_end({"health": {"healthy": True, "rounds_seen": 5}})
    assert "verdict: HEALTHY (5 rounds, 0 alarms)" \
        in capsys.readouterr().out


def test_health_silent_on_healthy_baseline(mode_runs):
    for mode in ("full", "sketch"):
        v = mode_runs[mode][0].report.health_verdict()
        assert v == {"healthy": True, "n_alarms": 0, "by_monitor": {},
                     "first_alarm_round": None, "rounds_seen": ROUNDS}


# ---------------------------------------------------------------------------
# dashboard
# ---------------------------------------------------------------------------
def test_dashboard_renders_both_modes(mode_runs, blackout_run):
    for mode in ("full", "sketch"):
        frame = render_dashboard(mode_runs[mode][0].report)
        assert "participants" in frame and "outcomes" in frame
        assert "health        OK (run complete, 0 alarms)" in frame
        assert "acc=" in frame
    frame = render_dashboard(blackout_run.report)
    assert "ALARMS" in frame and "empty_cohort" in frame


def test_watch_once_over_live_and_truncated_logs(mode_runs, tmp_path):
    runner, _ = mode_runs["sketch"]
    buf = io.StringIO()
    watch(runner.cfg.telemetry_log, once=True, stream=buf)
    assert "participants" in buf.getvalue()
    # a mid-run log (no run_end yet, half-written last line) still renders
    lines = open(runner.cfg.telemetry_log).read().splitlines()
    live = tmp_path / "live.ndjson"
    live.write_text("\n".join(lines[:3]) + "\n" + lines[3][:10])
    buf = io.StringIO()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        watch(str(live), once=True, stream=buf)
    assert "participants" in buf.getvalue()
    assert "health        OK" in buf.getvalue()   # no verdict yet: still live


def test_dashboard_sink_paints_per_round(capsys):
    rep = SketchReport()
    from repro.obs import DashboardSink
    tel = Telemetry(sinks=[rep, DashboardSink(rep)],
                    sketch=SketchState(4, k=8))
    tel.start_run({"n_clients": 4, "rounds": 2})
    for r in (1, 2):
        tel.begin_round(r)
        for i in range(4):
            tel.client_outcome(r, i, AGGREGATED, upload_bytes=10.0)
        tel.gauge(r, "participants", 4.0)
        tel.end_round(r)
    tel.end_run()
    out = capsys.readouterr().out
    # one frame per round plus the final frame
    assert out.count("┌") == 3
