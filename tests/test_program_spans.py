"""Program spans: the ``phase.*`` timers and ``fl.round`` as profiler
annotations.

A tiny sync FedAuto run (streaming aggregation, one class held by no
client, so the compensatory model trains every round) is traced by the JAX
profiler through ``FFTConfig.telemetry_trace``: with telemetry off the spans
are there, with their ids, nested as the calls nest; with telemetry on each
round's profiler self time per phase equals the phase's gauge; and the
trace directory holds a Perfetto JSON with the spans.
"""
import dataclasses
import gzip
import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.strategies import STRATEGIES
from repro.fl.runtime import FFTConfig
from repro.fl.toy import make_toy_runner
from repro.obs import NULL_TELEMETRY, Telemetry

BASE = dict(n_clients=6, k_selected=4, local_steps=2, batch_size=8, lr=0.05,
            seed=3, eval_every=2, deadline_s=30.0,
            failure_mode="scenario:bursty_handover")
TOY = dict(n_samples=300, n_classes=4, image_size=8, public_per_class=10,
           pretrain_steps=0, seed=3)
ROUNDS = 3
EXPECTED = {"fl.round", "phase.local_update", "phase.uplink",
            "phase.aggregate", "phase.weight_solve", "phase.accumulate",
            "phase.flush", "phase.compensatory"}
IDS = {"fl.round": {"round"}, "phase.local_update": {"round"},
       "phase.uplink": {"client"}, "phase.aggregate": {"round"},
       "phase.weight_solve": {"round"}, "phase.accumulate": {"round"},
       "phase.compensatory": {"round"}, "phase.network_draw": {"round"},
       "phase.downlink": set(), "phase.eval": set(),
       "phase.flush": {"family", "payloads"}}


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict

    def holds(self, other: "Span") -> bool:
        return (self is not other and self.start <= other.start
                and other.end <= self.end)


def _read_spans(trace_dir) -> list:
    path = next(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         {k: v for k, v in e.stats})
                    for e in line.events
                    if e.name == "fl.round" or e.name.startswith("phase.")]
    return sorted(out, key=lambda s: (s.start, -s.end))


def _parent(span, spans):
    """The innermost program span that holds ``span``."""
    holders = [s for s in spans if s.holds(span)]
    return min(holders, key=lambda s: s.end - s.start) if holders else None


def _enclosing_round(span, spans):
    return next((s for s in spans if s.name == "fl.round" and
                 (s is span or s.holds(span))), None)


def _traced_run(trace_dir, telemetry):
    cfg = FFTConfig(**BASE, telemetry=telemetry,
                    telemetry_trace=str(trace_dir))
    runner = make_toy_runner(cfg, **TOY)
    runner.run(STRATEGIES["fedauto"](), rounds=ROUNDS)
    return runner, _read_spans(trace_dir)


@pytest.fixture(scope="module")
def off_run(tmp_path_factory):
    return _traced_run(tmp_path_factory.mktemp("spans_off"), False)


@pytest.fixture(scope="module")
def on_run(tmp_path_factory):
    return _traced_run(tmp_path_factory.mktemp("spans_on"), True)


def test_spans_present_with_telemetry_off(off_run):
    runner, spans = off_run
    assert runner.report is None                 # telemetry stayed off
    assert EXPECTED <= {s.name for s in spans}
    rounds = [s for s in spans if s.name == "fl.round"]
    assert [s.stats["round"] for s in rounds] == list(range(1, ROUNDS + 1))
    # clients + the server + the compensatory model, every round
    for rnd, n in zip(rounds, runner.loop.participants_per_round):
        updates = [s for s in spans
                   if s.name == "phase.local_update" and rnd.holds(s)]
        uploads = [s for s in spans if s.name == "phase.uplink" and rnd.holds(s)]
        assert len(updates) == n + 2 and len(uploads) == n


def test_span_ids(off_run):
    _, spans = off_run
    for s in spans:
        assert set(s.stats) == IDS[s.name], s
        if "round" in s.stats:
            rnd = _enclosing_round(s, spans)
            assert rnd is not None and rnd.stats["round"] == s.stats["round"]
    clients = {s.stats["client"] for s in spans if s.name == "phase.uplink"}
    assert clients <= set(range(BASE["n_clients"]))
    for s in spans:
        if s.name == "phase.flush":
            assert s.stats["family"] == "fp32"
            acc = _parent(s, spans)
            uploads = [u for u in spans if u.name == "phase.uplink"
                       and _enclosing_round(u, spans)
                       is _enclosing_round(s, spans)]
            assert acc.name == "phase.accumulate"
            assert s.stats["payloads"] == len(uploads)


def test_span_nesting(off_run):
    _, spans = off_run
    parents = {}
    for s in spans:
        p = _parent(s, spans)
        if s.name in ("fl.round", "phase.eval"):
            # evaluation follows the round, in the loop's own bookkeeping
            assert p is None, s
            continue
        assert p is not None, s              # every other phase in a round
        parents.setdefault(s.name, set()).add(p.name)
    assert parents["phase.uplink"] == {"fl.round"}
    assert parents["phase.aggregate"] == {"fl.round"}
    assert parents["phase.compensatory"] == {"phase.aggregate"}
    assert parents["phase.weight_solve"] == {"phase.aggregate"}
    assert parents["phase.accumulate"] == {"phase.aggregate"}
    assert parents["phase.flush"] == {"phase.accumulate"}
    # clients and the server in the round, the compensatory model under its
    # own phase
    assert parents["phase.local_update"] == {"fl.round", "phase.compensatory"}


def _self_ns(spans):
    """Self time of each span: its length minus its direct children's."""
    own = {id(s): s.end - s.start for s in spans}
    for s in spans:
        p = _parent(s, spans)
        if p is not None:
            own[id(p)] -= s.end - s.start
    return own


def test_profiler_self_times_match_phase_gauges(on_run):
    runner, spans = on_run
    own = _self_ns(spans)
    starts = sorted((s.start, s.stats["round"]) for s in spans
                    if s.name == "fl.round")
    per_round = {}
    for s in spans:
        if s.name == "fl.round":
            continue
        # a phase belongs to the last round begun before it (the last
        # round's evaluation runs after its fl.round closes)
        rnd = max(r for t, r in starts if t <= s.start)
        bucket = per_round.setdefault(rnd, {})
        bucket[s.name] = bucket.get(s.name, 0.0) + own[id(s)] * 1e-9
    assert len(runner.report.rounds) == ROUNDS
    for rec in runner.report.rounds:
        gauges = {k: v for k, v in rec["gauges"].items()
                  if k.startswith("phase.")}
        got = per_round[rec["round"]]
        assert set(gauges) == set(got)
        for name, want in gauges.items():
            assert got[name] == pytest.approx(want, abs=2e-3), name


def test_telemetry_trace_writes_perfetto_json(off_run):
    runner, _ = off_run
    path = next(Path(runner.cfg.telemetry_trace).glob(
        "plugins/profile/*/perfetto_trace.json.gz"))
    doc = json.loads(gzip.decompress(path.read_bytes()))
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    names = {e.get("name") for e in events}
    assert EXPECTED <= names
    rounds = [e for e in events if e.get("name") == "fl.round"]
    assert sorted(int(e["args"]["round"]) for e in rounds) == \
        list(range(1, ROUNDS + 1))


def test_null_timer_is_only_a_span():
    """Telemetry off: the timer is the program span and nothing else."""
    span = NULL_TELEMETRY.timer("phase.x", round=1)
    assert isinstance(span, TraceAnnotation)
    with span:
        pass
    tel = Telemetry()
    with tel.timer("phase.x", round=1, client=2):
        pass
    assert set(tel.timers_s) == {"phase.x"}
