"""Device idle split among the program's host spans (``spans.py``) and the
four ``*_idle_ms`` readers: on hand-made events, on the recorded chip trace
(which holds no program span), and on a CPU trace of the program recorded
here."""
from pathlib import Path

import numpy as np
import pytest

import device_trace as dt
import run
import spans

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb"
IDLE_METRICS = ("local_update_idle_ms", "encode_idle_ms", "aggregate_idle_ms",
                "loop_idle_ms")
E = dt.Event


def _nested():
    host = [E("round", 0, 110), E("fl.round", 0, 100), E("PjitFunction(f)", 5, 8),
            E("phase.aggregate", 10, 60), E("phase.weight_solve", 20, 30),
            E("phase.accumulate", 30, 50), E("phase.flush", 35, 45)]
    ops = [E("fusion.1", 0, 15), E("fusion.2", 25, 40), E("fusion.3", 70, 100)]
    return host, ops


def _ctx(host, ops, lo, hi, rounds=1, n_devices=1):
    tr = dt.Trace(modules=[], ops=ops, host=host, n_devices=n_devices)
    return run.TraceCtx(trace=tr, lo=lo, hi=hi, window_s=(hi - lo) * 1e-9,
                        busy_s=dt.busy_ns(tr, lo, hi) / n_devices * 1e-9, rounds=rounds,
                        peaks=run.load_peaks("TPU v5 lite"), samples_per_update=1,
                        train_flops_per_sample=1.0, uplink_bytes=0.0, global_bytes=0.0)


def _readers(cell="resnet18-c100.sync-fp32"):
    found = {name: reader for name, _, reader in
             run.cell_metrics(run.load_benchmark(), cell)}
    return {name: found[name] for name in IDLE_METRICS}


def test_self_time_excludes_children():
    host, _ = _nested()
    got = spans.self_intervals(spans.program_spans(host, 0, 110))
    assert got == [("fl.round", 0, 10), ("phase.aggregate", 10, 20),
                   ("phase.weight_solve", 20, 30), ("phase.accumulate", 30, 35),
                   ("phase.flush", 35, 45), ("phase.accumulate", 45, 50),
                   ("phase.aggregate", 50, 60), ("fl.round", 60, 100)]
    # spans that overlap without nesting: the later one owns the overlap
    cross = [E("phase.a", 0, 10), E("phase.b", 5, 15)]
    assert spans.self_intervals(cross) == [("phase.a", 0, 5), ("phase.b", 5, 15)]


def test_idle_split_around_nested_spans():
    host, ops = _nested()
    assert spans.idle_intervals(ops, 0, 110) == [(15, 25), (40, 70), (100, 110)]
    by_span = spans.idle_by_span(host, ops, 0, 110)
    assert by_span == {"phase.aggregate": 5 + 10, "phase.weight_solve": 5,
                       "phase.flush": 5, "phase.accumulate": 5, "fl.round": 10,
                       spans.NO_SPAN: 10}
    by_layer = spans.layer_idle_ns(host, ops, 0, 110)
    assert by_layer == {"local_update": 0, "encode": 0, "aggregate": 30, "loop": 20}
    # no program span in the window: no reading
    assert spans.idle_by_span([E("round", 0, 110)], ops, 0, 110) is None


def test_readers_partition_the_idle_per_round():
    rng = np.random.default_rng(7)
    names = ["phase.local_update", "phase.uplink", "phase.aggregate", "phase.flush",
             "phase.network_draw", "phase.eval"]
    host, ops, t = [E("round", 0, 3000)], [], 0.0
    for r in range(3):                  # three rounds of nested program spans
        r0 = t
        t += 10
        for _ in range(8):
            outer = E(str(rng.choice(names)), t, t + rng.uniform(20, 80))
            host.append(outer)
            if rng.random() < 0.5:
                host.append(E("phase.flush", outer.start + 5, outer.end - 5))
            t = outer.end + rng.uniform(0, 15)
        host.append(E("fl.round", r0, t))
        t += 5
    for _ in range(60):
        s = rng.uniform(0, 2900)
        ops.append(E("fusion", s, s + rng.uniform(1, 40)))
    lo, hi = 0.0, t
    ctx = _ctx(host, ops, lo, hi, rounds=3)
    got = {name: reader.read(ctx) for name, reader in _readers().items()}
    idle_pct = run.load_module(run.HERE / "metrics" / "idle_pct.py").read(ctx)
    want = idle_pct / 100 * ctx.window_s / ctx.rounds * 1e3
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(want, rel=1e-9)
    assert got["aggregate_idle_ms"] > 0 and got["loop_idle_ms"] > 0


def test_readers_on_recorded_chip_trace():
    """The recorded v5e trace predates the program spans: no reading, and
    no error."""
    tr = dt.read(str(FIXTURE))
    lo, hi = dt.window(tr)
    ctx = _ctx(tr.host, tr.ops, lo, hi, rounds=3)
    for cell in ("resnet18-c100.sync-fp32", "vitb16-lora-c100.sync-fp32"):
        for name, reader in _readers(cell).items():
            assert reader.read(ctx) is None, name


def test_readers_on_program_cpu_trace(tmp_path):
    """A tiny sync FedAuto run traced as the harness traces its window, on
    the CPU (no device plane: the whole window is idle)."""
    import jax
    from repro.core.strategies import FedAuto
    from repro.fl.runtime import FFTConfig
    from repro.fl.toy import make_toy_runner

    cfg = FFTConfig(n_clients=4, k_selected=4, local_steps=1, batch_size=8, lr=0.05,
                    seed=3, eval_every=10 ** 9, failure_mode="scenario:table6")
    runner = make_toy_runner(cfg, n_samples=200, n_classes=4, image_size=8,
                             public_per_class=8, pretrain_steps=0, seed=3)
    runner.run(FedAuto(), rounds=0)
    runner.loop.run_round(1)                     # compiles outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for r in (2, 3):
            with jax.profiler.StepTraceAnnotation("round", step_num=r):
                runner.loop.run_round(r)
    finally:
        jax.profiler.stop_trace()
    tr = dt.read(str(next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))))
    lo, hi = dt.window(tr)
    ctx = _ctx(tr.host, tr.ops, lo, hi, rounds=2)
    got = {name: reader.read(ctx) for name, reader in _readers().items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(
        (ctx.window_s - ctx.busy_s) / ctx.rounds * 1e3, rel=1e-6)
    # the program's spans name nearly all of the window
    assert got["loop_idle_ms"] < 0.5 * sum(got.values())
