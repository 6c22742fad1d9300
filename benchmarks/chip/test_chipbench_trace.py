"""The trace reduction: busy union, idle share, per-module device time,
the breakdown, on hand-made events and on a small recorded chip trace."""
from pathlib import Path

import pytest

import device_trace as dt
import run

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb"


def _trace():
    E = dt.Event
    modules = [E("jit_local_update", 100, 400), E("jit_solve_weights", 500, 560),
               E("jit__float_reduce", 700, 800), E("jit_local_update", 900, 1300)]
    ops = [E("fusion.1", 100, 250), E("fusion.2", 240, 400),      # overlapping pair
           E("sort.3", 500, 560), E("custom-call.4", 700, 800), E("fusion.1", 900, 1300)]
    host = [E("round", 50, 1350), E("encode_upload", 420, 690), E("qp", 560, 700)]
    tr = dt.Trace(modules=modules, ops=ops, host=host, n_devices=1)
    dt._assign_modules(tr.ops, tr.modules)
    return tr


def test_union_busy_and_window():
    tr = _trace()
    assert dt.union([(0, 5), (3, 9), (12, 14)], 2, 13) == [(2, 9), (12, 13)]
    lo, hi = dt.window(tr)
    assert (lo, hi) == (50, 1350)
    assert dt.busy_ns(tr, lo, hi) == 300 + 60 + 100 + 400


def test_module_time_and_breakdown():
    tr = _trace()
    lo, hi = dt.window(tr)
    assert dt.module_time(tr, ("local_update",), lo, hi) == (700, 2)
    assert dt.module_time(tr, ("_float_reduce", "_quant_reduce"), lo, hi) == (100, 1)
    assert dt.module_time(tr, ("local_update",), 800, hi) == (400, 1)
    top = dt.top_ops(tr, lo, hi, k=2)
    assert top[0] == ["jit_local_update:fusion.1", pytest.approx(550e-9)]
    loop = dt.Event("%while.4 = (s32[]) while(...)", 890, 1310)
    assert dt.short(loop.name) == "while.4"
    assert [o.name for o in dt.leaf_ops(tr.ops + [loop])].count(loop.name) == 0
    gaps = dt.idle_gaps(tr, lo, hi, k=3)
    # 400..500 and 560..700 lie under host spans; 800..900 and the ends do not
    assert gaps[0] == ["qp", pytest.approx(140e-9)]
    assert gaps[1] == ["encode_upload", pytest.approx(100e-9)]


def test_metric_readers_on_events():
    tr = _trace()
    lo, hi = dt.window(tr)
    ctx = run.TraceCtx(trace=tr, lo=lo, hi=hi, window_s=(hi - lo) * 1e-9,
                       busy_s=dt.busy_ns(tr, lo, hi) * 1e-9, rounds=1,
                       peaks=run.load_peaks("TPU v5 lite"), samples_per_update=160,
                       train_flops_per_sample=1e3, uplink_bytes=8e4, global_bytes=1e4)
    bench = run.load_benchmark()
    got = {name: reader.read(ctx) for name, _, reader in
           run.cell_metrics(bench, "resnet18-c100.sync-fp32")}
    assert got["idle_pct"] == pytest.approx(100 * (1 - 860 / 1300))
    assert got["local_update_ms"] == pytest.approx(350e-6)
    assert got["qp_ms"] == pytest.approx(60e-6)
    assert got["round_mfu"] == pytest.approx(100 * 2 * 160 * 1e3 / (1300e-9 * 197e12))
    assert got["agg_roofline"] == pytest.approx(100 * (1e5 / 819e9) / 100e-9)
    empty = run.TraceCtx(trace=dt.Trace([], [], [], 1), lo=0, hi=1, window_s=1e-9, busy_s=0,
                         rounds=1, peaks=ctx.peaks, samples_per_update=1,
                         train_flops_per_sample=1, uplink_bytes=0, global_bytes=0)
    for name, _, reader in run.cell_metrics(bench, "resnet18-c100.sync-fp32"):
        if name != "idle_pct":
            assert reader.read(empty) is None, name


def test_recorded_chip_trace():
    """Three "round" steps of two small jitted programs on one TPU v5e."""
    tr = dt.read(str(FIXTURE))
    lo, hi = dt.window(tr)
    assert tr.n_devices == 1
    rounds = [h for h in tr.host if h.name == "round"]
    for m in tr.modules:      # on the host's clock, each program runs inside its round
        assert any(r.start <= m.start and m.end <= r.end for r in rounds), m
    ns, n = dt.module_time(tr, ("local_update",), lo, hi)
    assert n == 3 and ns > 0
    assert dt.module_time(tr, ("solve_weights",), lo, hi)[1] == 3
    busy = dt.busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    assert ns <= busy + 1e3 * n      # module spans hold their ops
    gaps = dt.idle_gaps(tr, lo, hi)
    assert gaps and gaps[0][1] > 1e-3          # the 2 ms host sleep in every round
    assert abs(sum(g for _, g in dt.idle_gaps(tr, lo, hi, k=10 ** 6)) - (hi - lo - busy) * 1e-9) < 1e-9
