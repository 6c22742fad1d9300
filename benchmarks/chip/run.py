#!/usr/bin/env python3
"""On-chip benchmark of the federated round: one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed N --seconds S --trace 0|1

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``
and its module) and a traffic mix (``traffic/<name>.json``); the
configuration names its task (``"task"``: ``tasks/<name>.py``, with ``-``
read as ``_``), which makes the job, its histograms and the reference's loss.
The run makes the data and the weights from ``--seed`` on the device, builds
the program's ``FFTRunner``, makes the run's loop with ``FFTRunner.run(strategy,
rounds=0)`` and drives it with ``run_round`` through the traffic's warm-up
rounds (set-up), then on until ``--seconds`` have passed (the window). ``round_s`` is the window's wall time
over the rounds it completed, ending after ``block_until_ready`` on the
global parameters; ``setup_s`` runs from process start to the window.

With ``--trace 1`` the window runs under the JAX profiler, for the rounds
that fill ``TRACE_SECONDS`` of it, and the result holds the per-layer
metrics (``metrics/<name>.py``) and a breakdown instead.

After the window, and after the device's peak memory has been read, the plain
reference (``reference.py``) follows the first three warm-up rounds from the
same weights and inputs; ``correct`` holds when every compared number is
within its limit (``limits/<cell>.json``). The last stdout line is one JSON
object. Without a TPU, or with fewer chips than the cell asks for, the run
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import traceback
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 3.0     # a traced window closes after the round that passes this


# ------------------------------------------------------------------ discovery
def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


_MODULES: Dict[Path, Any] = {}


def load_module(path: Path):
    """A configuration's or metric's module, loaded once per process."""
    path = path.resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def find_cell(bench: Dict[str, Any], name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


def load_config(entry: Dict[str, Any], root: Path = ROOT):
    """(sizes, module) of a configuration: its JSON file and the module the
    file names beside it."""
    path = root / entry["file"]
    sizes = json.loads(path.read_text())
    return sizes, load_module(path.parent / sizes["module"])


def cell_metrics(bench: Dict[str, Any], cell_name: str, here: Path = HERE):
    """[(name, unit, reader module)] of the per-layer metrics this cell reports."""
    out = []
    for m in bench["per_layer"]:
        if cell_name in m.get("workloads", [cell_name]):
            out.append((m["name"], m["unit"], load_module(here / "metrics" / f"{m['name']}.py")))
    return out


def load_task(name: str, here: Path = HERE):
    """The module of the task a configuration names: ``tasks/<name>.py``,
    with ``-`` read as ``_``; an unknown name lists the known ones."""
    known = sorted(p.stem.replace("_", "-") for p in (here / "tasks").glob("*.py"))
    if name not in known:
        raise ValueError(f"unknown task {name!r} (known: {known})")
    return load_module(here / "tasks" / f"{name.replace('-', '_')}.py")


def load_limits(cell_name: str, here: Path = HERE) -> Dict[str, float]:
    return json.loads((here / "limits" / f"{cell_name}.json").read_text())["limits"]


def load_peaks(kind: str, here: Path = HERE) -> Dict[str, float]:
    kinds = json.loads((here / "peaks.json").read_text())["kinds"]
    if kind not in kinds:
        raise KeyError(f"device kind {kind!r} is not in peaks.json (known: {sorted(kinds)})")
    return kinds[kind]


_INITS: Dict[tuple, tuple] = {}


def init_fn(mod, sizes):
    """The configuration's weight init as one jitted call, one per
    configuration in a process."""
    key = (id(mod), json.dumps(sizes, sort_keys=True))
    if key not in _INITS:
        import jax
        _INITS[key] = (mod, jax.jit(lambda k: mod.init(sizes, k)))
    return _INITS[key][1]


# -------------------------------------------------------------- compile clock
class CompileCounter:
    """Backend compilations and persistent-cache hits, from JAX's own
    monitoring events. Registered once per process."""
    _instance = None

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def snapshot(self):
        return self.compiles, self.cache_hits


# --------------------------------------------------------------- the run
class Recorder:
    """Stands in for the runner's jitted local update during the warm-up
    rounds: calls it unchanged and keeps each call's inputs for the
    reference."""

    def __init__(self, runner):
        self.runner = runner
        self.inner = runner._local_update
        self.calls: List[tuple] = []
        runner._local_update = self

    def __call__(self, t, t_global, corr, x, y, key, lr, mu):
        self.calls.append((t_global, x, y, key, float(lr), float(mu)))
        return self.inner(t, t_global, corr, x, y, key, lr, mu)

    def remove(self):
        self.runner._local_update = self.inner

    def rounds(self):
        """[(t_global, [Update])] per round, in order, from the calls."""
        import reference
        runner = self.runner
        out: List[list] = []
        for t_global, x, y, key, lr, mu in self.calls:
            if mu != 0.0:
                raise RuntimeError("the reference models plain SGD (mu = 0)")
            if not out or out[-1][0] is not t_global:
                out.append([t_global, [], lr])
            client = next((i for i, cx in enumerate(runner.client_x) if cx is x), None)
            if client is not None:
                role = "client"
            else:
                role, client = ("server" if x is runner.public_x else "comp"), -1
            out[-1][1].append(reference.Update(role, client, x, y, key))
        return out


@contextlib.contextmanager
def fallback_counter(counts: List[int]):
    """Counts payloads the stream accumulator could not fuse."""
    from repro.fl.comm import stream
    cls = stream.StreamAccumulator
    inner = cls.total

    def total(self):
        counts[0] += self.n_fallback
        return inner(self)

    cls.total = total
    try:
        yield
    finally:
        cls.total = inner


@dataclasses.dataclass
class TraceCtx:
    """What a per-layer metric reader sees."""
    trace: Any
    lo: float
    hi: float
    window_s: float
    busy_s: float
    rounds: int
    peaks: Dict[str, float]
    samples_per_update: int
    train_flops_per_sample: float
    uplink_bytes: float
    global_bytes: float

    def module_time(self, names):
        import device_trace
        return device_trace.module_time(self.trace, names, self.lo, self.hi)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, bench: Optional[Dict[str, Any]] = None,
             sizes: Optional[Dict[str, Any]] = None,
             traffic: Optional[Dict[str, Any]] = None,
             plant: Optional[Callable[[Any], None]] = None,
             keep: Optional[Dict[str, Any]] = None,
             log: Callable[[str], None] = print) -> Optional[Dict[str, Any]]:
    """One run of one cell; returns the result record, or None without a
    chip. ``rehearsal`` (tests only) skips the look for a chip, takes the
    given ``sizes``/``traffic`` and reports no metric; ``plant`` breaks the
    built runner, for tests that must see ``correct`` come out false;
    ``keep``, where given, receives what the reference was given and what it
    gave (for ``probe.py``)."""
    import jax
    import numpy as np

    bench = bench or load_benchmark()
    cell, centry = find_cell(bench, workload)
    devs = jax.devices()
    if not rehearsal and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        print(f"run.py: JAX found {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind}); {workload} needs {cell['chips']} TPU chip(s). "
              "Nothing was run.", file=sys.stderr)
        return None

    import reference
    import workload as traffic_gen
    from repro.core import strategies
    from repro.data.synthetic import Dataset
    from repro.fl.runtime import FFTConfig, FFTRunner
    from repro.launch.compile_cache import enable_compile_cache

    if not rehearsal:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter.get()
    csizes, mod = load_config(centry)
    sizes = sizes or csizes
    task = load_task(sizes["task"])
    traffic = traffic or traffic_gen.load_traffic(cell["traffic"])
    fft = traffic["fft"]
    warm = int(traffic["warmup_rounds"])
    if warm < 3:
        raise ValueError("the reference follows the first three rounds: warmup_rounds >= 3")

    # ---- set-up: data and weights from the seed, the runner, the warm-up
    key = traffic_gen.seed_key(seed)
    k_data, k_model = jax.random.split(key)
    job = task.make_job(traffic, sizes, k_data)
    base, w0 = init_fn(mod, sizes)(k_model)
    prog = mod.program(sizes)
    cfg = FFTConfig(**fft, seed=int(traffic["network_seed"]), eval_every=10 ** 9,
                    eval_batch=len(job.test.y), telemetry=False)
    ds = lambda s: Dataset(x=s.x, y=s.y, n_classes=job.n_classes)
    runner = FFTRunner(cfg, lambda _k: base, prog["apply"], ds(job.public),
                       job.client_indices, ds(job.private), ds(job.test),
                       lora_cfg=prog["lora"])
    runner.global_params = w0
    if plant is not None:
        plant(runner)
    strategy = getattr(strategies, traffic["strategy"])()
    rec = Recorder(runner)
    fallbacks = [0]
    with fallback_counter(fallbacks):
        # run() with no rounds makes the per-run resets and the loop; the
        # warm-up and the window drive that loop, so no evaluation runs
        runner.run(strategy, rounds=0)
        loop = runner.loop
        for r in range(1, warm + 1):
            loop.run_round(r)
        rec.remove()
        warm_fallbacks = fallbacks[0]
        rounds_in = rec.rounds()
        prog_ws = [t for t, _, _ in rounds_in[1:4]]
        if len(prog_ws) < 3:
            prog_ws.append(runner.global_params)
        jax.block_until_ready(runner.global_params)

        # ---- the window
        c0 = counter.snapshot()
        up0 = runner.comm.total_uplink_bytes
        trace_path = None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0        # keeps the host's overhead low
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
            seconds = min(seconds, TRACE_SECONDS)
        failed, attempted, round_times, r = 0, 0, [], warm
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        while True:
            r += 1
            attempted += 1
            f0, ts = fallbacks[0], time.perf_counter()
            try:
                with (jax.profiler.StepTraceAnnotation("round", step_num=r) if trace
                      else contextlib.nullcontext()):
                    loop.run_round(r)
            except Exception:                     # a round that raises is failed
                traceback.print_exc()
                failed += 1
                break
            round_times.append(time.perf_counter() - ts)
            failed += fallbacks[0] > f0
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(runner.global_params)
        t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            trace_path = next(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"), None)
    window_s = t1 - t0
    c1 = counter.snapshot()
    uplink_bytes = runner.comm.total_uplink_bytes - up0
    participants = loop.participants_per_round[warm:warm + len(round_times)]
    n_upd = sum(participants) + len(participants)      # clients + the server
    samples = n_upd * fft["local_steps"] * fft["batch_size"]
    global_bytes = sum(4 * l.size for l in jax.tree.leaves(runner.global_params))
    dev = devs[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    log(f"window: {len(round_times)} rounds in {window_s:.4f} s after {warm} warm-up "
        f"rounds; participants {participants}; {samples / window_s:.1f} samples/s")
    log("round seconds (host clock, not synced per round): "
        + " ".join(f"{x:.4f}" for x in round_times))
    log(f"compilations in the window: {c1[0] - c0[0]} backend compiles, "
        f"{c1[1] - c0[1]} persistent-cache loads; set-up compiled "
        f"{c0[0]} programs in {counter.compile_s:.1f} s and loaded {c0[1]} from the cache; "
        f"fallback payloads warm-up {warm_fallbacks}, window {fallbacks[0] - warm_fallbacks}")

    metrics: Dict[str, Any] = {}
    breakdown = None
    if trace and trace_path is not None and not rehearsal:
        import device_trace
        tr = device_trace.read(str(trace_path))
        lo, hi = device_trace.window(tr) or (0.0, 0.0)
        busy = device_trace.busy_ns(tr, lo, hi) / tr.n_devices
        ctx = TraceCtx(trace=tr, lo=lo, hi=hi, window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                       rounds=len(round_times), peaks=load_peaks(dev.device_kind),
                       samples_per_update=fft["local_steps"] * fft["batch_size"],
                       train_flops_per_sample=task.flops_per_sample(mod, sizes, traffic),
                       uplink_bytes=uplink_bytes, global_bytes=global_bytes)
        for name, unit, reader in cell_metrics(bench, workload):
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        breakdown = {"device_ops": device_trace.top_ops(tr, lo, hi),
                     "idle_gaps": device_trace.idle_gaps(tr, lo, hi)}
        del tr, ctx
    elif not trace and not rehearsal:
        by_name = {"round_s": window_s / max(len(round_times), 1), "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": by_name[m["name"]], "unit": m["unit"]}

    # ---- correctness, once the program's state is freed
    hists = task.histograms(job)
    ref_rounds = [reference.Round(updates=u, lr=lr) for _, u, lr in rounds_in[:3]]
    public_y = np.asarray(job.public.y)
    del runner, loop, strategy, rec
    gc.collect()
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref_ws = reference.follow(mod, sizes, task, base, w0, ref_rounds, server_hist=hists[0],
                                  client_hists=hists[1], public_y=public_y,
                                  steps=fft["local_steps"], batch=fft["batch_size"])
    numbers = reference.compare(w0, prog_ws, ref_ws)
    if keep is not None:
        keep.update(mod=mod, sizes=sizes, task=task, base=base, w0=w0, rounds=ref_rounds,
                    hists=hists, public_y=public_y, fft=fft, prog_ws=prog_ws,
                    ref_ws=ref_ws, numbers=numbers)
    log(f"reference: 3 rounds in {time.perf_counter() - t_ref:.1f} s; "
        f"{numbers['leaves_left_out']} leaves left out")
    limits = load_limits(workload)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = (failed == 0 and len(round_times) > 0 and
               all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                   for c in checks.values()))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
