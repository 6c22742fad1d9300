"""``correct`` on the CPU at a tiny size: the timed path agrees with the plain
reference, and comes out not correct when the timed path is broken
underneath or the reference runs in bfloat16 (the control). The image task's
job and the reference's numbers at that size are pinned.

The harness's look for a chip is skipped (``rehearsal``); everything else of
a run is driven: data and weights from the seed, the program's runner, the
warm-up rounds, a one-round window, the reference and the limits of the
cell."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import workload

CELL = "resnet18-c100.sync-fp32"
SEED = 2 ** 31 + 11
SIZES = None


def _tiny():
    entry = {c["name"]: c for c in run.load_benchmark()["configs"]}["resnet18-c100"]
    sizes, _ = run.load_config(entry)
    sizes = dict(sizes, image_size=8, num_classes=10, stages=[1, 1], widths=[8, 16],
                 groups=[4, 4])
    traffic = copy.deepcopy(workload.load_traffic("sync-fp32"))
    traffic["fft"].update(n_clients=4, k_selected=4, local_steps=2, batch_size=8)
    traffic["data"].update(private_samples=160, public_per_class=2, test_samples=16,
                           classes_per_group=10)
    return sizes, traffic


def _run(plant=None, keep=None):
    sizes, traffic = _tiny()
    return run.run_cell(CELL, SEED, 0.0, False, rehearsal=True, sizes=sizes,
                        traffic=traffic, plant=plant, keep=keep, log=lambda s: None)


def test_timed_path_agrees_and_the_control_does_not():
    keep = {}
    res = _run(keep=keep)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    limits = run.load_limits(CELL)
    ws = reference.follow(keep["mod"], keep["sizes"], keep["task"], keep["base"], keep["w0"],
                          keep["rounds"],
                          server_hist=keep["hists"][0], client_hists=keep["hists"][1],
                          public_y=keep["public_y"], steps=2, batch=8, dtype=jnp.bfloat16)
    control = reference.compare(keep["w0"], ws, keep["ref_ws"])
    assert any(control[k] > limits[k] for k in limits), control


# The tiny run's job and numbers, from the harness before the task moved out
# of it: the task must give the same job and the reference the same rounds.
PINNED = {
    "server_hist": [2] * 10,
    "client_hists": [[4] * 10] * 4,
    "x_shape": [196, 192],
    "x_sumsq": 71012.10457179809,
    "x_wsum": 258.33910454058423,
    "change_1": 6.1637376071912496e-06,
    "change_3": 3.7042671835001356e-06,
    "ref_change_norms": [0.12299805940537338, 0.21985883892780042, 0.3017655455550273],
}


def test_image_task_is_unchanged():
    sizes, traffic = _tiny()
    assert sizes["task"] == "image-classes"
    task = run.load_task(sizes["task"])
    job = task.make_job(traffic, sizes, jax.random.split(workload.seed_key(SEED))[0])
    server, clients = task.histograms(job)
    assert server.tolist() == PINNED["server_hist"]
    assert clients.tolist() == PINNED["client_hists"]
    x = np.concatenate([np.asarray(s.x, np.float64).reshape(len(s.y), -1)
                        for s in (job.public, job.private, job.test)])
    weights = np.arange(x.size, dtype=np.float64).reshape(x.shape) % 7 - 3
    assert list(x.shape) == PINNED["x_shape"]
    assert np.sum(x * x) == pytest.approx(PINNED["x_sumsq"], rel=1e-6)
    assert np.sum(x * weights) == pytest.approx(PINNED["x_wsum"], rel=1e-6)

    keep = {}
    assert _run(keep=keep)["correct"]
    for name in ("change_1", "change_3"):
        assert keep["numbers"][name] == pytest.approx(PINNED[name], rel=1e-6)
    norms = [float(np.sqrt(sum(np.sum((np.asarray(a, np.float64) - np.asarray(b)) ** 2)
                               for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(keep["w0"])))))
             for w in keep["ref_ws"]]
    assert norms == pytest.approx(PINNED["ref_change_norms"], rel=1e-6)


def _wrap_local_update(runner, fn):
    inner = runner._local_update
    runner._local_update = lambda *a: fn(inner, runner, *a)


def _unchanged(monkeypatch):
    from repro.core import strategies
    monkeypatch.setattr(strategies.FedAuto, "aggregate", lambda self, ctx: ctx.global_params)


def _half_minibatch(monkeypatch):
    def plant(runner):
        runner.cfg.batch_size //= 2
        runner._build_jits()
    return plant


def _half_cohort(monkeypatch):
    from repro.core import strategies
    inner = strategies._stream_accumulate

    def half(ctx, dense, packed):
        kept = packed[::2]
        scale = sum(w for w, _ in packed) / sum(w for w, _ in kept)
        return inner(ctx, dense, [(w * scale, p) for w, p in kept])

    monkeypatch.setattr(strategies, "_stream_accumulate", half)


def _negated_upload(monkeypatch):
    def negate(inner, runner, t, tg, corr, x, y, key, lr, mu):
        out = inner(t, tg, corr, x, y, key, lr, mu)
        if x is runner.client_x[0]:
            out = jax.tree.map(lambda a, g: 2 * g - a, out, tg)
        return out
    return lambda runner: _wrap_local_update(runner, negate)


def _bf16_local_update(monkeypatch):
    def low(inner, runner, t, tg, corr, x, y, key, lr, mu):
        bf = lambda tree: jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)
        out = inner(bf(t), bf(tg), corr, x.astype(jnp.bfloat16), y, key, lr, mu)
        return jax.tree.map(lambda a: a.astype(jnp.float32), out)
    return lambda runner: _wrap_local_update(runner, low)


def _bf16_aggregation(monkeypatch):
    from repro.core import strategies
    inner = strategies._stream_accumulate

    def low(ctx, dense, packed):
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype),
                            inner(ctx, dense, packed))

    monkeypatch.setattr(strategies, "_stream_accumulate", low)


@pytest.mark.parametrize("fault", [_unchanged, _half_minibatch, _half_cohort,
                                   _negated_upload, _bf16_local_update, _bf16_aggregation])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    plant = fault(monkeypatch)
    res = _run(plant=plant)
    assert not res["correct"], res["checks"]
