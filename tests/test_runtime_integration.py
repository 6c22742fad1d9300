"""Integration: every strategy executes rounds end-to-end on a micro FFT
problem (8 clients, 8×8 images) under mixed failures, and the global model
stays finite + above-chance. Also covers LoRA-mode FFT with FedEx-LoRA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategies import (STRATEGIES, CentralizedPublic, FedAuto,
                                   FedAvg, FedAWE, FedExLoRA, FedLAW, FedProx,
                                   Scaffold, TFAggregation)
from repro.data.synthetic import fft_split, make_dataset, train_test_split
from repro.fl.lora import LoRAConfig
from repro.fl.partition import partition
from repro.fl.runtime import FFTConfig, FFTRunner
from repro.models.vision import make_model


def _setup(failure_mode="mixed", k=8, lora=False, seed=0):
    ds = make_dataset(1200, n_classes=4, image_size=8, channels=1, noise=0.8,
                      seed=seed)
    train, test = train_test_split(ds, 200, seed=seed + 1)
    pub, priv = fft_split(train, public_per_class=25, seed=seed)
    parts, _ = partition("group_classes", priv.y, 8, 4, classes_per_group=1,
                         group_size=2, seed=seed)
    name = "vit" if lora else "cnn"
    init_fn, apply_fn = make_model(name, 4, 8, 1)
    cfg = FFTConfig(n_clients=8, k_selected=k, local_steps=3, batch_size=16,
                    lr=0.05 if not lora else 0.02, failure_mode=failure_mode,
                    seed=seed, eval_every=100, model_bytes=0.2e6,
                    tx_delay_s=0.8)
    lcfg = LoRAConfig(rank=4, match=lambda p: "qkv/w" in p) if lora else None
    runner = FFTRunner(cfg, init_fn, apply_fn, pub, parts, priv, test,
                       lora_cfg=lcfg, pretrain_steps=30)
    return runner


@pytest.fixture(scope="module")
def runner():
    return _setup()


@pytest.mark.parametrize("strategy_cls", [FedAvg, lambda: FedProx(0.01),
                                          FedAuto, CentralizedPublic,
                                          Scaffold, FedLAW, FedAWE,
                                          TFAggregation])
def test_strategy_runs_and_stays_finite(runner, strategy_cls):
    g0 = runner.global_params
    runner.rng = np.random.default_rng(42)
    strat = strategy_cls() if callable(strategy_cls) else strategy_cls
    hist = runner.run(strat, rounds=4)
    acc = hist[-1]
    assert 0.0 <= acc <= 1.0
    for leaf in jax.tree.leaves(runner.global_params):
        assert bool(np.all(np.isfinite(np.asarray(leaf, np.float32)))), strat.name
    runner.global_params = g0


def test_fedauto_learns_above_chance(runner):
    g0 = runner.global_params
    runner.rng = np.random.default_rng(7)
    hist = runner.run(FedAuto(), rounds=10)
    assert hist[-1] > 0.4            # 4 classes, chance = 0.25
    runner.global_params = g0


def test_fedauto_ablations_run(runner):
    for m1, m2 in [(True, False), (False, True), (False, False)]:
        g0 = runner.global_params
        runner.rng = np.random.default_rng(3)
        hist = runner.run(FedAuto(use_module1=m1, use_module2=m2), rounds=3)
        assert 0 <= hist[-1] <= 1
        runner.global_params = g0


def test_partial_participation():
    r = _setup(k=4)
    hist = r.run(FedAuto(), rounds=4)
    assert 0 <= hist[-1] <= 1


def test_lora_mode_with_fedex():
    r = _setup(lora=True)
    for strat in [FedAvg(), FedExLoRA(), FedAuto()]:
        g0 = r.global_params
        r.rng = np.random.default_rng(5)
        hist = r.run(strat, rounds=3)
        assert 0 <= hist[-1] <= 1
        r.global_params = g0


def test_resource_opt_modes_construct():
    for mode in ["joint", "per_standard"]:
        ds = make_dataset(400, n_classes=4, image_size=8, channels=1, seed=0)
        train, test = train_test_split(ds, 100)
        pub, priv = fft_split(train, public_per_class=10)
        parts, _ = partition("iid", priv.y, 8, 4)
        init_fn, apply_fn = make_model("cnn", 4, 8, 1)
        cfg = FFTConfig(n_clients=8, k_selected=8, local_steps=2,
                        batch_size=8, failure_mode="transient",
                        resource_opt=mode, seed=0, model_bytes=0.2e6)
        r = FFTRunner(cfg, init_fn, apply_fn, pub, parts, priv, test)
        hist = r.run(FedAvg(), rounds=2)
        assert 0 <= hist[-1] <= 1


# --- the local update's correction term ------------------------------------
@pytest.fixture(scope="module")
def lora_runner():
    return _setup(lora=True)


def _replay_key(r, fn):
    """Runs ``fn`` and returns its result with the key ``_next_key`` handed
    out inside it, by replaying the runner's key stream."""
    k0 = r._key
    out = fn()
    r._key = k0
    return out, r._next_key()


def _plain_update(r, t, tg, corr, x, y, key, lr, mu):
    """E SGD steps written out step by step: grad + mu (t - tg) + corr."""
    n, bs = x.shape[0], r.cfg.batch_size
    f32 = lambda a: a.astype(jnp.float32)
    for k in jax.random.split(key, r.cfg.local_steps):
        idx = jax.random.randint(k, (bs,), 0, n)
        g = jax.grad(r.loss_on)(t, x[idx], y[idx])
        g = jax.tree.map(lambda gg, p, pg, c: f32(gg) + mu * (f32(p) - f32(pg)) + c,
                         g, t, tg, corr)
        t = jax.tree.map(lambda p, gg: (f32(p) - lr * gg).astype(p.dtype), t, g)
    return t


def _assert_tree_close(a, b, rtol, atol=0.0):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=rtol,
                                   atol=atol)


@pytest.mark.parametrize("lora", [False, True], ids=["full", "lora"])
@pytest.mark.parametrize("mu", [0.0, 0.01], ids=["sgd", "prox"])
def test_run_local_without_correction_equals_zero_correction(
        runner, lora_runner, lora, mu):
    r = lora_runner if lora else runner
    tg, x, y = r.global_params, r.client_x[0], r.client_y[0]
    out, key = _replay_key(r, lambda: r.run_local(tg, x, y, 1, mu=mu))
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tg)
    ref = r._local_update(tg, tg, zeros, x, y, key, r.lr(1), mu)
    _assert_tree_close(out, ref, rtol=1e-6)
    if mu:                      # the proximal term reaches the elided path
        plain = r.run_local(tg, x, y, 1)
        assert any(not np.array_equal(u, v) for u, v in
                   zip(jax.tree.leaves(out), jax.tree.leaves(plain)))


def test_run_local_correction_is_applied(runner):
    tg, x, y = runner.global_params, runner.client_x[1], runner.client_y[1]
    corr = jax.tree.map(lambda a: jnp.full(a.shape, 0.3, jnp.float32), tg)
    out, key = _replay_key(runner, lambda: runner.run_local(tg, x, y, 1,
                                                            mu=0.01, corr=corr))
    bare = runner._local_update(tg, tg, None, x, y, key, runner.lr(1), 0.01)
    assert all(not np.allclose(u, v) for u, v in
               zip(jax.tree.leaves(out), jax.tree.leaves(bare)))
    # eager steps round apart from the fused scan near zero: hence atol
    plain = _plain_update(runner, tg, tg, corr, x, y, key, runner.lr(1), 0.01)
    _assert_tree_close(out, plain, rtol=1e-5, atol=1e-7)


def _spy_corrections(r, seen):
    inner = r._local_update

    def spy(t, t_global, corr, x, y, key, lr, mu):
        seen.append(corr)
        return inner(t, t_global, corr, x, y, key, lr, mu)
    r._local_update = spy
    return inner


@pytest.mark.parametrize("strategy_cls", [FedAuto, lambda: FedProx(0.01),
                                          Scaffold],
                         ids=["fedauto", "fedprox", "scaffold"])
def test_local_update_sees_correction_only_under_scaffold(runner,
                                                          strategy_cls):
    g0, seen = runner.global_params, []
    runner.rng = np.random.default_rng(11)
    inner = _spy_corrections(runner, seen)
    try:
        runner.run(strategy_cls(), rounds=2)
    finally:
        runner._local_update, runner.global_params = inner, g0
    trees = [c for c in seen if c is not None]
    if strategy_cls is Scaffold:
        # every client's update is corrected; the server's, one in each of
        # the 2 rounds, is not
        n_server = 2
        assert len(trees) == len(seen) - n_server > 0
        assert all(jax.tree.structure(c) == jax.tree.structure(g0)
                   for c in trees)
    else:
        assert seen and not trees


@pytest.mark.parametrize("strategy_cls", [FedAuto, Scaffold],
                         ids=["fedauto", "scaffold"])
def test_local_update_counters(runner, strategy_cls):
    g0, seen = runner.global_params, []
    runner.rng = np.random.default_rng(13)
    inner = _spy_corrections(runner, seen)
    runner.cfg.telemetry = True
    try:
        runner.run(strategy_cls(), rounds=2)
    finally:
        runner.cfg.telemetry = False
        runner._local_update, runner.global_params = inner, g0
    counters = runner.report.summary["counters"]
    assert counters["local_update.calls"] == len(seen) > 0
    n_corrected = sum(c is not None for c in seen)
    assert counters.get("local_update.corrected", 0) == n_corrected
    assert (n_corrected > 0) == (strategy_cls is Scaffold)
