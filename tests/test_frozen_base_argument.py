"""In LoRA mode FFTRunner's jitted local update takes the frozen base as an
argument: no constant as large as a base leaf is compiled into it. The
update gives what the form that closed over the base gave: to the last bit
for full fine-tuning (CNN), where nothing changed, and to float32 rounding
for LoRA (ViT), whose constant base XLA compiled into products of another
summation order."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import fft_split, make_dataset, train_test_split
from repro.fl.lora import LoRAConfig, apply_lora
from repro.fl.partition import partition
from repro.fl.runtime import FFTConfig, FFTRunner
from repro.models.vision import make_model


def _runner(lora):
    ds = make_dataset(400, n_classes=4, image_size=8, channels=1, noise=0.8, seed=0)
    train, test = train_test_split(ds, 50, seed=1)
    pub, priv = fft_split(train, public_per_class=10, seed=0)
    parts, _ = partition("group_classes", priv.y, 4, 4, classes_per_group=1,
                         group_size=1, seed=0)
    init_fn, apply_fn = make_model("vit" if lora else "cnn", 4, 8, 1)
    cfg = FFTConfig(n_clients=4, k_selected=4, local_steps=3, batch_size=8, lr=0.05,
                    failure_mode="none", seed=0, eval_every=100)
    lcfg = LoRAConfig(rank=4, match=lambda p: "qkv/w" in p) if lora else None
    return FFTRunner(cfg, init_fn, apply_fn, pub, parts, priv, test, lora_cfg=lcfg)


def _closure_update(r):
    """The local update as it was written before the base became an
    argument: the loss closes over ``r.base_params``."""
    apply_fn, E, bs = r.apply_fn, r.cfg.local_steps, r.cfg.batch_size

    def loss_t(t, x, y):
        params = apply_lora(r.base_params, t, r.lora_cfg) if r.lora_cfg else t
        logp = jax.nn.log_softmax(apply_fn(params, x).astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    @jax.jit
    def local_update(t, t_global, x, y, key, lr, mu):
        n = x.shape[0]

        def step(tt, k):
            idx = jax.random.randint(k, (bs,), 0, n)
            g = jax.grad(loss_t)(tt, x[idx], y[idx])
            g = jax.tree.map(lambda gg, p_, pg: gg.astype(jnp.float32) + mu * (
                p_.astype(jnp.float32) - pg.astype(jnp.float32)), g, tt, t_global)
            tt = jax.tree.map(lambda p_, gg: (p_.astype(jnp.float32) - lr * gg).astype(p_.dtype),
                              tt, g)
            return tt, None

        return jax.lax.scan(step, t, jax.random.split(key, E))[0]

    return local_update


def _constant_sizes(hlo_text):
    """Element counts of the f32 constants of a lowered module."""
    sizes = []
    for dims in re.findall(r"stablehlo\.constant dense<[^>]*> : tensor<([0-9x]*)f32>", hlo_text):
        sizes.append(int(np.prod([int(d) for d in dims.split("x") if d])) if dims else 1)
    return sizes


@pytest.fixture(scope="module")
def lora_runner():
    return _runner(lora=True)


def test_lowered_update_takes_the_base_as_an_argument(lora_runner):
    r = lora_runner
    tg, x, y = r.global_params, r.client_x[0], r.client_y[0]
    key = jax.random.PRNGKey(3)
    smallest = min(a.size for a in jax.tree.leaves(r.base_params) if a.ndim == 2)
    text = r._update_program.lower(r.base_params, tg, tg, None, x, y, key, 0.05, 0.0).as_text()
    assert max(_constant_sizes(text), default=0) < smallest
    n_base = len(jax.tree.leaves(r.base_params))
    assert text.count("%arg") >= n_base
    # the closure form compiles the base in, which the count above would see
    closed = _closure_update(r).lower(tg, tg, x, y, key, 0.05, 0.0).as_text()
    assert max(_constant_sizes(closed)) >= smallest


# LoRA: largest gap over a leaf's largest value; measured 6.4e-7 on the CPU
ROUNDING = 2e-6


@pytest.mark.parametrize("lora", [True, False], ids=["lora-vit", "full-cnn"])
@pytest.mark.parametrize("mu", [0.0, 0.01], ids=["sgd", "prox"])
def test_update_equals_the_closure_form(lora_runner, lora, mu):
    r = lora_runner if lora else _runner(lora=False)
    tg = r.global_params
    for c in range(2):
        x, y = r.client_x[c], r.client_y[c]
        key = jax.random.PRNGKey(10 + c)
        got = r._local_update(tg, tg, None, x, y, key, r.lr(1), mu)
        want = _closure_update(r)(tg, tg, x, y, key, r.lr(1), mu)
        for u, v in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
            u, v = np.asarray(u), np.asarray(v)
            if lora:
                assert np.max(np.abs(u - v)) <= ROUNDING * np.max(np.abs(v))
            else:
                assert np.array_equal(u, v)
        tg = got


def test_a_base_changed_after_the_first_call_reaches_the_update(lora_runner):
    """A fold into the base (FedEx-LoRA's residual) takes effect at the next
    call: the base is read per call, not frozen into the first compile."""
    r = lora_runner
    tg, x, y = r.global_params, r.client_x[0], r.client_y[0]
    key = jax.random.PRNGKey(5)
    before = r._local_update(tg, tg, None, x, y, key, r.lr(1), 0.0)
    path = next(iter(tg))
    w = r.base_params
    for k in path.split("/"):
        w = w[k]
    r.fold_into_base(path, jnp.full(w.shape, 0.5, jnp.float32))
    try:
        after = r._local_update(tg, tg, None, x, y, key, r.lr(1), 0.0)
        assert any(not np.array_equal(np.asarray(u), np.asarray(v))
                   for u, v in zip(jax.tree.leaves(before), jax.tree.leaves(after)))
    finally:
        r.fold_into_base(path, jnp.full(w.shape, -0.5, jnp.float32))
