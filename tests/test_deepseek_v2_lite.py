"""DeepSeek-V2-Lite's layers in the zoo: YaRN, MLA with a plain query
projection, softmax gates without renormalisation, the held-expert MoE layer
(one chip's share of an expert-parallel deployment), the logits ``apply``,
and FFTRunner on next-token targets: the loss, the FedAuto token-bucket rows
and the compensatory subset."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.data.synthetic import Dataset
from repro.fl.lora import LoRAConfig
from repro.fl.runtime import FFTConfig, FFTRunner
from repro.models import attention as attn
from repro.models import transformer as T
from repro.models.layers import yarn_frequencies
from repro.models.moe import _route, held_rows, moe_forward, moe_init


def _fp32(cfg, **kw):
    return dataclasses.replace(cfg, dtype="float32", **kw)


def test_published_shape_is_registered():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size) == (27, 2048, 16, 102400)
    assert (cfg.mla_kv_lora_rank, cfg.mla_q_lora_rank, cfg.mla_nope_head_dim,
            cfg.mla_rope_head_dim, cfg.mla_v_head_dim) == (512, 0, 128, 64, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts,
            cfg.moe_d_ff, cfg.d_ff, cfg.first_k_dense) == (64, 6, 2, 1408, 10944, 1)
    assert not cfg.moe_norm_topk_prob and cfg.moe_experts_held == 0
    # published 15.7B; the analytic count gives the dense lead layer an
    # expert FFN too (0.5B more)
    assert 15.5e9 < cfg.param_count() < 16.5e9


def test_yarn_inverse_frequencies_match_the_closed_form():
    """inv_freq = f_e mask + f_e / 40 (1 - mask), f_e(i) = 10000^(-2i/64),
    mask = 1 - clip((i - 10) / (23 - 10), 0, 1)."""
    cfg = get_config("deepseek-v2-lite")
    got = np.asarray(yarn_frequencies(64, cfg.rope_theta, cfg.rope_yarn_factor,
                                      cfg.rope_yarn_original_max, cfg.rope_yarn_beta_fast,
                                      cfg.rope_yarn_beta_slow))
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    i = np.arange(32, dtype=np.float64)
    f_e = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = f_e * mask + f_e / 40 * (1 - mask)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(f_e[-1] / 40, rel=1e-6)


def test_yarn_softmax_scale_and_rope_factor():
    cfg = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attn.mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    assert attn.mla_rope(cfg)[1] == pytest.approx(1.0)
    plain = get_config("deepseek-v2-236b")           # no YaRN: unchanged
    assert attn.mla_softmax_scale(plain) == 1.0 / math.sqrt(192)
    assert attn.mla_rope(plain)[1] == 1.0


def test_plain_query_mla_shapes():
    cfg = _fp32(get_smoke_config("deepseek-v2-lite"))
    p = attn.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    qh = cfg.mla_nope_head_dim + cfg.mla_rope_head_dim
    assert p["wq"]["w"].shape == (cfg.d_model, cfg.num_heads * qh)
    assert not {"q_down", "q_norm", "q_up"} & set(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    assert attn.mla_forward(p, cfg, x, pos, q_chunk=8).shape == (2, 16, cfg.d_model)
    d, h, kvr = cfg.d_model, cfg.num_heads, cfg.mla_kv_lora_rank
    rd, nd, vd = cfg.mla_rope_head_dim, cfg.mla_nope_head_dim, cfg.mla_v_head_dim
    assert sum(a.size for a in jax.tree.leaves(p)) == (
        d * h * qh + d * (kvr + rd) + kvr + kvr * h * (nd + vd) + h * vd * d)


def test_gates_are_probabilities_without_renormalisation():
    cfg = _fp32(get_smoke_config("deepseek-v2-lite"), moe_experts_held=0)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.d_model))
    gates, eids, _ = _route(p, cfg, x)
    probs = jax.nn.softmax(x @ p["router"]["w"], axis=-1)
    want = np.take_along_axis(np.asarray(probs), np.asarray(eids), axis=1)
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)
    assert float(jnp.max(jnp.sum(gates, -1))) < 1.0
    renorm, _, _ = _route(p, dataclasses.replace(cfg, moe_norm_topk_prob=True), x)
    np.testing.assert_allclose(np.asarray(jnp.sum(renorm, -1)), 1.0, rtol=1e-6)
    scaled, _, _ = _route(p, dataclasses.replace(cfg, moe_routed_scaling=2.5), x)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(gates), rtol=1e-6)


def test_expert_shares_add_up_to_the_whole_layer():
    """At a small size: the 8 shares of 64 experts (offsets 0, 8, ..., 56),
    each computed by the held-expert layer, with the shared experts counted
    once, sum to the layer that holds every expert."""
    cfg = _fp32(get_smoke_config("deepseek-v2-lite"), d_model=32, moe_d_ff=16,
                num_experts=64, num_experts_per_tok=6, num_shared_experts=2,
                moe_experts_held=0)
    whole = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    want, _ = moe_forward(whole, cfg, x)
    shared = moe_forward(whole, dataclasses.replace(cfg, num_shared_experts=0), x)[0]
    shared = want - shared                                      # the shared experts' part
    got = shared
    for off in range(0, 64, 8):
        part = dataclasses.replace(cfg, moe_experts_held=8, moe_expert_offset=off,
                                   num_shared_experts=0)
        p = dict(whole, **{k: whole[k][off:off + 8] for k in ("w_gate", "w_up", "w_down")})
        del p["shared"]
        got = got + jax.jit(lambda p, x: moe_forward(p, part, x)[0])(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_held_layer_drops_no_pair_when_every_token_picks_held_experts():
    """A router biased onto the held experts sends all T x min(k, held)
    pairs here, the static row bound: every one is computed."""
    cfg = _fp32(get_smoke_config("deepseek-v2-lite"), d_model=32, moe_d_ff=16,
                num_experts=16, num_experts_per_tok=3, moe_experts_held=4,
                moe_expert_offset=4, num_shared_experts=0)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 20, cfg.d_model)))
    held = 3.0 + jax.random.uniform(jax.random.PRNGKey(2), (cfg.d_model, 4))
    p["router"]["w"] = jnp.zeros((cfg.d_model, 16)).at[:, 4:8].set(held)
    gates, eids, _ = _route(p, cfg, x[0])
    assert bool(jnp.all((eids >= 4) & (eids < 8)))
    assert held_rows(cfg, 20) == 20 * 3
    got, _ = moe_forward(p, cfg, x)
    want = 0.0
    for j in range(4):
        w = jnp.sum(jnp.where(eids == 4 + j, gates, 0.0), axis=1)[:, None]
        h = jax.nn.silu(x[0] @ p["w_gate"][j]) * (x[0] @ p["w_up"][j])
        want = want + w * (h @ p["w_down"][j])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_smoke_train_step_and_decode_agree_with_forward():
    cfg = _fp32(get_smoke_config("deepseek-v2-lite"))
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    assert params["layers"]["moe"]["w_gate"].shape[1] == cfg.moe_experts_held
    assert params["layers"]["moe"]["router"]["w"].shape[-1] == cfg.num_experts
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (2, 16), 0, cfg.vocab_size)
    logits = T.apply(params, cfg, tokens, q_chunk=8)
    assert logits.shape == (2, 16, cfg.vocab_size) and logits.dtype == jnp.float32
    loss, grads = jax.value_and_grad(
        lambda p: T.forward(p, cfg, {"tokens": tokens, "labels": tokens}, q_chunk=8,
                            loss_chunk=8)[0])(params)
    assert jnp.isfinite(loss)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    state = T.init_decode_state(params, cfg, 2, 16)
    step = jax.jit(lambda p, s, t: T.decode_step(p, cfg, s, t))
    for t in range(16):
        out, state = step(params, state, tokens[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(logits[:, t]),
                                   rtol=5e-3, atol=5e-3)


# ------------------------------------------------------- FFTRunner on tokens
V, S, BUCKETS = 64, 12, 16


def _token_runner(seed=0):
    cfg_m = _fp32(get_smoke_config("deepseek-v2-lite"), vocab_size=V)
    rng = np.random.default_rng(seed)
    rows = lambda n: rng.integers(0, V, (n, S + 1)).astype(np.int32)
    pub, priv, test = rows(10), rows(24), rows(4)
    # public row 0 alone holds targets in token 60's bucket
    hot = np.where(_buckets(np.arange(V)) == _buckets(60))[0]
    cold = int(np.setdiff1d(np.arange(V), hot)[0])
    for r in (pub[1:], priv):
        r[:, 1:] = np.where(np.isin(r[:, 1:], hot), cold, r[:, 1:])
    pub[0, 1] = 60
    ds = lambda r: Dataset(x=jnp.asarray(r[:, :S]), y=r[:, 1:], n_classes=BUCKETS)
    clients = [np.arange(i, 24, 4) for i in range(4)]
    cfg = FFTConfig(n_clients=4, k_selected=4, local_steps=2, batch_size=2, lr=0.05,
                    failure_mode="none", seed=seed, eval_every=10 ** 9)
    lora = LoRAConfig(rank=2, match=lambda p: p.endswith(("attn/wq/w", "attn/wo/w")))
    runner = FFTRunner(cfg, lambda k: T.init_params(k, cfg_m),
                       lambda p, x: T.apply(p, cfg_m, x, q_chunk=S), ds(pub), clients,
                       ds(priv), ds(test), lora_cfg=lora)
    return runner, pub[:, 1:], priv[:, 1:]


def _buckets(t):
    return (np.asarray(t).astype(np.int64) * 2654435761 % 2 ** 31) % BUCKETS


def test_token_loss_is_the_mean_over_every_position():
    runner, _, _ = _token_runner()
    t = jax.tree.map(lambda a: a + 0.01, runner.global_params)
    x, y = runner.client_x[0][:3], runner.client_y[0][:3]
    got = float(runner.loss_on(t, x, y))
    from repro.fl.lora import apply_lora
    logits = runner.apply_fn(apply_lora(runner.base_params, t, runner.lora_cfg), x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -np.mean([float(logp[i, s, int(y[i, s])]) for i in range(3) for s in range(S)])
    assert got == pytest.approx(want, rel=1e-5)


def test_fedauto_rows_count_hashed_token_buckets():
    runner, pub_y, priv_y = _token_runner()
    for c in range(4):
        want = np.bincount(_buckets(priv_y[c::4]).ravel(), minlength=BUCKETS)
        assert runner.client_hists[c].tolist() == want.tolist()
    assert runner.server_hist.tolist() == np.bincount(_buckets(pub_y).ravel(),
                                                      minlength=BUCKETS).tolist()
    assert np.array_equal(runner.global_hist, runner.server_hist + runner.client_hists.sum(0))


def test_compensatory_subset_takes_the_rows_with_a_missing_bucket():
    runner, pub_y, _ = _token_runner()
    missing = np.zeros(BUCKETS, bool)
    missing[_buckets(60)] = True
    assert not (runner.client_hists[:, missing] > 0).any()
    rows = np.where(np.isin(_buckets(pub_y), np.where(missing)[0]).any(axis=1))[0]
    assert rows.tolist() == [0]
    model, hist = runner.train_compensatory(missing, 1)
    assert hist.tolist() == np.bincount(_buckets(pub_y[0]), minlength=BUCKETS).tolist()
    assert jax.tree.structure(model) == jax.tree.structure(runner.global_params)
    none, _ = runner.train_compensatory(np.zeros(BUCKETS, bool), 1)
    assert none is None


def test_token_runner_evaluates_next_token_accuracy():
    runner, _, _ = _token_runner()
    acc = runner.evaluate()
    assert 0.0 <= acc <= 1.0
    assert acc * 4 * S == pytest.approx(round(acc * 4 * S))


def test_rows_past_the_held_pairs_add_nothing_forward_or_backward(monkeypatch):
    """The grouped products leave unspecified values in rows that lie in no
    group: put large values there, in the products and in their transposes,
    and the layer and its input gradient stay as they were."""
    from repro.models import moe
    cfg = _fp32(get_smoke_config("deepseek-v2-lite"), d_model=32, moe_d_ff=16,
                num_experts=16, num_experts_per_tok=3, moe_experts_held=4,
                moe_expert_offset=4, num_shared_experts=1)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))

    def layer_and_grad():
        f = lambda x: jnp.sum(jnp.sin(moe_forward(p, cfg, x)[0]))
        return jax.jit(jax.value_and_grad(f))(x)

    want_v, want_g = layer_and_grad()
    ragged = jax.lax.ragged_dot

    def junk(out, group_sizes):
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], 1e3, out)

    @jax.custom_vjp
    def dirty(lhs, rhs, group_sizes):
        return junk(ragged(lhs, rhs, group_sizes), group_sizes)

    def dirty_fwd(lhs, rhs, group_sizes):
        return dirty(lhs, rhs, group_sizes), (rhs, group_sizes)

    def dirty_bwd(res, ct):
        rhs, group_sizes = res
        d_lhs = junk(ragged(ct, jnp.swapaxes(rhs, 1, 2), group_sizes), group_sizes)
        return d_lhs, jnp.zeros_like(rhs), None

    dirty.defvjp(dirty_fwd, dirty_bwd)

    monkeypatch.setattr(moe.jax.lax, "ragged_dot", dirty)
    got_v, got_g = layer_and_grad()
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-6)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g), rtol=1e-5, atol=1e-6)
