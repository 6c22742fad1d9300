"""Unified model zoo: one functional Transformer covering all 10 assigned
architectures (dense GQA, MLA+MoE, SWA, qk-norm, GeGLU, Mamba2 hybrid,
xLSTM, enc-dec audio, VLM-with-stub-frontend).

Layout decisions (see DESIGN.md §5):
  * Homogeneous stacks (all big archs) are ``lax.scan`` over stacked layer
    params with per-layer ``jax.checkpoint`` — small HLO, fast compiles,
    remat keeps live activations to one layer input per layer.
  * Heterogeneous patterns (xlstm, zamba2 — small models) use a Python loop.
  * zamba2's SHARED_ATTN positions all reuse one shared param set.

API:
  init_params(key, cfg)               -> pytree
  forward(params, cfg, batch)         -> (loss, metrics)        # train
  hidden_states(params, cfg, batch)   -> (B,S,d)                # backbone out
  apply(params, cfg, tokens)          -> (B,S,V) logits         # fine-tuning
  init_decode_state(params, cfg, batch, cache_len) -> state
  decode_step(params, cfg, state, tokens (B,1)) -> (logits (B,V), state)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, MAMBA2, MLSTM, SLSTM, SHARED_ATTN, ModelConfig
from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm, xlstm
from repro.models.layers import constrain, embed_init, rmsnorm, rmsnorm_init
from repro.models.loss import chunked_cross_entropy

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------
def _layer_uses_moe(cfg: ModelConfig, layer_idx: int) -> bool:
    return cfg.moe and layer_idx >= cfg.first_k_dense


def block_init(key, cfg: ModelConfig, kind: str, dtype, *, use_moe: bool,
               cross: bool = False):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind in (ATTN, SHARED_ATTN):
        p = {"ln1": rmsnorm_init(d, dtype), "attn": attn.attn_init(ks[0], cfg, dtype),
             "ln2": rmsnorm_init(d, dtype)}
        if use_moe:
            p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
        else:
            p["ffn"] = ffn_mod.ffn_init(ks[1], cfg, dtype)
        if cross:
            p["ln_cross"] = rmsnorm_init(d, dtype)
            p["cross"] = attn.cross_attn_init(ks[2], cfg, dtype)
        return p
    if kind == MAMBA2:
        return {"ln1": rmsnorm_init(d, dtype), "mamba": ssm.mamba2_init(ks[0], cfg, dtype)}
    if kind == MLSTM:
        return {"ln1": rmsnorm_init(d, dtype), "mlstm": xlstm.mlstm_init(ks[0], cfg, dtype)}
    if kind == SLSTM:
        return {"ln1": rmsnorm_init(d, dtype), "slstm": xlstm.slstm_init(ks[0], cfg, dtype)}
    raise ValueError(kind)


def block_forward(p, cfg: ModelConfig, kind: str, x, positions, *,
                  enc_out=None, causal: bool = True, q_chunk: int = 2048):
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind in (ATTN, SHARED_ATTN):
        if cfg.mla:
            with jax.named_scope("mla"):
                a = attn.mla_forward(p["attn"], cfg, h, positions, q_chunk=q_chunk)
        else:
            a = attn.gqa_forward(p["attn"], cfg, h, positions, causal=causal,
                                 q_chunk=q_chunk)
        x = x + a
        if "cross" in p:
            hc = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
            x = x + attn.cross_attn_forward(p["cross"], cfg, hc, enc_out, q_chunk=q_chunk)
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            f, aux = moe_mod.moe_forward(p["moe"], cfg, h2)
        else:
            f = ffn_mod.ffn_forward(p["ffn"], cfg, h2)
        return x + f, aux
    if kind == MAMBA2:
        return x + ssm.mamba2_forward(p["mamba"], cfg, h), aux
    if kind == MLSTM:
        return x + xlstm.mlstm_forward(p["mlstm"], cfg, h), aux
    if kind == SLSTM:
        return x + xlstm.slstm_forward(p["slstm"], cfg, h), aux
    raise ValueError(kind)


def block_decode(p, cfg: ModelConfig, kind: str, x, cache, *, enc_out=None):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind in (ATTN, SHARED_ATTN):
        if cfg.mla:
            a, cache = attn.mla_decode(p["attn"], cfg, h, cache)
        else:
            a, cache = attn.gqa_decode(p["attn"], cfg, h, cache)
        x = x + a
        if "cross" in p:
            hc = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
            x = x + attn.cross_attn_forward(p["cross"], cfg, hc, enc_out, q_chunk=2048)
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            f, _ = moe_mod.moe_forward(p["moe"], cfg, h2)
        else:
            f = ffn_mod.ffn_forward(p["ffn"], cfg, h2)
        return x + f, cache
    if kind == MAMBA2:
        y, cache = ssm.mamba2_decode(p["mamba"], cfg, h, cache)
    elif kind == MLSTM:
        y, cache = xlstm.mlstm_decode(p["mlstm"], cfg, h, cache)
    elif kind == SLSTM:
        y, cache = xlstm.slstm_decode(p["slstm"], cfg, h, cache)
    else:
        raise ValueError(kind)
    return x + y, cache


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------
def _is_homogeneous(cfg: ModelConfig) -> bool:
    return cfg.block_pattern is None


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
                      "final_norm": rmsnorm_init(cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[1], cfg.vocab_size, cfg.d_model, dtype)

    cross = cfg.encoder_decoder
    if _is_homogeneous(cfg):
        n_scan = cfg.num_layers - cfg.first_k_dense
        for i in range(cfg.first_k_dense):
            params[f"dense_layer_{i}"] = block_init(
                jax.random.fold_in(keys[2], i), cfg, ATTN, dtype, use_moe=False,
                cross=cross)
        lkeys = jax.random.split(keys[3], n_scan)
        params["layers"] = jax.vmap(
            lambda k: block_init(k, cfg, ATTN, dtype, use_moe=cfg.moe, cross=cross)
        )(lkeys)
        if cfg.encoder_decoder:
            ekeys = jax.random.split(keys[4], cfg.num_encoder_layers)
            params["enc_layers"] = jax.vmap(
                lambda k: block_init(k, cfg, ATTN, dtype, use_moe=False)
            )(ekeys)
            params["enc_norm"] = rmsnorm_init(cfg.d_model, dtype)
    else:
        kinds = cfg.layer_kinds()
        blocks = {}
        shared = None
        for i, kind in enumerate(kinds):
            bk = jax.random.fold_in(keys[2], i)
            if kind == SHARED_ATTN:
                if shared is None:
                    shared = block_init(bk, cfg, SHARED_ATTN, dtype, use_moe=False)
                continue
            blocks[str(i)] = block_init(bk, cfg, kind, dtype,
                                        use_moe=_layer_uses_moe(cfg, i))
        params["blocks"] = blocks
        if shared is not None:
            params["shared_attn_block"] = shared
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def _embed_tokens(params, cfg: ModelConfig, tokens):
    x = params["embed"]["embedding"][tokens]
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _lm_head_w(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T
    return params["lm_head"]["embedding"].T


def _scan_stack(stacked, cfg, x, positions, *, enc_out=None, causal=True,
                q_chunk, use_remat=True):
    def body(carry, layer_params):
        h, aux = carry
        h2, a = block_forward(layer_params, cfg, ATTN, h, positions,
                              enc_out=enc_out, causal=causal, q_chunk=q_chunk)
        return (h2, aux + a), None

    fn = jax.checkpoint(body) if use_remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def hidden_states(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                  q_chunk: int = 2048, remat: bool = True):
    """Backbone forward. batch keys: tokens (B,St) int32; optional
    image_embeds (B,Ni,d); encoder_embeds (B,Se,d). Returns ((B,S,d), aux)."""
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens)
    if cfg.vision_frontend and "image_embeds" in batch:
        x = jnp.concatenate([batch["image_embeds"].astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, "batch", "seq", "embed")

    enc_out = None
    if cfg.encoder_decoder:
        e = batch["encoder_embeds"].astype(x.dtype)
        Be, Se, _ = e.shape
        epos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (Be, Se))
        e, _ = _scan_stack(params["enc_layers"], cfg, e, epos, causal=False,
                           q_chunk=q_chunk)
        enc_out = rmsnorm(params["enc_norm"], e, cfg.norm_eps)

    aux = jnp.zeros((), jnp.float32)
    if _is_homogeneous(cfg):
        for i in range(cfg.first_k_dense):
            x, a = block_forward(params[f"dense_layer_{i}"], cfg, ATTN, x, positions,
                                 enc_out=enc_out, q_chunk=q_chunk)
            aux += a
        x, a = _scan_stack(params["layers"], cfg, x, positions, enc_out=enc_out,
                           q_chunk=q_chunk, use_remat=remat)
        aux += a
    else:
        for i, kind in enumerate(cfg.layer_kinds()):
            p = params["shared_attn_block"] if kind == SHARED_ATTN else params["blocks"][str(i)]
            x, a = block_forward(p, cfg, kind, x, positions, q_chunk=q_chunk)
            aux += a
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            q_chunk: int = 2048, loss_chunk: int = 512, remat: bool = True):
    """Next-token LM loss. labels: (B, S_total) int32, negatives masked."""
    h, aux = hidden_states(params, cfg, batch, q_chunk=q_chunk, remat=remat)
    loss, cnt = chunked_cross_entropy(h, _lm_head_w(params, cfg), batch["labels"],
                                      chunk=loss_chunk)
    return loss + aux, {"ce_loss": loss, "aux_loss": aux, "target_tokens": cnt}


def apply(params, cfg: ModelConfig, tokens, q_chunk: int = 2048):
    """float32 logits (B, S, V) of tokens (B, S): the backbone, with remat per
    scanned layer, then the lm head. The router's auxiliary balance loss is
    not part of it."""
    h, _ = hidden_states(params, cfg, {"tokens": tokens}, q_chunk=q_chunk)
    return (h @ _lm_head_w(params, cfg)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def _init_block_cache(params_block, cfg: ModelConfig, kind: str, batch: int,
                      cache_len: int, dtype):
    if kind in (ATTN, SHARED_ATTN):
        if cfg.mla:
            return attn.mla_init_cache(cfg, batch, cache_len, dtype)
        return attn.gqa_init_cache(cfg, batch, cache_len, dtype)
    if kind == MAMBA2:
        return ssm.mamba2_init_cache(cfg, batch, dtype)
    if kind == MLSTM:
        return xlstm.mlstm_init_cache(cfg, batch)
    if kind == SLSTM:
        return xlstm.slstm_init_cache(cfg, batch)
    raise ValueError(kind)


def init_decode_state(params, cfg: ModelConfig, batch: int, cache_len: int,
                      encoder_embeds: Optional[jax.Array] = None):
    """Build the per-layer cache pytree (plus enc_out for enc-dec)."""
    dtype = jnp.dtype(cfg.dtype)
    state: Dict[str, Any] = {}
    if _is_homogeneous(cfg):
        n_scan = cfg.num_layers - cfg.first_k_dense
        one = _init_block_cache(None, cfg, ATTN, batch, cache_len, dtype)
        state["layers"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_scan,) + t.shape).copy(), one)
        for i in range(cfg.first_k_dense):
            state[f"dense_layer_{i}"] = _init_block_cache(None, cfg, ATTN, batch,
                                                          cache_len, dtype)
    else:
        state["blocks"] = {
            str(i): _init_block_cache(None, cfg, kind, batch, cache_len, dtype)
            for i, kind in enumerate(cfg.layer_kinds())}
    if cfg.encoder_decoder:
        assert encoder_embeds is not None
        e = encoder_embeds.astype(dtype)
        Be, Se, _ = e.shape
        epos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (Be, Se))
        e, _ = _scan_stack(params["enc_layers"], cfg, e, epos, causal=False,
                           q_chunk=2048, use_remat=False)
        state["enc_out"] = rmsnorm(params["enc_norm"], e, cfg.norm_eps)
    return state


def decode_step(params, cfg: ModelConfig, state, tokens):
    """tokens: (B, 1) int32 -> (logits (B, V), new_state)."""
    x = _embed_tokens(params, cfg, tokens)
    enc_out = state.get("enc_out")
    if _is_homogeneous(cfg):
        for i in range(cfg.first_k_dense):
            x, c = block_decode(params[f"dense_layer_{i}"], cfg, ATTN, x,
                                state[f"dense_layer_{i}"], enc_out=enc_out)
            state = dict(state)
            state[f"dense_layer_{i}"] = c

        def body(h, xs):
            layer_params, layer_cache = xs
            h2, c2 = block_decode(layer_params, cfg, ATTN, h, layer_cache,
                                  enc_out=enc_out)
            return h2, c2

        xs = (params["layers"], state["layers"])
        # Under an explicit-axes mesh (jax.set_mesh) the body returns the
        # carry typed with the sharding its layers give it (the seq-sharded
        # decode's shard_map shards the batch); scan needs equal carry
        # types, so the carry starts out with that sharding.
        h_out, _ = jax.eval_shape(body, x, jax.tree.map(lambda a: a[0], xs))
        if h_out.sharding is not None:
            x = jax.sharding.reshard(x, h_out.sharding.spec)
        x, new_caches = jax.lax.scan(body, x, xs)
        state = dict(state)
        state["layers"] = new_caches
    else:
        state = dict(state, blocks=dict(state["blocks"]))
        for i, kind in enumerate(cfg.layer_kinds()):
            p = params["shared_attn_block"] if kind == SHARED_ATTN else params["blocks"][str(i)]
            x, c = block_decode(p, cfg, kind, x, state["blocks"][str(i)])
            state["blocks"][str(i)] = c
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = (h[:, 0] @ _lm_head_w(params, cfg)).astype(jnp.float32)
    logits = constrain(logits, "batch", "vocab")
    return logits, state
