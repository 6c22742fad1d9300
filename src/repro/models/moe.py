"""Mixture-of-Experts block.

TPU-native design (see DESIGN.md §5): tokens stay sharded over the batch axes
and *replicated* over the tensor axis; experts are sharded over the tensor
('model') axis. Each model-shard selects the (token, k) pairs routed to its
local experts with a sort, runs grouped matmuls via ``jax.lax.ragged_dot``
(MXU-friendly, no one-hot dispatch tensors), scatter-adds into the output and
``psum``s over the tensor axis. No all-to-all is needed because activations
are already replicated across that axis — the psum doubles as the combine.

Three paths:
  * ``_moe_local``  — single device / GSPMD-auto fallback (also the oracle).
  * ``_moe_sharded`` — shard_map expert-parallel path, enabled when a
    MeshContext is installed and num_experts % model_axis_size == 0.
  * ``_moe_held`` — one chip's share of an expert-parallel deployment
    (``cfg.moe_experts_held`` < ``num_experts``): the layer holds experts
    ``moe_expert_offset`` .. + held, routes over all ``num_experts`` and
    computes the part of the result its own experts give, every (token,
    expert) pair of them, with no exchange.

Named scopes: ``moe.route`` (router, top-k, sort, group sizes),
``moe.experts`` (gather, grouped products, gate-weighted scatter-add) and
``moe.shared``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import dist
from repro.models.layers import dense_init, gelu


def moe_init(key, cfg: ModelConfig, dtype):
    """Router over all ``num_experts``; expert stacks of the held ones."""
    d_ff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.moe_experts_held or cfg.num_experts
    ks = jax.random.split(key, 6)
    scale = 1.0 / jnp.sqrt(cfg.d_model).astype(jnp.float32)
    p = {
        "router": dense_init(ks[0], cfg.d_model, cfg.num_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, cfg.d_model, d_ff)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, cfg.d_model, d_ff)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, d_ff, cfg.d_model))
                   * (1.0 / jnp.sqrt(d_ff))).astype(dtype),
    }
    if cfg.num_shared_experts:
        from repro.models.ffn import ffn_init
        p["shared"] = ffn_init(ks[4], cfg, dtype,
                               d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.num_shared_experts)
    return p


def _activation(cfg, g, u):
    return (jax.nn.silu(g) if cfg.ffn_activation == "swiglu" else gelu(g)) * u


def _route(p, cfg: ModelConfig, x2d):
    """x2d: (T, d) -> (gates (T,k), eids (T,k) int32, aux_loss scalar)."""
    logits = jnp.dot(x2d.astype(jnp.float32), p["router"]["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, eids = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = top_p
    if cfg.moe_norm_topk_prob:
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)     # renormalize
    if cfg.moe_routed_scaling != 1.0:
        gates = gates * cfg.moe_routed_scaling
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)                                   # (E,)
    one_hot = jax.nn.one_hot(eids, E, dtype=jnp.float32)           # (T,k,E)
    fe = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)                # (E,)
    aux = E * jnp.sum(fe * me)
    return gates, eids.astype(jnp.int32), aux


def _grouped_ffn(cfg, x_sel, w_gate, w_up, w_down, group_sizes):
    """x_sel: (R, d) rows grouped contiguously by expert; ragged matmuls."""
    g = jax.lax.ragged_dot(x_sel, w_gate, group_sizes)
    u = jax.lax.ragged_dot(x_sel, w_up, group_sizes)
    h = _activation(cfg, g, u)
    return jax.lax.ragged_dot(h, w_down, group_sizes)


def _sort_by_expert(eids_flat, num_buckets):
    """Returns (sorted_eids, perm) sorting (token,k) pairs by expert id."""
    T = eids_flat.shape[0]
    sorted_eids, perm = jax.lax.sort_key_val(eids_flat, jnp.arange(T, dtype=jnp.int32))
    return sorted_eids, perm


def _moe_local(p, cfg: ModelConfig, x2d):
    T, d = x2d.shape
    k, E = cfg.num_experts_per_tok, cfg.num_experts
    with jax.named_scope("moe.route"):
        gates, eids, aux = _route(p, cfg, x2d)
        eflat = eids.reshape(T * k)
        gflat = gates.reshape(T * k)
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        se, perm = _sort_by_expert(eflat, E)
        tok_s, gate_s = tok[perm], gflat[perm]
        group_sizes = jnp.bincount(se, length=E).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        x_sel = x2d[tok_s]                                          # (T*k, d)
        y_sel = _grouped_ffn(cfg, x_sel, p["w_gate"], p["w_up"], p["w_down"], group_sizes)
        out = jnp.zeros_like(x2d).at[tok_s].add(
            (y_sel.astype(jnp.float32) * gate_s[:, None]).astype(x2d.dtype))
    return out, aux


def held_rows(cfg: ModelConfig, tokens: int) -> int:
    """Static row bound of the held-expert layer: top-k picks k distinct
    experts per token, so a token sends at most min(k, held) pairs here."""
    return tokens * min(cfg.num_experts_per_tok, cfg.moe_experts_held)


def _moe_held(p, cfg: ModelConfig, x2d):
    """The routed part that the held experts give. p's expert stacks are
    (held, ...); the router has all ``num_experts`` outputs. Held pairs sort
    to a prefix grouped by held expert, and the grouped products run over
    ``held_rows`` rows, so no pair is ever dropped. Rows past the held pairs
    lie in no group, and what the grouped products leave there is
    unspecified (the TPU's are not zero): they are masked on the way in and
    on the way out, so they add nothing to the result or to its gradient."""
    T, d = x2d.shape
    k, held, off = cfg.num_experts_per_tok, cfg.moe_experts_held, cfg.moe_expert_offset
    rows = held_rows(cfg, T)
    with jax.named_scope("moe.route"):
        gates, eids, aux = _route(p, cfg, x2d)
        local = eids.reshape(T * k) - off
        key = jnp.where((local >= 0) & (local < held), local, held)   # held: sentinel
        sk, perm = jax.lax.sort_key_val(key, jnp.arange(T * k, dtype=jnp.int32))
        sk, perm = sk[:rows], perm[:rows]
        tok_s = perm // k
        mine = sk < held
        gate_s = gates.reshape(T * k)[perm]
        group_sizes = jnp.bincount(sk, length=held + 1)[:held].astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        x_sel = jnp.where(mine[:, None], x2d[tok_s], 0.0)            # (rows, d)
        y_sel = _grouped_ffn(cfg, x_sel, p["w_gate"], p["w_up"], p["w_down"], group_sizes)
        y_sel = jnp.where(mine[:, None], y_sel.astype(jnp.float32) * gate_s[:, None], 0.0)
        out = jnp.zeros_like(x2d).at[tok_s].add(y_sel.astype(x2d.dtype))
    return out, aux


def _moe_sharded_body(x, wr, wg, wu, wd, *, cfg: ModelConfig, ctx: dist.MeshContext,
                      capacity: int):
    """Per-device body under shard_map. x: (B_loc, S, d) replicated over the
    model axis; wg/wu/wd: local expert shards (E_loc, ...)."""
    B, S, d = x.shape
    T = B * S
    k = cfg.num_experts_per_tok
    E = cfg.num_experts
    E_loc = wg.shape[0]
    midx = jax.lax.axis_index(ctx.model_axis)
    x2d = x.reshape(T, d)
    gates, eids, aux = _route({"router": {"w": wr}}, cfg, x2d)
    eflat = eids.reshape(T * k)
    gflat = gates.reshape(T * k)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    local = (eflat // E_loc) == midx
    local_eid = eflat - midx * E_loc
    # sort key: local expert id for local pairs, E_loc (sentinel) otherwise —
    # local pairs become a contiguous prefix grouped by local expert.
    key = jnp.where(local, local_eid, E_loc)
    sk, perm = jax.lax.sort_key_val(key, jnp.arange(T * k, dtype=jnp.int32))
    sk, perm = sk[:capacity], perm[:capacity]
    tok_s = tok[perm]
    gate_s = jnp.where(sk < E_loc, gflat[perm], 0.0)   # sentinel rows: weight 0
    eid_s = jnp.minimum(sk, E_loc - 1)                 # sentinel rows: run thru last expert
    group_sizes = jnp.bincount(eid_s, length=E_loc).astype(jnp.int32)
    x_sel = x2d[tok_s]
    y_sel = _grouped_ffn(cfg, x_sel, wg, wu, wd, group_sizes)
    out = jnp.zeros_like(x2d).at[tok_s].add(
        (y_sel.astype(jnp.float32) * gate_s[:, None]).astype(x2d.dtype))
    out = jax.lax.psum(out, ctx.model_axis)
    aux = jax.lax.pmean(aux, ctx.batch_axes)           # identical over model axis
    return out.reshape(B, S, d), aux


def _moe_sharded_body_virtual(x, wr, wg, wu, wd, *, cfg: ModelConfig,
                              ctx: dist.MeshContext, within: int,
                              capacity: int):
    """Virtual-expert body for num_experts < model-axis size (§Perf B):
    each real expert's FFN hidden dim is split over `within` shards; wg/wu
    arrive as (1, d, f/within) and wd as (1, f/within, d) local slices. The
    final psum over the model axis simultaneously reduces the partial-hidden
    sums (within an expert) and combines disjoint experts' tokens."""
    B, S, d = x.shape
    T = B * S
    k = cfg.num_experts_per_tok
    midx = jax.lax.axis_index(ctx.model_axis)
    real_e = midx // within
    x2d = x.reshape(T, d)
    gates, eids, aux = _route({"router": {"w": wr}}, cfg, x2d)
    eflat = eids.reshape(T * k)
    gflat = gates.reshape(T * k)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    local = eflat == real_e
    # bring local pairs to a contiguous prefix, truncate at capacity
    key = jnp.where(local, 0, 1).astype(jnp.int32)
    sk, perm = jax.lax.sort_key_val(key, jnp.arange(T * k, dtype=jnp.int32))
    sk, perm = sk[:capacity], perm[:capacity]
    tok_s = tok[perm]
    gate_s = jnp.where(sk == 0, gflat[perm], 0.0)
    x_sel = x2d[tok_s]                                   # (cap, d)
    g = x_sel @ wg[0]                                    # (cap, f/within)
    u = x_sel @ wu[0]
    y_sel = _activation(cfg, g, u) @ wd[0]               # partial over hidden
    out = jnp.zeros_like(x2d).at[tok_s].add(
        (y_sel.astype(jnp.float32) * gate_s[:, None]).astype(x2d.dtype))
    out = jax.lax.psum(out, ctx.model_axis)
    aux = jax.lax.pmean(aux, ctx.batch_axes)
    return out.reshape(B, S, d), aux


def moe_forward(p, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B,S,d), aux_loss scalar). Adds shared experts."""
    B, S, d = x.shape
    ctx = dist.get_mesh_context()
    E = cfg.num_experts
    ms = ctx.model_size if ctx is not None else 0
    d_ff = cfg.moe_d_ff or cfg.d_ff
    bspec = P(ctx.batch_axes, None, None) if ctx else None
    m = ctx.model_axis if ctx else None
    if 0 < cfg.moe_experts_held < E:
        out2d, aux = _moe_held(p, cfg, x.reshape(B * S, d))
        out = out2d.reshape(B, S, d)
    elif ctx is not None and E % ms == 0 and (B % ctx.batch_size == 0):
        E_loc = E // ms
        T_loc = (B // ctx.batch_size) * S
        # expected local load = T_loc*k*E_loc/E, scaled by the capacity
        # factor (default 2x), clamped to all pairs
        capacity = min(T_loc * cfg.num_experts_per_tok,
                       int(cfg.moe_capacity_factor * T_loc *
                           cfg.num_experts_per_tok * E_loc / E) + 64)
        body = functools.partial(_moe_sharded_body, cfg=cfg, ctx=ctx,
                                 capacity=capacity)
        out, aux = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(bspec, P(None, None), P(m, None, None),
                      P(m, None, None), P(m, None, None)),
            out_specs=(bspec, P()),
            check_vma=False,
        )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    elif (ctx is not None and ms % E == 0 and d_ff % (ms // E) == 0
          and B % ctx.batch_size == 0):
        # virtual experts: E real experts × (ms/E) hidden slices (§Perf B)
        within = ms // E
        T_loc = (B // ctx.batch_size) * S
        capacity = min(T_loc * cfg.num_experts_per_tok,
                       int(cfg.moe_capacity_factor * T_loc *
                           cfg.num_experts_per_tok / E) + 64)
        f_loc = d_ff // within
        wg = p["w_gate"].reshape(E, cfg.d_model, within, f_loc) \
            .transpose(0, 2, 1, 3).reshape(E * within, cfg.d_model, f_loc)
        wu = p["w_up"].reshape(E, cfg.d_model, within, f_loc) \
            .transpose(0, 2, 1, 3).reshape(E * within, cfg.d_model, f_loc)
        wd = p["w_down"].reshape(E, within, f_loc, cfg.d_model) \
            .reshape(E * within, f_loc, cfg.d_model)
        body = functools.partial(_moe_sharded_body_virtual, cfg=cfg, ctx=ctx,
                                 within=within, capacity=capacity)
        out, aux = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(bspec, P(None, None), P(m, None, None),
                      P(m, None, None), P(m, None, None)),
            out_specs=(bspec, P()),
            check_vma=False,
        )(x, p["router"]["w"], wg, wu, wd)
    else:
        out2d, aux = _moe_local(p, cfg, x.reshape(B * S, d))
        out = out2d.reshape(B, S, d)
    if cfg.num_shared_experts:
        from repro.models.ffn import ffn_forward
        with jax.named_scope("moe.shared"):
            out = out + ffn_forward(p["shared"], cfg, x)
    return out, aux * cfg.router_aux_loss_coef
