"""Attention: GQA (with RoPE / qk-norm / sliding-window / bias), MLA
(DeepSeek-V2 multi-head latent attention with absorbed decode), and
cross-attention for the enc-dec arch.

Two execution paths:
  * XLA path (default, portable): einsum attention with optional
    query-chunking so 32k prefill never materializes (S, S) score tensors.
  * Pallas path (TPU target): repro.kernels.flash_attention /
    decode_attention — selected by ``repro.kernels.ops.use_pallas()``.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import dist
from repro.models.layers import (apply_rope, constrain, dense, dense_init, rmsnorm, rmsnorm_init,
                                 rope_frequencies, yarn_frequencies, yarn_mscale)

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Ring-buffer KV cache. For SWA archs ``k.shape[1]`` is the window."""
    k: jax.Array          # (B, S_cache, KV, hd)  — MLA: c_kv (B, S, lora)
    v: jax.Array          # (B, S_cache, KV, hd)  — MLA: k_rope (B, S, rope_hd)
    length: jax.Array     # (), int32: tokens seen so far


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    if cfg.mla:
        qh = cfg.mla_nope_head_dim + cfg.mla_rope_head_dim
        if cfg.mla_q_lora_rank:
            q = {"q_down": dense_init(ks[0], cfg.d_model, cfg.mla_q_lora_rank, dtype),
                 "q_norm": rmsnorm_init(cfg.mla_q_lora_rank, dtype),
                 "q_up": dense_init(ks[1], cfg.mla_q_lora_rank, cfg.num_heads * qh, dtype)}
        else:
            q = {"wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * qh, dtype)}
        return {
            **q,
            "kv_down": dense_init(
                ks[2], cfg.d_model, cfg.mla_kv_lora_rank + cfg.mla_rope_head_dim, dtype),
            "kv_norm": rmsnorm_init(cfg.mla_kv_lora_rank, dtype),
            "kv_up": dense_init(
                ks[3], cfg.mla_kv_lora_rank,
                cfg.num_heads * (cfg.mla_nope_head_dim + cfg.mla_v_head_dim), dtype),
            "wo": dense_init(ks[4], cfg.num_heads * cfg.mla_v_head_dim, cfg.d_model, dtype),
        }
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * hd, dtype, bias=cfg.attn_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype, bias=cfg.attn_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype, bias=cfg.attn_bias),
        "wo": dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def cross_attn_init(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * hd, dtype, bias=cfg.attn_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype, bias=cfg.attn_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype, bias=cfg.attn_bias),
        "wo": dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }


# ---------------------------------------------------------------------------
# core scaled-dot-product with GQA + chunked queries (XLA path)
# ---------------------------------------------------------------------------
def _sdpa(q, k, v, *, causal: bool, window: Optional[int], q_offset,
          scale: float, q_chunk: int = 2048):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd). q_offset: absolute position of q[0]
    minus position of k[0] (for caches/chunks). Returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    vd = v.shape[-1]                     # may differ from hd (MLA)
    groups = H // KV

    def attend(qc, off):
        # qc: (B, C, H, hd) -> scores (B, KV, groups, C, Sk)
        qg = qc.reshape(B, qc.shape[1], KV, groups, hd)
        s = jnp.einsum("bckgh,bskh->bkgcs", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        qpos = off + jnp.arange(qc.shape[1])[:, None]     # (C,1) absolute q pos
        kpos = jnp.arange(Sk)[None, :]                    # (1,Sk)
        mask = jnp.ones((qc.shape[1], Sk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgcs,bskh->bckgh", p, v.astype(jnp.float32))
        return o.reshape(B, qc.shape[1], H, vd).astype(q.dtype)

    if Sq <= q_chunk:
        return attend(q, q_offset)
    assert Sq % q_chunk == 0, (Sq, q_chunk)
    n = Sq // q_chunk
    qs = q.reshape(B, n, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    offs = q_offset + jnp.arange(n) * q_chunk

    def body(_, xs):
        qc, off = xs
        return None, attend(qc, off)

    _, out = jax.lax.scan(body, None, (qs, offs))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, vd)


# ---------------------------------------------------------------------------
# GQA forward (prefill / train)
# ---------------------------------------------------------------------------
def gqa_forward(p, cfg: ModelConfig, x, positions, *, causal=True,
                q_chunk: int = 2048):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, hd)
    k = dense(p["wk"], x).reshape(B, S, cfg.num_kv_heads, hd)
    v = dense(p["wv"], x).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    from repro.kernels import ops as kops
    if kops.use_pallas():
        o = kops.flash_attention(q, k, v, causal=causal, window=cfg.sliding_window)
    else:
        o = _sdpa(q, k, v, causal=causal, window=cfg.sliding_window,
                  q_offset=0, scale=1.0 / math.sqrt(hd), q_chunk=q_chunk)
    o = constrain(o, "batch", "seq", "heads", None)
    return dense(p["wo"], o.reshape(B, S, cfg.num_heads * hd))


def cross_attn_forward(p, cfg: ModelConfig, x, enc_out, q_chunk: int = 2048):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, hd)
    k = dense(p["wk"], enc_out).reshape(B, enc_out.shape[1], cfg.num_kv_heads, hd)
    v = dense(p["wv"], enc_out).reshape(B, enc_out.shape[1], cfg.num_kv_heads, hd)
    o = _sdpa(q, k, v, causal=False, window=None, q_offset=0,
              scale=1.0 / math.sqrt(hd), q_chunk=q_chunk)
    return dense(p["wo"], o.reshape(B, S, cfg.num_heads * hd))


# ---------------------------------------------------------------------------
# GQA decode (1 token against ring-buffer cache)
#
# When num_kv_heads < model-axis size, a head-sharded cache is impossible and
# GSPMD falls back to all-gathering the multi-GB cache every step (measured:
# 60 GB/step on qwen3 decode_32k — EXPERIMENTS.md §Perf A). The production
# path instead SEQUENCE-shards the cache over the model axis and runs a
# distributed flash combine (local partial softmax + tiny psum of per-head
# stats) inside shard_map.
# ---------------------------------------------------------------------------
def _use_seq_sharded_cache(cfg: ModelConfig, cache_len: int, batch: int):
    from repro.models import dist
    ctx = dist.get_mesh_context()
    if ctx is None:
        return None
    ms = ctx.model_size
    if cfg.num_kv_heads % ms == 0:       # head sharding works — keep it
        return None
    if cache_len % ms != 0:
        return None
    if batch % ctx.batch_size != 0 and batch != 1:
        return None
    return ctx


def _gqa_decode_core_seq_sharded(ctx, cfg: ModelConfig, q, k_new, v_new,
                                 cache: KVCache, window):
    """q: (B,1,H,hd); k_new/v_new: (B,1,KV,hd); cache.k/v seq-sharded over
    the model axis. Returns (o (B,1,H,hd), new_cache)."""
    import functools as _ft
    from jax.sharding import PartitionSpec as P

    B = q.shape[0]
    S = cache.k.shape[1]
    ms = ctx.model_size
    S_loc = S // ms
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    m_ax = ctx.model_axis
    b_ax = ctx.batch_axes if B % ctx.batch_size == 0 else ()
    bspec = (b_ax if len(b_ax) > 1 else (b_ax[0] if b_ax else None))

    def body(q_, kn, vn, ck, cv, pos):
        midx = jax.lax.axis_index(m_ax)
        slot = pos % S
        local_start = midx * S_loc
        in_shard = (slot >= local_start) & (slot < local_start + S_loc)
        off = jnp.where(in_shard, slot - local_start, 0)
        ck_upd = jax.lax.dynamic_update_slice(ck, kn.astype(ck.dtype), (0, off, 0, 0))
        cv_upd = jax.lax.dynamic_update_slice(cv, vn.astype(cv.dtype), (0, off, 0, 0))
        ck = jnp.where(in_shard, ck_upd, ck)
        cv = jnp.where(in_shard, cv_upd, cv)
        # validity of local ring-buffer slots (global positions)
        kpos = local_start + jnp.arange(S_loc)
        abs_pos = jnp.where(kpos <= slot, pos - slot + kpos, pos - slot - S + kpos)
        ok = abs_pos >= 0
        if window is not None:
            ok &= abs_pos > pos - window
        KV = ck.shape[2]
        g = q_.shape[2] // KV
        bloc = q_.shape[0]
        qg = q_.reshape(bloc, KV, g, hd).astype(jnp.float32)
        s = jnp.einsum("bkgh,bskh->bkgs", qg, ck.astype(jnp.float32)) * scale
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                         # (b,KV,g)
        m_glob = jax.lax.pmax(m_loc, m_ax)
        p = jnp.exp(s - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bkgs,bskh->bkgh", p, cv.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, m_ax)
        o_glob = jax.lax.psum(o_loc, m_ax)
        o = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        o = o.reshape(bloc, 1, q_.shape[2], hd).astype(q_.dtype)
        return o, ck, cv

    cache_spec = P(bspec, m_ax, None, None)
    rep4 = P(bspec, None, None, None)
    o, ck, cv = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(rep4, rep4, rep4, cache_spec, cache_spec, P()),
        out_specs=(rep4, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, cache.k, cache.v, cache.length)
    return o, KVCache(k=ck, v=cv, length=cache.length + 1)



def gqa_init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> KVCache:
    hd = cfg.resolved_head_dim
    S = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    shape = (batch, S, cfg.num_kv_heads, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def gqa_decode(p, cfg: ModelConfig, x, cache: KVCache):
    """x: (B, 1, d). Returns (out, new_cache)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = cache.length                                   # scalar absolute pos
    q = dense(p["wq"], x).reshape(B, 1, cfg.num_heads, hd)
    k = dense(p["wk"], x).reshape(B, 1, cfg.num_kv_heads, hd)
    v = dense(p["wv"], x).reshape(B, 1, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    posb = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    S = cache.k.shape[1]
    ctx = _use_seq_sharded_cache(cfg, S, B)
    if ctx is not None:
        # PERF (EXPERIMENTS.md §Perf A): seq-sharded cache + distributed
        # flash combine — avoids GSPMD's full cache all-gather when
        # num_kv_heads < model-axis size.
        o, new_cache = _gqa_decode_core_seq_sharded(
            ctx, cfg, q, k, v, cache, cfg.sliding_window)
        out = dense(p["wo"], o.reshape(B, 1, cfg.num_heads * hd))
        return out, new_cache
    slot = pos % S                                       # ring-buffer slot
    # PERF (EXPERIMENTS.md §Perf A, iteration 1 — kept): force the 1-token
    # k/v update onto the cache's head layout BEFORE the in-place write.
    # Batch axis left unpinned: constraining it on B=1 decode (long_500k)
    # made GSPMD rematerialize the cache (measured 4× regression).
    k = constrain(k, None, None, "kv_cache_heads", None)
    v = constrain(v, None, None, "kv_cache_heads", None)
    ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
    kpos = jnp.arange(S)
    # absolute position currently stored in each slot of the ring buffer
    abs_pos = jnp.where(kpos <= slot, pos - slot + kpos, pos - slot - S + kpos)
    valid = abs_pos >= 0
    if cfg.sliding_window:
        valid &= abs_pos > pos - cfg.sliding_window
    from repro.kernels import ops as kops
    groups = cfg.num_heads // cfg.num_kv_heads
    if kops.use_pallas():
        o = kops.decode_attention(q, ck, cv, valid, scale=1.0 / math.sqrt(hd))
    else:
        qg = q.reshape(B, cfg.num_kv_heads, groups, hd)
        s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                       ck.astype(jnp.float32)) / math.sqrt(hd)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskh->bkgh", w, cv.astype(jnp.float32))
        o = o.reshape(B, 1, cfg.num_heads, hd).astype(x.dtype)
    out = dense(p["wo"], o.reshape(B, 1, cfg.num_heads * hd))
    return out, KVCache(k=ck, v=cv, length=pos + 1)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------
def _mla_project_q(p, cfg, x, B, S):
    if "wq" in p:                                   # no query compression
        q = dense(p["wq"], x)
    else:
        q = dense(p["q_down"], x)
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        q = dense(p["q_up"], q)
    qh = cfg.mla_nope_head_dim + cfg.mla_rope_head_dim
    q = q.reshape(B, S, cfg.num_heads, qh)
    return jnp.split(q, [cfg.mla_nope_head_dim], axis=-1)   # nope, rope


def mla_rope(cfg: ModelConfig):
    """(inverse frequencies, cos/sin factor) of MLA's rope dims: theta's
    own, or YaRN's with mscale over mscale_all_dim."""
    rd = cfg.mla_rope_head_dim
    f = cfg.rope_yarn_factor
    if f <= 1:
        return rope_frequencies(rd, cfg.rope_theta), 1.0
    freqs = yarn_frequencies(rd, cfg.rope_theta, f, cfg.rope_yarn_original_max,
                             cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow)
    return freqs, (yarn_mscale(f, cfg.rope_yarn_mscale)
                   / yarn_mscale(f, cfg.rope_yarn_mscale_all_dim))


def _yarn_temperature(cfg: ModelConfig) -> float:
    """YaRN's mscale_all_dim factor squared, which multiplies the softmax scale."""
    if cfg.rope_yarn_factor > 1 and cfg.rope_yarn_mscale_all_dim:
        return yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim) ** 2
    return 1.0


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times YaRN's temperature."""
    return _yarn_temperature(cfg) / math.sqrt(cfg.mla_nope_head_dim + cfg.mla_rope_head_dim)


def mla_forward(p, cfg: ModelConfig, x, positions, q_chunk: int = 2048):
    """Training/prefill MLA: expand the latent, run standard attention."""
    B, S, _ = x.shape
    nh, nd, rd, vd = cfg.num_heads, cfg.mla_nope_head_dim, cfg.mla_rope_head_dim, cfg.mla_v_head_dim
    freqs, rs = mla_rope(cfg)
    q_nope, q_rope = _mla_project_q(p, cfg, x, B, S)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, freqs, rs)
    kv = dense(p["kv_down"], x)
    c_kv, k_rope = jnp.split(kv, [cfg.mla_kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta, freqs, rs)  # (B,S,1,rd)
    kvu = dense(p["kv_up"], c_kv).reshape(B, S, nh, nd + vd)
    k_nope, v = jnp.split(kvu, [nd], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, nh, rd))], axis=-1)
    q = constrain(q, "batch", "seq", "heads", None)
    o = _sdpa(q, k, v, causal=True, window=cfg.sliding_window, q_offset=0,
              scale=mla_softmax_scale(cfg), q_chunk=q_chunk)
    return dense(p["wo"], o.reshape(B, S, nh * vd))


def mla_init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, seq_len, cfg.mla_kv_lora_rank), dtype),   # c_kv
        v=jnp.zeros((batch, seq_len, cfg.mla_rope_head_dim), dtype),  # k_rope
        length=jnp.zeros((), jnp.int32))


def mla_decode(p, cfg: ModelConfig, x, cache: KVCache):
    """Absorbed MLA decode: score via latent space, never expand the cache."""
    B = x.shape[0]
    nh, nd, rd, vd = cfg.num_heads, cfg.mla_nope_head_dim, cfg.mla_rope_head_dim, cfg.mla_v_head_dim
    lora = cfg.mla_kv_lora_rank
    pos = cache.length
    freqs, rs = mla_rope(cfg)
    q_nope, q_rope = _mla_project_q(p, cfg, x, B, 1)
    posb = jnp.full((B, 1), pos, jnp.int32)
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta, freqs, rs)       # (B,1,H,rd)
    kv = dense(p["kv_down"], x)                             # (B,1,lora+rd)
    c_kv, k_rope = jnp.split(kv, [lora], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], posb, cfg.rope_theta, freqs, rs)[:, :, 0, :]
    ck = jax.lax.dynamic_update_slice(cache.k, c_kv.astype(cache.k.dtype), (0, pos, 0))
    cr = jax.lax.dynamic_update_slice(cache.v, k_rope.astype(cache.v.dtype), (0, pos, 0))
    # absorb kv_up into the query:  q_c[h] = W_uk[h]^T q_nope[h]
    w_uk = p["kv_up"]["w"].reshape(lora, nh, nd + vd)[:, :, :nd]      # (lora,H,nd)
    w_uv = p["kv_up"]["w"].reshape(lora, nh, nd + vd)[:, :, nd:]      # (lora,H,vd)
    q_c = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0].astype(jnp.float32),
                     w_uk.astype(jnp.float32))                         # (B,H,lora)
    s = jnp.einsum("bhl,bsl->bhs", q_c, ck.astype(jnp.float32))
    s = s + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                       cr.astype(jnp.float32))
    s = s / math.sqrt(nd + rd)
    temperature = _yarn_temperature(cfg)
    if temperature != 1.0:
        s = s * temperature
    kpos = jnp.arange(cache.k.shape[1])
    s = jnp.where((kpos <= pos)[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsl->bhl", w, ck.astype(jnp.float32))      # (B,H,lora)
    o = jnp.einsum("bhl,lhv->bhv", o_lat, w_uv.astype(jnp.float32))    # (B,H,vd)
    o = o.reshape(B, 1, nh * vd).astype(x.dtype)
    out = dense(p["wo"], o)
    return out, KVCache(k=ck, v=cr, length=pos + 1)
