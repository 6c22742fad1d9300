"""Pure-jnp oracles for every Pallas kernel. These are the correctness
reference (tests assert_allclose kernel-vs-ref across shape/dtype sweeps) and
what ``kernels.ops`` runs in mode "off": the default for every kernel on
non-TPU backends, and for the model-zoo kernels (attention, LoRA, scan) on
a TPU too.  On a TPU the compiled aggregation kernels run unless "off" is
asked for explicitly.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# fedagg: β-weighted aggregation of stacked model parameters (Eq. 7)
# ---------------------------------------------------------------------------
def fedagg(stacked: jax.Array, betas: jax.Array) -> jax.Array:
    """stacked: (M, P) — M participant parameter vectors; betas: (M,).
    Returns (P,) = Σ_m β_m · stacked[m], fp32 accumulation."""
    return jnp.einsum("mp,m->p", stacked.astype(jnp.float32),
                      betas.astype(jnp.float32)).astype(stacked.dtype)


# ---------------------------------------------------------------------------
# dequant_fedagg: fedagg fused with int8 payload dequantization
# (repro.fl.comm int8/qsgd/sign uploads)
# ---------------------------------------------------------------------------
def dequant_fedagg(q: jax.Array, scales: jax.Array,
                   betas: jax.Array) -> jax.Array:
    """q: (M, P) int8 quantized payloads; scales/betas: (M,).
    Returns (P,) fp32 = Σ_m β_m · s_m · q[m] — the unfused oracle
    (dequantize to fp32, then β-reduce)."""
    deq = q.astype(jnp.float32) * scales.astype(jnp.float32)[:, None]
    return jnp.einsum("mp,m->p", deq, betas.astype(jnp.float32))


# ---------------------------------------------------------------------------
# float_fedagg: fedagg over packed fp16/fp32 payloads, fp32 accumulator out
# ---------------------------------------------------------------------------
def float_fedagg(stacked: jax.Array, betas: jax.Array) -> jax.Array:
    """stacked: (M, P) fp16/fp32 payload vectors; betas: (M,).
    Returns (P,) fp32 = Σ_m β_m · stacked[m].  Unlike :func:`fedagg` the
    accumulator stays fp32 (it feeds a shared cross-rung accumulator, not a
    finished model), which also makes it bit-compatible with the per-payload
    decode-to-fp32 + β-weighted-sum reference."""
    return jnp.einsum("mp,m->p", stacked.astype(jnp.float32),
                      betas.astype(jnp.float32))


# ---------------------------------------------------------------------------
# topk_fedagg: β-weighted scatter-accumulate of sparse top-k payloads
# ---------------------------------------------------------------------------
def topk_fedagg(idx: jax.Array, vals: jax.Array, betas: jax.Array,
                n: int) -> jax.Array:
    """idx: (M, k) int32 (indices unique within a row), vals: (M, k) fp32,
    betas: (M,).  Returns (n,) fp32 = Σ_m β_m · scatter(idx[m], vals[m]).

    Accumulates as a sequential left-fold over the participant axis so the
    result is bit-identical to decoding each sparse payload to dense fp32
    and running-summing β·decode(p_m) in payload order (adding β_m·0 at
    untouched positions is exact)."""
    out = jnp.zeros((int(n),), jnp.float32)

    def step(acc, x):
        i, v, b = x
        return acc.at[i].add(b.astype(jnp.float32) *
                             v.astype(jnp.float32)), None

    out, _ = jax.lax.scan(step, out, (idx, vals, betas))
    return out


# ---------------------------------------------------------------------------
# flash attention (causal / sliding-window, GQA)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, scale: Optional[float] = None):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (1 query token vs KV cache with validity mask)
# ---------------------------------------------------------------------------
def decode_attention(q, k, v, valid, *, scale: float):
    """q: (B,1,H,hd), k/v: (B,S,KV,hd), valid: (S,) bool -> (B,1,H,hd)."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, v.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# fused LoRA matmul: y = x @ W + scaling * (x @ A) @ B
# ---------------------------------------------------------------------------
def lora_matmul(x, w, a, b, scaling: float):
    """x: (T, d), w: (d, o), a: (d, r), b: (r, o)."""
    base = x @ w
    delta = (x @ a) @ b
    return base + jnp.asarray(scaling, base.dtype) * delta.astype(base.dtype)


# ---------------------------------------------------------------------------
# selective scan (Mamba2 SSD recurrence, per head)
# ---------------------------------------------------------------------------
def selective_scan(xdt, a_log, B_mat, C_mat, h0):
    """Sequential oracle of the SSD recurrence.
    xdt: (B,S,H,dh) fp32 (already dt-scaled), a_log: (B,S,H) = log a_t,
    B_mat/C_mat: (B,S,n), h0: (B,H,dh,n). Returns (y (B,S,H,dh), h_end)."""
    def step(h, t):
        a = jnp.exp(a_log[:, t])                                     # (B,H)
        u = jnp.einsum("bhd,bn->bhdn", xdt[:, t], B_mat[:, t])
        h = a[:, :, None, None] * h + u
        y = jnp.einsum("bhdn,bn->bhd", h, C_mat[:, t])
        return h, y

    S = xdt.shape[1]
    h_end, ys = jax.lax.scan(step, h0, jnp.arange(S))
    return ys.transpose(1, 0, 2, 3), h_end
