"""Shared low-level layers: norms, RoPE, initializers, sharding constraints.

Everything is functional: ``init_*`` returns a param dict, ``apply``-style
functions are pure. Models are dtype-polymorphic; params are created in
``config.dtype`` and math runs in that dtype with fp32 accumulation where it
matters (norms, softmax, losses).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical-axis sharding constraints.
#
# Models annotate activations with *logical* axes; the launcher installs a
# mapping logical -> mesh axes. When no mesh is active the constraint is a
# no-op, so all model code runs unchanged on a single CPU device.
# ---------------------------------------------------------------------------
_LOGICAL_RULES: dict = {}


def set_logical_rules(rules: dict) -> None:
    """rules: logical axis name -> mesh axis (str, tuple of str, or None)."""
    global _LOGICAL_RULES
    _LOGICAL_RULES = dict(rules)


def clear_logical_rules() -> None:
    set_logical_rules({})


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply with_sharding_constraint mapped through the logical rules."""
    if not _LOGICAL_RULES:
        return x
    spec = P(*[_LOGICAL_RULES.get(a) if a is not None else None for a in logical_axes])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no ambient mesh — single-device execution


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embed_init(key, vocab: int, d: int, dtype):
    return {"embedding": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


# ---------------------------------------------------------------------------
# Norms (fp32 internal)
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, 0.1 m ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, factor: float, original_max: int,
                     beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies, as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``
    computes them: dims that turn more than ``beta_fast`` times over
    ``original_max`` positions keep theta's frequency, dims that turn fewer
    than ``beta_slow`` times are divided by ``factor``, with a linear ramp
    between."""
    def dim_of(turns):
        return head_dim * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    f_e = rope_frequencies(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return f_e * keep + f_e / factor * (1.0 - keep)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None, scale: float = 1.0) -> jax.Array:
    """x: (..., S, H, head_dim); positions: broadcastable to (..., S).
    ``freqs`` replaces theta's inverse frequencies (YaRN), and ``scale``
    multiplies cos and sin."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)             # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    angles = angles[..., None, :]                              # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)
