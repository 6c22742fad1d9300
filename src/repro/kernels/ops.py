"""Jit'd dispatch layer for the Pallas kernels.

Modes (``set_mode``):
  * "on"        — compiled Pallas TPU kernels; asking for it on any backend
                  other than a TPU raises.
  * "off"       — the pure-jnp references of ``kernels.ref``.
  * "interpret" — Pallas kernels in interpret mode (CPU correctness tests).

An explicit ``set_mode`` applies to every kernel.  Without one the mode
follows ``jax.default_backend()``, and only for the aggregation kernels
(``fedagg``, ``float_fedagg``, ``dequant_fedagg``): "on" on a TPU, "off"
elsewhere.  The model-zoo kernels (attention, LoRA matmul, selective scan)
stay "off" by default on every backend: they have no backward pass, so a
``jax.grad`` through them would fail, and no v5e compile test covers them.
The models and the aggregation server call through this module, so the
same code runs the references, the interpreter, or the chip's kernels.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref as _ref

MODES = ("off", "interpret", "on")
_MODE: Optional[str] = None          # None = the backend default above


def set_mode(mode: str) -> None:
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r} (known: {MODES})")
    backend = jax.default_backend()
    if mode == "on" and backend != "tpu":
        raise RuntimeError(
            f"kernel mode 'on' compiles Pallas TPU kernels, but the JAX "
            f"backend is {backend!r}; use 'interpret' or 'off' here")
    global _MODE
    _MODE = mode


def get_mode() -> str:
    """Mode of the aggregation kernels."""
    if _MODE is not None:
        return _MODE
    return "on" if jax.default_backend() == "tpu" else "off"


def model_mode() -> str:
    """Mode of the model-zoo kernels: "off" unless ``set_mode`` chose one."""
    return "off" if _MODE is None else _MODE


def use_pallas() -> bool:
    """Whether the models take the Pallas attention / scan kernels."""
    return model_mode() != "off"


def fedagg(stacked, betas):
    mode = get_mode()
    if mode == "off":
        return _ref.fedagg(stacked, betas)
    from repro.kernels.dequant_agg import fedagg as k
    return k(stacked, betas, interpret=mode == "interpret")


def dequant_fedagg(q, scales, betas):
    mode = get_mode()
    if mode == "off":
        return _ref.dequant_fedagg(q, scales, betas)
    from repro.kernels.dequant_agg import dequant_fedagg as k
    return k(q, scales, betas, interpret=mode == "interpret")


def float_fedagg(stacked, betas):
    mode = get_mode()
    if mode == "off":
        return _ref.float_fedagg(stacked, betas)
    from repro.kernels.dequant_agg import float_fedagg as k
    return k(stacked, betas, interpret=mode == "interpret")


def topk_fedagg(idx, vals, betas, n):
    # Scatter-accumulate over dynamic indices is XLA's territory on TPU (no
    # contiguous-tile reuse for a Pallas kernel to exploit), so every
    # dispatch mode shares the sequential-fold reference — which is also
    # what keeps the streaming path bit-identical to per-payload decode.
    return _ref.topk_fedagg(idx, vals, betas, n)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, scale=None):
    mode = model_mode()
    if mode == "off":
        return _ref.flash_attention(q, k, v, causal=causal, window=window, scale=scale)
    from repro.kernels.flash_attention import flash_attention as kn
    return kn(q, k, v, causal=causal, window=window, scale=scale,
              interpret=mode == "interpret")


def decode_attention(q, k, v, valid, *, scale: float):
    mode = model_mode()
    if mode == "off":
        return _ref.decode_attention(q, k, v, valid, scale=scale)
    from repro.kernels.decode_attention import decode_attention as kn
    return kn(q, k, v, valid, scale=scale, interpret=mode == "interpret")


def lora_matmul(x, w, a, b, scaling: float):
    mode = model_mode()
    if mode == "off":
        return _ref.lora_matmul(x, w, a, b, scaling)
    from repro.kernels.lora_matmul import lora_matmul as kn
    return kn(x, w, a, b, scaling, interpret=mode == "interpret")


def selective_scan(xdt, a_log, B_mat, C_mat, *, chunk: int = 128):
    mode = model_mode()
    if mode == "off":
        import jax.numpy as jnp
        h0 = jnp.zeros((xdt.shape[0], xdt.shape[2], xdt.shape[3],
                        B_mat.shape[-1]), jnp.float32)
        return _ref.selective_scan(xdt, a_log, B_mat, C_mat, h0)[0]
    from repro.kernels.selective_scan import selective_scan as kn
    return kn(xdt, a_log, B_mat, C_mat, chunk=chunk,
              interpret=mode == "interpret")
