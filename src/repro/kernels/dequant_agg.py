"""Pallas TPU kernels: batched decode-and-accumulate over packed uploads.

The kernel family behind the streaming aggregation server — every rung of
the comm ladder has a batched form that takes K packed payloads plus β
weights and produces ONE fp32 accumulator pass, so K arrivals never
materialize K fp32 delta pytrees:

    dequant_fedagg  int8-family rungs (``sign1``/``qsgd:<bits>``/``int8``):
                    out[p] = Σ_m β_m · s_m · q[m, p]
    float_fedagg    fp16/fp32 rungs: out[p] = Σ_m β_m · x[m, p], fp32 out
    fedagg          Eq. 7 over M = K+2 decoded participant models (the
                    materializing path): ``float_fedagg`` cast back to the
                    models' dtype
    topk_fedagg     sparse top-k rungs — β-weighted scatter-add; dynamic
                    index scatter is XLA's territory on TPU, so it lives in
                    ``kernels.ref`` and every dispatch mode shares it

Each fuses ``fedagg`` (Eq. 7) with server-side payload decode: instead of
materializing M float32 participant vectors (4 bytes/param) and then
reducing them, the packed payloads stream HBM→VMEM *once at wire width*
(1 byte/param for int8, 2 for fp16) and decode in-tile — up to 4× less HBM
traffic on a purely memory-bound op, exactly the regime the aggregation
server lives in at 10k+ arrivals/round.  Mixed-rung cohorts batch per rung
family and add the per-family partial sums into one shared accumulator
(``repro.fl.comm.stream.StreamAccumulator``).

β and the per-participant dequant scales collapse into one coefficient
c_m = β_m·s_m before the kernel (held in SMEM), so the inner loop is a
single scaled reduction over the participant axis.

Tiling: the flat parameter axis P is viewed as (rows, BP) and tiled into
(32, BP) blocks — int8's minimum sublane tile is 32 (16-bit's 16 and
fp32's 8 divide it).  BP is the widest multiple of 128 lanes up to
``block`` that tiles P exactly, so P is only padded (a copy) when it is
not a multiple of 32·128.  The participant axis M is tiled too: a second,
sequential grid axis walks (BM, 32, BP) payload tiles of about
``_TILE_BYTES`` each into the (32, BP) fp32 output block, which stays
resident in VMEM across it.  VMEM use is therefore independent of M, and
a ragged last M tile is handled by its valid-row count.  Inside a tile the
reduction runs per 512-lane chunk with the chunk's partial sum held in
registers, so the output block is read and written once per tile.

fp16 payloads enter the kernel as their raw 16-bit patterns
(``bitcast_convert_type`` to uint16, free in XLA): v5e's Mosaic has no f16
vector loads, and the in-tile decode to fp32 is exact (normals,
subnormals, ±0, inf, nan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE_I8 = 32      # int8 minimum sublane tile (16-bit's 16, fp32's 8 divide it)
_CHUNK = 512         # lanes per register-resident partial sum
_TILE_BYTES = 2 << 20  # payload bytes per (BM, SUBLANE_I8, BP) VMEM tile


def _to_f32(x):
    return x.astype(jnp.float32)


def _f16_bits_to_f32(h):
    """Exact fp16 → fp32 from the raw uint16 bit pattern, in int32 ops."""
    h = h.astype(jnp.int32)
    sign = (h & 0x8000) << 16
    exp = (h >> 10) & 0x1F
    mant = h & 0x3FF
    normal = (exp + 112) << 23 | mant << 13          # rebias 15 → 127
    special = 0x7F800000 | mant << 13                # inf / nan
    bits = sign | jnp.where(exp == 31, special, normal)
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    sub = mant.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return jnp.where(exp == 0, jnp.where(sign != 0, -sub, sub), val)


def _kernel(coef_ref, x_ref, o_ref, *, m_total, bm, chunk, decode):
    # coef: (M,) fp32 in SMEM = β·scale (β alone for float payloads);
    # x: (BM, SUBLANE_I8, BP) payload tile; o: (SUBLANE_I8, BP) fp32,
    # resident across the M grid axis.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    base = j * bm
    n_valid = jnp.minimum(bm, m_total - base)
    for c0 in range(0, o_ref.shape[1], chunk):
        cols = pl.ds(c0, chunk)

        def body(i, acc, cols=cols):
            return acc + decode(x_ref[i, :, cols]) * coef_ref[base + i]

        o_ref[:, cols] = jax.lax.fori_loop(0, n_valid, body, o_ref[:, cols])


def _coef_reduce(x: jax.Array, coef: jax.Array, *, name: str, block: int,
                 interpret: bool) -> jax.Array:
    """Shared host-side wrapper: tile the (M, P) payload matrix and run the
    coefficient-weighted in-tile decode+reduce, (P,) fp32 out.  ``name``
    is the kernel's name in the compiled program and the device trace."""
    decode = _to_f32
    if x.dtype == jnp.float16:
        x = jax.lax.bitcast_convert_type(x, jnp.uint16)
        decode = _f16_bits_to_f32
    M, P = x.shape
    tile = SUBLANE_I8 * LANE
    P_pad = pl.cdiv(P, tile) * tile
    if P_pad != P:
        x = jnp.pad(x, ((0, 0), (0, P_pad - P)))
    n_tiles = P_pad // tile
    lanes = max(d for d in range(1, max(block // LANE, 1) + 1)
                if n_tiles % d == 0) * LANE
    chunk = next(c for c in (_CHUNK, 256, LANE) if lanes % c == 0)
    row_bytes = SUBLANE_I8 * lanes * x.dtype.itemsize
    n_m = pl.cdiv(M, max(1, _TILE_BYTES // row_bytes))
    bm = pl.cdiv(M, n_m)
    x3 = x.reshape(M, P_pad // lanes, lanes)
    out = pl.pallas_call(
        functools.partial(_kernel, m_total=M, bm=bm, chunk=chunk,
                          decode=decode),
        grid=(x3.shape[1] // SUBLANE_I8, n_m),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, SUBLANE_I8, lanes), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANE_I8, lanes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x3.shape[1], lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(coef, x3)
    return out.reshape(P_pad)[:P]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequant_fedagg(q: jax.Array, scales: jax.Array, betas: jax.Array, *,
                   block: int = 2048, interpret: bool = False) -> jax.Array:
    """q: (M, P) int8; scales, betas: (M,) -> (P,) fp32 = Σ_m β_m s_m q[m]."""
    coef = betas.astype(jnp.float32) * scales.astype(jnp.float32)
    return _coef_reduce(q, coef, name="dequant_fedagg", block=block,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def float_fedagg(x: jax.Array, betas: jax.Array, *,
                 block: int = 2048, interpret: bool = False) -> jax.Array:
    """x: (M, P) fp16/bf16/fp32; betas: (M,) -> (P,) fp32 = Σ_m β_m x[m]."""
    return _coef_reduce(x, betas.astype(jnp.float32), name="float_fedagg",
                        block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fedagg(stacked: jax.Array, betas: jax.Array, *, block: int = 2048,
           interpret: bool = False) -> jax.Array:
    """stacked: (M, P); betas: (M,) -> (P,) = Σ_m β_m stacked[m]."""
    return float_fedagg(stacked, betas, block=block,
                        interpret=interpret).astype(stacked.dtype)
