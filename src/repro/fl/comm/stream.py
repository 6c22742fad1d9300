"""Streaming server-side aggregation: K arrivals, one fp32 accumulator.

The materializing server path decodes every upload to a full fp32 model
pytree and hands strategies a ``client_models`` dict — K arrivals cost K
fp32 pytrees of HBM before the β-reduction even starts.  This module is the
other half of the ``CommState.roundtrip`` split: uploads arrive as *packed*
payloads (``CommState.encode_upload``) and a ``StreamAccumulator`` consumes
``(payload, β)`` pairs incrementally, batching per rung family through the
batched decode-and-accumulate kernels (``kernels.ops.dequant_fedagg`` /
``float_fedagg`` / ``topk_fedagg``) into ONE shared fp32 accumulator:

    acc[p] += Σ_{batch} β_m · decode(p_m)[p]        one kernel pass per batch

Peak *decoded* memory is O(1) in K — the accumulator (one fp32 template)
plus one batch's in-flight tile — instead of O(K).  The packed payloads
themselves are wire-sized (the server had to receive those bytes anyway)
and are dropped as soon as their batch flushes.

Mixed-rung cohorts work out of the box: payloads bucket by rung *family*
(``quant`` = int8/qsgd/sign1, ``fp16``, ``fp32``, ``topk:<spec>``) and every
family's partial sums land in the same accumulator.  A payload whose family
is unknown falls back to per-payload decode into the accumulator — counted
in the ``uplink_decode`` attribution so the profiler shows when and why the
fused path was not taken.

``weighted_model_sum`` builds the full strategy-facing aggregate

    Σ_j β_j · (origin_global_j + decode(p_j))  +  Σ_t w_t · tree_t

without materializing any per-client model: the origin-global coefficients
group per *distinct* origin pytree (at most staleness-bound-many under the
async server, exactly one under the sync server), so the dense part of the
sum is O(τ_max) pytrees, never O(K).

Distortion bookkeeping is untouched by streaming: the normalized
compression distortion is measured client-side in ``encode_upload`` (error
feedback already needs the transient decode there) and travels as wire
metadata on the ``PackedUpdate``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.comm.codecs import Payload, make_codec
from repro.kernels import ops as kops
from repro.obs.telemetry import NULL_TELEMETRY

#: rung families a batched kernel exists for (bucket keys of the accumulator)
FUSED_FAMILIES = ("quant", "fp16", "fp32", "topk")


# Jitted flush reductions: a whole batch reduces inside ONE compiled call,
# which is what makes the fused path beat K eager per-payload decodes.  In
# "off" (reference) dispatch the weighted sum is left UNROLLED — XLA fuses
# it into a single pass that reads each packed payload once, which on CPU
# beats stacking into an (M, P) batch by an order of magnitude (the
# many-operand concatenate alone costs more than the reduction).  The
# Pallas modes stack, because the tiled kernels take the (M, P) batch and
# on TPU the stack is a cheap contiguous HBM layout.  ``mode`` is a static
# cache key as well as the dispatch switch, so a kernel-mode change
# (kernels.ops.set_mode) can never hit a trace cached under the old mode.
@functools.partial(jax.jit, static_argnames=("mode",))
def _quant_reduce(qs, scales, betas, *, mode):
    if mode == "off":
        out = None
        for i, (q, s) in enumerate(zip(qs, scales)):
            term = ((betas[i] * jnp.asarray(s, jnp.float32))
                    * q.astype(jnp.float32).reshape(-1))
            out = term if out is None else out + term
        return out
    q = jnp.stack([x.reshape(-1) for x in qs])
    s = jnp.stack([jnp.asarray(x, jnp.float32).reshape(()) for x in scales])
    return kops.dequant_fedagg(q, s, betas)


@functools.partial(jax.jit, static_argnames=("mode",))
def _float_reduce(xs, betas, *, mode):
    if mode == "off":
        out = None
        for i, x in enumerate(xs):
            term = betas[i] * x.astype(jnp.float32).reshape(-1)
            out = term if out is None else out + term
        return out
    return kops.float_fedagg(jnp.stack([x.reshape(-1) for x in xs]), betas)


@functools.partial(jax.jit, static_argnames=("mode", "n"))
def _topk_reduce(idx, vals, betas, *, n, mode):
    # top-k index/value vectors are k-sized, so the stack is cheap in every
    # mode; the scatter fold itself is shared across modes (kernels.ops)
    del mode
    return kops.topk_fedagg(jnp.stack(idx), jnp.stack(vals), betas, n)


@dataclasses.dataclass
class PackedUpdate:
    """One upload exactly as the server receives it on the wire: the packed
    payload plus wire metadata.  ``origin_global`` is the global pytree the
    payload's delta is relative to (the round-r broadcast for a round-r
    upload) — shared by reference across a cohort, never copied."""
    client: int
    payload: Payload
    origin_global: Any
    codec: str
    nbytes: float
    distortion: float
    origin_round: int = 0


def _size(shape) -> int:
    return int(np.prod(shape)) if shape else 1


def payload_family(payload: Payload) -> Optional[str]:
    """The batched-kernel bucket a payload belongs to, or ``None`` when no
    batched kernel covers it (→ per-payload decode fallback).  Top-k buckets
    carry the codec spec — two top-k payloads only stack when their per-leaf
    k agree, which the shared spec guarantees."""
    fams = set()
    for el in payload.leaves:
        keys = set(el.data)
        if keys == {"q", "scale"} and el.data["q"].dtype == jnp.int8:
            fams.add("quant")
        elif keys == {"v"}:
            fams.add("fp16" if el.data["v"].dtype == jnp.float16 else "fp32")
        elif keys == {"idx", "val"}:
            fams.add(payload.codec)              # "topk:<frac>" — k must agree
        else:
            return None
    return fams.pop() if len(fams) == 1 else None


class StreamAccumulator:
    """Incremental β-weighted decode-and-accumulate over packed payloads.

    ``add(payload, β)`` buckets the payload by rung family; every
    ``batch_k`` payloads of a family flush through that family's batched
    kernel into the shared per-leaf fp32 accumulator.  ``total()`` flushes
    the stragglers and returns the accumulated pytree
    ``Σ β_m · decode(p_m)`` in fp32.

    ``peak_decoded_bytes`` tracks the high-water mark of *decoded* fp32
    bytes ever live at once: the accumulator itself plus either one batched
    partial leaf (fused flush) or one template (fallback decode) — O(1) in
    the number of payloads, which is the whole point.  The telemetry
    counters ``uplink.fused_payloads`` / ``uplink.fallback_payloads`` feed
    the profiler's ``uplink_decode`` attribution.
    """

    def __init__(self, template, *, batch_k: int = 64,
                 telemetry=NULL_TELEMETRY):
        leaves, treedef = jax.tree.flatten(template)
        self._treedef = treedef
        self._shapes = [tuple(l.shape) for l in leaves]
        self._acc: Optional[List[jnp.ndarray]] = None
        self._buckets: Dict[str, List[Tuple[Payload, float]]] = {}
        self.batch_k = int(batch_k)
        self.telemetry = telemetry
        self.n_added = 0
        self.n_fused = 0
        self.n_fallback = 0
        self.n_flushes = 0
        self._acc_bytes = sum(4 * _size(s) for s in self._shapes)
        self.peak_decoded_bytes = 0

    # ------------------------------------------------------------- feeding
    def add(self, payload: Payload, beta: float) -> None:
        """Consume one ``(payload, β)`` pair; may trigger a batch flush."""
        self.n_added += 1
        fam = payload_family(payload)
        if fam is None:
            self._fallback(payload, beta)
            return
        bucket = self._buckets.setdefault(fam, [])
        bucket.append((payload, float(beta)))
        if len(bucket) >= self.batch_k:
            self._flush(fam)

    def add_tree(self, tree, weight: float) -> None:
        """Accumulate ``weight · tree`` directly (already-dense terms, e.g.
        a strategy's server-model anchor)."""
        self._ensure_acc()
        w = jnp.float32(weight)
        for li, leaf in enumerate(jax.tree.leaves(tree)):
            self._acc[li] = self._acc[li] + w * (
                leaf.astype(jnp.float32).reshape(-1))

    # ------------------------------------------------------------ flushing
    def _ensure_acc(self) -> None:
        if self._acc is None:
            self._acc = [jnp.zeros((_size(s),), jnp.float32)
                         for s in self._shapes]
            self._note_peak(0)

    def _note_peak(self, transient_bytes: int) -> None:
        live = self._acc_bytes + transient_bytes
        if live > self.peak_decoded_bytes:
            self.peak_decoded_bytes = live

    def _fallback(self, payload: Payload, beta: float) -> None:
        # no batched kernel for this payload: decode it alone and fold it
        # in — one transient fp32 template, immediately released
        codec = make_codec(payload.codec)
        self.add_tree(codec.decode(payload), beta)
        self.n_fallback += 1
        self._note_peak(self._acc_bytes)
        if self.telemetry:
            self.telemetry.counter("uplink.fallback_payloads")
            self.telemetry.counter("uplink.decoded_bytes", self._acc_bytes)

    def _flush(self, fam: str) -> None:
        entries = self._buckets.pop(fam, [])
        if not entries:
            return
        with self.telemetry.timer("phase.flush", family=fam,
                                  payloads=len(entries)):
            self._ensure_acc()
            betas = jnp.asarray([b for _, b in entries], jnp.float32)
            payloads = [p for p, _ in entries]
            mode = kops.get_mode()
            for li, shape in enumerate(self._shapes):
                els = [p.leaves[li] for p in payloads]
                n = _size(shape)
                if fam == "quant":
                    part = _quant_reduce([e.data["q"] for e in els],
                                         [e.data["scale"] for e in els],
                                         betas, mode=mode)
                elif fam in ("fp16", "fp32"):
                    part = _float_reduce([e.data["v"] for e in els], betas,
                                         mode=mode)
                else:                                   # topk:<spec>
                    part = _topk_reduce([e.data["idx"] for e in els],
                                        [e.data["val"] for e in els],
                                        betas, n=n, mode=mode)
                self._acc[li] = self._acc[li] + part
                self._note_peak(4 * n)      # one batched partial leaf live
        self.n_fused += len(entries)
        self.n_flushes += 1
        if self.telemetry:
            self.telemetry.counter("uplink.fused_payloads", len(entries))

    def total(self):
        """Flush every bucket and return ``Σ β_m·decode(p_m)`` (+ any
        ``add_tree`` terms) as an fp32 pytree of the template's structure.
        An empty accumulator (empty cohort) returns exact zeros."""
        for fam in list(self._buckets):
            self._flush(fam)
        self._ensure_acc()
        return jax.tree.unflatten(
            self._treedef,
            [a.reshape(s) for a, s in zip(self._acc, self._shapes)])

    @property
    def stats(self) -> Dict[str, float]:
        return {"added": self.n_added, "fused": self.n_fused,
                "fallback": self.n_fallback, "flushes": self.n_flushes,
                "peak_decoded_bytes": float(self.peak_decoded_bytes)}


def weighted_tree_sum(trees: Sequence[Any], weights: Sequence[float]):
    """Σ_t w_t · tree_t with fp32 leaves, through the batched float kernel.
    Small-M companion of the accumulator for the dense terms of a streaming
    aggregate (server anchor + distinct origin globals)."""
    if not trees:
        raise ValueError("weighted_tree_sum needs at least one tree")
    w = jnp.asarray(list(weights), jnp.float32)
    leaves0, treedef = jax.tree.flatten(trees[0])
    flats = [jax.tree.leaves(t) for t in trees]
    mode = kops.get_mode()
    out = [_float_reduce([f[li] for f in flats], w, mode=mode)
           .reshape(leaves0[li].shape) for li in range(len(leaves0))]
    return jax.tree.unflatten(treedef, out)


def weighted_model_sum(packed_terms: Sequence[Tuple[float, PackedUpdate]],
                       dense_terms: Sequence[Tuple[float, Any]] = (), *,
                       template, batch_k: int = 64,
                       telemetry=NULL_TELEMETRY, rnd: Optional[int] = None):
    """The streaming form of a strategy's β-weighted model aggregate:

        Σ_j β_j·(origin_global_j + decode(payload_j)) + Σ_t w_t·tree_t

    computed as one StreamAccumulator pass over the packed payloads plus an
    O(#distinct origin globals + #dense terms) dense sum — identical in
    exact arithmetic to materializing every ``origin_global_j +
    decode(payload_j)`` model and β-reducing, without ever building one.
    Returns fp32 leaves (callers cast to their model dtype).  When ``rnd``
    is given, emits the per-round ``uplink_decode`` attribution gauges.
    """
    acc = StreamAccumulator(template, batch_k=batch_k, telemetry=telemetry)
    origin: Dict[int, List[Any]] = {}        # id(tree) -> [tree, coef]
    for beta, pu in packed_terms:
        acc.add(pu.payload, beta)
        ent = origin.setdefault(id(pu.origin_global), [pu.origin_global, 0.0])
        ent[1] += float(beta)
    trees = [t for _, t in dense_terms] + [t for t, _ in origin.values()]
    weights = [w for w, _ in dense_terms] + [c for _, c in origin.values()]
    delta = acc.total()
    if trees:
        base = weighted_tree_sum(trees, weights)
        out = jax.tree.map(jnp.add, base, delta)
    else:
        out = delta
    if telemetry and rnd is not None:
        telemetry.gauge(rnd, "uplink_fused_payloads", acc.n_fused)
        telemetry.gauge(rnd, "uplink_fallback_payloads", acc.n_fallback)
        telemetry.gauge(rnd, "uplink_peak_decoded_bytes",
                        acc.peak_decoded_bytes)
    return out
