"""Per-run communication state: error feedback + bytes-on-wire accounting.

``CommState`` sits between a client's local update and the server's
aggregation: the client encodes its *delta* from the round's global model
(plus its carried error-feedback residual), the link carries exactly
``payload.nbytes`` bytes, and the server decodes back to a model pytree, so
every strategy aggregates reconstructed models unchanged.

Error feedback (EF / EF21 family): for client i with residual e_i,

    c   = (w_i − w̄) + e_i          # compress the residual-corrected delta
    p   = encode(c);  d = decode(p)
    e_i ← c − d                     # what the wire dropped, retried next time
    ŵ_i = w̄ + d                    # what the server reconstructs

For lossless codecs e_i stays exactly zero and ŵ_i ≡ w_i (up to fp32 cast).
The residual carry is what keeps biased compressors (deterministic
quantizers, top-k, sign) convergent: the compression error is not lost, it
is re-sent, so the *cumulative* decoded mass tracks the cumulative true
delta with bounded lag (tested as residual contraction in
``tests/test_comm.py``).  The residual is per-*client* and codec-agnostic
— the adaptive controller may hand a client a different rung every round
and the carry still conserves mass (a lossless rung flushes it to zero).

Downlink: the server's broadcast travels through ``downlink_codec`` with a
*server-side* error-feedback residual of the same shape: the server tracks
``_dl_ref``, the decoded global replica every client holds, encodes the
delta (new global − replica) + residual each round, and clients apply the
decoded delta to their replica.  ``broadcast`` returns that replica — the
parameters clients actually start local training from — so the accuracy
cost of compressing the downlink is borne honestly, not just the byte
count.  ``downlink_codec=None`` keeps the exact fp32 broadcast (and the
fp32 byte accounting) of earlier revisions.

Byte accounting: every codec's payload size is value-independent, so
``upload_nbytes`` is known before local training — the deadline simulator
prices uploads with it.  When ``FFTConfig.model_bytes`` overrides the
derived fp32 size (simulating a larger model over the same toy problem),
wire bytes scale by each codec's exact compression ratio on the real
template, keeping the override and every codec (static, downlink, or
adaptive rung) composable.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.comm.codecs import Codec, EncodedLeaf, Payload, make_codec
from repro.obs.telemetry import NULL_TELEMETRY


def fp32_nbytes(template) -> int:
    """Bytes of the baseline uncompressed fp32 upload of ``template``."""
    return sum(4 * l.size for l in jax.tree.leaves(template))


class _ResidualStore:
    """Error-feedback residuals for all clients, leaf-major.

    Dense mode (``n`` known): one ``(N, *leaf.shape)`` float32 array per
    template leaf, allocated lazily on the first lossy store, plus an
    ``(N,)`` presence mask — O(1) per-client access with no dict churn at
    population scale, and the whole store is two allocations instead of N
    pytrees.  Sparse mode (``n`` is None): a plain per-client dict, for
    direct ``CommState`` constructions that never declare a population
    size.  ``get`` always returns a fresh pytree (device copies of the
    rows), so a caller-held residual is never aliased by a later store.
    """

    def __init__(self, template, n: Optional[int]):
        self.n = n
        self._treedef = jax.tree.structure(template)
        self._shapes = [tuple(l.shape) for l in jax.tree.leaves(template)]
        self._dict: Optional[Dict[int, Any]] = {} if n is None else None
        self._stacks: Optional[list] = None
        self._present = None if n is None else np.zeros(n, dtype=bool)

    def __len__(self) -> int:
        if self._dict is not None:
            return len(self._dict)
        return int(self._present.sum())

    def clear(self) -> None:
        if self._dict is not None:
            self._dict.clear()
        else:
            self._stacks = None
            self._present[:] = False

    def get(self, client: int):
        if self._dict is not None:
            return self._dict.get(client)
        if self._stacks is None or not self._present[client]:
            return None
        return jax.tree.unflatten(
            self._treedef, [jnp.asarray(s[client]) for s in self._stacks])

    def set(self, client: int, tree) -> None:
        if self._dict is not None:
            self._dict[client] = tree
            return
        leaves = jax.tree.leaves(tree)
        if self._stacks is None:
            self._stacks = [np.zeros((self.n,) + shp, dtype=np.float32)
                            for shp in self._shapes]
        for s, leaf in zip(self._stacks, leaves):
            s[client] = np.asarray(leaf, dtype=np.float32)
        self._present[client] = True

    def pop(self, client: int) -> None:
        if self._dict is not None:
            self._dict.pop(client, None)
        elif self._present is not None:
            self._present[client] = False


class _DenseFloatMap:
    """Dict-shaped view over a dense ``(N,)`` float array + presence mask.

    Drop-in for the per-client ``last_distortions`` dict when the
    population size is known: ``m[i]`` / ``m[i] = x`` / ``m.get(i)`` /
    ``i in m`` / ``len(m)`` all work, backed by two fixed arrays instead
    of a hash map that churns at population scale."""

    def __init__(self, n: int):
        self._vals = np.zeros(n, dtype=np.float64)
        self._present = np.zeros(n, dtype=bool)

    def __getitem__(self, client: int) -> float:
        if not self._present[client]:
            raise KeyError(client)
        return float(self._vals[client])

    def __setitem__(self, client: int, value: float) -> None:
        self._vals[client] = value
        self._present[client] = True

    def __contains__(self, client) -> bool:
        c = int(client)
        return 0 <= c < len(self._vals) and bool(self._present[c])

    def __len__(self) -> int:
        return int(self._present.sum())

    def get(self, client: int, default: float = None):
        c = int(client)
        if 0 <= c < len(self._vals) and self._present[c]:
            return float(self._vals[c])
        return default

    def clear(self) -> None:
        self._present[:] = False
        self._vals[:] = 0.0

    def keys(self):
        return (int(i) for i in np.nonzero(self._present)[0])

    def items(self):
        return ((int(i), float(self._vals[i]))
                for i in np.nonzero(self._present)[0])


def _l2(tree) -> float:
    """Global L2 norm across all leaves of a pytree (fp32 accumulate)."""
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                              for l in jax.tree.leaves(tree))))


class CommState:
    """Codec + per-client error-feedback residuals for one runner."""

    def __init__(self, codec: Codec, template, *,
                 model_bytes_override: Optional[float] = None,
                 lora_cfg=None, downlink_codec: Optional[Codec] = None,
                 n_clients: Optional[int] = None):
        codec.validate_template(template, lora_cfg=lora_cfg)
        if downlink_codec is not None:
            downlink_codec.validate_template(template, lora_cfg=lora_cfg)
        self.codec = codec
        self.downlink_codec = downlink_codec
        self._template = template
        self._lora_cfg = lora_cfg
        self._model_bytes_override = model_bytes_override
        self.fp32_nbytes = fp32_nbytes(template)
        self.wire_nbytes = codec.nbytes(template)
        self.compression_ratio = self.wire_nbytes / max(self.fp32_nbytes, 1)
        self._codec_cache: Dict[str, Codec] = {codec.name: codec}
        # one jitted delta-and-encode per codec name (``_encode_program``)
        self._encode_programs: Dict[str, Any] = {}
        self._nbytes_cache: Dict[str, float] = {}
        # Simulated sizes: exact codec bytes by default; scaled by the
        # codec's measured ratio under an explicit model_bytes override.
        # ``ref_bytes`` is the uncompressed fp32 reference everything scales
        # against (the historical ``model_bytes``).
        self.ref_bytes = (float(model_bytes_override)
                          if model_bytes_override is not None
                          else float(self.fp32_nbytes))
        self.upload_bytes = self.nbytes_for(codec)
        self.download_bytes = (self.ref_bytes if downlink_codec is None
                               else self.nbytes_for(downlink_codec))
        # per-client state: dense arrays indexed by client id when the
        # population size is declared, dicts otherwise (see _ResidualStore)
        self.n_clients = n_clients
        self._residuals = _ResidualStore(template, n_clients)
        self._dl_ref = None                    # clients' decoded global replica
        self._dl_residual = None               # server-side EF residual
        self.total_uplink_bytes = 0.0          # cumulative, all clients
        self.total_downlink_bytes = 0.0        # cumulative broadcast bytes
        self.n_encoded = 0
        # last measured normalized compression distortion per client
        # (‖carry − decoded‖/‖carry‖ of the most recent roundtrip; exactly
        # 0.0 for lossless uploads)
        self.last_distortions = (_DenseFloatMap(n_clients)
                                 if n_clients is not None else {})
        # telemetry hub (repro.obs); the runner swaps in a live one per
        # instrumented run — the comm counters are a third, independent
        # accounting the reconcile cross-check compares against
        self.telemetry = NULL_TELEMETRY

    # -------------------------------------------------------------- sizing
    def codec_named(self, name: str) -> Codec:
        """Resolve (and cache) a codec by spec, validated on the template."""
        if name not in self._codec_cache:
            c = make_codec(name)
            c.validate_template(self._template, lora_cfg=self._lora_cfg)
            self._codec_cache[name] = c
        return self._codec_cache[name]

    def nbytes_for(self, codec) -> float:
        """Simulated wire bytes of one upload under ``codec`` (a ``Codec``
        or a spec string): exact template bytes, scaled by the codec's
        measured compression ratio when ``model_bytes`` is overridden.
        Cached per codec name — the result is constant and this sits on the
        per-client per-round upload path."""
        if isinstance(codec, str):
            codec = self.codec_named(codec)
        if codec.name not in self._nbytes_cache:
            exact = codec.nbytes(self._template)
            self._nbytes_cache[codec.name] = (
                float(exact) if self._model_bytes_override is None
                else float(self._model_bytes_override * exact /
                           max(self.fp32_nbytes, 1)))
        return self._nbytes_cache[codec.name]

    # ---------------------------------------------------------------- wire
    def reset(self) -> None:
        self._residuals.clear()
        self._dl_ref = None
        self._dl_residual = None
        self.total_uplink_bytes = 0.0
        self.total_downlink_bytes = 0.0
        self.n_encoded = 0
        self.last_distortions.clear()

    def residual(self, client: int):
        return self._residuals.get(client)

    def _encode_program(self, codec: Codec):
        """The jitted program of one upload under ``codec``, built once per
        codec name; jit's own cache specialises it on the tree, the shapes
        and whether a residual is carried (``resid=None`` is an empty
        pytree, so that case traces a program with no residual add).

        ``_encode_delta(model, global_params, resid)`` computes the fp32
        delta plus the residual.  For a lossless codec it also encodes
        every leaf and returns the leaves' ``data`` dicts: one dispatch per
        upload where the eager form made one per leaf.  A lossy codec gets
        the carry back and encodes it eagerly: under jit XLA turns a
        division by a constant into a product with its reciprocal and
        fuses multiply-subtract, which moves quantizer scales and residuals
        off the eager values by an ulp."""
        fn = self._encode_programs.get(codec.name)
        if fn is None:
            def _encode_delta(model, global_params, resid):
                tel = self.telemetry
                if tel:
                    # the body runs only when jit traces a new program
                    tel.counter("comm.encode_traces")
                carry = jax.tree.map(
                    lambda w, g: w.astype(jnp.float32) - g.astype(jnp.float32),
                    model, global_params)
                if resid is not None:
                    carry = jax.tree.map(jnp.add, carry, resid)
                if not codec.lossless:
                    return carry
                return [codec.encode_leaf(l).data
                        for l in jax.tree.leaves(carry)]

            fn = self._encode_programs[codec.name] = jax.jit(_encode_delta)
        return fn

    def _encode(self, client: int, model, global_params,
                codec: Optional[Codec]):
        """Client-side half of one upload: delta, EF carry, encode, residual
        update, byte charging.  Returns ``(payload, decoded, distortion)``.

        The delta (plus residual) and a lossless encode are one jitted
        program (``_encode_program``).  A lossless codec keeps no residual
        and builds no decoded tree: ``decoded`` is then None, and
        ``roundtrip`` decodes the payload itself (for ``fp32`` and
        ``lora_only`` that is the payload's own arrays, no dispatch).  A
        lossy codec's ``decoded`` is what the server will reconstruct:
        error feedback needs it for the residual and the distortion, and
        ``roundtrip`` reuses it so the materializing path never decodes
        twice."""
        codec = self.codec if codec is None else codec
        resid = self._residuals.get(client)
        out = self._encode_program(codec)(model, global_params, resid)
        decoded = None
        distortion = 0.0
        if codec.lossless:
            # the layout is value-independent: shapes and byte counts come
            # from the model, the arrays from the program
            leaves, treedef = jax.tree.flatten(model)
            enc = []
            for leaf, data in zip(leaves, out):
                shape = tuple(jnp.shape(leaf))
                enc.append(EncodedLeaf(shape, data, codec.leaf_nbytes(shape)))
            payload = Payload(codec=codec.name, leaves=enc, treedef=treedef,
                              nbytes=sum(e.nbytes for e in enc))
            if resid is not None:
                # wire carried the full corrected delta: residual flushed
                self._residuals.pop(client)
        else:
            carry = out
            payload = codec.encode(carry)
            decoded = codec.decode(payload)
            new_resid = jax.tree.map(jnp.subtract, carry, decoded)
            self._residuals.set(client, new_resid)
            carry_norm = _l2(carry)
            if carry_norm > 0.0:
                distortion = _l2(new_resid) / carry_norm
        # accumulate *simulated* wire bytes (override-scaled), the same
        # unit the deadline simulator, traces, and total_downlink_bytes
        # use
        nbytes = self.nbytes_for(codec)
        self.total_uplink_bytes += nbytes
        self.n_encoded += 1
        self.last_distortions[client] = distortion
        tel = self.telemetry
        if tel:
            tel.counter("comm.uploads")
            tel.counter("comm.upload_bytes", nbytes)
        return payload, decoded, distortion

    def encode_upload(self, client: int, model, global_params, *,
                      codec: Optional[Codec] = None) -> Tuple[Payload, float]:
        """Client-side encode of one upload, for the streaming server path.

        Returns ``(payload, distortion)`` — the server receives the *packed*
        payload plus wire metadata and feeds it to a
        ``repro.fl.comm.stream.StreamAccumulator`` without ever
        materializing the fp32 delta.  Error-feedback residual mutation,
        distortion bookkeeping, and byte accounting are identical to
        ``roundtrip`` (they are the same code); the server-side
        reconstruction is omitted, and a lossless upload is one jitted
        program that builds no decoded tree."""
        tel = self.telemetry
        with tel.timer("phase.uplink", client=client):
            payload, _decoded, distortion = self._encode(
                client, model, global_params, codec)
            if tel:
                # device time is honest only once the encode finished
                jax.block_until_ready([el.data for el in payload.leaves])
        return payload, distortion

    def decode_upload(self, payload: Payload, global_params,
                      codec: Optional[Codec] = None):
        """Server-side decode of one packed upload back to a full model
        pytree — the *materializing* path, for strategies that genuinely
        need per-client models/deltas (Scaffold's control variates, FedLAW's
        proxy optimization, FedExLoRA's adapter products).  Counts itself as
        a fallback in the ``uplink_decode`` attribution so the profiler
        shows when the fused path was not taken."""
        tel = self.telemetry
        with tel.timer("phase.uplink_decode"):
            codec = (self.codec if codec is None else
                     self.codec_named(codec) if isinstance(codec, str)
                     else codec)
            decoded = codec.decode(payload)
            recon = jax.tree.map(
                lambda g, d: (g.astype(jnp.float32) + d).astype(g.dtype),
                global_params, decoded)
            if tel:
                jax.block_until_ready(recon)
                tel.counter("uplink.fallback_payloads")
                tel.counter("uplink.decoded_bytes", self.fp32_nbytes)
        return recon

    def roundtrip(self, client: int, model, global_params, *,
                  codec: Optional[Codec] = None) -> Tuple[Any, Payload, float]:
        """Client-encode then server-decode one upload.

        Returns ``(reconstructed_model, payload, distortion)`` where the
        reconstruction has ``model``'s dtypes, the payload carries the exact
        wire bytes, and ``distortion`` is the upload's normalized
        compression distortion ``‖carry − decoded‖/‖carry‖`` (essentially
        free to measure — both pytrees are already in hand; exactly 0.0 for
        lossless uploads).  Mutates the client's error-feedback residual and
        records the distortion in ``last_distortions[client]``.  ``codec``
        overrides the run's static codec for this one upload (the adaptive
        controller's per-client rung); the residual carries across rung
        changes unchanged — EF is codec-agnostic.

        This is the composition ``encode_upload`` + reconstruction with the
        encode-side decode of a lossy codec reused (one decode total) — the
        materializing server path.  Streaming strategies take
        ``encode_upload`` alone and never build ``recon``.
        """
        tel = self.telemetry
        with tel.timer("phase.uplink", client=client):
            codec = self.codec if codec is None else codec
            payload, decoded, distortion = self._encode(
                client, model, global_params, codec)
            if decoded is None:
                decoded = codec.decode(payload)
            recon = jax.tree.map(
                lambda g, d: (g.astype(jnp.float32) + d).astype(g.dtype),
                global_params, decoded)
            if tel:
                # device time is honest only once the reconstruction exists
                jax.block_until_ready(recon)
        return recon, payload, distortion

    # ----------------------------------------------------------- downlink
    def next_broadcast_nbytes(self) -> float:
        """Wire bytes the *next* ``broadcast`` call will charge: the full
        ``ref_bytes`` enrollment transfer for a downlink codec's first
        broadcast, the steady-state ``download_bytes`` otherwise.  The round
        loops query this before the network draw so the deadline simulator,
        the trace, and ``total_downlink_bytes`` all price the same round in
        the same unit."""
        if self.downlink_codec is not None and self._dl_ref is None:
            return float(self.ref_bytes)
        return float(self.download_bytes)

    def broadcast(self, global_params) -> Tuple[Any, float]:
        """Server-encode the round's broadcast; returns ``(params clients
        start from, simulated broadcast bytes)``.

        With no downlink codec the broadcast is the exact global model at
        fp32 size.  With one, the server encodes the delta from the clients'
        decoded replica (plus its error-feedback residual) and the replica
        advances by the decoded delta — every client then trains from the
        replica, never from state it could not have received.  The first
        broadcast initializes the replica to the current global — that
        enrollment transfer ships the *full* model, so it is charged at
        ``ref_bytes`` (the uncompressed fp32 reference), not the compressed
        per-round rate: a 100×-compressed downlink run must still account
        for how clients got the model in the first place.
        """
        tel = self.telemetry
        with tel.timer("phase.downlink"):
            if self.downlink_codec is None:
                self.total_downlink_bytes += self.download_bytes
                if tel:
                    tel.counter("comm.broadcasts")
                    tel.counter("comm.download_bytes", self.download_bytes)
                return global_params, self.download_bytes
            nbytes = self.download_bytes
            if self._dl_ref is None:
                self._dl_ref = jax.tree.map(
                    lambda g: g.astype(jnp.float32), global_params)
                nbytes = self.ref_bytes      # enrollment: full-model transfer
            else:
                delta = jax.tree.map(
                    lambda g, ref: g.astype(jnp.float32) - ref,
                    global_params, self._dl_ref)
                if self._dl_residual is not None:
                    delta = jax.tree.map(jnp.add, delta, self._dl_residual)
                payload = self.downlink_codec.encode(delta)
                decoded = self.downlink_codec.decode(payload)
                if not self.downlink_codec.lossless:
                    self._dl_residual = jax.tree.map(
                        jnp.subtract, delta, decoded)
                self._dl_ref = jax.tree.map(jnp.add, self._dl_ref, decoded)
            self.total_downlink_bytes += nbytes
            out = jax.tree.map(lambda ref, g: ref.astype(g.dtype),
                               self._dl_ref, global_params)
            if tel:
                jax.block_until_ready(out)
                tel.counter("comm.broadcasts")
                tel.counter("comm.download_bytes", nbytes)
        return out, nbytes
