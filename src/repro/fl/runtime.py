"""FFT round engine (Algorithm 1 + Algorithm 2).

Drives: client selection → failure draw → parallel local SGD (clients +
server, Eq. 2–3) → strategy aggregation (Eq. 5/7). Supports full- and
partial-parameter (LoRA) fine-tuning, all strategies in
``repro.core.strategies``, and the ResourceOpt network interventions.
The round loop itself is pluggable (``repro.fl.server``):
``FFTConfig.server_mode`` picks the synchronous driver or the
staleness-buffered asynchronous/buffered ones.  Client uploads travel
through the communication codec (``FFTConfig.codec``, ``repro.fl.comm``):
encoded client-side after the local update, decoded server-side before
strategy aggregation, with the codec's exact byte count pricing the upload
in the deadline simulator.

Local updates are one jitted ``lax.scan`` of E minibatch-SGD steps; client
datasets are resampled to a common static shape so a single compiled update
serves every participant. In LoRA mode the frozen base is an argument of the
jitted programs, never a constant compiled into them.

Targets are class labels (N,) or next-token targets (N, S). Token targets
take the mean next-token cross-entropy over all N x S positions, and the
FedAuto rows count hashed token buckets (``repro.data.tokens``) in place of
classes.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.strategies import Strategy
from repro.data.synthetic import Dataset
from repro.data.tokens import token_buckets, token_class_histogram
from repro.fl import failures as fail_mod
from repro.fl import network as net_mod
from repro.fl.lora import LoRAConfig, apply_lora, lora_init
from repro.fl.partition import class_histogram


@dataclasses.dataclass
class FFTConfig:
    n_clients: int = 20
    k_selected: int = 20                  # K (20 = full participation)
    local_steps: int = 5                  # E
    batch_size: int = 32
    lr: float = 0.05
    lr_boundary: Optional[int] = None     # step decay at this round
    failure_mode: str = "mixed"           # none | transient | intermittent |
    #                                       mixed | scenario:<name> | replay:<path>
    duration_max: int = 10
    model_bytes: Optional[float] = None   # fp32 upload bytes; None = derive
    #                                       from the actual trainable pytree
    tx_delay_s: float = 0.8
    resource_opt: Optional[str] = None    # None | "joint" | "per_standard"
    seed: int = 0
    eval_every: int = 10
    eval_batch: int = 256
    # --- scenario engine (repro.fl.scenarios) ---------------------------------
    deadline_s: float = 30.0              # server round timeout (scenario modes)
    compute_s: float = 2.0                # mean local-compute wall-clock per round
    engine: str = "vectorized"            # timing engine: "vectorized" batch
    #                                       closed-form | "heap" reference
    #                                       event loop (bit-identical)
    cohort_size: int = 0                  # stream clients through the round in
    #                                       fixed-size cohorts (0 = whole
    #                                       population at once); bounds peak
    #                                       memory at O(cohort) for the
    #                                       timing arrays and local updates
    trace_record: Optional[str] = None    # NDJSON path: record realized rounds
    trace_replay: Optional[str] = None    # NDJSON path: replay (overrides
    #                                       failure_mode)
    trace_mode: str = "auto"              # "full": per-client rows every round
    #                                       (v1–v4 behavior); "sketch": v5
    #                                       bounded rows — per-round counts,
    #                                       cause histogram + GK sketches,
    #                                       regenerable from the seed;
    #                                       "auto": full below
    #                                       TRACE_SKETCH_THRESHOLD clients,
    #                                       sketch at or above it
    # --- asynchronous server (repro.fl.server) --------------------------------
    server_mode: str = "sync"             # sync | async | buffered
    tau_max: int = 5                      # max staleness (rounds) accepted async
    buffer_k: int = 4                     # buffered mode: arrivals per agg step
    streaming_agg: str = "auto"           # "auto": streaming-capable strategies
    #                                       aggregate packed uploads through the
    #                                       StreamAccumulator (K arrivals never
    #                                       materialize K fp32 models); "off":
    #                                       force the materializing path
    #                                       (per-client decoded models) — the
    #                                       benchmark's control arm
    # --- communication codec (repro.fl.comm) ----------------------------------
    codec: str = "fp32"                   # fp32 | fp16 | int8 | qsgd:<bits> |
    #                                       topk:<frac> | sign1 | lora_only |
    #                                       adaptive:<lo>-<hi>
    skip_stragglers: bool = False         # adaptive runs: exclude clients whose
    #                                       capacity estimate cannot land even
    #                                       the lowest rung from selection
    #                                       (telemetry outcome
    #                                       "skipped_straggler")
    controller_state_in: Optional[str] = None   # JSON path: warm-start the
    #                                       adaptive controller's capacity
    #                                       estimates from a previous run
    controller_state_out: Optional[str] = None  # JSON path: persist the
    #                                       controller's converged estimates
    #                                       at run end
    downlink_codec: Optional[str] = None  # broadcast codec; None = fp32 for
    #                                       static runs, the hi rung for
    #                                       adaptive ones ("fp32" forces the
    #                                       uncompressed broadcast)
    fidelity_discount_b: float = 0.0      # exponent b of the (1−d)^b post-QP
    #                                       fidelity discount applied by the
    #                                       fedauto/fedauto_async strategies
    #                                       to each upload's measured
    #                                       compression distortion d (0 = no
    #                                       discount, today's behavior; a
    #                                       strategy's own fidelity_discount
    #                                       knob overrides this)
    # --- run telemetry (repro.obs) --------------------------------------------
    telemetry: Any = False                # per-round flight recorder; off =
    #                                       shared no-op hub, bit-identical
    #                                       to an uninstrumented run.
    #                                       True/"full": per-client rows;
    #                                       "sketch": bounded-memory mode —
    #                                       exact counters/byte totals +
    #                                       streaming quantile sketches,
    #                                       state O(rounds + K) instead of
    #                                       O(n_clients × rounds)
    telemetry_log: Optional[str] = None   # NDJSON event-log path (implies
    #                                       telemetry; observational only —
    #                                       replay never reads it)
    telemetry_console: bool = False       # per-round terminal summary line
    #                                       (implies telemetry)
    telemetry_sketch_k: int = 64          # sketch mode: reservoir-sample rows
    telemetry_health: bool = True         # online run-health monitors (when
    #                                       telemetry is on): alarm records +
    #                                       run-end verdict; observational
    telemetry_trace: Optional[str] = None  # directory: run() runs under
    #                                       the JAX profiler and writes its
    #                                       trace there (xplane + Perfetto
    #                                       JSON: phase spans and device ops;
    #                                       does not turn telemetry on)
    telemetry_dashboard: bool = False     # in-place live console dashboard
    #                                       (implies telemetry)


class FFTRunner:
    """One experiment: (model, data split, network, strategy) → accuracy curve."""

    def __init__(self, cfg: FFTConfig, init_fn: Callable, apply_fn: Callable,
                 public: Dataset, client_indices: Sequence[np.ndarray],
                 private: Dataset, test: Dataset,
                 lora_cfg: Optional[LoRAConfig] = None,
                 pretrain_steps: int = 0):
        self.cfg = cfg
        self.apply_fn = apply_fn
        self.n_clients = cfg.n_clients
        self.k_selected = cfg.k_selected
        self.local_steps = cfg.local_steps
        self.lora_cfg = lora_cfg
        self.rng = np.random.default_rng(cfg.seed)
        key = jax.random.PRNGKey(cfg.seed)

        self.public = public
        self.test = test
        self.n_classes = public.n_classes

        # --- per-client data, resampled to a common static size ------------
        sizes = [max(len(ix), 1) for ix in client_indices]
        self.data_size = max(max(sizes), cfg.batch_size)
        self.client_x, self.client_y = [], []
        for ix in client_indices:
            ix = np.asarray(ix)
            if len(ix) == 0:
                ix = np.array([0])
            res = self.rng.choice(ix, self.data_size, replace=True)
            self.client_x.append(jnp.asarray(private.x[res]))
            self.client_y.append(jnp.asarray(private.y[res]))
        self.client_hists = np.stack([
            self._histogram(private.y[np.asarray(ix)])
            if len(ix) else np.zeros(self.n_classes, dtype=np.int64)
            for ix in client_indices])
        self.server_hist = self._histogram(public.y)
        self.global_hist = self.server_hist + self.client_hists.sum(axis=0)

        pub_res = self.rng.choice(len(public.y), self.data_size, replace=True)
        self.public_x = jnp.asarray(public.x[pub_res])
        self.public_y = jnp.asarray(public.y[pub_res])
        self.public_x_raw = jnp.asarray(public.x)
        self.public_y_raw = jnp.asarray(public.y)

        # p weights (Eq. 1): dataset-size proportions, index 0 = server
        counts = np.array([len(public.y)] + [max(len(ix), 1)
                                             for ix in client_indices], float)
        self.p = counts / counts.sum()

        # --- params ---------------------------------------------------------
        self.base_params = init_fn(key)
        if lora_cfg is not None:
            self.global_params = lora_init(jax.random.fold_in(key, 1),
                                           self.base_params, lora_cfg)
        else:
            self.global_params = self.base_params

        # --- communication codec (repro.fl.comm) ------------------------------
        # The trainable pytree (adapters in LoRA mode, full params otherwise)
        # fixes the wire sizes: model_bytes derives from it unless the config
        # overrides, and the codec's exact compression ratio prices uploads.
        from repro.fl.comm import (CommState, is_adaptive_spec, make_codec,
                                   parse_adaptive_spec)
        self.adaptive_spec = cfg.codec if is_adaptive_spec(cfg.codec) else None
        if self.adaptive_spec:
            self._rung_lo, self._rung_hi = parse_adaptive_spec(cfg.codec)
            # the hi rung is the ceiling: it fixes the static accounting
            # (upload_bytes, ctx.upload_nbytes) the controller adapts below
            static_codec = make_codec(self._rung_hi)
        else:
            static_codec = make_codec(cfg.codec)
        dl_spec = cfg.downlink_codec
        if dl_spec is None and self.adaptive_spec:
            dl_spec = self._rung_hi
        self.downlink_codec_resolved = dl_spec or "fp32"
        dl_codec = (None if self.downlink_codec_resolved == "fp32"
                    else make_codec(self.downlink_codec_resolved))
        self.comm = CommState(static_codec, self.global_params,
                              model_bytes_override=cfg.model_bytes,
                              lora_cfg=lora_cfg, downlink_codec=dl_codec,
                              n_clients=cfg.n_clients)
        self.model_bytes = self.comm.ref_bytes            # fp32 reference size
        self.upload_bytes = self.comm.upload_bytes        # codec wire size
        self.download_bytes = self.comm.download_bytes    # broadcast wire size

        # --- network + failures ----------------------------------------------
        self.channels = net_mod.build_network(cfg.n_clients, seed=cfg.seed)
        rate = net_mod.uplink_rate(self.upload_bytes, cfg.tx_delay_s)
        if cfg.resource_opt:
            self.channels = net_mod.resource_opt(
                self.channels, rate, per_standard=cfg.resource_opt == "per_standard",
                seed=cfg.seed)
        mode = (f"replay:{cfg.trace_replay}" if cfg.trace_replay
                else cfg.failure_mode)
        self.failure_mode_resolved = mode
        if cfg.engine not in ("heap", "vectorized"):
            raise ValueError(f"unknown engine {cfg.engine!r}")
        self.failures = fail_mod.make_failure_model(
            mode, self.channels, rate,
            duration_max=cfg.duration_max, seed=cfg.seed,
            model_bytes=self.model_bytes, deadline_s=cfg.deadline_s,
            compute_s=cfg.compute_s, engine=cfg.engine)
        if cfg.server_mode not in ("sync", "async", "buffered"):
            raise ValueError(f"unknown server_mode {cfg.server_mode!r}")
        if cfg.streaming_agg not in ("auto", "off"):
            raise ValueError(f"unknown streaming_agg {cfg.streaming_agg!r} "
                             "(known: auto, off)")
        if ((cfg.server_mode != "sync" or self.adaptive_spec)
                and not hasattr(self.failures, "draw_events")):
            # Legacy boolean failure models have no time dimension; the async
            # server needs per-client arrival instants — and so does the
            # adaptive codec controller, whose whole input is arrival times —
            # so synthesize them from the physical channels (capacity ->
            # upload time, Eq. 41).
            from repro.fl.server.timeline import TimedFailureAdapter
            self.failures = TimedFailureAdapter(
                self.failures, self.channels, model_bytes=self.model_bytes,
                deadline_s=cfg.deadline_s, compute_s=cfg.compute_s,
                seed=cfg.seed, engine=cfg.engine)
        sim = getattr(self.failures, "sim", None)
        if sim is not None and cfg.cohort_size:
            sim.cohort_size = int(cfg.cohort_size)
        # Wire sizes into the timing model: uploads carry the codec's payload,
        # downloads the (possibly compressed) global broadcast.  Adaptive
        # runs re-price every round through the controller; this is the
        # round-1-and-static default.
        self.failures.set_payload_bytes(
            upload_bytes=np.full(cfg.n_clients, self.upload_bytes),
            download_bytes=np.full(cfg.n_clients, self.download_bytes))
        self.controller = None
        if self.adaptive_spec:
            from repro.fl.comm import AdaptiveCommController
            self.controller = AdaptiveCommController(
                cfg.n_clients, self.comm, lo=self._rung_lo, hi=self._rung_hi,
                deadline_s=cfg.deadline_s, compute_s=cfg.compute_s)
        if cfg.trace_replay:
            # self.failures is the ReplayFailureModel here (replay overrides
            # failure_mode and always has draw_events, so it is never
            # wrapped).  Codec AND wire sizes must match the recording: the
            # recorded timings were priced at the recorded byte counts.
            if self.failures.codec != cfg.codec:
                raise ValueError(
                    f"trace {cfg.trace_replay} was recorded under codec "
                    f"{self.failures.codec!r} but this run uses "
                    f"{cfg.codec!r}; the recorded upload timings would be "
                    "wrong — replay with the matching codec")
            rec_dl = self.failures.header.get("downlink_codec") or "fp32"
            if rec_dl != self.downlink_codec_resolved:
                raise ValueError(
                    f"trace {cfg.trace_replay} was recorded under downlink "
                    f"codec {rec_dl!r} but this run uses "
                    f"{self.downlink_codec_resolved!r}; the recorded "
                    "download timings would be wrong — replay with the "
                    "matching downlink_codec")
            # adaptive runs have no single upload size; the per-round byte
            # vectors in the v3 rounds are cross-checked by the round loop
            checks = [("model_bytes", self.model_bytes),
                      ("download_bytes", self.download_bytes)]
            if not self.adaptive_spec:
                checks.append(("upload_bytes", self.upload_bytes))
            for field, ours in checks:
                rec = self.failures.header.get(field)
                if rec is not None and not np.isclose(float(rec), ours,
                                                      rtol=1e-6):
                    raise ValueError(
                        f"trace {cfg.trace_replay} was recorded with "
                        f"{field}={float(rec):.0f} but this run derives "
                        f"{ours:.0f}; the recorded upload timings would be "
                        "wrong — replay with the matching model_bytes")
        mc = np.random.default_rng(cfg.seed + 7)
        self.eps_estimates = np.array([
            c.outage_probability(rate, mc, 200) for c in self.channels])

        # --- run telemetry (repro.obs; per-run hub built by run()) ------------
        from repro.obs import NULL_TELEMETRY
        self.telemetry = NULL_TELEMETRY
        self.report = None                # RunReport of the last telemetry run

        # --- jitted kernels ---------------------------------------------------
        self._build_jits()
        self._key = jax.random.fold_in(key, 2)

        if pretrain_steps:
            self.pretrain(pretrain_steps)

    # ------------------------------------------------------------------ jits
    def trainable(self, params):
        return params

    def _histogram(self, y):
        """FedAuto's row of targets: class counts, or for token targets
        (N, S) the counts of their hash buckets."""
        if np.ndim(y) > 1:
            return token_class_histogram(np.asarray(y), self.n_classes)
        return class_histogram(y, self.n_classes)

    def _base(self):
        """The frozen base the jitted programs take (LoRA), else None."""
        return self.base_params if self.lora_cfg is not None else None

    def _effective(self, base, t):
        if self.lora_cfg is not None:
            return apply_lora(base, t, self.lora_cfg)
        return t

    def _build_jits(self):
        apply_fn = self.apply_fn
        E, bs = self.cfg.local_steps, self.cfg.batch_size

        def loss(t, base, x, y):
            logits = apply_fn(self._effective(base, t), x)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            if y.ndim == 1:
                return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
            # next-token targets (N, S): the mean over all N x S positions
            return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

        @jax.jit
        def local_update(base, t, t_global, corr, x, y, key, lr, mu):
            n = x.shape[0]

            def step(tt, k):
                idx = jax.random.randint(k, (bs,), 0, n)
                g = jax.grad(loss)(tt, base, x[idx], y[idx])
                g = jax.tree.map(
                    lambda gg, p_, pg: gg.astype(jnp.float32) +
                    mu * (p_.astype(jnp.float32) - pg.astype(jnp.float32)),
                    g, tt, t_global)
                # corr None (an empty pytree) traces a program of its own
                # with no correction term; SCAFFOLD's tree is added here
                if corr is not None:
                    g = jax.tree.map(jnp.add, g, corr)
                tt = jax.tree.map(lambda p_, gg: (p_.astype(jnp.float32) -
                                                  lr * gg).astype(p_.dtype), tt, g)
                return tt, None

            keys = jax.random.split(key, E)
            t, _ = jax.lax.scan(step, t, keys)
            return t

        def local_update_at_base(t, t_global, corr, x, y, key, lr, mu):
            return local_update(self._base(), t, t_global, corr, x, y, key, lr, mu)

        self._update_program = local_update          # jit(base, t, ...)
        self._local_update = local_update_at_base

        @jax.jit
        def accuracy_batch(base, t, x, y):
            logits = apply_fn(self._effective(base, t), x)
            return jnp.sum(jnp.argmax(logits, -1) == y)

        self._accuracy_batch = accuracy_batch

        @jax.jit
        def loss_on(base, t, x, y):
            return loss(t, base, x, y)

        self._loss_on = loss_on

    # -------------------------------------------------------------- helpers
    def lr(self, rnd: int) -> float:
        if self.cfg.lr_boundary is not None and rnd > self.cfg.lr_boundary:
            return self.cfg.lr * 0.1
        return self.cfg.lr

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def run_local(self, t_global, x, y, rnd, *, mu=0.0, corr=None):
        tel = self.telemetry
        with tel.timer("phase.local_update", round=rnd):
            out = self._local_update(t_global, t_global, corr, x, y,
                                     self._next_key(), self.lr(rnd), mu)
            if tel:
                tel.counter("local_update.calls")
                if corr is not None:
                    tel.counter("local_update.corrected")
                # the update is one jitted lax.scan: without a sync the timer
                # would stop at dispatch, not completion
                jax.block_until_ready(out)
        return out

    def loss_on(self, t, x, y):
        return self._loss_on(self._base(), t, x, y)

    def public_proxy_batch(self, n: int, rnd: int):
        idx = self.rng.integers(0, len(self.public_y_raw), n)
        return self.public_x_raw[idx], self.public_y_raw[idx]

    def fold_into_base(self, path: str, resid):
        from repro.fl.lora import _get, _set
        w = _get(self.base_params, path)
        _set(self.base_params, path,
             (w.astype(jnp.float32) + resid).astype(w.dtype))

    def train_compensatory(self, miss_mask: np.ndarray, rnd: int):
        """Module 1 (Eq. 6): E SGD steps on the missing-class public subset."""
        with self.telemetry.timer("phase.compensatory", round=rnd):
            miss_classes = np.where(miss_mask)[0]
            public_y = np.asarray(self.public_y_raw)
            if public_y.ndim > 1:
                # token rows: those holding a target in a missing bucket
                buckets = token_buckets(public_y, self.n_classes)
                sel = np.isin(buckets, miss_classes).any(axis=1)
            else:
                sel = np.isin(public_y, miss_classes)
            idx = np.where(sel)[0]
            if len(idx) == 0:
                return None, None
            res = self.rng.choice(idx, self.data_size, replace=True)
            x = self.public_x_raw[res]
            y = self.public_y_raw[res]
            model = self.run_local(self.global_params, x, y, rnd)
            hist = self._histogram(public_y[idx])
        return model, hist

    def pretrain(self, steps: int) -> None:
        """Stage 1 (§II-B1): server pre-training on the public dataset."""
        t = self.global_params
        for s in range(0, steps, self.cfg.local_steps):
            t = self.run_local(t, self.public_x, self.public_y, 0)
        self.global_params = t

    def evaluate(self) -> float:
        with self.telemetry.timer("phase.eval"):
            t, base = self.global_params, self._base()
            bs = self.cfg.eval_batch
            n = len(self.test.y)
            correct = 0
            for i in range(0, n, bs):
                x = jnp.asarray(self.test.x[i:i + bs])
                y = jnp.asarray(self.test.y[i:i + bs])
                # int() already forces the device sum, so the timer is honest
                correct += int(self._accuracy_batch(base, t, x, y))
            # token targets: the share of next tokens predicted
            return correct / np.size(self.test.y)

    def _draw_network(self, r: int):
        """(up, met_deadline, RoundEvents|None) for round ``r``.

        Scenario/replay models expose full per-client timing via
        ``draw_events``; legacy models have no time dimension, so every
        surviving draw trivially meets the deadline."""
        if hasattr(self.failures, "draw_events"):
            events = self.failures.draw_events(r)
            return events.up_mask(), events.deadline_mask(), events
        up = self.failures.draw(r)
        return up, np.ones(self.n_clients, dtype=bool), None

    # ------------------------------------------------------------------ run
    def run(self, strategy: Strategy, rounds: int,
            log: Optional[Callable[[int, float], None]] = None) -> List[float]:
        """Drive ``rounds`` rounds under ``cfg.server_mode``'s loop.

        Returns the accuracy history (one entry per evaluation, as before);
        ``self.timeline`` additionally holds ``TimePoint(rnd, t_s, acc)``
        entries indexed by simulated wall-clock seconds, and ``self.loop``
        exposes the driver (staleness stats for the async modes)."""
        from repro.fl.server.loops import TimePoint, make_round_loop

        strategy.init_state(self)
        self.failures.reset()
        self.comm.reset()                 # error-feedback residuals per run
        if self.controller is not None:
            self.controller.reset()       # capacity estimates per run
            if self.cfg.controller_state_in:
                # warm start: seed this run's capacity estimates with a
                # previous run's converged state (reset first, so a missing
                # field in the file falls back to the cold-start value)
                self.controller.load_state(self.cfg.controller_state_in)
        self.report = None
        self.telemetry = self._make_telemetry(strategy, rounds)
        tracer = None
        if self.cfg.trace_record:
            from repro.fl.scenarios.trace import TraceRecorder
            # resolved mode: a replayed run's re-recording must name the
            # replay source, not the scenario the config nominally asked for
            version_override = {}
            if self.cfg.trace_replay and self.adaptive_spec:
                src_v = int(self.failures.header.get("version", 0) or 0)
                if 0 < src_v < 4:
                    # a legacy replay re-derives its controller trajectory
                    # under the pre-v4 enrollment pricing; stamp the
                    # re-recording with the source version so future replays
                    # apply the same shim instead of tripping the drift check
                    version_override = {"version": src_v}
            tracer = TraceRecorder(self.cfg.trace_record, {
                **version_override,
                "scenario": self.failure_mode_resolved,
                "n_clients": self.n_clients,
                "deadline_s": self.cfg.deadline_s,
                "compute_s": self.cfg.compute_s,
                "model_bytes": self.model_bytes,
                "codec": self.cfg.codec,
                # adaptive runs have no single upload size: the per-round
                # per-client byte vectors in the round records are the truth
                "upload_bytes": (None if self.adaptive_spec
                                 else self.upload_bytes),
                "downlink_codec": self.downlink_codec_resolved,
                "download_bytes": self.download_bytes,
                "seed": self.cfg.seed}, mode=self.cfg.trace_mode)
        self.timeline: List[TimePoint] = []
        self.loop = make_round_loop(self.cfg.server_mode, self, strategy,
                                    tracer=tracer, log=log)
        profile = contextlib.nullcontext()
        if self.cfg.telemetry_trace:
            # program spans and device ops; the Python tracer (every call)
            # would slow the run and crowd the Perfetto file
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            profile = jax.profiler.trace(self.cfg.telemetry_trace,
                                         create_perfetto_trace=True,
                                         profiler_options=options)
        with profile:
            try:
                return self.loop.run(rounds)
            finally:
                self.telemetry.end_run()
                if tracer is not None:
                    tracer.close()
                if (self.controller is not None
                        and self.cfg.controller_state_out):
                    self.controller.save_state(
                        self.cfg.controller_state_out)

    def _make_telemetry(self, strategy: Strategy, rounds: int):
        """Build this run's telemetry hub (a fresh one per run, like the
        error-feedback residuals) and attach it to every collaborator that
        emits into it.  Disabled (the default) this is the shared falsy
        no-op hub — zero per-round work, bit-identical histories."""
        from repro.obs import (ConsoleSink, DashboardSink, HealthMonitors,
                               NdjsonSink, NULL_TELEMETRY, RunReport,
                               SketchReport, SketchState, Telemetry)
        cfg = self.cfg
        mode = cfg.telemetry
        if mode is True:
            mode = "full"
        elif mode and mode not in ("full", "sketch"):
            raise ValueError(f"FFTConfig.telemetry must be False, True, "
                             f"'full', or 'sketch', got {cfg.telemetry!r}")
        enabled = bool(mode or cfg.telemetry_log or cfg.telemetry_console
                       or cfg.telemetry_dashboard)
        if enabled:
            mode = mode or "full"
            sketch = None
            if mode == "sketch":
                # bounded-memory mode: per-client events fold into sketches;
                # the report mirrors RunReport's aggregate API
                sketch = SketchState(self.n_clients,
                                     k=cfg.telemetry_sketch_k, seed=cfg.seed)
                self.report = SketchReport()
            else:
                self.report = RunReport()
            sinks = [self.report]
            if cfg.telemetry_log:
                sinks.append(NdjsonSink(cfg.telemetry_log))
            if cfg.telemetry_console:
                sinks.append(ConsoleSink())
            if cfg.telemetry_dashboard:
                # after the report sink, so each frame sees the new round
                sinks.append(DashboardSink(self.report))
            health = HealthMonitors() if cfg.telemetry_health else None
            tel = Telemetry(sinks=sinks, sketch=sketch, health=health)
            tel.start_run({
                "scenario": self.failure_mode_resolved,
                "server_mode": cfg.server_mode,
                "strategy": strategy.name,
                "codec": cfg.codec,
                "downlink_codec": self.downlink_codec_resolved,
                "n_clients": self.n_clients,
                "k_selected": self.k_selected,
                "rounds": rounds,
                "deadline_s": cfg.deadline_s,
                "tau_max": cfg.tau_max,
                "seed": cfg.seed})
        else:
            tel = NULL_TELEMETRY
        # observational fan-in points; each holds NULL_TELEMETRY otherwise
        self.comm.telemetry = tel
        if self.controller is not None:
            self.controller.telemetry = tel
        sim = getattr(self.failures, "sim", None)
        if sim is not None:
            sim.telemetry = tel
        return tel
