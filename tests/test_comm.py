"""Communication codec subsystem (repro.fl.comm) + bytes-on-wire threading.

Covers the ISSUE-3 checklist: registry parsing, round-trip exactness of the
lossless codecs, quantizer error bounds, error-feedback residual
contraction, byte accounting through the deadline simulator (compression
converting deadline drops into participants), sync-vs-async equivalence at
infinite deadline under every codec, the fused Pallas dequantize-and-
β-accumulate kernel vs the fp32 path, and the v2 trace schema.
"""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import aggregate_pytrees
from repro.core.strategies import STRATEGIES
from repro.fl.comm import (CommState, aggregate_quantized, fp32_nbytes,
                           is_quantized, make_codec)
from repro.fl.runtime import FFTConfig
from repro.fl.scenarios.engine import (CAUSE_DEADLINE, DeadlineSimulator,
                                       LinkState)
from repro.fl.toy import make_toy_runner

ALL_SPECS = ["fp32", "fp16", "int8", "qsgd:4", "topk:0.25", "sign1"]


def _tree(seed=0, shapes=((13, 7), (7,), (3, 5, 2))):
    rng = np.random.default_rng(seed)
    return {f"l{i}": jnp.asarray(rng.normal(size=s), jnp.float32)
            for i, s in enumerate(shapes)}


def _maxdiff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec", ALL_SPECS)
def test_registry_builds_every_spec(spec):
    c = make_codec(spec)
    assert c.name == spec or spec in ("topk:0.25",)  # topk normalizes float
    p = c.encode(_tree())
    assert p.nbytes == c.nbytes(_tree())


@pytest.mark.parametrize("spec", ["fp99", "qsgd:", "qsgd:0", "qsgd:9",
                                  "qsgd:x", "topk:0", "topk:1.5", "topk:x",
                                  "huff:2"])
def test_registry_rejects_bad_specs(spec):
    with pytest.raises(ValueError):
        make_codec(spec)


def test_byte_counts_are_value_independent_and_exact():
    t = _tree()
    n = sum(l.size for l in jax.tree.leaves(t))
    leaves = len(jax.tree.leaves(t))
    assert make_codec("fp32").nbytes(t) == 4 * n == fp32_nbytes(t)
    assert make_codec("fp16").nbytes(t) == 2 * n
    assert make_codec("int8").nbytes(t) == n + 4 * leaves
    assert make_codec("qsgd:4").nbytes(t) == sum(
        math.ceil(4 * l.size / 8) + 4 for l in jax.tree.leaves(t))
    assert make_codec("sign1").nbytes(t) == sum(
        math.ceil(l.size / 8) + 4 for l in jax.tree.leaves(t))
    assert make_codec("topk:0.25").nbytes(t) == sum(
        8 * max(1, math.ceil(0.25 * l.size)) for l in jax.tree.leaves(t))
    # value-independence: zeros cost the same as noise
    zeros = jax.tree.map(jnp.zeros_like, t)
    for spec in ALL_SPECS:
        assert make_codec(spec).encode(zeros).nbytes == \
            make_codec(spec).encode(t).nbytes


# ---------------------------------------------------------------------------
# round-trip exactness (lossless family) and quantizer error bounds
# ---------------------------------------------------------------------------
def test_fp32_round_trip_exact():
    c = make_codec("fp32")
    t = _tree()
    assert _maxdiff(c.decode(c.encode(t)), t) == 0.0


def test_fp16_round_trip_exact_on_fp16_values():
    c = make_codec("fp16")
    t = jax.tree.map(lambda l: l.astype(jnp.float16).astype(jnp.float32),
                     _tree())
    assert _maxdiff(c.decode(c.encode(t)), t) == 0.0


def test_lora_only_round_trip_exact_and_guards():
    c = make_codec("lora_only")
    adapters = {"blk/qkv/w": {"a": jnp.ones((8, 4)), "b": jnp.zeros((4, 8))}}

    class _L:  # minimal lora_cfg stand-in
        rank = 4

    c.validate_template(adapters, lora_cfg=_L())
    assert _maxdiff(c.decode(c.encode(adapters)), adapters) == 0.0
    with pytest.raises(ValueError, match="lora"):
        c.validate_template(adapters, lora_cfg=None)      # not a LoRA run
    with pytest.raises(ValueError, match="adapter"):
        c.validate_template({"w": jnp.ones((8, 8))}, lora_cfg=_L())


def test_int8_error_bound():
    c = make_codec("int8")
    t = _tree(3)
    dec = c.decode(c.encode(t))
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(dec)):
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(x - y))) <= scale / 2 + 1e-7


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_qsgd_error_bound_tightens_with_bits(bits):
    c = make_codec(f"qsgd:{bits}")
    t = _tree(4)
    dec = c.decode(c.encode(t))
    levels = (1 << (bits - 1)) - 1
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(dec)):
        scale = float(jnp.max(jnp.abs(x))) / levels
        assert float(jnp.max(jnp.abs(x - y))) <= scale / 2 + 1e-7


def test_topk_keeps_exactly_the_largest_coordinates():
    c = make_codec("topk:0.25")
    t = {"w": jnp.asarray(np.random.default_rng(5).normal(size=(10, 8)),
                          jnp.float32)}
    dec = c.decode(c.encode(t))["w"].reshape(-1)
    flat = np.asarray(t["w"]).reshape(-1)
    k = math.ceil(0.25 * flat.size)
    top = np.argsort(-np.abs(flat))[:k]
    np.testing.assert_allclose(dec[top], flat[top], rtol=0)   # kept exactly
    mask = np.ones(flat.size, bool)
    mask[top] = False
    assert np.all(np.asarray(dec)[mask] == 0.0)               # rest zeroed


def test_sign1_is_one_bit_with_l1_scale():
    c = make_codec("sign1")
    t = {"w": jnp.asarray([[1.5, -0.5, 2.0, -1.0]], jnp.float32)}
    dec = np.asarray(c.decode(c.encode(t))["w"])
    scale = np.mean(np.abs(np.asarray(t["w"])))
    np.testing.assert_allclose(np.abs(dec), scale, rtol=1e-6)
    assert np.all(np.sign(dec) == np.sign(np.asarray(t["w"])))


# ---------------------------------------------------------------------------
# error feedback: residual stays bounded, cumulative decoded mass tracks the
# cumulative true delta (the EF contraction that keeps biased codecs honest)
# ---------------------------------------------------------------------------
def _l2(tree):
    return float(sum(jnp.sum(jnp.square(l))
                     for l in jax.tree.leaves(tree))) ** 0.5


@pytest.mark.parametrize("spec", ["fp16", "int8", "qsgd:4", "topk:0.25",
                                  "sign1"])
def test_compressor_is_a_contraction(spec):
    """Every lossy codec is a δ-contraction: ‖x − C(x)‖ < ‖x‖ — the property
    the EF convergence theory needs from the compressor itself."""
    c = make_codec(spec)
    x = _tree(11)
    err = jax.tree.map(jnp.subtract, x, c.decode(c.encode(x)))
    assert _l2(err) < _l2(x) * (1.0 - 1e-4)


@pytest.mark.parametrize("spec", ["int8", "qsgd:4", "topk:0.25", "sign1"])
def test_error_feedback_residual_contraction(spec):
    """EF invariants under a constant true update delta:

    1. conservation: Σ_t decoded_t + e_T = T·delta exactly — the wire never
       loses mass, it only delays it;
    2. the residual respects the contraction bound ‖e_t‖ ≤ γ/(1−γ)·‖delta‖
       where γ is the codec's worst observed per-step contraction factor
       (< 1 by the test above), so the mean decoded update converges to
       delta at rate O(‖e‖/T).
    """
    st = CommState(make_codec(spec), _tree())
    g = jax.tree.map(jnp.zeros_like, _tree())      # global stays at 0
    delta = _tree(7)                               # constant true update
    model = jax.tree.map(lambda gg, d: gg + d, g, delta)
    T = 30
    acc = jax.tree.map(jnp.zeros_like, g)
    gamma = 0.0
    for _ in range(T):
        prev = st.residual(0)
        carry = delta if prev is None else jax.tree.map(jnp.add, delta, prev)
        recon, _, _ = st.roundtrip(0, model, g)
        acc = jax.tree.map(lambda a, r: a + r, acc, recon)
        gamma = max(gamma, _l2(st.residual(0)) / max(_l2(carry), 1e-12))
    assert gamma < 1.0 - 1e-4                      # contraction every step
    bound = gamma / (1.0 - gamma) * _l2(delta)
    assert _l2(st.residual(0)) <= bound * (1.0 + 1e-3)
    # conservation: acc + e_T == T·delta, leaf-wise
    total = jax.tree.map(lambda a, e: a + e, acc, st.residual(0))
    want = jax.tree.map(lambda d: T * d, delta)
    assert _maxdiff(total, want) <= 1e-3


def test_lossless_codecs_keep_no_residual():
    for spec in ["fp32", "lora_only"]:
        codec = make_codec(spec)
        tmpl = ({"p/x": {"a": jnp.ones((4, 2)), "b": jnp.zeros((2, 4))}}
                if spec == "lora_only" else _tree())

        class _L:
            rank = 2

        st = CommState(codec, tmpl, lora_cfg=_L() if spec == "lora_only"
                       else None)
        model = jax.tree.map(lambda l: l + 1.0, tmpl)
        recon, payload, dist = st.roundtrip(0, model, tmpl)
        assert _maxdiff(recon, model) == 0.0
        assert st.residual(0) is None
        assert dist == 0.0                         # lossless: exactly zero
        assert payload.nbytes == codec.nbytes(tmpl)


class _Lora:
    rank = 2


def _mixed_tree(seed, lora=False):
    """Leaves of mixed shapes and dtypes, a 0-d and a bf16 one among them;
    ``lora`` arranges them as an adapter dict ({path: {'a', 'b'}})."""
    rng = np.random.default_rng(seed)
    leaf = lambda shape, dt=jnp.float32: jnp.asarray(
        rng.normal(size=shape), dt)
    if lora:
        return {"blk0/qkv": {"a": leaf((6, 2)), "b": leaf((2, 6))},
                "blk1/qkv": {"a": leaf((6, 2), jnp.bfloat16),
                             "b": leaf((2, 6), jnp.bfloat16)},
                "head": {"a": leaf(()), "b": leaf((1,))}}
    return {"conv": leaf((3, 3, 2, 4)), "norm": leaf((9,), jnp.bfloat16),
            "scale": leaf(()), "fc": leaf((17, 5))}


@pytest.mark.parametrize("carried", [False, True],
                         ids=["no_residual", "carried_residual"])
@pytest.mark.parametrize("spec", ["fp32", "fp16", "int8", "qsgd:4", "sign1",
                                  "topk:0.1", "lora_only"])
def test_encode_program_matches_eager_formula(spec, carried):
    """The jitted delta-and-encode gives the payload, residual and
    reconstruction of the eager per-leaf formula
    ``codec.encode_leaf(w.astype(f32) - g.astype(f32) [+ residual])``
    bit for bit; the distortion to rounding."""
    codec = make_codec(spec)
    lora = spec == "lora_only"
    g = _mixed_tree(0, lora)
    model = _mixed_tree(1, lora)
    states = [CommState(codec, g, lora_cfg=_Lora() if lora else None)
              for _ in range(2)]
    if carried:
        # a lossy upload first leaves a residual behind; a lossless codec
        # then flushes it, a lossy one carries it on
        for st in states:
            st.roundtrip(0, _mixed_tree(2, lora), g, codec=make_codec("int8"))
    resid = states[0].residual(0)
    assert (resid is not None) == carried

    leaves, treedef = jax.tree.flatten(model)
    carry = [w.astype(jnp.float32) - gg.astype(jnp.float32)
             for w, gg in zip(leaves, jax.tree.leaves(g))]
    if carried:
        carry = [c + r for c, r in zip(carry, jax.tree.leaves(resid))]
    want = [codec.encode_leaf(c) for c in carry]
    decoded = [codec.decode_leaf(e) for e in want]

    payload, dist = states[0].encode_upload(0, model, g)
    recon, payload_rt, dist_rt = states[1].roundtrip(0, model, g)
    for p in (payload, payload_rt):
        assert p.codec == codec.name and p.treedef == treedef
        assert p.nbytes == sum(e.nbytes for e in want) == codec.nbytes(g)
        for got, exp in zip(p.leaves, want, strict=True):
            assert got.shape == exp.shape and got.nbytes == exp.nbytes
            assert set(got.data) == set(exp.data)
            for k in exp.data:
                np.testing.assert_array_equal(np.asarray(got.data[k]),
                                              np.asarray(exp.data[k]))
    want_recon = [(gg.astype(jnp.float32) + d).astype(gg.dtype)
                  for gg, d in zip(jax.tree.leaves(g), decoded)]
    for got, exp in zip(jax.tree.leaves(recon), want_recon, strict=True):
        assert got.dtype == exp.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    if codec.lossless:
        want_resid, want_dist = None, 0.0
    else:
        want_resid = [c - d for c, d in zip(carry, decoded)]
        want_dist = _l2(want_resid) / _l2(carry)
    for st, d in ((states[0], dist), (states[1], dist_rt)):
        got_resid = st.residual(0)
        if want_resid is None:
            assert got_resid is None and d == 0.0
        else:
            for got, exp in zip(jax.tree.leaves(got_resid), want_resid,
                                strict=True):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(exp))
            assert d == pytest.approx(want_dist)
        assert st.last_distortions[0] == d


def test_encode_program_traces_once_per_codec():
    """20 uploads from 5 clients under one codec trace the encode program
    once; a second rung traces one more, and its residual-carrying variant
    (a lossy rung's second upload per client) one after that."""
    from repro.obs.telemetry import Telemetry
    g = _mixed_tree(0)
    st = CommState(make_codec("fp32"), g)
    st.telemetry = tel = Telemetry()
    traces = lambda: tel.counters.get("comm.encode_traces", 0)
    for r in range(4):
        for client in range(5):
            st.encode_upload(client, _mixed_tree(10 * r + client), g)
    assert tel.counters["comm.uploads"] == 20
    assert traces() == 1
    fp16 = st.codec_named("fp16")
    for client in range(5):                        # no residual yet
        st.encode_upload(client, _mixed_tree(100 + client), g, codec=fp16)
    assert traces() == 2
    for r in range(2):                             # residuals carried
        for client in range(5):
            st.encode_upload(client, _mixed_tree(200 + client), g,
                             codec=fp16)
    assert traces() == 3
    for client in range(5):                        # fp32 flushes them
        st.encode_upload(client, _mixed_tree(300 + client), g)
    assert traces() == 4
    assert len(st._residuals) == 0


# ---------------------------------------------------------------------------
# bytes-on-wire through the deadline simulator
# ---------------------------------------------------------------------------
def test_simulator_prices_per_client_per_direction_bytes():
    sim = DeadlineSimulator(2, model_bytes=1e6, deadline_s=1e9,
                            compute_s=0.0, jitter_sigma=0.0, seed=0)
    links = [LinkState(8e6, downlink_ratio=8.0),
             LinkState(8e6, downlink_ratio=8.0)]
    base = sim.simulate_round(1, links)
    # default: both directions priced at model_bytes
    assert base.events[0].t_upload_s == pytest.approx(1.0)
    assert base.events[0].t_download_s == pytest.approx(1.0 / 8.0)
    # per-client uploads: client 1 compressed 4x; downloads stay full-size
    sim.set_payload_bytes(upload_bytes=np.array([1e6, 0.25e6]),
                          download_bytes=1e6)
    ev = sim.simulate_round(2, links)
    assert ev.events[0].t_upload_s == pytest.approx(1.0)
    assert ev.events[1].t_upload_s == pytest.approx(0.25)
    assert ev.events[1].t_download_s == pytest.approx(1.0 / 8.0)


def test_compression_converts_deadline_drops_into_participants():
    """The acceptance mechanism in miniature: a link where fp32 misses the
    deadline but a 4x-smaller int8 payload lands."""
    mk = lambda up: DeadlineSimulator(1, model_bytes=4e6, deadline_s=5.0,
                                      compute_s=1.0, hetero_sigma=0.0,
                                      jitter_sigma=0.0, seed=0)
    links = [LinkState(8e6)]                       # fp32: 4s up + 0.5s down
    slow = mk(None)
    ev = slow.simulate_round(1, links)
    assert not ev.events[0].met_deadline
    assert ev.events[0].cause == CAUSE_DEADLINE
    fast = mk(None)
    fast.set_payload_bytes(upload_bytes=1e6)       # int8-sized: 1s up
    ev = fast.simulate_round(1, links)
    assert ev.events[0].met_deadline


BASE = dict(n_clients=6, k_selected=6, local_steps=2, batch_size=8, lr=0.05,
            seed=0, eval_every=2, model_bytes=4e6, deadline_s=5.0)
TOY = dict(n_samples=600, public_per_class=10, pretrain_steps=9)


def test_runner_derives_model_bytes_from_trainable_pytree():
    cfg = FFTConfig(**{**BASE, "model_bytes": None})
    runner = make_toy_runner(cfg, **TOY)
    assert runner.model_bytes == fp32_nbytes(runner.global_params)
    assert runner.upload_bytes == runner.model_bytes          # fp32 codec
    # explicit override wins, codec ratio still applies
    cfg8 = FFTConfig(codec="int8", **BASE)
    runner8 = make_toy_runner(cfg8, **TOY)
    assert runner8.model_bytes == 4e6
    assert runner8.upload_bytes == pytest.approx(
        4e6 * runner8.comm.compression_ratio)
    assert runner8.comm.compression_ratio < 0.26


def test_lora_runs_upload_adapter_sized_payloads():
    """Satellite: LoRA runs must not simulate full-model upload times."""
    from benchmarks.common import make_problem
    r = make_problem(non_iid=False, failure_mode="none", quick=True,
                     model="vit", model_bytes=None)
    # trainable pytree is the adapter dict -> derived bytes are adapter bytes
    assert r.model_bytes == fp32_nbytes(r.global_params)
    full = fp32_nbytes(r.base_params)
    assert r.model_bytes < 0.5 * full


@pytest.mark.parametrize("codec", ALL_SPECS)
def test_lossy_codec_recovers_participants_end_to_end(codec):
    """Every smaller-than-fp32 codec weakly increases the per-round
    participant count under deadline pressure; int8 strictly."""
    runners = {}
    for name in ["fp32", codec]:
        cfg = FFTConfig(codec=name,
                        failure_mode="scenario:lossy_uplink", **BASE)
        r = make_toy_runner(cfg, **TOY)
        r.run(STRATEGIES["fedavg"](), rounds=3)
        runners[name] = np.mean(r.loop.participants_per_round)
    assert runners[codec] >= runners["fp32"]
    if codec == "int8":
        assert runners[codec] > runners["fp32"]


@pytest.mark.parametrize("codec", ["fp32", "fp16", "int8", "qsgd:4",
                                   "topk:0.25", "sign1"])
def test_sync_async_equivalent_under_infinite_deadline_per_codec(codec):
    """With no deadline pressure the async server degenerates to the sync
    one under *every* codec — compression must not break the equivalence
    (deterministic codecs + per-client EF residuals)."""
    hist = {}
    for mode in ["sync", "async"]:
        cfg = FFTConfig(codec=codec, failure_mode="scenario:correlated_wifi",
                        server_mode=mode,
                        **{**BASE, "deadline_s": 1e9})
        hist[mode] = make_toy_runner(cfg, **TOY).run(
            STRATEGIES["fedavg"](), rounds=3)
    assert hist["sync"] == hist["async"]


def test_codec_works_under_buffered_mode_and_legacy_failures():
    cfg = FFTConfig(codec="int8", failure_mode="mixed",
                    server_mode="buffered", tau_max=3, buffer_k=2, **BASE)
    r = make_toy_runner(cfg, **TOY)
    hist = r.run(STRATEGIES["fedbuff"](buffer_k=1), rounds=3)
    assert len(hist) == 2 and all(0.0 <= a <= 1.0 for a in hist)


# ---------------------------------------------------------------------------
# trace schema v2
# ---------------------------------------------------------------------------
def test_trace_records_codec_and_payload_bytes(tmp_path):
    path = str(tmp_path / "c.ndjson")
    cfg = FFTConfig(codec="int8", failure_mode="scenario:diurnal",
                    trace_record=path, **BASE)
    runner = make_toy_runner(cfg, **TOY)
    runner.run(STRATEGIES["fedavg"](), rounds=2)
    lines = [json.loads(l) for l in open(path)]
    hdr = lines[0]
    assert hdr["version"] == 5
    assert hdr["codec"] == "int8"
    assert hdr["downlink_codec"] == "fp32"
    assert hdr["upload_bytes"] == pytest.approx(runner.upload_bytes)
    for rec in lines[1:]:
        for c in rec["clients"]:
            assert c["payload_bytes"] == pytest.approx(runner.upload_bytes)


def test_compressed_record_replay_bit_exact(tmp_path):
    path = str(tmp_path / "c.ndjson")
    rec_cfg = FFTConfig(codec="int8", failure_mode="scenario:diurnal",
                        trace_record=path, **BASE)
    live = make_toy_runner(rec_cfg, **TOY).run(STRATEGIES["fedavg"](),
                                               rounds=3)
    rep_cfg = FFTConfig(codec="int8", trace_replay=path, **BASE)
    rep1 = make_toy_runner(rep_cfg, **TOY).run(STRATEGIES["fedavg"](),
                                               rounds=3)
    rep2 = make_toy_runner(rep_cfg, **TOY).run(STRATEGIES["fedavg"](),
                                               rounds=3)
    assert rep1 == rep2 == live


def test_replay_with_mismatched_codec_fails_loudly(tmp_path):
    path = str(tmp_path / "c.ndjson")
    rec_cfg = FFTConfig(codec="int8", failure_mode="scenario:diurnal",
                        trace_record=path, **BASE)
    make_toy_runner(rec_cfg, **TOY).run(STRATEGIES["fedavg"](), rounds=2)
    with pytest.raises(ValueError, match="codec"):
        make_toy_runner(FFTConfig(codec="topk:0.25", trace_replay=path,
                                  **BASE), **TOY)


def test_replay_with_mismatched_model_bytes_fails_loudly(tmp_path):
    """Same codec but a different wire size also invalidates the recorded
    timings — the guard checks bytes, not just the codec name."""
    path = str(tmp_path / "c.ndjson")
    rec_cfg = FFTConfig(codec="int8", failure_mode="scenario:diurnal",
                        trace_record=path, **BASE)        # model_bytes=4e6
    make_toy_runner(rec_cfg, **TOY).run(STRATEGIES["fedavg"](), rounds=2)
    derived = dict(BASE)
    derived["model_bytes"] = None                         # derive -> ~121 kB
    with pytest.raises(ValueError, match="model_bytes"):
        make_toy_runner(FFTConfig(codec="int8", trace_replay=path,
                                  **derived), **TOY)


def test_v1_trace_still_loads_as_fp32(tmp_path):
    """Version-1 traces predate codecs: they load, replay under fp32, and
    refuse any other codec."""
    from repro.fl.scenarios.trace import ReplayFailureModel
    path = str(tmp_path / "v1.ndjson")
    with open(path, "w") as fh:
        fh.write(json.dumps({"record": "header", "version": 1,
                             "scenario": "x", "n_clients": 2}) + "\n")
        fh.write(json.dumps({
            "record": "round", "round": 1, "deadline_s": 5.0,
            "duration_s": 1.0,
            "clients": [{"id": 0, "up": True, "duration_s": 1.0,
                         "selected": True, "met_deadline": True,
                         "connected": True, "cause": "ok"},
                        {"id": 1, "up": False, "duration_s": None,
                         "selected": True, "met_deadline": False,
                         "connected": False, "cause": "outage"}]}) + "\n")
    m = ReplayFailureModel(path)
    assert m.codec == "fp32"
    assert m.payload_bytes(1) is None
    np.testing.assert_array_equal(m.draw(1), [True, False])


def test_unsupported_trace_version_rejected(tmp_path):
    from repro.fl.scenarios.trace import load_trace
    path = str(tmp_path / "v9.ndjson")
    with open(path, "w") as fh:
        fh.write(json.dumps({"record": "header", "version": 9}) + "\n")
    with pytest.raises(ValueError, match="version"):
        load_trace(path)


# ---------------------------------------------------------------------------
# fused Pallas dequantize-and-β-accumulate kernel
# ---------------------------------------------------------------------------
def _quant_inputs(M=5, P=3000, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.integers(-127, 128, (M, P)), jnp.int8)
    scales = jnp.asarray(rng.uniform(1e-4, 1e-2, M), jnp.float32)
    betas = jnp.asarray(rng.dirichlet(np.ones(M)), jnp.float32)
    return q, scales, betas


def test_dequant_fedagg_ref_matches_fp32_path():
    from repro.kernels import ref
    q, scales, betas = _quant_inputs()
    fused = ref.dequant_fedagg(q, scales, betas)
    fp32 = ref.fedagg(q.astype(jnp.float32) * scales[:, None], betas)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(fp32),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("M,P", [(1, 100), (5, 3000), (22, 70000)])
def test_dequant_fedagg_pallas_matches_ref(M, P):
    """Acceptance: the Pallas kernel (interpret mode on CPU) matches the
    reference path to fp32 tolerance, including padded/ragged P."""
    from repro.kernels import ref
    from repro.kernels.dequant_agg import dequant_fedagg
    q, scales, betas = _quant_inputs(M, P, seed=M)
    out = dequant_fedagg(q, scales, betas, interpret=True, block=256)
    expect = ref.dequant_fedagg(q, scales, betas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


def test_dequant_fedagg_ops_dispatch():
    from repro.kernels import ops, ref
    q, scales, betas = _quant_inputs(3, 512, seed=9)
    mode0 = ops.get_mode()
    try:
        ops.set_mode("off")
        off = ops.dequant_fedagg(q, scales, betas)
        ops.set_mode("interpret")
        interp = ops.dequant_fedagg(q, scales, betas)
    finally:
        ops.set_mode(mode0)
    np.testing.assert_allclose(np.asarray(off), np.asarray(interp),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(off),
                               np.asarray(ref.dequant_fedagg(q, scales,
                                                             betas)),
                               rtol=1e-6)


def test_fused_payload_aggregation_matches_decode_then_aggregate():
    c = make_codec("int8")
    trees = [_tree(seed=i) for i in range(4)]
    payloads = [c.encode(t) for t in trees]
    assert all(is_quantized(p) for p in payloads)
    betas = np.random.default_rng(1).dirichlet(np.ones(4))
    fused = aggregate_quantized(payloads, betas)
    unfused = aggregate_pytrees([c.decode(p) for p in payloads], betas)
    assert _maxdiff(fused, unfused) <= 1e-6
    with pytest.raises(ValueError, match="int8-family"):
        aggregate_quantized([make_codec("fp32").encode(trees[0])], [1.0])


def test_strategy_context_carries_codec_metadata():
    cfg = FFTConfig(codec="int8", failure_mode="scenario:lossy_uplink",
                    **BASE)
    runner = make_toy_runner(cfg, **TOY)
    seen = {}

    class Probe(STRATEGIES["fedavg"]):
        def aggregate(self, ctx):
            seen["codec"] = ctx.codec
            seen["upload_nbytes"] = ctx.upload_nbytes
            return super().aggregate(ctx)

    runner.run(Probe(), rounds=1)
    assert seen["codec"] == "int8"
    assert seen["upload_nbytes"] == pytest.approx(runner.upload_bytes)
