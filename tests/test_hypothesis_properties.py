"""All hypothesis property tests, gated behind ``pytest.importorskip`` so
the rest of the suite collects and runs on environments without hypothesis
(install it via ``pip install -r requirements-dev.txt``).

Moved here from test_fl_system / test_qp_solver / test_kernels, which keep
deterministic variants of the same invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.aggregation import (fedauto_async_weights,  # noqa: E402
                                    fedauto_discounted_weights,
                                    fedauto_weights)
from repro.core.weights_qp import (chi2_effective, project_simplex,  # noqa: E402
                                   solve_weights)
from repro.fl.comm import (AdaptiveCommController, CommState,  # noqa: E402
                           RUNG_LADDER, make_codec)
from repro.fl.partition import partition  # noqa: E402
from repro.fl.scenarios.engine import (DeadlineSimulator,  # noqa: E402
                                       LinkState)
from repro.fl.scenarios.trace import _num, _unnum  # noqa: E402
from repro.kernels.dequant_agg import dequant_fedagg, fedagg  # noqa: E402


# ---------------------------------------------------------------------------
# partitioner invariants (from test_fl_system)
# ---------------------------------------------------------------------------
@given(st.integers(0, 1000), st.sampled_from(["iid", "group_classes",
                                              "dirichlet"]))
@settings(max_examples=20, deadline=None)
def test_partition_invariants(seed, mode):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, 400).astype(np.int64)
    parts, hists = partition(mode, labels, 20, 10, classes_per_group=2,
                             seed=seed)
    assert len(parts) == 20
    all_idx = np.concatenate([p for p in parts if len(p)])
    assert len(np.unique(all_idx)) == len(all_idx)        # no duplicates
    assert hists.sum() == len(all_idx)
    for p_, h in zip(parts, hists):
        if len(p_):
            np.testing.assert_array_equal(
                np.bincount(labels[p_], minlength=10), h)
    if mode == "group_classes":
        for i, h in enumerate(hists):                     # ≤2 classes each
            assert (h > 0).sum() <= 2
    if mode == "iid":
        assert len(all_idx) == 400                        # covers everything


# ---------------------------------------------------------------------------
# QP solver invariants (from test_qp_solver)
# ---------------------------------------------------------------------------
def _random_problem(rng, J, C):
    alpha = rng.dirichlet(np.ones(C) * 0.5, size=J)
    p = rng.dirichlet(np.ones(J))
    alpha_g = p @ alpha
    return alpha, alpha_g


@st.composite
def qp_problems(draw):
    seed = draw(st.integers(0, 2 ** 31 - 1))
    J = draw(st.integers(2, 12))
    C = draw(st.integers(2, 20))
    n_active = draw(st.integers(1, J))
    rng = np.random.default_rng(seed)
    alpha, alpha_g = _random_problem(rng, J, C)
    mask = np.zeros(J, dtype=bool)
    mask[rng.choice(J, n_active, replace=False)] = True
    mask[0] = True                      # server always present
    return alpha, alpha_g, mask


@given(qp_problems())
@settings(max_examples=25, deadline=None)
def test_solver_feasibility(problem):
    alpha, alpha_g, mask = problem
    beta = np.asarray(solve_weights(jnp.asarray(alpha), jnp.asarray(alpha_g),
                                    jnp.asarray(mask)))
    assert np.all(beta >= -1e-6)
    assert abs(beta.sum() - 1.0) < 1e-4
    assert np.all(beta[~mask] <= 1e-6)          # Eq. (10c)


@given(qp_problems())
@settings(max_examples=15, deadline=None)
def test_solver_no_worse_than_uniform(problem):
    alpha, alpha_g, mask = problem
    beta = np.asarray(solve_weights(jnp.asarray(alpha), jnp.asarray(alpha_g),
                                    jnp.asarray(mask)))
    uni = np.where(mask, 1.0 / mask.sum(), 0.0)
    f_beta = float(chi2_effective(jnp.asarray(beta), jnp.asarray(alpha),
                                  jnp.asarray(alpha_g)))
    f_uni = float(chi2_effective(jnp.asarray(uni), jnp.asarray(alpha),
                                 jnp.asarray(alpha_g)))
    assert f_beta <= f_uni + 1e-5


@st.composite
def discount_problems(draw):
    seed = draw(st.integers(0, 2 ** 31 - 1))
    J = draw(st.integers(2, 10))
    C = draw(st.integers(2, 12))
    b = draw(st.floats(0.0, 4.0))
    rng = np.random.default_rng(seed)
    alpha, alpha_g = _random_problem(rng, J, C)
    staleness = rng.integers(0, 5, J).astype(float)
    staleness[0] = 0.0
    distortion = rng.uniform(0.0, 1.0, J)
    distortion[0] = 0.0
    return alpha, alpha_g, staleness, distortion, b


@given(discount_problems())
@settings(max_examples=25, deadline=None)
def test_discounted_weights_simplex_and_pin_property(problem):
    """Eq. 8/9 invariants survive the staleness × fidelity discount: β on
    the simplex, server pin β_s = 1/(1+m) intact."""
    alpha, alpha_g, staleness, distortion, b = problem
    beta = fedauto_discounted_weights(alpha, alpha_g, staleness, distortion,
                                      server_row=0, discount_b=b)
    assert np.all(beta >= -1e-6)
    assert abs(beta.sum() - 1.0) < 1e-4
    assert abs(beta[0] - 1.0 / len(alpha)) < 1e-4


@given(discount_problems())
@settings(max_examples=25, deadline=None)
def test_discounted_weights_zero_distortion_reductions(problem):
    """At zero distortion the pipeline is bit-exact with the staleness-only
    solution, and additionally with the sync QP when everything is fresh."""
    alpha, alpha_g, staleness, _, b = problem
    zeros = np.zeros(len(alpha))
    got = fedauto_discounted_weights(alpha, alpha_g, staleness, zeros,
                                     server_row=0, discount_b=b)
    want = fedauto_async_weights(alpha, alpha_g, staleness, server_row=0)
    np.testing.assert_array_equal(got, want)
    fresh = fedauto_discounted_weights(alpha, alpha_g, zeros, zeros,
                                       server_row=0, discount_b=b)
    sync = fedauto_weights(alpha, alpha_g, np.ones(len(alpha), bool),
                           server_row=0)
    np.testing.assert_array_equal(fresh, sync)


@given(discount_problems(), st.integers(1, 9), st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_discounted_weights_monotone_in_distortion_property(problem, j, bump):
    """Raising one participant's distortion (all else equal) must never
    raise its own weight."""
    alpha, alpha_g, staleness, distortion, b = problem
    j = j % len(alpha)
    if j == 0:
        j = len(alpha) - 1
    lo = fedauto_discounted_weights(alpha, alpha_g, staleness, distortion,
                                    server_row=0, discount_b=b)
    worse = distortion.copy()
    worse[j] = min(worse[j] + bump * (1.0 - worse[j]), 1.0)
    hi = fedauto_discounted_weights(alpha, alpha_g, staleness, worse,
                                    server_row=0, discount_b=b)
    assert hi[j] <= lo[j] + 1e-9
    assert abs(hi[0] - lo[0]) < 1e-9               # pin untouched


@given(st.integers(0, 10_000), st.integers(2, 16))
@settings(max_examples=30, deadline=None)
def test_simplex_projection_properties(seed, n):
    rng = np.random.default_rng(seed)
    v = rng.normal(0, 3, n)
    mask = rng.uniform(size=n) > 0.3
    if not mask.any():
        mask[0] = True
    total = float(rng.uniform(0.1, 2.0))
    x = np.asarray(project_simplex(jnp.asarray(v, jnp.float32),
                                   jnp.asarray(mask), jnp.float32(total)))
    assert np.all(x >= -1e-6)
    assert abs(x.sum() - total) < 1e-4
    assert np.all(x[~mask] == 0)


# ---------------------------------------------------------------------------
# trace float encoding: lossless JSON round-trip incl. inf/-inf/nan, so an
# async run's recorded arrival times replay bit-exactly
# ---------------------------------------------------------------------------
@given(st.one_of(st.none(),
                 st.floats(allow_nan=True, allow_infinity=True)))
@settings(max_examples=200, deadline=None)
def test_trace_num_unnum_round_trip(x):
    import json
    got = _unnum(json.loads(json.dumps(_num(x))))
    if x is None:
        assert got is None
    elif np.isnan(x):
        assert np.isnan(got)
    else:
        assert got == x


# ---------------------------------------------------------------------------
# communication codecs (repro.fl.comm): byte counts are value-independent
# and exactly nbytes(template); quantizers respect their error bounds; every
# lossy codec is a contraction (the EF convergence prerequisite)
# ---------------------------------------------------------------------------
CODEC_SPECS = ["fp32", "fp16", "int8", "qsgd:2", "qsgd:4", "qsgd:8",
               "topk:0.1", "topk:0.5", "sign1"]


@given(st.integers(0, 10_000), st.sampled_from(CODEC_SPECS),
       st.integers(2, 40), st.integers(2, 40))
@settings(max_examples=40, deadline=None)
def test_codec_nbytes_value_independent_and_exact(seed, spec, d0, d1):
    rng = np.random.default_rng(seed)
    codec = make_codec(spec)
    tree = {"w": jnp.asarray(rng.normal(0, 10, (d0, d1)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(d1,)), jnp.float32)}
    payload = codec.encode(tree)
    assert payload.nbytes == codec.nbytes(tree)
    zeros = {k: jnp.zeros_like(v) for k, v in tree.items()}
    assert codec.encode(zeros).nbytes == payload.nbytes
    if not spec.startswith("topk"):
        # topk pays 8 B per kept entry (index + value), which can exceed
        # 4 B/param on tiny leaves or f = 0.5; the dense codecs only exceed
        # fp32 on 1-element leaves (the 4 B per-leaf scale dominates), which
        # the d0,d1 >= 2 draw excludes
        assert payload.nbytes <= make_codec("fp32").nbytes(tree)


@given(st.integers(0, 10_000), st.integers(2, 8), st.integers(1, 400))
@settings(max_examples=25, deadline=None)
def test_quantizer_error_bound_property(seed, bits, n):
    rng = np.random.default_rng(seed)
    codec = make_codec(f"qsgd:{bits}")
    x = {"w": jnp.asarray(rng.normal(0, 5, (n,)), jnp.float32)}
    dec = codec.decode(codec.encode(x))["w"]
    levels = (1 << (bits - 1)) - 1
    half_step = float(jnp.max(jnp.abs(x["w"]))) / levels / 2
    assert float(jnp.max(jnp.abs(dec - x["w"]))) <= half_step + 1e-6


@given(st.integers(0, 10_000),
       st.sampled_from(["fp16", "int8", "qsgd:4", "topk:0.25", "sign1"]),
       st.integers(2, 200))
@settings(max_examples=30, deadline=None)
def test_lossy_codec_contraction_property(seed, spec, n):
    rng = np.random.default_rng(seed)
    codec = make_codec(spec)
    x = {"w": jnp.asarray(rng.normal(0, 3, (n,)), jnp.float32)}
    if float(jnp.sum(jnp.abs(x["w"]))) < 1e-3:
        return
    dec = codec.decode(codec.encode(x))["w"]
    err = float(jnp.sum(jnp.square(dec - x["w"]))) ** 0.5
    norm = float(jnp.sum(jnp.square(x["w"]))) ** 0.5
    assert err < norm * (1.0 - 1e-6) + 1e-6


# ---------------------------------------------------------------------------
# per-round repricing (ISSUE 4): re-simulating the same link realization at
# different payload bytes moves only the transfer timings, monotonically in
# bytes — never the link draw itself
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000), st.integers(1, 10),
       st.floats(0.01, 1.0), st.floats(1.0, 100.0))
@settings(max_examples=30, deadline=None)
def test_repricing_is_monotone_in_bytes_and_preserves_links(seed, n, frac,
                                                            scale):
    rng = np.random.default_rng(seed)
    sim = DeadlineSimulator(n, model_bytes=4e6, deadline_s=float(
        rng.uniform(0.5, 20.0)), compute_s=float(rng.uniform(0.0, 3.0)),
        seed=seed)
    links = [LinkState(float(rng.uniform(0.05e6, 50e6 * scale)),
                       up=bool(rng.uniform() > 0.3),
                       cause="outage" if rng.uniform() > 0.5 else "ok")
             for _ in range(n)]
    big = sim.simulate_round(2, links)
    sim.set_payload_bytes(upload_bytes=4e6 * frac, download_bytes=4e6 * frac)
    small = sim.simulate_round(2, links)
    for e_big, e_small in zip(big.events, small.events):
        assert e_big.up == e_small.up
        assert e_big.capacity_bps == e_small.capacity_bps
        if not e_big.up:
            assert e_big.cause == e_small.cause          # link draw frozen
            continue
        assert e_small.t_upload_s <= e_big.t_upload_s
        assert e_small.t_download_s <= e_big.t_download_s
        assert e_small.finish_s <= e_big.finish_s
        assert e_small.t_compute_s == e_big.t_compute_s  # jitter keyed (seed, rnd)
        # met_deadline monotone: fewer bytes can only add participants
        assert e_small.met_deadline or not e_big.met_deadline


# ---------------------------------------------------------------------------
# adaptive controller (ISSUE 4): the rung policy is monotone in estimated
# capacity and never assigns beyond the ladder ceiling (fp32)
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000), st.integers(0, len(RUNG_LADDER) - 1),
       st.integers(0, len(RUNG_LADDER) - 1))
@settings(max_examples=30, deadline=None)
def test_adaptive_ladder_monotone_property(seed, a, b):
    lo, hi = RUNG_LADDER[min(a, b)], RUNG_LADDER[max(a, b)]
    rng = np.random.default_rng(seed)
    tmpl = {"w": jnp.zeros((int(rng.integers(10, 5000)),), jnp.float32)}
    comm = CommState(make_codec("fp32"), tmpl,
                     model_bytes_override=float(rng.uniform(1e5, 1e8)))
    ctl = AdaptiveCommController(
        4, comm, lo=lo, hi=hi, deadline_s=float(rng.uniform(0.5, 60.0)),
        compute_s=float(rng.uniform(0.0, 3.0)))
    caps = np.sort(rng.uniform(1e2, 1e13, 25))
    idx = [ctl.rung_index_for(c) for c in caps]
    assert idx == sorted(idx)                            # monotone in capacity
    assert all(0 <= k < len(ctl.rungs) for k in idx)
    assert ctl.rungs[-1] == hi                           # ceiling respected
    assert (np.diff(ctl.rung_bytes) >= 0).all()          # ladder byte order
    assert ctl.rung_bytes[-1] <= comm.nbytes_for("fp32") + 1e-9


# ---------------------------------------------------------------------------
# fused dequantize-and-β-accumulate kernel == reference on random payloads
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000), st.integers(1, 8), st.integers(1, 700))
@settings(max_examples=15, deadline=None)
def test_dequant_fedagg_matches_ref_property(seed, m, p):
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.integers(-127, 128, (m, p)), jnp.int8)
    scales = jnp.asarray(rng.uniform(1e-4, 1e-1, m), jnp.float32)
    betas = jnp.asarray(rng.dirichlet(np.ones(m)), jnp.float32)
    out = np.asarray(dequant_fedagg(q, scales, betas, interpret=True,
                                    block=256))
    expect = np.asarray(ref.dequant_fedagg(q, scales, betas))
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fedagg kernel convexity (from test_kernels)
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000), st.integers(1, 8), st.integers(1, 700))
@settings(max_examples=15, deadline=None)
def test_fedagg_convex_hull_property(seed, m, p):
    """With β on the simplex, every output coordinate lies within
    [min_m x, max_m x] — aggregation can never extrapolate."""
    rng = np.random.default_rng(seed)
    stacked = jnp.asarray(rng.normal(0, 5, (m, p)).astype(np.float32))
    beta = jnp.asarray(rng.dirichlet(np.ones(m)).astype(np.float32))
    out = np.asarray(fedagg(stacked, beta, interpret=True, block=256))
    lo = np.min(np.asarray(stacked), axis=0) - 1e-4
    hi = np.max(np.asarray(stacked), axis=0) + 1e-4
    assert np.all(out >= lo) and np.all(out <= hi)


# ---------------------------------------------------------------------------
# sketch-mode telemetry (ISSUE 8): GK quantile sketches honor their
# documented rank-error bound and exact summation is order-independent
# (deterministic variants live in test_obs_scale)
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000),
       st.sampled_from(["normal", "exp", "ints", "sorted", "constant"]),
       st.integers(50, 4000), st.sampled_from([0.01, 0.05]))
@settings(max_examples=25, deadline=None)
def test_gk_quantile_rank_error_property(seed, dist, n, eps):
    """For any stream and quantile q, the sketch's answer has rank within
    ε·n of ⌈q·n⌉ — the bound SKETCH_EPS documents for sketch-mode reports."""
    import math
    from bisect import bisect_left, bisect_right

    from repro.obs import GKQuantiles

    rng = np.random.default_rng(seed)
    vals = {"normal": lambda: rng.normal(0, 1, n),
            "exp": lambda: rng.exponential(1.0, n),
            "ints": lambda: rng.integers(0, 7, n).astype(float),
            "sorted": lambda: np.sort(rng.uniform(0, 1, n)),
            "constant": lambda: np.full(n, 3.25)}[dist]()
    gk = GKQuantiles(eps)
    for v in vals:
        gk.add(float(v))
    srt = sorted(float(v) for v in vals)
    for q in (0.05, 0.25, 0.5, 0.75, 0.95, 0.99):
        got = gk.query(q)
        target = max(1, math.ceil(q * n))
        lo = bisect_left(srt, got) + 1
        hi = bisect_right(srt, got)
        slack = eps * n + 1
        assert lo - slack <= target <= hi + slack


@given(st.integers(0, 10_000), st.integers(1, 300))
@settings(max_examples=40, deadline=None)
def test_exact_sum_order_independent_property(seed, n):
    """Shewchuk accumulation is bit-equal to math.fsum over the same
    multiset regardless of fold order — the property that makes sketch-mode
    byte totals reconcile bit-for-bit against full mode."""
    import math

    from repro.obs import ExactSum

    rng = np.random.default_rng(seed)
    vals = list(np.exp(rng.normal(0.0, 12.0, n)) *
                rng.choice([-1.0, 1.0], n))
    want = math.fsum(vals)
    fwd, rev = ExactSum(), ExactSum()
    for v in vals:
        fwd.add(v)
    for v in reversed(vals):
        rev.add(v)
    assert fwd.value() == want == rev.value()


# ---------------------------------------------------------------------------
# population-scale engine invariants (PR 9; deterministic variants in
# tests/test_population.py)
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000),
       st.floats(min_value=0.05, max_value=8.0),
       st.floats(min_value=1.01, max_value=8.0))
@settings(max_examples=25, deadline=None)
def test_arrival_times_monotone_in_payload_both_engines(seed, mb, factor):
    """Growing the payload can never make any client's arrival earlier —
    on fixed links with the deadline out of the way, the realized finish
    times are elementwise monotone in payload bytes, identically under the
    heap and vectorized engines (which must also agree bit-for-bit)."""
    n = 12
    rng = np.random.default_rng(seed)
    links = [LinkState(float(c)) for c in
             np.exp(rng.normal(14.0, 2.0, n))]          # ~1e4..1e8 bps
    fins = {}
    for eng in ("heap", "vectorized"):
        fin = []
        for bytes_ in (mb * 1e6, mb * factor * 1e6):
            sim = DeadlineSimulator(n, model_bytes=bytes_, deadline_s=1e12,
                                    seed=seed, engine=eng)
            fin.append(sim.simulate_round(1, links).finish_array())
        assert np.all(fin[1] >= fin[0])                 # monotone in payload
        fins[eng] = fin
    for a, b in zip(fins["heap"], fins["vectorized"]):  # engines bit-equal
        assert np.array_equal(a, b)
