"""Per-kernel correctness: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dequant_agg, ref
from repro.kernels import ops as kops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.dequant_agg import dequant_fedagg, fedagg, float_fedagg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lora_matmul import lora_matmul


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# fedagg
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,p", [(3, 100), (22, 4096), (7, 13000), (1, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedagg_matches_ref(m, p, dtype):
    key = jax.random.PRNGKey(m * 7 + p)
    stacked = _rand(key, (m, p), dtype)
    betas = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (m,)))
    got = fedagg(stacked, betas, interpret=True, block=512)
    want = ref.fedagg(stacked, betas)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("kernel,dtype,m", [
    ("fedagg", jnp.float32, 22),          # M tiles 8, 8, 6
    ("float_fedagg", jnp.float32, 64),    # M tiles 8 x 8
    ("float_fedagg", jnp.float16, 40),    # M tiles 14, 14, 12
    ("dequant_fedagg", jnp.int8, 70),     # M tiles 24, 24, 22
])
def test_aggregation_kernels_tile_participants(kernel, dtype, m):
    """At the default block the participant axis spans several sequential
    M tiles, the last one ragged, and P needs padding."""
    p = 3 * 65536 - 5
    rows_per_tile = dequant_agg._TILE_BYTES // (
        dequant_agg.SUBLANE_I8 * 2048 * jnp.dtype(dtype).itemsize)
    assert m > rows_per_tile
    key = jax.random.PRNGKey(m)
    betas = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (m,)))
    if kernel == "dequant_fedagg":
        q = jax.random.randint(key, (m, p), -127, 128).astype(jnp.int8)
        scales = jax.random.uniform(jax.random.fold_in(key, 2), (m,))
        got = dequant_fedagg(q, scales, betas, interpret=True)
        want = ref.dequant_fedagg(q, scales, betas)
    else:
        x = _rand(key, (m, p), dtype)
        fn, rf = ((fedagg, ref.fedagg) if kernel == "fedagg"
                  else (float_fedagg, ref.float_fedagg))
        got, want = fn(x, betas, interpret=True), rf(x, betas)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


def test_f16_bit_decode_is_exact_for_every_pattern():
    bits = np.arange(1 << 16, dtype=np.uint16)
    got = np.asarray(dequant_agg._f16_bits_to_f32(jnp.asarray(bits)))
    want = bits.view(np.float16).astype(np.float32)
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_float_fedagg_f16_special_values_match_ref():
    """Subnormals, ±0, ±inf, nan and the fp16 extremes through the kernel.
    Each column has one non-zero row and β are powers of two, so every sum
    is exact and the kernel must equal the reference bit for bit."""
    special = np.array([2.0 ** -24, -(2.0 ** -24), 3 * 2.0 ** -24,
                        2.0 ** -15, -(2.0 ** -14), 0.0, -0.0, np.inf,
                        -np.inf, np.nan, 65504.0, -65504.0, 1.5, -0.333],
                       np.float16)
    m, p = 3, 4096
    x = np.zeros((m, p), np.float16)
    cols = np.arange(p)
    x[cols % m, cols] = np.resize(special, p)
    betas = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    got = np.asarray(float_fedagg(jnp.asarray(x), betas, interpret=True))
    want = np.asarray(ref.float_fedagg(jnp.asarray(x), betas))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend,agg,model", [("cpu", "off", "off"),
                                               ("tpu", "on", "off")])
def test_dispatch_default_follows_backend(monkeypatch, backend, agg, model):
    """Without set_mode only the aggregation kernels follow the backend;
    the model kernels (no backward pass) stay on the references."""
    monkeypatch.setattr(kops, "_MODE", None)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert (kops.get_mode(), kops.model_mode()) == (agg, model)
    assert not kops.use_pallas()


def test_explicit_mode_wins_and_on_refuses_non_tpu(monkeypatch):
    monkeypatch.setattr(kops, "_MODE", None)
    kops.set_mode("interpret")
    assert (kops.get_mode(), kops.model_mode()) == ("interpret", "interpret")
    with pytest.raises(RuntimeError, match="cpu"):
        kops.set_mode("on")
    assert kops.get_mode() == "interpret"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", [
    dict(B=1, S=128, H=4, KV=4, hd=64, causal=True, window=None),
    dict(B=2, S=256, H=8, KV=2, hd=64, causal=True, window=None),
    dict(B=1, S=256, H=4, KV=4, hd=128, causal=True, window=64),
    dict(B=1, S=192, H=4, KV=1, hd=32, causal=True, window=None),   # odd S, MQA
    dict(B=1, S=128, H=4, KV=4, hd=64, causal=False, window=None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    key = jax.random.PRNGKey(0)
    B, S, H, KV, hd = case["B"], case["S"], case["H"], case["KV"], case["hd"]
    q = _rand(key, (B, S, H, hd), dtype)
    k = _rand(jax.random.fold_in(key, 1), (B, S, KV, hd), dtype)
    v = _rand(jax.random.fold_in(key, 2), (B, S, KV, hd), dtype)
    got = flash_attention(q, k, v, causal=case["causal"], window=case["window"],
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=case["causal"],
                               window=case["window"])
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", [
    dict(B=2, S=512, H=8, KV=2, hd=64, n_valid=300),
    dict(B=1, S=1024, H=4, KV=4, hd=128, n_valid=1024),
    dict(B=3, S=200, H=6, KV=1, hd=32, n_valid=7),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(case, dtype):
    key = jax.random.PRNGKey(1)
    B, S, H, KV, hd = case["B"], case["S"], case["H"], case["KV"], case["hd"]
    q = _rand(key, (B, 1, H, hd), dtype)
    k = _rand(jax.random.fold_in(key, 1), (B, S, KV, hd), dtype)
    v = _rand(jax.random.fold_in(key, 2), (B, S, KV, hd), dtype)
    valid = jnp.arange(S) < case["n_valid"]
    scale = 1.0 / np.sqrt(hd)
    got = decode_attention(q, k, v, valid, scale=scale, block_s=128,
                           interpret=True)
    want = ref.decode_attention(q, k, v, valid, scale=scale)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# fused LoRA matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,d,o,r", [(64, 128, 128, 8), (100, 300, 200, 16),
                                     (8, 512, 1024, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_matches_ref(t, d, o, r, dtype):
    key = jax.random.PRNGKey(2)
    x = _rand(key, (t, d), dtype)
    w = _rand(jax.random.fold_in(key, 1), (d, o), dtype)
    a = _rand(jax.random.fold_in(key, 2), (d, r), dtype)
    b = _rand(jax.random.fold_in(key, 3), (r, o), dtype)
    got = lora_matmul(x, w, a, b, 2.0, block_t=32, block_o=128, block_d=128,
                      interpret=True)
    # oracle in fp32 (the kernel accumulates fp32; bf16 ref would round per-op)
    want = ref.lora_matmul(*(t.astype(jnp.float32) for t in (x, w, a, b)), 2.0)
    wantf = np.asarray(want, np.float32)
    scale = np.abs(wantf).mean() + 1e-6
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               wantf / scale, rtol=0, atol=tol)


# ---------------------------------------------------------------------------
# Pallas selective-scan kernel vs sequential oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", [
    dict(B=2, S=64, H=4, dh=8, n=16, chunk=16),
    dict(B=1, S=100, H=2, dh=32, n=64, chunk=32),    # ragged S
    dict(B=2, S=128, H=3, dh=16, n=24, chunk=128),   # single chunk, odd dims
])
def test_selective_scan_kernel_matches_ref(case):
    from repro.kernels.selective_scan import selective_scan
    key = jax.random.PRNGKey(9)
    B, S, H, dh, n = case["B"], case["S"], case["H"], case["dh"], case["n"]
    xdt = jax.random.normal(key, (B, S, H, dh))
    a_log = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                               (B, S, H)))
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, n))
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, n))
    got = selective_scan(xdt, a_log, Bm, Cm, chunk=case["chunk"],
                         interpret=True)
    want, _ = ref.selective_scan(xdt, a_log, Bm, Cm,
                                 jnp.zeros((B, H, dh, n)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# selective-scan oracle vs the chunked SSD used by the model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunked_matches_sequential_scan(chunk):
    from repro.models.ssm import _ssd_chunked
    key = jax.random.PRNGKey(3)
    B, S, H, dh, n = 2, 64, 4, 8, 16
    xdt = jax.random.normal(key, (B, S, H, dh))
    a_log = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                               (B, S, H)))
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, n))
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, n))
    h0 = jnp.zeros((B, H, dh, n))
    y1, h1 = _ssd_chunked(xdt.astype(jnp.float32), Bm, Cm,
                          jnp.ones((B, S, H)), jnp.zeros((H,)), h0, chunk)
    # _ssd_chunked computes a_log internally from dt & A_log; instead compare
    # via ref.selective_scan on identical a_log by reusing its internals:
    y2, h2 = ref.selective_scan(xdt.astype(jnp.float32) * 1.0,
                                jnp.zeros((B, S, H)) - 1.0 * jnp.exp(jnp.zeros((H,))),
                                Bm, Cm, h0)
    # align definitions: _ssd_chunked(dt=1, A_log=0) -> a_log = -1 everywhere
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4,
                               atol=2e-4)

# Property tests (hypothesis) live in tests/test_hypothesis_properties.py so
# this module collects even when hypothesis is not installed.
