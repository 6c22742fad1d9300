"""DeepSeek-V2-Lite, one chip's share of an eight-chip expert-parallel
deployment, with rank-8 LoRA adapters on the attention projections.

The chip holds the dense layer and 6 MoE layers at every published width, 8
of each MoE layer's 64 routed experts (``expert_offset`` ..+8) and 12,800 of
the 102,400 vocabulary rows. The router keeps its 64 outputs and its top-6;
the layer computes the part of the result its own experts give, every
(token, expert) pair of them, and the shared experts once.

The program's model is ``repro.models.transformer`` (MLA with a plain query
projection and YaRN, the held-expert MoE layer) through ``repro.fl.lora``;
the benchmark makes the base and the adapters from the seed in the program's
tree layout. The reference below imports nothing of the program. It follows
``modeling_deepseek.py``'s equations in float32 with these departures, all
shared with the program: RoPE rotates the halves of the rope dims (the
published code de-interleaves pairs first: the same map under a fixed
permutation of the rope columns of W_q and W_kv_a); every held expert is
applied densely to all tokens and weighted by its gate where routed (zero
elsewhere), the absent experts add nothing; the adapters are applied
unmerged, x W + s (x A) B; each layer is rematerialised in the backward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _dims(sizes):
    return dict(d=sizes["hidden_size"], h=sizes["num_attention_heads"],
                nd=sizes["qk_nope_head_dim"], rd=sizes["qk_rope_head_dim"],
                vd=sizes["v_head_dim"], kvr=sizes["kv_lora_rank"],
                f=sizes["intermediate_size"], fe=sizes["moe_intermediate_size"],
                held=sizes["n_routed_experts"], experts=sizes["router_experts"],
                k=sizes["num_experts_per_tok"], shared=sizes["n_shared_experts"],
                v=sizes["vocab_size"], dense=sizes["first_k_dense_replace"],
                moe=sizes["num_hidden_layers"] - sizes["first_k_dense_replace"])


def _attn_shapes(sizes):
    """{name: (d_in, d_out)} of the attention projections (no query
    compression: q_lora_rank is null)."""
    if sizes["q_lora_rank"] is not None:
        raise ValueError("this configuration has a plain query projection")
    m = _dims(sizes)
    return {"wq": (m["d"], m["h"] * (m["nd"] + m["rd"])),
            "kv_down": (m["d"], m["kvr"] + m["rd"]),
            "kv_up": (m["kvr"], m["h"] * (m["nd"] + m["vd"])),
            "wo": (m["h"] * m["vd"], m["d"])}


def _lora_paths(sizes):
    m = _dims(sizes)
    layers = [f"dense_layer_{i}" for i in range(m["dense"])] + ["layers"]
    return [f"{layer}/attn/{n}/w" for layer in layers for n in sizes["lora_targets"]]


def init(sizes, key):
    """(frozen base, adapters) from one key, in the program's tree layout:
    ``dense_layer_<i>`` blocks, then the MoE blocks stacked under ``layers``
    (leading dim = MoE layers), expert stacks (held, ...). Matrices are
    N(0, 1/fan_in) (the head's fan-in is d), the embedding N(0, 1), norm
    scales 1; adapters A ~ N(0, 1/d_in) and B = 0."""
    m = _dims(sizes)
    d, r = m["d"], sizes["lora_rank"]
    shapes = _attn_shapes(sizes)
    keys = iter(jax.random.split(key, 64))

    def mat(lead, d_in, d_out):
        return jax.random.normal(next(keys), lead + (d_in, d_out)) / math.sqrt(d_in)

    def ones(lead, n):
        return jnp.ones(lead + (n,), jnp.float32)

    def block(lead, moe):
        attn = {n: {"w": mat(lead, *shapes[n])} for n in shapes}
        attn["kv_norm"] = {"scale": ones(lead, m["kvr"])}
        p = {"ln1": {"scale": ones(lead, d)}, "attn": attn, "ln2": {"scale": ones(lead, d)}}
        if not moe:
            p["ffn"] = {"w_gate": {"w": mat(lead, d, m["f"])}, "w_up": {"w": mat(lead, d, m["f"])},
                        "w_down": {"w": mat(lead, m["f"], d)}}
            return p
        e, fs = m["held"], m["fe"] * m["shared"]
        p["moe"] = {"router": {"w": mat(lead, d, m["experts"])},
                    "w_gate": mat(lead + (e,), d, m["fe"]), "w_up": mat(lead + (e,), d, m["fe"]),
                    "w_down": mat(lead + (e,), m["fe"], d),
                    "shared": {"w_gate": {"w": mat(lead, d, fs)}, "w_up": {"w": mat(lead, d, fs)},
                               "w_down": {"w": mat(lead, fs, d)}}}
        return p

    base = {"embed": {"embedding": jax.random.normal(next(keys), (m["v"], d))},
            "final_norm": {"scale": ones((), d)},
            "lm_head": {"embedding": jax.random.normal(next(keys), (m["v"], d)) / math.sqrt(d)}}
    for i in range(m["dense"]):
        base[f"dense_layer_{i}"] = block((), moe=False)
    base["layers"] = block((m["moe"],), moe=True)
    adapters = {}
    for path in _lora_paths(sizes):
        lead = (m["moe"],) if path.startswith("layers/") else ()
        d_in, d_out = shapes[path.split("/")[-2]]
        adapters[path] = {"a": jax.random.normal(next(keys), lead + (d_in, r)) / math.sqrt(d_in),
                          "b": jnp.zeros(lead + (r, d_out), jnp.float32)}
    return base, adapters


def model_config(sizes):
    """The program's ModelConfig of this configuration."""
    from repro.configs.base import ModelConfig
    m = _dims(sizes)
    y = sizes["rope_scaling"]
    return ModelConfig(
        name=sizes["name"], arch_type="moe", num_layers=sizes["num_hidden_layers"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=sizes["num_key_value_heads"],
        d_ff=m["f"], moe_d_ff=m["fe"], vocab_size=m["v"],
        mla=True, mla_kv_lora_rank=m["kvr"], mla_q_lora_rank=0,
        mla_rope_head_dim=m["rd"], mla_nope_head_dim=m["nd"], mla_v_head_dim=m["vd"],
        rope_theta=float(sizes["rope_theta"]), rope_yarn_factor=float(y["factor"]),
        rope_yarn_original_max=int(y["original_max_position_embeddings"]),
        rope_yarn_beta_fast=float(y["beta_fast"]), rope_yarn_beta_slow=float(y["beta_slow"]),
        rope_yarn_mscale=float(y["mscale"]), rope_yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        moe=True, num_experts=m["experts"], num_experts_per_tok=m["k"],
        num_shared_experts=m["shared"], first_k_dense=m["dense"],
        moe_norm_topk_prob=bool(sizes["norm_topk_prob"]),
        moe_routed_scaling=float(sizes["routed_scaling_factor"]),
        moe_experts_held=m["held"], moe_expert_offset=int(sizes["expert_offset"]),
        ffn_activation="swiglu", norm_eps=float(sizes["rms_norm_eps"]),
        tie_embeddings=bool(sizes["tie_word_embeddings"]), dtype=sizes["dtype"])


def program(sizes):
    """The system under test: the zoo's transformer logits and the LoRA
    config selecting the attention projections by their paths."""
    from repro.fl.lora import LoRAConfig
    from repro.models import transformer
    cfg = model_config(sizes)
    targets = tuple(f"attn/{n}/w" for n in sizes["lora_targets"])
    lora = LoRAConfig(rank=sizes["lora_rank"], alpha=sizes["lora_alpha"],
                      match=lambda path: path.endswith(targets))
    return {"apply": lambda p, x: transformer.apply(p, cfg, x), "lora": lora}


# ------------------------------------------------------------------ reference
def yarn_inv_freq(sizes) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims, in closed form (float64):
    theta^(-2i/rd) where a dim turns more than beta_fast times over the
    original positions, divided by the factor where it turns fewer than
    beta_slow times, a linear ramp between."""
    y, rd, theta = sizes["rope_scaling"], sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    orig = y["original_max_position_embeddings"]

    def dim_of(turns):
        return rd * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), rd - 1)
    i = np.arange(rd // 2, dtype=np.float64)
    f_e = theta ** (-2.0 * i / rd)
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return f_e * keep + f_e / y["factor"] * (1.0 - keep)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(sizes) -> float:
    """(nope + rope)^-1/2 x mscale(factor, mscale_all_dim)^2."""
    y = sizes["rope_scaling"]
    m = _mscale(y["factor"], y["mscale_all_dim"]) if y.get("mscale_all_dim") else 1.0
    return m * m / math.sqrt(sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"])


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), precision=_HI)


def _rmsnorm(p, x, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(x.dtype)


def _swiglu(p, x):
    return _dot(jax.nn.silu(_dot(x, p["w_gate"]["w"])) * _dot(x, p["w_up"]["w"]), p["w_down"]["w"])


def _rope(x, pos, inv_freq, factor):
    """Rotate the two halves of the last dim; x (n, S, heads, rd)."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)[None]
    cos = (jnp.cos(ang) * factor).astype(x.dtype)[None, :, None]
    sin = (jnp.sin(ang) * factor).astype(x.dtype)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mla(sizes, p, ad, x):
    m = _dims(sizes)
    n, s, _ = x.shape
    h, nd, rd, vd, kvr = m["h"], m["nd"], m["rd"], m["vd"], m["kvr"]
    scale = sizes["lora_alpha"] / sizes["lora_rank"]
    y = sizes["rope_scaling"]
    rope_factor = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"], y["mscale_all_dim"])
    inv_freq = yarn_inv_freq(sizes)

    def lin(name, z):
        a = ad[name]
        return _dot(z, p[name]["w"]) + scale * _dot(_dot(z, a["a"]), a["b"])

    pos = jnp.arange(s)
    q = lin("wq", x).reshape(n, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], _rope(q[..., nd:], pos, inv_freq, rope_factor)
    kv = lin("kv_down", x)
    c = _rmsnorm(p["kv_norm"], kv[..., :kvr], sizes["rms_norm_eps"])
    k_rope = _rope(kv[..., kvr:][:, :, None], pos, inv_freq, rope_factor)
    kvu = lin("kv_up", c).reshape(n, s, h, nd + vd)
    k_nope, v = kvu[..., :nd], kvu[..., nd:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=_HI)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0], precision=_HI))
    scores = scores * softmax_scale(sizes)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, jnp.finfo(scores.dtype).min)
    att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=_HI).reshape(n, s, h * vd)
    return lin("wo", o)


def _moe(sizes, p, x):
    """Softmax router over all experts, top-k gates as probabilities (not
    renormalised when ``norm_topk_prob`` is false) x the routed scale; every
    held expert applied to every token and weighted by its gate where the
    token routed to it; the shared experts once."""
    m = _dims(sizes)
    n, s, d = x.shape
    x2 = x.reshape(n * s, d)
    probs = jax.nn.softmax(_dot(x2, p["router"]["w"]), axis=-1)
    top, ids = jax.lax.top_k(probs, m["k"])
    if sizes["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * sizes["routed_scaling_factor"]
    held = sizes["expert_offset"] + jnp.arange(m["held"])
    weight = jnp.sum(jnp.where(ids[:, :, None] == held[None, None], top[:, :, None], 0.0),
                     axis=1).astype(x.dtype)                           # (T, held)
    g = jnp.einsum("td,edf->etf", x2, p["w_gate"].astype(x.dtype), precision=_HI)
    u = jnp.einsum("td,edf->etf", x2, p["w_up"].astype(x.dtype), precision=_HI)
    ye = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype), precision=_HI)
    routed = jnp.einsum("etd,te->td", ye, weight, precision=_HI)
    return routed.reshape(n, s, d) + _swiglu(p["shared"], x)


def _block(sizes, p, ad, x, moe):
    eps = sizes["rms_norm_eps"]
    x = x + _mla(sizes, p["attn"], ad, _rmsnorm(p["ln1"], x, eps))
    hn = _rmsnorm(p["ln2"], x, eps)
    return x + (_moe(sizes, p["moe"], hn) if moe else _swiglu(p["ffn"], hn))


def reference_logits(sizes, base, trainable, x):
    """Plain forward in the dtype of the adapters (float32 for the
    reference); x holds token ids (n, S)."""
    m = _dims(sizes)
    dtype = jax.tree.leaves(trainable)[0].dtype
    h = base["embed"]["embedding"].astype(dtype)[x]

    def adapters(layer):
        return {n: trainable[f"{layer}/attn/{n}/w"] for n in sizes["lora_targets"]}

    for i in range(m["dense"]):
        layer = f"dense_layer_{i}"
        h = jax.checkpoint(lambda h, p, a: _block(sizes, p, a, h, moe=False))(
            h, base[layer], adapters(layer))

    def body(h, layer):
        p, a = layer
        return _block(sizes, p, a, h, moe=True), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, (base["layers"], adapters("layers")))
    h = _rmsnorm(base["final_norm"], h, sizes["rms_norm_eps"])
    return _dot(h, base["lm_head"]["embedding"].T)


# ---------------------------------------------------------------------- FLOPs
def _parts(sizes, seq_len):
    """Multiply-add FLOPs (2 per MAC) of one sequence, by part."""
    m = _dims(sizes)
    s, d = seq_len, m["d"]
    proj = 2.0 * s * sum(a * b for a, b in _attn_shapes(sizes).values())
    attn = 2.0 * s * s * m["h"] * (m["nd"] + m["rd"] + m["vd"])    # q k^T and a v, full square
    dense_mlp = 2.0 * s * 3 * d * m["f"]
    router = 2.0 * s * d * m["experts"]
    shared = 2.0 * s * 3 * d * m["fe"] * m["shared"]
    routed = 2.0 * (s * m["k"] * m["held"] / m["experts"]) * 3 * d * m["fe"]
    head = 2.0 * s * d * m["v"]
    return dict(proj=proj, attn=attn, dense_mlp=dense_mlp, router=router, shared=shared,
                routed=routed, head=head)


def forward_flops(sizes, seq_len) -> float:
    """Multiply-add FLOPs of one sequence's forward through the frozen base:
    the attention projections, both attention products over the full S x S
    square (the causal mask is applied to it), the dense MLP, the router, the
    shared experts, the held experts at their expected load (S x k x held /
    experts pairs, not the static row bound computed and discarded), the
    head. Norms, softmax, RoPE and the activations are not counted."""
    p = _parts(sizes, seq_len)
    m = _dims(sizes)
    layers = sizes["num_hidden_layers"]
    return (layers * (p["proj"] + p["attn"]) + m["dense"] * p["dense_mlp"]
            + m["moe"] * (p["router"] + p["shared"] + p["routed"]) + p["head"])


def train_flops(sizes, seq_len) -> float:
    """LoRA on a frozen base, per sequence: the forward; the activation
    gradients through every product above the first layer's input
    projections (attention's two products each need two; the first layer's
    W_q and W_kv_a need none, the embedding being frozen); the adapters'
    own products and weight gradients at rank r (forward, dx, dW). Remat's
    recomputed forward is not counted."""
    p = _parts(sizes, seq_len)
    m = _dims(sizes)
    layers, r = sizes["num_hidden_layers"], sizes["lora_rank"]
    shapes = _attn_shapes(sizes)
    first_in = 2.0 * seq_len * sum(a * b for n, (a, b) in shapes.items()
                                   if n in ("wq", "kv_down"))
    backward = (layers * (p["proj"] + 2.0 * p["attn"]) - first_in + m["dense"] * p["dense_mlp"]
                + m["moe"] * (p["router"] + p["shared"] + p["routed"]) + p["head"])
    adapter_fwd = 2.0 * seq_len * r * sum(a + b for n, (a, b) in shapes.items()
                                          if n in sizes["lora_targets"])
    return forward_flops(sizes, seq_len) + backward + layers * 3.0 * adapter_fwd
