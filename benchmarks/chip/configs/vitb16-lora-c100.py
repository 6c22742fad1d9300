"""ViT-B/16 with rank-8 LoRA adapters on every qkv projection, frozen base.

The program's model is ``repro.models.vision``'s ViT at ViT-Base's published
widths, fine-tuned through ``repro.fl.lora``; the benchmark makes the base and
the adapters from the seed in the program's tree layouts. The 32x32 data is
repeated 7x7 (nearest) to 224x224 at the head of the forward, in the program
and in the reference alike. The reference below imports nothing of the
program; it applies each adapter unmerged, x W + s (x A) B, and uses the tanh
form of GELU, as the program does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _paths(sizes):
    return [f"blk{i}/qkv/w" for i in range(sizes["num_hidden_layers"])]


def upsample(sizes, x):
    f = sizes["image_size"] // sizes["data_image_size"]
    return jnp.repeat(jnp.repeat(x, f, axis=1), f, axis=2)


def init(sizes, key):
    """(frozen base, adapters) from one key; B starts at zero, A ~ N(0, 1/d)."""
    d, m, p = sizes["hidden_size"], sizes["intermediate_size"], sizes["patch_size"]
    c, n_cls, r = sizes["channels"], sizes["num_classes"], sizes["lora_rank"]
    depth = sizes["num_hidden_layers"]
    tokens = (sizes["image_size"] // p) ** 2 + 1
    ks = jax.random.split(key, 4 + 5 * depth)

    def dense(k, din, dout):
        return {"w": jax.random.normal(k, (din, dout)) / math.sqrt(din),
                "b": jnp.zeros((dout,), jnp.float32)}

    def ln():
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}

    base = {"patch": dense(ks[0], p * p * c, d),
            "pos": jax.random.normal(ks[1], (1, tokens, d)) * 0.02,
            "cls": jnp.zeros((1, 1, d), jnp.float32),
            "head": dense(ks[2], d, n_cls), "ln_f": ln()}
    adapters = {}
    for i in range(depth):
        k = ks[4 + 5 * i: 9 + 5 * i]
        base[f"blk{i}"] = {"ln1": ln(), "qkv": dense(k[0], d, 3 * d),
                           "proj": dense(k[1], d, d), "ln2": ln(),
                           "fc1": dense(k[2], d, m), "fc2": dense(k[3], m, d)}
        adapters[f"blk{i}/qkv/w"] = {
            "a": jax.random.normal(k[4], (d, r)) / math.sqrt(d),
            "b": jnp.zeros((r, 3 * d), jnp.float32)}
    return base, adapters


def program(sizes):
    """The system under test: the program's ViT apply and LoRA config."""
    from repro.fl.lora import LoRAConfig
    from repro.models.vision import vit_apply
    kw = dict(patch=sizes["patch_size"], heads=sizes["num_attention_heads"],
              depth=sizes["num_hidden_layers"])
    target = sizes["lora_targets"]
    lora = LoRAConfig(rank=sizes["lora_rank"], alpha=sizes["lora_alpha"],
                      match=lambda path: path.endswith(target))
    return {"apply": lambda p, x: vit_apply(p, upsample(sizes, x), **kw),
            "lora": lora}


# ------------------------------------------------------------------ reference
def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), precision=_HI)


def _layernorm(p, x, eps=1e-6):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_logits(sizes, base, trainable, x):
    """Plain forward in the dtype of ``x`` (float32 for the reference)."""
    d, p = sizes["hidden_size"], sizes["patch_size"]
    heads = sizes["num_attention_heads"]
    hd = d // heads
    s = sizes["lora_alpha"] / sizes["lora_rank"]
    x = upsample(sizes, x)
    n, hh, ww, c = x.shape
    xp = x.reshape(n, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    h = _dot(xp.reshape(n, -1, p * p * c), base["patch"]["w"]) + base["patch"]["b"].astype(x.dtype)
    cls = jnp.broadcast_to(base["cls"].astype(x.dtype), (n, 1, d))
    h = jnp.concatenate([cls, h], axis=1) + base["pos"].astype(x.dtype)
    t = h.shape[1]
    stack = lambda trees: jax.tree.map(lambda *a: jnp.stack(a), *trees)
    blocks = stack([base[f"blk{i}"] for i in range(sizes["num_hidden_layers"])])
    adapters = stack([trainable[path] for path in _paths(sizes)])

    def block(h, layer):
        blk, ad = layer
        hn = _layernorm(blk["ln1"], h)
        qkv = (_dot(hn, blk["qkv"]["w"]) + s * _dot(_dot(hn, ad["a"]), ad["b"])
               + blk["qkv"]["b"].astype(x.dtype))
        qkv = qkv.reshape(n, t, 3, heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / math.sqrt(hd)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=_HI).reshape(n, t, d)
        h = h + _dot(o, blk["proj"]["w"]) + blk["proj"]["b"].astype(x.dtype)
        hn = _layernorm(blk["ln2"], h)
        f = _gelu_tanh(_dot(hn, blk["fc1"]["w"]) + blk["fc1"]["b"].astype(x.dtype))
        return h + _dot(f, blk["fc2"]["w"]) + blk["fc2"]["b"].astype(x.dtype), None

    h, _ = jax.lax.scan(block, h, (blocks, adapters))
    h = _layernorm(base["ln_f"], h)
    return _dot(h[:, 0], base["head"]["w"]) + base["head"]["b"].astype(x.dtype)


# ---------------------------------------------------------------------- FLOPs
def _parts(sizes):
    d, m, p = sizes["hidden_size"], sizes["intermediate_size"], sizes["patch_size"]
    t = (sizes["image_size"] // p) ** 2 + 1
    embed = 2.0 * (t - 1) * p * p * sizes["channels"] * d
    linear = 2.0 * t * d * (3 * d + d + 2 * m)              # qkv, proj, fc1, fc2
    attn = 2.0 * 2.0 * t * t * d                            # q k^T and a v
    head = 2.0 * d * sizes["num_classes"]
    return embed, linear, attn, head, t


def forward_flops(sizes) -> float:
    """Multiply-add FLOPs (2 per MAC) of one sample's forward through the
    frozen base; norms, softmax and GELU are not counted."""
    embed, linear, attn, head, _ = _parts(sizes)
    return embed + sizes["num_hidden_layers"] * (linear + attn) + head


def train_flops(sizes) -> float:
    """LoRA on a frozen base: the forward, the activation gradients through
    every block and the head (attention's two products each need two), and
    the adapters' own products and weight gradients at rank r."""
    embed, linear, attn, head, t = _parts(sizes)
    depth, d, r = sizes["num_hidden_layers"], sizes["hidden_size"], sizes["lora_rank"]
    adapter_fwd = 2.0 * t * r * (d + 3 * d)
    backward = depth * (linear + 2.0 * attn) + head
    adapters = depth * 3.0 * adapter_fwd                    # forward, dx, dW
    return forward_flops(sizes) + backward + adapters
