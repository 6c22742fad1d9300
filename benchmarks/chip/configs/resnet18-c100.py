"""ResNet-18 with GroupNorm on CIFAR-100-shaped input, all parameters trained.

The program's model is ``repro.models.vision``'s ResNet; the benchmark makes
its weights from the seed in the same tree layout and keeps here, beside the
sizes, a plain float32 reference forward that imports nothing of the program.
Departure from He et al. 2016 that the reference shares with the program:
GroupNorm in place of BatchNorm (the FedAuto paper's choice), XLA "SAME"
padding for the stride-2 convolutions, and a global mean pool before the head.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _blocks(sizes):
    """(name, c_in, c_out, stride) of every basic block, stem excluded."""
    out, cin = [], sizes["widths"][0]
    for s, (n, w) in enumerate(zip(sizes["stages"], sizes["widths"])):
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            out.append((f"s{s}b{b}", cin, w, stride, sizes["groups"][s]))
            cin = w
    return out


def init(sizes, key):
    """(base, trainable) from one key; full fine-tuning trains the base."""
    def conv(k, kh, cin, cout):
        w = jax.random.normal(k, (kh, kh, cin, cout)) * math.sqrt(2.0 / (kh * kh * cin))
        return {"w": w.astype(jnp.float32), "b": jnp.zeros((cout,), jnp.float32)}

    def gn(c):
        return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}

    blocks = _blocks(sizes)
    ks = jax.random.split(key, 2 + 3 * len(blocks))
    w0, c = sizes["widths"][0], sizes["channels"]
    p = {"stem": conv(ks[0], 3, c, w0), "gn0": gn(w0)}
    for i, (name, cin, cout, stride, _) in enumerate(blocks):
        blk = {"conv1": conv(ks[1 + 3 * i], 3, cin, cout), "gn1": gn(cout),
               "conv2": conv(ks[2 + 3 * i], 3, cout, cout), "gn2": gn(cout)}
        if stride != 1 or cin != cout:
            blk["proj"] = conv(ks[3 + 3 * i], 1, cin, cout)
        p[name] = blk
    d = sizes["widths"][-1]
    p["fc"] = {"w": jax.random.normal(ks[-1], (d, sizes["num_classes"])) / math.sqrt(d),
               "b": jnp.zeros((sizes["num_classes"],), jnp.float32)}
    return p, p


def program(sizes):
    """The system under test: the program's ResNet apply, no LoRA."""
    from repro.models.vision import resnet_apply
    kw = dict(stages=tuple(sizes["stages"]), widths=tuple(sizes["widths"]),
              groups=tuple(sizes["groups"]))
    return {"apply": lambda p, x: resnet_apply(p, x, **kw), "lora": None}


# ------------------------------------------------------------------ reference
def _conv(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return y + p["b"].astype(x.dtype)


def _groupnorm(p, x, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def reference_logits(sizes, base, trainable, x):
    """Plain forward in the dtype of ``x`` (float32 for the reference)."""
    del base
    p = trainable
    g0 = sizes["groups"][0]
    h = jax.nn.relu(_groupnorm(p["gn0"], _conv(p["stem"], x, 1), g0))
    for name, _, _, stride, groups in _blocks(sizes):
        blk = p[name]
        y = jax.nn.relu(_groupnorm(blk["gn1"], _conv(blk["conv1"], h, stride), groups))
        y = _groupnorm(blk["gn2"], _conv(blk["conv2"], y, 1), groups)
        skip = _conv(blk["proj"], h, stride) if "proj" in blk else h
        h = jax.nn.relu(y + skip)
    h = h.mean(axis=(1, 2))
    return jnp.dot(h, p["fc"]["w"].astype(h.dtype),
                   precision=jax.lax.Precision.HIGHEST) + p["fc"]["b"].astype(h.dtype)


# ---------------------------------------------------------------------- FLOPs
def _taps(n: int, k: int, stride: int) -> int:
    """Kernel taps that land inside an input of size n, summed over the
    outputs of one axis of a "SAME" convolution (padding taps do no work)."""
    out = -(-n // stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(sum(1 for j in range(k) if 0 <= i * stride - lo + j < n) for i in range(out))


def forward_flops(sizes) -> float:
    """Multiply-add FLOPs (2 per MAC) of one sample's forward: the
    convolutions' taps inside the image and the head; norms, activations and
    the pool are not counted."""
    hw = sizes["image_size"]
    w0 = sizes["widths"][0]
    total = 2.0 * _taps(hw, 3, 1) ** 2 * sizes["channels"] * w0
    for _, cin, cout, stride, _ in _blocks(sizes):
        total += 2.0 * _taps(hw, 3, stride) ** 2 * cin * cout      # conv1
        if stride != 1 or cin != cout:
            total += 2.0 * _taps(hw, 1, stride) ** 2 * cin * cout  # 1x1 projection
        hw = -(-hw // stride)
        total += 2.0 * _taps(hw, 3, 1) ** 2 * cout * cout          # conv2
    total += 2.0 * sizes["widths"][-1] * sizes["num_classes"]
    return total


def train_flops(sizes) -> float:
    """Full fine-tuning: forward plus backward, 3x the forward."""
    return 3.0 * forward_flops(sizes)
