"""Each configuration: the analytic FLOPs against XLA's cost analysis, and
the plain reference forward against the program's forward (CPU, small)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run

# XLA also counts the norms' and activations' elementwise work, which the
# analytic count leaves out: a few tenths of a percent at full width.
FLOP_TOLERANCE = 0.03


def _config(name, **changes):
    entry = {c["name"]: c for c in run.load_benchmark()["configs"]}[name]
    sizes, mod = run.load_config(entry)
    return dict(sizes, **changes), mod


@pytest.mark.parametrize("name,changes", [
    ("resnet18-c100", {}),                       # full size, batch 2
    ("vitb16-lora-c100", {"num_hidden_layers": 1}),   # published widths, one block
])
def test_forward_flops_match_cost_analysis(name, changes):
    sizes, mod = _config(name, **changes)
    base, trainable = jax.eval_shape(lambda k: mod.init(sizes, k), jax.random.PRNGKey(0))
    hw = sizes.get("data_image_size", sizes["image_size"])
    x = jax.ShapeDtypeStruct((2, hw, hw, sizes["channels"]), jnp.float32)
    prog = mod.program(sizes)
    params = base if prog["lora"] is not None else trainable
    cost = jax.jit(prog["apply"]).lower(params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    got = cost["flops"] / 2
    want = mod.forward_flops(sizes)
    assert abs(got / want - 1) < FLOP_TOLERANCE, (got, want)
    assert mod.train_flops(sizes) > 1.9 * want


@pytest.mark.parametrize("name,changes", [
    ("resnet18-c100", dict(image_size=8, num_classes=10, stages=[1, 1], widths=[8, 16],
                           groups=[4, 4])),
    ("vitb16-lora-c100", dict(image_size=16, data_image_size=8, num_classes=10,
                              patch_size=4, hidden_size=32, num_hidden_layers=2,
                              num_attention_heads=2, intermediate_size=64, lora_rank=4)),
])
def test_reference_forward_matches_program(name, changes):
    from repro.fl.lora import apply_lora
    sizes, mod = _config(name, **changes)
    base, trainable = jax.jit(lambda k: mod.init(sizes, k))(jax.random.PRNGKey(3))
    # non-zero adapters, so the LoRA path contributes
    trainable = jax.jit(lambda t: jax.tree.map(lambda a: a + 0.05, t))(trainable)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, sizes.get("data_image_size", sizes["image_size"]),
                                                   sizes.get("data_image_size", sizes["image_size"]), 3))
    prog = mod.program(sizes)
    def program_logits(b, t, x):
        return prog["apply"](t if prog["lora"] is None else apply_lora(b, t, prog["lora"]), x)

    got = np.asarray(jax.jit(program_logits)(base, trainable, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda b, t, x: mod.reference_logits(sizes, b, t, x))(
            base, trainable, x))
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))
