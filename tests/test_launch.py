"""Launch-layer units: HLO collective parser, roofline terms, sharding-rule
divisibility (via AbstractMesh — no 512-device init in the test process)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.roofline import (collective_bytes, model_flops,
                                   roofline_terms)
from repro.launch.sharding import INPUT_SHAPES, LONG_CONTEXT_OK, param_pspecs


HLO_SNIPPET = """
ENTRY %main {
  %ag = bf16[16,4096,512]{2,1,0} all-gather(%p0), replica_groups={...}
  %ar = f32[1024]{0} all-reduce(%x), to_apply=%add
  %t = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) all-to-all(%a, %b)
  %rs = f32[2048]{0} reduce-scatter(%y), dimensions={0}
  %cp = bf16[64,64]{1,0} collective-permute(%z)
  %ags = bf16[32,32]{1,0} all-gather-start(%q)
  %dot = f32[128,128]{1,0} dot(%l, %r)
}
"""


def test_collective_parser_counts_all_kinds():
    out = collective_bytes(HLO_SNIPPET)
    assert out["all-gather"] == 16 * 4096 * 512 * 2 + 32 * 32 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["all-to-all"] == 2 * 8 * 128 * 2
    assert out["reduce-scatter"] == 2048 * 4
    assert out["collective-permute"] == 64 * 64 * 2
    assert "dot" not in out


def test_roofline_terms_dominance():
    t = roofline_terms(flops=197e12, bytes_accessed=1e9, coll_bytes=0)
    assert t["dominant"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(flops=1e12, bytes_accessed=819e9, coll_bytes=0)
    assert t["dominant"] == "memory"
    t = roofline_terms(flops=0, bytes_accessed=0, coll_bytes=50e9)
    assert t["dominant"] == "collective"


def test_model_flops_conventions():
    cfg = get_config("qwen3-1.7b")
    n = cfg.active_param_count()
    tr = model_flops(cfg, INPUT_SHAPES["train_4k"])
    assert tr == 6.0 * n * 256 * 4096
    de = model_flops(cfg, INPUT_SHAPES["decode_32k"])
    assert de == 2.0 * n * 128


def test_moe_active_params_smaller_than_total():
    cfg = get_config("deepseek-v2-236b")
    assert cfg.active_param_count() < 0.2 * cfg.param_count()
    dense = get_config("gemma-7b")
    assert dense.active_param_count() == dense.param_count()


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "mixtral-8x22b",
                                  "qwen3-1.7b", "zamba2-1.2b",
                                  "seamless-m4t-large-v2"])
@pytest.mark.parametrize("multi", [False, True])
def test_param_pspecs_divisible(arch, multi):
    """Every sharded param axis must divide by the mesh axis size — this is
    the invariant that makes all 70 dry-run lowerings legal."""
    from repro.models import transformer as T
    cfg = get_config(arch)
    shape = (2, 16, 16) if multi else (16, 16)
    names = ("pod", "data", "model") if multi else ("data", "model")
    mesh = jax.sharding.AbstractMesh(shape, names)
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    specs = param_pspecs(params, cfg, mesh)

    def check(leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            size = int(np.prod([dict(mesh.shape)[a] for a in
                                (ax if isinstance(ax, tuple) else (ax,))]))
            assert dim % size == 0, (arch, leaf.shape, spec)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    # at least the embeddings and attention weights actually shard
    n_sharded = sum(any(ax is not None for ax in tuple(s))
                    for s in jax.tree.leaves(
                        specs, is_leaf=lambda x: isinstance(
                            x, jax.sharding.PartitionSpec)))
    assert n_sharded >= 3


def test_long_context_gate_matches_design():
    assert "gemma-7b" not in LONG_CONTEXT_OK          # full attention
    assert "xlstm-125m" in LONG_CONTEXT_OK            # recurrent
    assert "mixtral-8x22b" in LONG_CONTEXT_OK         # SWA
    assert "deepseek-v2-236b" not in LONG_CONTEXT_OK  # MLA is still full attn
