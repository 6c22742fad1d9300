"""Multi-pod dry-run (deliverable e): prove every (architecture × input
shape × mesh) lowers AND compiles on the production meshes, and extract the
memory/cost/collective numbers the roofline analysis consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (device count locks on
# first init). Everything below is ordinary code.

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.configs.base import ATTN, MAMBA2, MLSTM, SLSTM, SHARED_ATTN, ModelConfig
from repro.launch import roofline as rl
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.sharding import (INPUT_SHAPES, LONG_CONTEXT_OK, input_specs,
                                   logical_rules, param_pspecs)
from repro.fl.parallel import make_fft_round_step
from repro.models import dist
from repro.models import transformer as T
from repro.models.layers import set_logical_rules

LR = 1e-3


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, q_chunk: int):
    def train_step(params, batch):
        def loss_fn(p):
            loss, _ = T.forward(p, cfg, batch, q_chunk=q_chunk, loss_chunk=512)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32) - LR * g.astype(jnp.float32))
            .astype(p.dtype), params, grads)
        return loss, new_params

    return train_step


def make_prefill_step(cfg: ModelConfig, q_chunk: int):
    def prefill_step(params, batch):
        h, _ = T.hidden_states(params, cfg, batch, q_chunk=q_chunk)
        w = (params["embed"]["embedding"].T if cfg.tie_embeddings
             else params["lm_head"]["embedding"].T)
        return (h[:, -1] @ w).astype(jnp.float32)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, state, tokens):
        return T.decode_step(params, cfg, state, tokens)

    return serve_step


# ---------------------------------------------------------------------------
# decode-state partition specs (mirrors transformer.init_decode_state)
# ---------------------------------------------------------------------------
def _maybe(mesh, ax, dim: int):
    if ax is None:
        return None
    size = 1
    for a in (ax if isinstance(ax, tuple) else (ax,)):
        size *= mesh.shape[a]
    return ax if dim % size == 0 else None


def decode_state_pspecs(cfg: ModelConfig, mesh, batch: int, cache_len: int):
    from repro.models.attention import KVCache
    from repro.models.ssm import MambaCache
    from repro.models.xlstm import MLSTMCache, SLSTMCache

    baxes = batch_axes(mesh)
    b = _maybe(mesh, baxes if len(baxes) > 1 else baxes[0], batch)
    H = cfg.ssm_num_heads or cfg.num_heads
    d_in = cfg.ssm_expand * cfg.d_model

    def kv(scanned: bool):
        ms = dict(mesh.shape)["model"]
        if cfg.mla:
            k = P(b, None, None)
            v = P(b, None, None)
        elif cfg.num_kv_heads % ms != 0 and cache_len % ms == 0:
            # seq-sharded cache (distributed flash decode — §Perf A)
            k = P(b, "model", None, None)
            v = P(b, "model", None, None)
        else:
            kvh = _maybe(mesh, "model", cfg.num_kv_heads)
            k = P(b, None, kvh, None)
            v = P(b, None, kvh, None)
        if scanned:
            k = P(*([None] + list(k)))
            v = P(*([None] + list(v)))
        return KVCache(k=k, v=v, length=P(None) if scanned else P())

    def block_spec(kind: str):
        if kind in (ATTN, SHARED_ATTN):
            return kv(False)
        if kind == MAMBA2:
            return MambaCache(h=P(b, _maybe(mesh, "model", H), None, None),
                              conv=P(b, None, _maybe(mesh, "model", d_in)),
                              length=P())
        if kind == MLSTM:
            return MLSTMCache(C=P(b, _maybe(mesh, "model", H), None, None),
                              n=P(b, _maybe(mesh, "model", H), None),
                              m=P(b, _maybe(mesh, "model", H)), length=P())
        if kind == SLSTM:
            return SLSTMCache(c=P(b, None), n=P(b, None), h=P(b, None),
                              m=P(b, None), length=P())
        raise ValueError(kind)

    state: Dict[str, object] = {}
    if cfg.block_pattern is None:
        state["layers"] = kv(True)
        for i in range(cfg.first_k_dense):
            state[f"dense_layer_{i}"] = kv(False)
    else:
        state["blocks"] = {str(i): block_spec(k)
                           for i, k in enumerate(cfg.layer_kinds())}
    if cfg.encoder_decoder:
        state["enc_out"] = P(b, None, None)
    return state


# ---------------------------------------------------------------------------
# one dry-run
# ---------------------------------------------------------------------------
def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    return min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len


def run_one(arch: str, shape_name: str, multi_pod: bool,
            verbose: bool = True, mesh_override=None) -> Dict:
    cfg = get_config(arch)
    sh = INPUT_SHAPES[shape_name]
    mesh_name = mesh_override or ("multi" if multi_pod else "single")
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_OK:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": "full-attention arch (DESIGN.md §4)"}
    t0 = time.time()
    if mesh_override:
        # exploration mesh, e.g. "64x4" -> (data=64, model=4); §Perf D
        d_, m_ = (int(v) for v in mesh_override.split("x"))
        mesh = jax.make_mesh((d_, m_), ("data", "model"),
                             devices=jax.devices()[:d_ * m_])
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    set_logical_rules(logical_rules(mesh, cfg))
    dist.set_mesh_context(dist.MeshContext(mesh=mesh, batch_axes=batch_axes(mesh),
                                           model_axis="model"))
    try:
        params_shape = jax.eval_shape(
            lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
        pspecs = param_pspecs(params_shape, cfg, mesh)
        pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        B, S = sh["global_batch"], sh["seq_len"]
        q_chunk = 2048 if S > 4096 else 4096

        with jax.set_mesh(mesh):
            if sh["kind"] == "fft_round":
                K, b = sh["clients"], sh["client_batch"]
                step = make_fft_round_step(cfg, lr=LR, q_chunk=q_chunk)
                dax = "data"
                tok = jax.ShapeDtypeStruct((K, b, S), jnp.int32)
                beta = jax.ShapeDtypeStruct((K,), jnp.float32)
                tshard = NamedSharding(mesh, P(dax, None, None))
                jitted = jax.jit(
                    step,
                    in_shardings=(pshard, tshard, tshard,
                                  NamedSharding(mesh, P(None))),
                    out_shardings=(pshard, NamedSharding(mesh, P())))
                lowered = jitted.lower(params_shape, tok, tok, beta)
            elif sh["kind"] in ("train", "prefill"):
                specs, in_pspecs = input_specs(cfg, shape_name, mesh)
                bshard = {k: NamedSharding(mesh, v) for k, v in in_pspecs.items()}
                if sh["kind"] == "train":
                    step = make_train_step(cfg, q_chunk)
                    out_shardings = (NamedSharding(mesh, P()), pshard)
                else:
                    specs.pop("labels"); bshard.pop("labels")
                    step = make_prefill_step(cfg, q_chunk)
                    out_shardings = NamedSharding(
                        mesh, P(list(bshard.values())[0].spec[0],
                                _maybe(mesh, "model", cfg.vocab_size)))
                jitted = jax.jit(step, in_shardings=(pshard, bshard),
                                 out_shardings=out_shardings)
                lowered = jitted.lower(params_shape, specs)
            else:  # decode
                clen = cache_len_for(cfg, S)
                enc_shape = None
                if cfg.encoder_decoder:
                    enc_shape = jax.ShapeDtypeStruct((B, 4096, cfg.d_model),
                                                     jnp.bfloat16)
                state_shape = jax.eval_shape(
                    lambda p: T.init_decode_state(p, cfg, B, clen,
                                                  encoder_embeds=(
                                                      jnp.zeros(enc_shape.shape, enc_shape.dtype)
                                                      if enc_shape else None)),
                    params_shape) if enc_shape is None else jax.eval_shape(
                    lambda p, e: T.init_decode_state(p, cfg, B, clen,
                                                     encoder_embeds=e),
                    params_shape, enc_shape)
                st_pspecs = decode_state_pspecs(cfg, mesh, B, clen)
                st_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), st_pspecs,
                                        is_leaf=lambda x: isinstance(x, P))
                baxes = batch_axes(mesh)
                bax = _maybe(mesh, baxes if len(baxes) > 1 else baxes[0], B)
                tok_shard = NamedSharding(mesh, P(bax, None))
                logits_shard = NamedSharding(mesh, P(bax, _maybe(mesh, "model",
                                                                 cfg.vocab_size)))
                step = make_serve_step(cfg)
                jitted = jax.jit(step, in_shardings=(pshard, st_shard, tok_shard),
                                 out_shardings=(logits_shard, st_shard))
                tok_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
                lowered = jitted.lower(params_shape, state_shape, tok_shape)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = rl.collective_bytes(hlo)
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        terms = rl.roofline_terms(flops, bytes_acc, sum(coll.values()))
        mf = rl.model_flops(cfg, sh)
        n_dev = 1
        for v in dict(mesh.shape).values():
            n_dev *= v
        from repro.launch.sharding import FSDP_THRESHOLD
        msh = dict(mesh.shape)["model"]
        bsh = n_dev // msh
        analytic = rl.analytic_roofline(
            cfg, sh, n_devices=n_dev, batch_shards=bsh, model_shards=msh,
            fsdp=cfg.param_count() >= FSDP_THRESHOLD)
        result = {
            "arch": arch, "shape": shape_name,
            "mesh": mesh_name,
            "status": "ok",
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "flops_per_device": flops, "bytes_per_device": bytes_acc,
            "collective_bytes_per_device": sum(coll.values()),
            "collectives": coll,
            "model_flops_total": mf,
            "model_flops_per_device": mf / n_dev,
            "useful_flops_frac": (mf / n_dev) / flops if flops else None,
            **terms,
            **analytic,
            "mem": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
        }
        if verbose:
            print(f"[ok] {arch:24s} {shape_name:12s} "
                  f"{'multi' if multi_pod else 'single':6s} "
                  f"compile={t_compile:6.1f}s flops/dev={flops:.3e} "
                  f"dom={terms['dominant']}")
        return result
    except Exception as e:  # noqa: BLE001 — a failed lowering is a result
        if verbose:
            print(f"[FAIL] {arch} {shape_name} {'multi' if multi_pod else 'single'}: {e}")
            traceback.print_exc()
        return {"arch": arch, "shape": shape_name,
                "mesh": mesh_name,
                "status": "fail", "error": f"{type(e).__name__}: {e}"}
    finally:
        dist.set_mesh_context(None)
        set_logical_rules({})


ASSIGNED = [
    "deepseek-v2-236b", "llava-next-mistral-7b", "starcoder2-7b",
    "mixtral-8x22b", "xlstm-125m", "qwen3-1.7b", "codeqwen1.5-7b",
    "zamba2-1.2b", "gemma-7b", "seamless-m4t-large-v2",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--remesh", default=None,
                    help="exploration mesh 'DxM' (e.g. 64x4) instead of the production meshes")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    if args.shape is None:
        # the assigned 4 shapes; fft_round_4k is an extra, run explicitly
        shapes = [s for s, v in INPUT_SHAPES.items() if v["kind"] != "fft_round"]
    else:
        shapes = [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    jsonl = (args.out + ".jsonl") if args.out else None
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in (meshes if not args.remesh else [False]):
                r = run_one(arch, shape, mp, mesh_override=args.remesh)
                results.append(r)
                if jsonl:                      # incremental, crash-safe
                    with open(jsonl, "a") as f:
                        f.write(json.dumps(r) + "\n")
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    fail = sum(r["status"] == "fail" for r in results)
    print(f"\nDRYRUN SUMMARY: {ok} ok, {sk} skipped, {fail} failed / {len(results)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    return 0 if fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
