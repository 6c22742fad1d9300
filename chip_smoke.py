#!/usr/bin/env python3
"""Smoke test of one federated fine-tuning job on one TPU chip, end to end.

Builds the paper's CIFAR-100 setting at full width through the public API:
synthetic 32x32x3 images in 100 classes (made from ``--seed``; nothing is
downloaded), 20 clients with non-IID ``group_classes`` partitions, K = 20,
the 11.2M-parameter ResNet-18 and a short server pretrain, over the paper's
Table 6 network as a deadline world (``scenario:table6``, 30 s deadline).
It then drives ``FFTRunner`` through three phases, all in this one process:

  (a) sync FedAuto, ``codec="fp32"``, 3 rounds: ``float_fedagg`` through the
      streaming accumulator;
  (b) sync FedAuto, ``codec="adaptive:sign1-fp16"``, 3 rounds: the fp16 and
      ``dequant_fedagg`` rungs (both must be aggregated) and the fp16
      downlink;
  (c) one round from the same global params and seeds with ``kernels.ops``
      in mode "off" and again in mode "on": the two global models must agree
      to ``PARITY_RTOL`` of the largest |param|.  Round 1 of an adaptive run
      uploads at fp16 only, so the three aggregation kernels are also run
      alone at the largest ResNet-18 leaf (M = K+2 for ``fedagg``, M = 64,
      the accumulator's batch, for the others) against a float64 host
      reference, to the same tolerance.

Every round of (a) and (b) must aggregate through the fused kernels
(``uplink_fused_payloads`` > 0, ``uplink_fallback_payloads`` == 0).  Any
failed check raises and the script exits nonzero.  The lines before the last
are set-up observations (compile and round seconds, parity error, peak device
memory), not benchmark metrics.  The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and it is printed only on a TPU.  Anywhere else the script exits nonzero and
names the platform it found, unless ``--cpu-rehearsal`` is given: that runs
the same phases at a tiny size with interpret-mode kernels and prints no
device line.

    python chip_smoke.py [--seed N]                       # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ROUNDS = 3
PARITY_RTOL = 1e-5          # max |off − on| over the largest |param|


@dataclasses.dataclass(frozen=True)
class Problem:
    model: str
    n_classes: int
    image_size: int
    n_clients: int
    n_samples: int
    n_test: int
    public_per_class: int
    classes_per_group: int   # groups of 4 clients share this many classes
    batch_size: int
    pretrain_steps: int
    model_bytes: Optional[float] = None   # None: priced from the real params


# the paper's CIFAR-100 / ResNet-18 setting (20 classes per group of 4
# clients covers all 100 classes)
FULL = Problem(model="resnet18", n_classes=100, image_size=32, n_clients=20,
               n_samples=12288, n_test=2048, public_per_class=10,
               classes_per_group=20, batch_size=32, pretrain_steps=10)
# CPU rehearsal: same phases, the paper's small CIFAR-10 ResNet and tiny
# data, with uploads priced as ResNet-18's fp32 bytes so that the network,
# the participants and the adaptive rungs are those of the chip run
TINY = Problem(model="resnet", n_classes=10, image_size=8, n_clients=20,
               n_samples=2400, n_test=256, public_per_class=5,
               classes_per_group=2, batch_size=8, pretrain_steps=4,
               model_bytes=4 * 11_223_140)
WORLD = "scenario:table6"
LARGEST_LEAF = 512 * 512 * 3 * 3            # ResNet-18's largest conv
QUANT_RUNGS = ("sign1", "qsgd", "int8")      # dequant_fedagg's family


def build_runner(prob: Problem, codec: str, seed: int):
    from repro.data.synthetic import fft_split, make_dataset, train_test_split
    from repro.fl.partition import partition
    from repro.fl.runtime import FFTConfig, FFTRunner
    from repro.models.vision import make_model

    ds = make_dataset(prob.n_samples, n_classes=prob.n_classes,
                      image_size=prob.image_size, channels=3, seed=seed)
    train, test = train_test_split(ds, prob.n_test, seed=seed + 1)
    pub, priv = fft_split(train, public_per_class=prob.public_per_class,
                          seed=seed)
    parts, _ = partition("group_classes", priv.y, prob.n_clients,
                         prob.n_classes,
                         classes_per_group=prob.classes_per_group, seed=seed)
    init_fn, apply_fn = make_model(prob.model, prob.n_classes,
                                   prob.image_size, 3)
    cfg = FFTConfig(n_clients=prob.n_clients, k_selected=prob.n_clients,
                    batch_size=prob.batch_size, codec=codec, seed=seed,
                    failure_mode=WORLD, model_bytes=prob.model_bytes,
                    eval_every=ROUNDS, telemetry=True)
    return FFTRunner(cfg, init_fn, apply_fn, pub, parts, priv, test,
                     pretrain_steps=prob.pretrain_steps)


class CompileClock:
    """Seconds the backend spent compiling, from JAX's own monitoring
    events (a persistent-cache hit compiles nothing)."""

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def run_phase(name, runner, want_mode, clock):
    from repro.core.strategies import FedAuto
    from repro.kernels import ops as kops

    if kops.get_mode() != want_mode:
        raise RuntimeError(f"phase {name}: kernels.ops mode is "
                           f"{kops.get_mode()!r}, expected {want_mode!r}")
    c0, t0 = clock.total, time.perf_counter()
    hist = runner.run(FedAuto(), rounds=ROUNDS)
    wall = time.perf_counter() - t0
    gauges = [rec["gauges"] for rec in runner.report.rounds]
    if len(gauges) != ROUNDS:
        raise RuntimeError(f"phase {name}: {len(gauges)} round records, "
                           f"expected {ROUNDS}")
    for r, g in enumerate(gauges, 1):
        fused = g.get("uplink_fused_payloads", 0)
        fallback = g.get("uplink_fallback_payloads", -1)
        if not (fused > 0 and fallback == 0):
            raise RuntimeError(f"phase {name} round {r}: fused={fused} "
                               f"fallback={fallback}; every upload must "
                               "aggregate through the fused kernels")
    rungs = runner.report.rung_histogram()      # aggregated uploads
    if runner.adaptive_spec and not ("fp16" in rungs and any(
            k.startswith(QUANT_RUNGS) for k in rungs)):
        raise RuntimeError(f"phase {name}: aggregated rungs {rungs} miss "
                           "fp16 or the int8 family")
    rounds_s = " ".join(f"{g['round_wall_s']:.3f}" for g in gauges)
    fused = [int(g["uplink_fused_payloads"]) for g in gauges]
    print(f"phase {name}: codec {runner.cfg.codec}, kernels {want_mode}, "
          f"wall {wall:.1f} s, compile {clock.total - c0:.1f} s, "
          f"round seconds [{rounds_s}], fused payloads {fused}, "
          f"rungs {rungs}, final accuracy {hist[-1]:.4f}")


def parity(runner, mode_on):
    """One round from identical state under "off" and under ``mode_on``;
    raises unless max |off − on| is within ``PARITY_RTOL`` of the largest
    |param| of the "off" model."""
    import jax
    import numpy as np

    from repro.core.strategies import FedAuto
    from repro.kernels import ops as kops

    g0, rng0, key0 = runner.global_params, runner.rng, runner._key
    out = {}
    for mode in ("off", mode_on):
        kops.set_mode(mode)
        runner.global_params = g0
        runner.rng = copy.deepcopy(rng0)
        runner._key = key0
        runner.run(FedAuto(), rounds=1)
        out[mode] = [np.asarray(l, np.float32)
                     for l in jax.tree.leaves(runner.global_params)]
    err = max(float(np.max(np.abs(a - b)))
              for a, b in zip(out["off"], out[mode_on]))
    scale = max(float(np.max(np.abs(a))) for a in out["off"])
    rel = err / scale
    print(f"phase c: one round, kernels off vs {mode_on}: max |diff| {err:.3e}"
          f", largest |param| {scale:.3e}, relative {rel:.3e} "
          f"(tolerance {PARITY_RTOL:g})")
    if not rel <= PARITY_RTOL:
        raise RuntimeError(f"phase c: kernels off vs {mode_on} differ by "
                           f"{rel:.3e} of the largest |param|")


def kernel_parity(p: int, interpret: bool, seed: int) -> None:
    """Each aggregation kernel at a main-path shape against a float64 numpy
    reference; error relative to the reference's largest |value|."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.dequant_agg import (dequant_fedagg, fedagg,
                                           float_fedagg)

    rng = np.random.default_rng(seed)
    cases = [("fedagg fp32", 22, np.float32), ("float_fedagg fp32", 64,
             np.float32), ("float_fedagg fp16", 64, np.float16),
             ("dequant_fedagg int8", 64, np.int8)]
    for name, m, dt in cases:
        if dt == np.int8:
            x = rng.integers(-127, 128, (m, p)).astype(dt)
        else:
            x = rng.normal(size=(m, p)).astype(dt)
        betas = rng.uniform(0.1, 1.0, m).astype(np.float32)
        betas /= betas.sum()
        scales = rng.uniform(1e-3, 1e-2, m).astype(np.float32)
        coef = betas.astype(np.float64)
        if dt == np.int8:
            coef = coef * scales
            got = dequant_fedagg(jnp.asarray(x), jnp.asarray(scales),
                                 jnp.asarray(betas), interpret=interpret)
        elif name.startswith("fedagg"):
            got = fedagg(jnp.asarray(x), jnp.asarray(betas),
                         interpret=interpret)
        else:
            got = float_fedagg(jnp.asarray(x), jnp.asarray(betas),
                               interpret=interpret)
        want = coef @ x.astype(np.float64)
        rel = (float(np.max(np.abs(np.asarray(got, np.float64) - want)))
               / float(np.max(np.abs(want))))
        print(f"phase c: {name} M={m} P={p} vs float64 host reference: "
              f"relative {rel:.3e}")
        if not rel <= PARITY_RTOL:
            raise RuntimeError(f"phase c: {name} M={m} differs by {rel:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the phases at a tiny size on the CPU with "
                         "interpret-mode kernels (prints no device line)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; nothing was run "
              "(--cpu-rehearsal runs a tiny CPU rehearsal)", file=sys.stderr)
        return 1

    from repro.kernels import ops as kops
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    if args.cpu_rehearsal:
        kops.set_mode("interpret")
    mode = kops.get_mode()
    prob = TINY if args.cpu_rehearsal else FULL
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, kernels {mode} (model-zoo kernels "
          f"{kops.model_mode()}), compile cache {cache_dir} "
          f"({warm} entries at start)")
    clock = CompileClock()

    t0 = time.perf_counter()
    fp32 = build_runner(prob, "fp32", args.seed)
    n_params = sum(int(l.size) for l in jax.tree.leaves(fp32.global_params))
    print(f"set-up: {prob.model} {n_params} params in "
          f"{len(jax.tree.leaves(fp32.global_params))} leaves, "
          f"{prob.n_clients} clients, pretrain {prob.pretrain_steps} steps, "
          f"{time.perf_counter() - t0:.1f} s (compile {clock.total:.1f} s)")
    run_phase("a", fp32, mode, clock)
    adaptive = build_runner(prob, "adaptive:sign1-fp16", args.seed)
    run_phase("b", adaptive, mode, clock)
    parity(adaptive, mode)
    kernel_parity(LARGEST_LEAF if mode == "on" else 70_000,
                  interpret=mode == "interpret", seed=args.seed)

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    if args.cpu_rehearsal:
        print("cpu rehearsal ok (no device line off the chip)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
