"""The DeepSeek-V2-Lite configuration on the CPU at small sizes: its file
against the published shape, the plain reference against the program (the
logits and one local update), the FedAuto rows, the FLOP count against XLA's,
a whole run of its cell, and the scope reader behind its per-layer metrics."""
import copy
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import device_trace
import op_scopes
import reference
import run
import workload

CELL = "deepseek-v2-lite-ep8.sync-lm-s2048"
SEED = 2 ** 31 + 29
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb"

# every width at a small size; 4 of 16 experts held, from expert 4
SMALL = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=16,
             v_head_dim=16, kv_lora_rank=32, intermediate_size=96, moe_intermediate_size=24,
             n_routed_experts=4, router_experts=16, num_experts_per_tok=3, vocab_size=128,
             num_hidden_layers=3, expert_offset=4, lora_rank=4)


def _config(**changes):
    _, entry = run.find_cell(run.load_benchmark(), CELL)
    sizes, mod = run.load_config(entry)
    return dict(sizes, **changes), mod


def _small():
    sizes, mod = _config(**SMALL)
    sizes["rope_scaling"] = dict(sizes["rope_scaling"], original_max_position_embeddings=64)
    return sizes, mod


def test_file_keeps_every_published_width():
    sizes, mod = _config()
    published = {"hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512,
                 "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 10944, "moe_intermediate_size": 1408,
                 "num_experts_per_tok": 6, "n_shared_experts": 2, "first_k_dense_replace": 1,
                 "norm_topk_prob": False, "routed_scaling_factor": 1, "rms_norm_eps": 1e-6,
                 "rope_theta": 10000, "router_experts": 64}
    assert {k: sizes[k] for k in published} == published
    assert sizes["rope_scaling"]["factor"] == 40 and sizes["rope_scaling"]["type"] == "yarn"
    cut = {"num_hidden_layers": 7, "n_routed_experts": 8, "vocab_size": 12800}
    assert {k: sizes[k] for k in cut} == cut
    assert sorted(sizes["reduced"]) == sorted(cut)
    assert sizes["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64,
                                  "vocab_size": 102400}
    assert sizes["deployment"]["chips"] * sizes["n_routed_experts"] == sizes["router_experts"]
    assert sizes["deployment"]["chips"] * sizes["vocab_size"] == 102400
    base, trainable = jax.eval_shape(lambda k: mod.init(sizes, k), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(base)) == sizes["n_params"] == 735_872_512
    assert sum(a.size for a in jax.tree.leaves(trainable)) == sizes["n_trainable"] == 921_088
    assert base["layers"]["moe"]["w_gate"].shape == (6, 8, 2048, 1408)
    assert base["layers"]["moe"]["router"]["w"].shape == (6, 2048, 64)


def test_reference_forward_matches_program():
    from repro.fl.lora import apply_lora
    sizes, mod = _small()
    base, trainable = jax.jit(lambda k: mod.init(sizes, k))(jax.random.PRNGKey(3))
    trainable = jax.jit(lambda t: jax.tree.map(lambda a: a + 0.05, t))(trainable)
    x = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, sizes["vocab_size"])
    prog = mod.program(sizes)
    got = np.asarray(jax.jit(lambda b, t, x: prog["apply"](apply_lora(b, t, prog["lora"]), x))(
        base, trainable, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda b, t, x: mod.reference_logits(sizes, b, t, x))(
            base, trainable, x))
    assert got.shape == (2, 32, sizes["vocab_size"])
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


def _tiny_traffic():
    traffic = copy.deepcopy(workload.load_traffic("sync-lm-s2048"))
    traffic["fft"].update(n_clients=8, k_selected=6)
    traffic["warmup_rounds"] = 3
    traffic["data"].update(seq_len=32, private_sequences=32, public_sequences=8,
                           test_sequences=4, group_size=2, buckets=64)
    return traffic


@pytest.fixture(scope="module")
def tiny_run():
    """One run of the cell at a small size, its runner kept."""
    sizes, _ = _small()
    keep, runners = {}, []
    res = run.run_cell(CELL, SEED, 0.0, False, rehearsal=True, sizes=sizes,
                       traffic=_tiny_traffic(), plant=runners.append, keep=keep,
                       log=lambda s: None)
    return res, keep, runners[0]


def test_tiny_cell_agrees_and_the_control_does_not(tiny_run):
    res, keep, _ = tiny_run
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    limits = run.load_limits(CELL)
    ws = reference.follow(keep["mod"], keep["sizes"], keep["task"], keep["base"], keep["w0"],
                          keep["rounds"], server_hist=keep["hists"][0],
                          client_hists=keep["hists"][1], public_y=keep["public_y"],
                          steps=keep["fft"]["local_steps"], batch=keep["fft"]["batch_size"],
                          dtype=jnp.bfloat16)
    control = reference.compare(keep["w0"], ws, keep["ref_ws"])
    assert any(control[k] > limits[k] for k in limits), control


def test_runner_rows_equal_the_task_histograms(tiny_run):
    _, keep, runner = tiny_run
    server, clients = keep["hists"]
    assert runner.server_hist.tolist() == server.tolist()
    assert runner.client_hists.tolist() == clients.tolist()


def test_one_local_update_matches_the_reference(tiny_run):
    _, keep, runner = tiny_run
    fft = keep["fft"]
    u = keep["rounds"][0].updates[1]
    got = runner._local_update(keep["w0"], keep["w0"], None, u.x, u.y, u.key, fft["lr"], 0.0)
    with jax.default_matmul_precision("highest"):
        fn = reference.local_update_fn(keep["mod"], keep["sizes"], keep["task"],
                                       fft["local_steps"], fft["batch_size"], jnp.float32, None)
        want = fn(keep["base"], keep["w0"], u.x, u.y, u.key, fft["lr"])
    moved = 0
    for path in want:
        for k in ("a", "b"):
            g, w, w0 = (np.asarray(t[path][k]) for t in (got, want, keep["w0"]))
            assert np.max(np.abs(g - w)) <= 1e-4 * max(np.max(np.abs(w - w0)), 1e-12), (path, k)
            moved += np.any(w != w0)
    assert moved == 2 * len(want)


def test_recorder_takes_and_passes_the_eight_arguments(tiny_run):
    _, keep, runner = tiny_run
    rec = run.Recorder(runner)
    x, y = runner.client_x[1], runner.client_y[1]
    key = jax.random.PRNGKey(7)
    try:
        out = runner._local_update(keep["w0"], keep["w0"], None, x, y, key, 0.05, 0.0)
    finally:
        rec.remove()
    assert len(rec.calls) == 1 and rec.calls[0][1] is x and rec.calls[0][3] is key
    direct = runner._update_program(runner.base_params, keep["w0"], keep["w0"], None, x, y,
                                    key, 0.05, 0.0)
    for u, v in zip(jax.tree.leaves(out), jax.tree.leaves(direct), strict=True):
        assert np.array_equal(np.asarray(u), np.asarray(v))


# XLA also counts the norms', softmax's and activations' elementwise work,
# which the analytic count leaves out.
FLOP_TOLERANCE = 0.03


def test_forward_flops_match_cost_analysis():
    """The dense layer with its MLA at published widths, and the head, batch
    1 of 64 tokens: the work both count alike. The MoE layers are left out:
    XLA's CPU form of the grouped products computes every held expert on
    every row, which is not the work counted."""
    sizes, mod = _config(num_hidden_layers=1)
    base, _ = jax.eval_shape(lambda k: mod.init(sizes, k), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    cost = jax.jit(mod.program(sizes)["apply"]).lower(base, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    got = cost["flops"]                      # one sequence
    want = mod.forward_flops(sizes, 64)
    assert abs(got / want - 1) < FLOP_TOLERANCE, (got, want)
    full, _ = _config()
    assert mod.train_flops(full, 2048) > 1.9 * mod.forward_flops(full, 2048)
    # the routed part counts the expected held load, T x k x 8 / 64 pairs
    parts = mod._parts(full, 2048)
    assert parts["routed"] == 2.0 * 2048 * 6 * 8 / 64 * 3 * 2048 * 1408


def _fixture_ctx():
    tr = device_trace.read(str(FIXTURE))
    lo, hi = device_trace.window(tr)
    return run.TraceCtx(trace=tr, lo=lo, hi=hi, window_s=(hi - lo) * 1e-9,
                        busy_s=device_trace.busy_ns(tr, lo, hi) * 1e-9, rounds=3,
                        peaks=run.load_peaks("TPU v5 lite"), samples_per_update=1,
                        train_flops_per_sample=1.0, uplink_bytes=0.0, global_bytes=0.0)


def test_op_scopes_read_the_fixture():
    paths = op_scopes.op_paths(str(FIXTURE))
    assert set(paths.values()) == {"jit(local_update)/dot_general"}
    assert all(name.startswith("%fusion") for name in paths)
    ctx = _fixture_ctx()
    ms = op_scopes.scope_ms(ctx, "dot_general", FIXTURE)
    ns, n = ctx.module_time(("local_update",))
    assert n == 3 and 0 < ms <= ns * 1e-6 / n
    assert op_scopes.scope_ms(ctx, "mla", FIXTURE) is None


def test_in_scope_sees_through_the_transforms():
    path = "jit(local_update)/while/body/closed_call/transpose(jvp(mla))/dot_general"
    assert op_scopes.in_scope(path, "mla")
    assert op_scopes.in_scope("jit(local_update)/while/body/moe.route/sort", "moe.route")
    assert not op_scopes.in_scope("jit(local_update)/moe.route/sort", "moe.experts")
    assert not op_scopes.in_scope("jit(local_update)/mla_x/dot", "mla")


def test_scope_metrics_read_nothing_where_the_program_has_no_scope(tmp_path):
    ctx = _fixture_ctx()
    bench = run.load_benchmark()
    names = [name for name, _, _ in run.cell_metrics(bench, CELL)]
    assert {"mla_ms", "moe_route_ms", "moe_expert_ms"} <= set(names)
    for name in ("mla_ms", "moe_route_ms", "moe_expert_ms"):
        reader = run.load_module(run.HERE / "metrics" / f"{name}.py")
        assert reader.read(ctx) is None
    assert op_scopes.latest_trace(tmp_path) is None
    assert "mla_ms" not in [n for n, _, _ in run.cell_metrics(bench, "vitb16-lora-c100.sync-fp32")]
