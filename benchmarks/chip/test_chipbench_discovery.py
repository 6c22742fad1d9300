"""The harness finds configurations, their tasks, traffic mixes, cells and
per-layer metrics by name, so that adding one takes a new file and no edit."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run
import workload

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def test_every_cell_resolves():
    bench = run.load_benchmark()
    for cell in bench["workloads"]:
        _, entry = run.find_cell(bench, cell["name"])
        sizes, mod = run.load_config(entry)
        assert sizes["name"] == entry["name"]
        for fn in ("init", "program", "reference_logits", "forward_flops", "train_flops"):
            assert callable(getattr(mod, fn))
        task = run.load_task(sizes["task"])
        for fn in ("make_job", "histograms", "loss", "missing_hist", "flops_per_sample"):
            assert callable(getattr(task, fn))
        traffic = workload.load_traffic(cell["traffic"])
        assert traffic["warmup_rounds"] >= 3
        assert set(run.load_limits(cell["name"])) == {"change_1", "change_3"}
        names = [m[0] for m in run.cell_metrics(bench, cell["name"])]
        assert names, cell["name"]


def test_every_metric_file_loads():
    bench = run.load_benchmark()
    for m in bench["per_layer"]:
        mod = run.load_module(HERE / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


def test_new_files_are_found_without_an_edit(tmp_path):
    """A cell, a traffic mix, a configuration and a metric added as files in
    a copy of the benchmark are found by the unchanged harness."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = workload.load_traffic("sync-fp32")
    traffic["fft"]["k_selected"] = 10
    (here / "traffic" / "sync-fp32-k10.json").write_text(json.dumps(traffic))
    cfg = json.loads((here / "configs" / "resnet18-c100.json").read_text())
    cfg["name"] = "resnet18-c10"
    cfg["num_classes"] = 10
    (here / "configs" / "resnet18-c10.json").write_text(json.dumps(cfg))
    (here / "metrics" / "rounds_traced.py").write_text("def read(ctx):\n    return ctx.rounds\n")
    (here / "limits" / "resnet18-c10.sync-fp32-k10.json").write_text(
        json.dumps({"limits": {"change_1": 0.1, "change_3": 0.1}}))
    bench["configs"].append(dict(bench["configs"][0], name="resnet18-c10",
                                 file="benchmarks/chip/configs/resnet18-c10.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="resnet18-c10.sync-fp32-k10",
                                   config="resnet18-c10", traffic="sync-fp32-k10"))
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds", "better": "higher",
                               "source": "device_trace", "layer": "round loop",
                               "moves": "round_s", "workloads": ["resnet18-c10.sync-fp32-k10"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = run.load_benchmark(tmp_path)
    cell, entry = run.find_cell(got, "resnet18-c10.sync-fp32-k10")
    sizes, mod = run.load_config(entry, tmp_path)
    assert sizes["num_classes"] == 10 and callable(mod.init)
    assert workload.load_traffic(cell["traffic"], here)["fft"]["k_selected"] == 10
    assert run.load_limits(cell["name"], here)["change_1"] == 0.1
    names = [m[0] for m in run.cell_metrics(got, cell["name"], here)]
    assert "rounds_traced" in names
    assert "rounds_traced" not in [m[0] for m in run.cell_metrics(got, "resnet18-c100.sync-fp32", here)]


TOY_LM = """
import jax
import jax.numpy as jnp


def init(sizes, key):
    v, d = sizes["vocab_size"], sizes["hidden_size"]
    return {}, {"emb": jax.random.normal(key, (v, d)), "head": jnp.zeros((d, v))}


def reference_logits(sizes, base, trainable, x):
    return trainable["emb"][x] @ trainable["head"]


def train_flops(sizes, seq_len):
    return 6.0 * sizes["vocab_size"] * sizes["hidden_size"] * seq_len
"""


def test_a_causal_lm_configuration_is_found_without_an_edit(tmp_path):
    """A token-level configuration and its traffic, added as files in a copy
    of the benchmark, resolve to the causal-LM task, which makes their job."""
    import jax

    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (here / "configs" / "toy-lm.py").write_text(TOY_LM)
    sizes = {"name": "toy-lm", "module": "toy-lm.py", "task": "causal-lm",
             "vocab_size": 32, "hidden_size": 8}
    (here / "configs" / "toy-lm.json").write_text(json.dumps(sizes))
    traffic = workload.load_traffic("sync-fp32")
    traffic["fft"].update(n_clients=4)
    traffic["data"] = {"partition": "group_domains", "seq_len": 8, "private_sequences": 16,
                       "public_sequences": 4, "test_sequences": 4, "group_size": 2,
                       "buckets": 4, "hop_prob": 0.8}
    (here / "traffic" / "tokens.json").write_text(json.dumps(traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="toy-lm",
                                 file="benchmarks/chip/configs/toy-lm.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="toy-lm.tokens",
                                   config="toy-lm", traffic="tokens"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = run.load_benchmark(tmp_path)
    cell, entry = run.find_cell(got, "toy-lm.tokens")
    sizes, mod = run.load_config(entry, tmp_path)
    task = run.load_task(sizes["task"], here)
    assert Path(task.__file__).name == "causal_lm.py"
    traffic = workload.load_traffic(cell["traffic"], here)
    job = task.make_job(traffic, sizes, workload.seed_key(2 ** 31 + 3))
    assert job.private.x.shape == (16, 8) and job.n_classes == 4
    assert task.flops_per_sample(mod, sizes, traffic) == mod.train_flops(sizes, 8)
    base, w = mod.init(sizes, jax.random.PRNGKey(0))
    logits = mod.reference_logits(sizes, base, w, job.test.x)
    assert float(task.loss(logits, job.test.y)) > 0


def test_an_unknown_task_lists_the_known_ones():
    with pytest.raises(ValueError) as err:
        run.load_task("image-regression")
    assert "'causal-lm'" in str(err.value) and "'image-classes'" in str(err.value)
    with pytest.raises(ValueError):
        run.load_task("../run")


def test_peaks_are_keyed_by_device_kind():
    assert run.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks("cpu")


def test_no_tpu_exits_nonzero_without_a_result():
    """Here JAX finds only the CPU: the run must refuse and print nothing."""
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "resnet18-c100.sync-fp32",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "resnet18-c100.sync-fp32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={k: v for k, v in __import__("os").environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert not proc.stdout.strip().endswith("}")
