"""The harness finds configurations, traffic mixes, cells and per-layer
metrics by name, so that adding one takes a new file and no edit."""
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
