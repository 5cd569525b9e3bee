"""BENCHMARK.json and the files it names: every cell, configuration,
traffic, limit and per-layer metric is found by name, and a new one is added
by adding files."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import check
import harness

B = harness.benchmark()
CELLS = [c["name"] for c in B["workloads"]]


def test_command_and_paths():
    assert B["paths"] == ["benchmarks/chip"]
    assert B["command"] == ["python3", "benchmarks/chip/run.py"]
    for c in B["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert os.path.exists(os.path.join(harness.CHECKOUT, c["file"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist(name):
    cell = harness.cell(name)
    cfg, mod = harness.config(cell["config"])
    assert cfg["name"] == cell["config"]
    tr = harness.traffic_mod.load(cell["traffic"])
    assert {"m", "segments", "log_every", "checkpoint",
            "trace_steps"} <= set(tr)
    assert mod.INPUT in ("images", "tokens")
    one = {"losses": [1.0] * 3, "grad1": [np.ones(2)],
           "change3": [np.ones(2)]}
    assert set(harness.limits(name)) <= set(check.numbers(one, one))
    assert cell["chips"] in (1, 4)
    assert cell["chips"] >= max(s["w"] for s in tr["segments"])


@pytest.mark.parametrize("metric", B["per_layer"],
                         ids=[m["name"] for m in B["per_layer"]])
def test_per_layer_metric_reader_and_moves(metric):
    reader = harness.metric_reader(metric["name"])
    assert callable(reader.reduce)
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in metric["workloads"]:
        assert cell in CELLS
        reported = e2e[metric["moves"]].get("workloads", CELLS)
        assert cell in reported


def test_every_cell_reports_setup_and_another_metric_and_a_layer():
    for name in CELLS:
        e2e = [m["name"] for m in B["end_to_end"]
               if name in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(name in m.get("workloads", CELLS) for m in B["per_layer"])


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


def _run_cmd(cwd, env):
    return subprocess.run(
        B["command"] + ["--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    r = _run_cmd(harness.CHECKOUT, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no accelerator" in r.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run_cmd(tmp_path, env)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_a_new_cell_is_files_only(tmp_path):
    """A configuration, a traffic mix, its limits and a per-layer metric,
    added as new files and one entry each in BENCHMARK.json, run through the
    harness unchanged."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.CHECKOUT, "src"), root / "src")
    b = json.loads(json.dumps(B))
    shutil.copy(bench / "configs" / "resnet110.py",
                bench / "configs" / "resnet20.py")
    cfg = json.loads((bench / "configs" / "resnet110.json").read_text())
    cfg.update(name="resnet20", depth=20)
    (bench / "configs" / "resnet20.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "w1.m128.json").read_text())
    tr.update(m=4, segments=[{"w": 1, "steps": 4}], trace_steps=[4, 6])
    (bench / "traffic" / "w1.m4.json").write_text(json.dumps(tr))
    (bench / "limits" / "resnet20.w1.train.json").write_text(json.dumps(
        {"limits": {"loss_gap": 0.05, "grad_gap": 0.1, "change_gap": 0.1}}))
    (bench / "metrics" / "steps_in_window.py").write_text(
        "def reduce(run):\n"
        "    return float(sum(s['steps'] for s in run.segments))\n")
    b["configs"].append({"name": "resnet20", "source": "x",
                         "file": "benchmarks/chip/configs/resnet20.json",
                         "reduced": ["depth"], "why": "x"})
    b["workloads"].append({"name": "resnet20.w1.train", "config": "resnet20",
                           "traffic": "w1.m4", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "x", "moves": "samples_per_s",
                           "workloads": ["resnet20.w1.train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    code = ("import sys, json\n"
            "sys.path[:0] = ['benchmarks/chip', 'src']\n"
            "import harness\n"
            "r = harness.run('resnet20.w1.train', 7, 1.0, True,\n"
            "                device={'platform': 'cpu'}, peak={})\n"
            "print(json.dumps(r))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=root,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["steps_in_window"]["value"] == 4.0
