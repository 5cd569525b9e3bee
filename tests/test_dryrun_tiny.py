"""Dry-run machinery test on an 8-device tiny mesh (subprocess — the main
pytest process must keep 1 device).  Full-size 256/512-device runs are the
EXPERIMENTS.md sweep; this validates the lowering path per family x kind."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Subprocess dry-runs take minutes: keep them out of the fast CI lane.
pytestmark = pytest.mark.slow

CASES = [
    ("qwen2.5-3b", "train_4k", "single"),
    ("qwen3-moe-30b-a3b", "prefill_32k", "single"),
    ("mamba2-780m", "decode_32k", "multi"),
    ("jamba-v0.1-52b", "train_4k", "multi"),
    ("whisper-base", "decode_32k", "single"),
]


@pytest.mark.parametrize("arch,shape,mesh", CASES)
def test_dryrun_tiny(arch, shape, mesh):
    env = dict(os.environ, REPRO_DRYRUN_DEVICES="8", PYTHONPATH=SRC)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--tiny", "--skip-costs"],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK " in r.stdout


def test_dryrun_records_roofline_terms(tmp_path):
    env = dict(os.environ, REPRO_DRYRUN_DEVICES="8", PYTHONPATH=SRC)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma-2b",
         "--shape", "train_4k", "--mesh", "single", "--tiny",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    import json
    files = list(tmp_path.iterdir())
    assert len(files) == 1
    rec = json.loads(files[0].read_text())
    roof = rec["roofline"]
    for key in ("compute_s", "memory_s", "collective_s", "dominant"):
        assert key in roof
    assert roof["compute_s"] > 0
    assert rec["memory"]["peak_bytes_per_device"] > 0
    assert rec["useful_flops_ratio"] is not None
    # scan-corrected flops must be ~L x the body-once raw number
    assert (roof["flops_per_device"]
            > 4 * rec["raw_costs_scan_body_once"]["flops"])
