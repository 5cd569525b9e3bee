"""chip_smoke.py off the chip: it refuses to run without a TPU or outside
the checkout, and its phases pass at a tiny depth on the CPU (the
four-device phase in a subprocess with 4 host devices)."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.resnet110 import smoke_config  # noqa: E402


def _run(cwd, *args, env=None):
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    r = _run(ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(tmp_path, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_restart_phase_tiny(capsys):
    chip_smoke.restart_phase(smoke_config(), m=8, steps=(6, 4))
    out = capsys.readouterr().out
    assert "leaves bit-identical" in out
    assert "segment2: w=1 devices=1" in out


def test_exchange_phase_tiny_4dev():
    code = ("import chip_smoke\n"
            "from repro.configs.resnet110 import smoke_config\n"
            "chip_smoke.exchange_phase(smoke_config(), m=4)\n"
            "print('EXCHANGE_OK')\n")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "EXCHANGE_OK" in r.stdout
    assert "resize_w2: w=2 devices=2 ids=[0, 1]" in r.stdout
