"""What decides ``correct``, at tiny sizes on the CPU: a sound run goes end
to end and reads finite numbers; the control (the reference in float8 in
the program's place) fails; and a run whose timed path is broken
underneath fails, once for each fault a cell can have.  The limits are the
cells' own, set from readings on the chip at the cells' sizes, so a sound
run at a tiny size is not held to them."""
import json
import math
import os
import subprocess
import sys

import pytest

import check
import harness
import tiny

ONE_CHIP = ["resnet110.w1.train", "qwen2.5-3b-l4.w1.train"]
RESIZE = tiny.RESIZE["name"]
FOUR = dict(os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")


def _in_four_devices(code: str, tmp_path) -> dict:
    root = tiny.checkout_with_resize(tmp_path)
    prog = ("import sys, json\n"
            "sys.path[:0] = ['benchmarks/chip', 'benchmarks/chip/tests', "
            "'src']\n"
            "import harness, tiny\n" + code +
            "\nprint(json.dumps(tiny.run(" + repr(RESIZE) + ")))\n")
    r = subprocess.run([sys.executable, "-c", prog], env=FOUR, cwd=root,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _sound(out):
    assert set(out["checks"]) == set(harness.limits(out["cell"]))
    assert all(math.isfinite(v["value"]) for v in out["checks"].values())
    assert out["metrics"]["samples_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_goes_end_to_end(cell):
    _sound(dict(tiny.run(cell), cell=cell))


def test_sound_resize_run_goes_end_to_end(tmp_path):
    out = _in_four_devices("", tmp_path)
    _sound(dict(out, cell=RESIZE))
    assert out["metrics"]["restart_s"]["value"] > 0


@pytest.mark.parametrize("cell", ONE_CHIP + [RESIZE])
def test_control_fails(cell, tmp_path):
    job = tiny.job(cell, str(tmp_path))
    ref = harness.reference_readings(job)
    ctl = harness.reference_readings(job, "fp8")
    nums = check.numbers(ctl, ref)
    assert not check.judge(nums, harness.limits(cell)), nums


UNCHANGED = """
import repro.core.elastic as el
_real = el.make_data_parallel_step
def _frozen(model, opt, mesh, grad_exchange=None):
    import jax
    step, rep, data = _real(model, opt, mesh, grad_exchange)
    def still(state, batch, lr):
        return state, step(state, batch, lr)[1]
    return still, rep, data
el.make_data_parallel_step = _frozen
"""

HALF = """
from repro.models.resnet import ResNetModel
from repro.models.transformer import TransformerModel
import jax
for cls in (ResNetModel, TransformerModel):
    def _half(self, params, batch, sh=None, _loss=cls.loss):
        batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        return _loss(self, params, batch, sh)
    cls.loss = _half
"""

NO_EXCHANGE = """
import repro.core.elastic as el
from repro.engine.steps import make_train_step
def _local(model, opt, mesh, grad_exchange=None):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    step = jax.shard_map(make_train_step(model, opt), mesh=mesh,
                         in_specs=(P(), P("data"), P()),
                         out_specs=(P(), P()), check_vma=False)
    rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    return (jax.jit(step, in_shardings=(rep, data, rep),
                    out_shardings=(rep, rep)), rep, data)
el.make_data_parallel_step = _local
"""


@pytest.mark.parametrize("fault", [UNCHANGED, HALF],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    import repro.core.elastic as el
    from repro.models.resnet import ResNetModel
    from repro.models.transformer import TransformerModel

    monkeypatch.setattr(el, "make_data_parallel_step",
                        el.make_data_parallel_step)
    monkeypatch.setattr(ResNetModel, "loss", ResNetModel.loss)
    monkeypatch.setattr(TransformerModel, "loss", TransformerModel.loss)
    exec(fault, {})
    out = tiny.run(cell)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", [UNCHANGED, HALF, NO_EXCHANGE],
                         ids=["state_unchanged", "half_batch",
                              "no_exchange"])
def test_resize_fault_is_not_correct(fault, tmp_path):
    out = _in_four_devices(fault, tmp_path)
    assert out["correct"] is False, out["checks"]
