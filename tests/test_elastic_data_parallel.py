"""Data-parallel ElasticTrainer on 4 host devices: a segment at w = 4 runs
on 4 devices (in a subprocess, so this test process keeps 1 device) and,
after one step, matches the same segment run here on one device with the 4m
global batch, for XLA's all-reduce and both explicit exchanges (atol as in
test_collectives_shardmap.py).  A resume at w = 2 runs on 2 devices."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.configs.resnet110 import smoke_config
from repro.core.elastic import ElasticTrainer
from repro.data.synthetic import CifarLike
from repro.models.resnet import ResNetModel
from repro.optim.optimizers import sgd

SCRIPT = r"""
import sys
import jax
sys.path.insert(0, sys.argv[3])
from test_elastic_data_parallel import trainer

exchange = None if sys.argv[1] == "xla" else sys.argv[1]
assert jax.device_count() == 4

def device_ids(state):
    leaf = jax.tree_util.tree_leaves(state["params"])[0]
    return sorted(d.id for d in leaf.sharding.device_set)

four = trainer(sys.argv[2], exchange).train_segment(
    w=4, n_steps=1, resume=False, log_every=1)
assert four.devices == 4 and device_ids(four.state) == [0, 1, 2, 3]
two = trainer(sys.argv[2], exchange).train_segment(
    w=2, n_steps=2, resume=True, log_every=1)
assert two.devices == 2 and device_ids(two.state) == [0, 1]
assert two.losses[0][0] == 1 and int(two.state["step"]) == 3
assert two.lr == four.lr / 2
print("ELASTIC_DP_OK", four.losses[0][2])
"""


def trainer(ckpt_dir, grad_exchange=None):
    return ElasticTrainer(ResNetModel(smoke_config()), sgd(),
                          CifarLike(size=256, seed=0),
                          CheckpointStore(ckpt_dir), base_lr_1w=0.05,
                          m_per_worker=8, dataset_size=256,
                          grad_exchange=grad_exchange)


@pytest.mark.parametrize("exchange", ["xla", "ring", "doubling_halving"])
def test_elastic_trainer_data_parallel_4dev(exchange, tmp_path):
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(here, "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, exchange,
                        str(tmp_path / "dp"), here], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ELASTIC_DP_OK" in r.stdout

    assert jax.device_count() == 1
    one = trainer(str(tmp_path / "ref")).train_segment(
        w=4, n_steps=1, resume=False, log_every=1)
    assert one.devices == 1
    four, _, _ = CheckpointStore(str(tmp_path / "dp")).restore(one.state,
                                                               step=1)
    loss4 = float(r.stdout.split("ELASTIC_DP_OK")[1])
    np.testing.assert_allclose(loss4, one.losses[0][2], rtol=1e-3)
    for a, b in zip(jax.tree_util.tree_leaves(one.state),
                    jax.tree_util.tree_leaves(four)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
