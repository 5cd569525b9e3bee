"""Executable ring / halving-doubling all-reduce under shard_map, validated
against lax.psum on 8 host devices (subprocess so the main test process
keeps 1 device)."""
import os
import subprocess
import sys

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.collectives.xla import (ring_allreduce,
                                   halving_doubling_allreduce, exchange_tree)

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 45)).astype(np.float32))

for name, fn in [("ring", ring_allreduce),
                 ("dh", halving_doubling_allreduce)]:
    @partial(shard_map, mesh=mesh, in_specs=P("data", None),
             out_specs=P("data", None), check_vma=False)
    def run(xs):
        return fn(xs[0], "data")[None]
    out = np.asarray(run(x))
    want = np.asarray(x.sum(0))
    assert np.allclose(out, want[None], atol=1e-4), name
    # also against psum
    @partial(shard_map, mesh=mesh, in_specs=P("data", None),
             out_specs=P("data", None), check_vma=False)
    def run_psum(xs):
        return jax.lax.psum(xs[0], "data")[None]
    assert np.allclose(out, np.asarray(run_psum(x)), atol=1e-4), name

# fusion-buffer tree exchange
tree = {"a": x[:, :10], "b": x[:, 10:].reshape(8, 35)}
@partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
         check_vma=False)
def run_tree(t):
    local = jax.tree.map(lambda v: v[0], t)
    out = exchange_tree(local, "data", "doubling_halving")
    return jax.tree.map(lambda v: v[None], out)
out = run_tree(tree)
assert np.allclose(np.asarray(out["a"]), np.asarray(tree["a"].sum(0))[None],
                   atol=1e-4)
assert np.allclose(np.asarray(out["b"]), np.asarray(tree["b"].sum(0))[None],
                   atol=1e-4)

# end-to-end: explicit-exchange DP training step == psum step
from repro.configs import get_smoke_config
from repro.models.registry import build_model
from repro.engine.steps import make_train_step, init_train_state
from repro.optim.optimizers import sgd

cfg = get_smoke_config("gemma-2b")
model = build_model(cfg)
opt = sgd()
state = init_train_state(model, opt)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

outs = {}
for mode in ("psum", "ring"):
    step = make_train_step(model, opt, grad_exchange=mode)
    jitted = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), {"tokens": P("data"), "labels": P("data")}, P()),
        out_specs=(P(), P()), check_vma=False))
    new_state, loss = jitted(state, batch, jnp.float32(0.1))
    outs[mode] = (new_state, float(loss))
leaves_a = jax.tree.leaves(outs["psum"][0])
leaves_b = jax.tree.leaves(outs["ring"][0])
for a, b in zip(leaves_a, leaves_b):
    assert np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                       atol=2e-3), "ring-exchange step != psum step"
print("SHARDMAP_OK")
"""


def test_shardmap_allreduce_8dev():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDMAP_OK" in r.stdout
