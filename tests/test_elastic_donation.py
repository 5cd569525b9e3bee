"""The train step donates the state (``make_data_parallel_step``): the
elastic loop never reads a state it has passed in, through a checkpoint, a
resume in a fresh trainer and a 4 -> 2 resize on 4 host devices, and it
trains bit-identically to the same loop with an undonated step."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

SCRIPT = r"""
import hashlib, json, sys
import jax
import numpy as np
import repro.core.elastic as el
from repro.checkpoint.store import CheckpointStore
from repro.configs import get_smoke_config
from repro.data.synthetic import CifarLike, TokenStream
from repro.models.registry import build_model
from repro.models.resnet import ResNetModel
from repro.configs.resnet110 import smoke_config
from repro.optim.optimizers import adamw, sgd

arch, root = sys.argv[1], sys.argv[2]
assert jax.device_count() == 4


def trainer(ckpt):
    if arch == "resnet110":
        model, opt, data = (ResNetModel(smoke_config()), sgd(),
                            CifarLike(size=256, seed=0))
    else:
        cfg = get_smoke_config(arch)
        model, opt, data = (build_model(cfg), adamw(),
                            TokenStream(cfg.vocab_size, 16, seed=0))
    return el.ElasticTrainer(model, opt, data, CheckpointStore(ckpt),
                             base_lr_1w=1e-3, m_per_worker=2,
                             dataset_size=256)


def run(tag):
    ckpt = f"{root}/{tag}"
    a = trainer(ckpt).train_segment(w=4, n_steps=2, resume=False,
                                    log_every=1)
    b = trainer(ckpt).train_segment(w=2, n_steps=3, resume=True,
                                    log_every=1)
    assert (a.devices, b.devices) == (4, 2)
    leaves = jax.tree_util.tree_leaves(b.state)
    return {"losses": [l for _, _, l in a.losses + b.losses],
            "state": [hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
                      for x in leaves]}


def gone_after_a_step():
    # Whether a state passed to a step is deleted by the call.
    tr = trainer(f"{root}/probe")
    step, rep, data = tr.step_for(2)
    state = jax.device_put({k: v for k, v in tr.fresh_state().items()
                            if k in ("params", "opt")}, rep)
    step(state, jax.device_put(tr.data.batch(0, 4), data), 0.1)
    return all(x.is_deleted() for x in jax.tree_util.tree_leaves(state))


donated, gone = run("donated"), gone_after_a_step()
# The same loop with every jit built from here on undonated.
real_jit = jax.jit
jax.jit = lambda f, donate_argnums=(), **kw: real_jit(f, **kw)
kept, kept_gone = run("kept"), gone_after_a_step()
print("DONATION " + json.dumps({"donated": donated, "kept": kept,
                                "gone": [gone, kept_gone]}))
"""


@pytest.mark.parametrize("arch", ["resnet110", "deepseek-v2-lite"])
def test_donated_state_survives_resume_and_resize(arch, tmp_path):
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(here, "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, arch, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.split("DONATION ", 1)[1])
    assert out["gone"] == [True, False]
    assert out["donated"] == out["kept"]
    assert len(out["donated"]["losses"]) == 5


def test_step_outputs_alias_the_donated_state():
    """One device: the jitted step's new state is written over the old."""
    from repro.configs.resnet110 import smoke_config
    from repro.engine.steps import make_data_parallel_step
    from repro.launch.mesh import make_data_mesh
    from repro.models.resnet import ResNetModel
    from repro.optim.optimizers import sgd

    step, rep, data = make_data_parallel_step(
        ResNetModel(smoke_config()), sgd(), make_data_mesh(1))
    model = ResNetModel(smoke_config())
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": sgd().init(params)}
    batch = {"images": jnp.zeros((2, 32, 32, 3)),
             "labels": jnp.zeros((2,), jnp.int32)}
    text = step.lower(state, batch, jnp.float32(0.1)).as_text()
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text


def test_loop_waits_for_the_step_in_flight_back(tmp_path, monkeypatch):
    """With the state donated nothing else holds the loop back: after
    dispatching step k it waits for step k - IN_FLIGHT, and for nothing
    later, before it dispatches the next."""
    import repro.core.elastic as el
    from repro.checkpoint.store import CheckpointStore
    from repro.configs.resnet110 import smoke_config
    from repro.data.synthetic import CifarLike
    from repro.models.resnet import ResNetModel
    from repro.optim.optimizers import sgd

    events = []
    real_make, real_wait = el.make_data_parallel_step, jax.block_until_ready

    def make(*args, **kw):
        step, rep, data = real_make(*args, **kw)

        def counted(state, batch, lr):
            out = step(state, batch, lr)
            events.append(("dispatch", out[1]))
            return out
        return counted, rep, data

    def wait(x):
        events.append(("wait", x))
        return real_wait(x)

    monkeypatch.setattr(el, "make_data_parallel_step", make)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    tr = el.ElasticTrainer(ResNetModel(smoke_config()), sgd(),
                           CifarLike(size=256, seed=0),
                           CheckpointStore(str(tmp_path)), base_lr_1w=0.05,
                           m_per_worker=4, dataset_size=256)
    steps = 7
    tr.train_segment(1, steps, resume=False, log_every=100)
    losses = [x for kind, x in events if kind == "dispatch"]
    assert len(losses) == steps
    at = [i for i, (kind, _) in enumerate(events) if kind == "dispatch"]
    for k in range(el.IN_FLIGHT, steps):
        nxt = at[k + 1] if k + 1 < steps else len(events)
        waited = [x for kind, x in events[at[k] + 1:nxt] if kind == "wait"]
        assert waited[0] is losses[k - el.IN_FLIGHT], k
        assert not any(x is y for x in waited for y in losses[k - 1:k + 1])
