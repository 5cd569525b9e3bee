"""Elastic checkpoint–stop–restart trainer (paper §5–6).

Drives any model exposing ``loss(params, batch, sh=None)`` through training
segments at varying worker counts w.  Per-worker minibatch m stays fixed
(global batch = m*w, §5), the LR rescales linearly on resize (eq. 7), and LR
decay boundaries stay pinned to *epochs* so they shift in step-space with the
batch size, exactly as the paper describes.  A segment at w workers runs on a
data mesh of w devices when that many exist (batch sharded, state
replicated, gradients exchanged by ``grad_exchange``); otherwise it runs the
global batch on one device, and ``SegmentRecord.devices`` says which
happened.  Stop and restart costs are measured, not assumed —
benchmarks/table2_stop_restart.py reports them.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.store import CheckpointStore
from repro.engine.steps import make_data_parallel_step
from repro.launch.mesh import make_data_mesh
from repro.optim.optimizers import Optimizer
from repro.optim.schedule import rescale_lr


@dataclasses.dataclass
class SegmentRecord:
    w: int
    devices: int           # devices the segment actually ran on
    steps: int
    epochs: float
    lr: float              # LR at the segment's first step (eq. 7)
    losses: list           # (global_step, cumulative_epoch, loss)
    seconds: float
    first_step_seconds: float   # first step to ready, compile included
    step_seconds: float    # mean per step after the first (nan if 1 step)
    restore_seconds: float
    save_seconds: float
    state: dict            # final state, as saved


class ElasticTrainer:
    def __init__(self, model, optimizer: Optimizer, data,
                 ckpt: CheckpointStore, *, base_lr_1w: float,
                 m_per_worker: int = 128,
                 decay_epochs: tuple = (100, 150), decay_factor: float = 0.1,
                 dataset_size: int | None = None,
                 grad_exchange: str | None = None):
        self.model = model
        self.opt = optimizer
        self.data = data
        self.ckpt = ckpt
        self.base_lr_1w = base_lr_1w
        self.m = m_per_worker
        self.decay_epochs = decay_epochs
        self.decay_factor = decay_factor
        self.dataset = dataset_size or getattr(data, "size", 50_000)
        self.grad_exchange = grad_exchange
        self._steps: dict[int, tuple] = {}

    # ------------------------------------------------------------ state ----
    def fresh_state(self, key=None) -> dict:
        params = self.model.init(key if key is not None
                                 else jax.random.PRNGKey(0))
        return {"params": params, "opt": self.opt.init(params),
                "step": jnp.zeros((), jnp.int32),
                "epoch": jnp.zeros((), jnp.float32)}

    def _lr(self, w: int, epoch: float) -> float:
        # linear scaling (eq. 7 relative to the 1-worker base) + epoch-pinned
        # step decay
        lr = rescale_lr(self.base_lr_1w, w, 1)
        for b in self.decay_epochs:
            if epoch >= b:
                lr *= self.decay_factor
        return lr

    def step_for(self, w: int):
        """-> (jitted step, replicated sharding, batch sharding) that a
        segment at w workers runs: w devices when that many exist, else
        one."""
        n = w if jax.device_count() >= w else 1
        if n not in self._steps:
            mesh = make_data_mesh(n)
            self._steps[n] = make_data_parallel_step(
                self.model, self.opt, mesh, self.grad_exchange)
        return self._steps[n]

    # ---------------------------------------------------------- segments ---
    def train_segment(self, w: int, n_steps: int, *, resume: bool = True,
                      log_every: int = 10) -> SegmentRecord:
        restore_s = 0.0
        if resume and self.ckpt.latest_step() is not None:
            template = self.fresh_state()
            state, meta, restore_s = self.ckpt.restore(template)
        else:
            state = self.fresh_state()
        step, rep, data_sharding = self.step_for(w)
        devices = len(rep.device_set)

        global_batch = self.m * w
        epochs_per_step = global_batch / self.dataset
        losses = []
        t0 = time.perf_counter()
        step0 = int(state["step"])
        epoch = float(state["epoch"])
        lr0 = self._lr(w, epoch)
        train_state = jax.device_put(
            {"params": state["params"], "opt": state["opt"]}, rep)
        first_s = 0.0
        for i in range(n_steps):
            gstep = step0 + i
            batch = jax.device_put(self.data.batch(gstep, global_batch),
                                   data_sharding)
            train_state, loss = step(train_state, batch,
                                     self._lr(w, epoch))
            if i == 0:
                jax.block_until_ready((train_state, loss))
                first_s = time.perf_counter() - t0
            epoch += epochs_per_step
            if i % log_every == 0 or i == n_steps - 1:
                losses.append((gstep, epoch, float(loss)))
        jax.block_until_ready(train_state)
        seconds = time.perf_counter() - t0
        step_s = ((seconds - first_s) / (n_steps - 1) if n_steps > 1
                  else float("nan"))

        state = {**train_state,
                 "step": jnp.asarray(step0 + n_steps, jnp.int32),
                 "epoch": jnp.asarray(epoch, jnp.float32)}
        save_s = self.ckpt.save(step0 + n_steps, state,
                                meta={"w": w, "epoch": epoch})
        return SegmentRecord(w=w, devices=devices, steps=n_steps,
                             epochs=epoch, lr=lr0, losses=losses,
                             seconds=seconds, first_step_seconds=first_s,
                             step_seconds=step_s, restore_seconds=restore_s,
                             save_seconds=save_s, state=state)
