"""Elastic checkpoint–stop–restart trainer (paper §5–6).

Drives any model exposing ``loss(params, batch, sh=None)`` through training
segments at varying worker counts w.  Per-worker minibatch m stays fixed
(global batch = m*w, §5), the LR rescales linearly on resize (eq. 7), and LR
decay boundaries stay pinned to *epochs* so they shift in step-space with the
batch size, exactly as the paper describes.  A segment at w workers runs on a
data mesh of w devices when that many exist (batch sharded, state
replicated, gradients exchanged by ``grad_exchange``); otherwise it runs the
global batch on one device, and ``SegmentRecord.devices`` says which
happened.

Stop and restart costs are measured, not assumed.  ``train_segment`` marks
each part of a segment with a span named ``elastic.<part>`` (``SPANS``):
a ``jax.profiler`` annotation on the profiler's clock, and a timer of the
same name in ``ElasticTrainer.registry`` (a ``core.telemetry.Registry``),
beside the counters ``elastic.steps``, ``elastic.samples`` and
``elastic.step_builds``.  The set-up of a segment (``init_state``,
``restore``, ``place``, ``first_step``) is what a restart costs; ``step``
(a ``StepTraceAnnotation`` numbered by the global step) holds ``input``,
``h2d``, ``dispatch`` and, at log steps, ``loss_sync``.  In a segment's
first step, ``first_step`` holds its ``input``, ``h2d`` and ``dispatch``
and lasts until that step is ready.

The step donates the state it is given (``make_data_parallel_step``): the
loop rebinds the state to the step's result and never reads one it has
passed in.  ``dispatch`` also holds the wait that keeps the loop at most
``IN_FLIGHT`` steps ahead of the device.  A model with ``step_stats`` (the drop-free MoE) has its step
return its held experts' loads as well; at log steps the loop reads them
in the same transfer as the loss and adds them to the counter
``moe.assignments_held`` and the peak ``moe.busiest_expert_ratio`` (a
layer's busiest held expert over its mean load, the highest seen).

The data source.  ``data.batch(step, global_batch)`` must be a pure
function of its arguments.  A segment asks for its first step's batch
inline; once that step is ready, one producer thread makes the batches of
the later steps ahead of the loop, at most ``PREFETCH`` of them, so host
input overlaps the loop's dispatch.  ``batch`` is thus called once per
step, in step order, never past the segment, and after the first step from
a thread that is not the caller's.  ``input`` then times the wait for the
prefetched batch (the input the loop is exposed to), the producer's span
``prefetch`` times each call of ``batch``, and the counter
``elastic.prefetch_ready`` counts the steps whose batch was made before the
loop asked for it.  An error that ``batch`` raises for step k surfaces
when the loop reaches step k; the producer stops with the segment.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.core import telemetry
from repro.engine.steps import make_data_parallel_step
from repro.launch.mesh import make_data_mesh
from repro.optim.optimizers import Optimizer
from repro.optim.schedule import rescale_lr


# The parts of a segment, each a span ``elastic.<part>`` (module docstring).
SPANS = ("segment", "init_state", "restore", "place", "first_step", "step",
         "input", "h2d", "dispatch", "loss_sync", "drain", "save",
         "prefetch")
_NO_SPAN = contextlib.nullcontext()
# Batches the producer makes ahead of the loop.  With one, a batch that
# takes longer than the loop's step stalls the loop, and the producer can
# build no lead to absorb it: on a TPU v5e host, ResNet-110 at 128 images a
# step trained 24% fewer images a second with one than with two.
PREFETCH = 2
# Steps the loop dispatches ahead of the device: after dispatching a step
# it waits, inside ``dispatch``, until the step IN_FLIGHT back is done.
# The step donates its state, so nothing else holds the loop back, and a
# loop that ran ahead of a device-bound step would queue the whole segment
# at once; two keep the next step queued behind the running one.
IN_FLIGHT = 2


class _Producer:
    """The batches of steps ``first``, ``first + 1``, ... ``end - 1``, made
    in order on one thread, at most ``PREFETCH`` ahead of the loop."""

    def __init__(self, data, global_batch: int, first: int, end: int,
                 span, ready):
        self._data = data
        self._rows = global_batch
        self._span = span
        self._ready = ready
        self._next = first
        self._end = end
        self._pending = collections.deque()
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="elastic.prefetch")
        for _ in range(PREFETCH):
            self._submit()

    def _make(self, step: int) -> dict:
        with self._span:
            return self._data.batch(step, self._rows)

    def _submit(self) -> None:
        if self._next < self._end:
            self._pending.append(self._pool.submit(self._make, self._next))
            self._next += 1

    def get(self) -> dict:
        """The next step's batch; raises what ``batch`` raised for it."""
        fut = self._pending.popleft()
        if fut.done():
            self._ready.inc()
        batch = fut.result()
        self._submit()
        return batch

    def close(self) -> None:
        """Cancel the batches not begun and join the thread."""
        self._pool.shutdown(wait=True, cancel_futures=True)


def _record_stats(registry, stats: dict) -> None:
    """Add one log step's held-expert loads ([layers, held]) to the MoE
    counters."""
    loads = np.asarray(stats["moe_assignments_held"], np.float64)
    registry.counter("moe.assignments_held").inc(int(loads.sum()))
    mean = np.maximum(loads.mean(axis=-1), 1e-9)
    registry.peak("moe.busiest_expert_ratio").see(
        float(np.max(loads.max(axis=-1) / mean)))


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
        self.registry = telemetry.Registry()
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
            self.registry.counter("elastic.step_builds").inc()
            mesh = make_data_mesh(n)
            self._steps[n] = make_data_parallel_step(
                self.model, self.opt, mesh, self.grad_exchange)
        return self._steps[n]

    # ---------------------------------------------------------- segments ---
    def train_segment(self, w: int, n_steps: int, *, resume: bool = True,
                      log_every: int = 10) -> SegmentRecord:
        sp = {p: self.registry.span("elastic." + p, step=p == "step")
              for p in SPANS}
        steps_done = self.registry.counter("elastic.steps")
        samples_done = self.registry.counter("elastic.samples")
        ready = self.registry.counter("elastic.prefetch_ready")
        with sp["segment"](w=w, steps=n_steps):
            restore_s = 0.0
            if resume and self.ckpt.latest_step() is not None:
                with sp["init_state"]:
                    # The store reads only the template's tree of names.
                    template = jax.eval_shape(self.fresh_state)
                with sp["restore"]:
                    state, meta, restore_s = self.ckpt.restore(template)
            else:
                with sp["init_state"]:
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
            with sp["place"]:
                train_state = jax.device_put(
                    {"params": state["params"], "opt": state["opt"]}, rep)
            # The placed state is donated to the first step; a state it was
            # copied from must not stay on the device beside it.
            del state
            first_s = 0.0
            producer = None
            in_flight = collections.deque()
            try:
                for i in range(n_steps):
                    gstep = step0 + i
                    with sp["step"](step_num=gstep):
                        with sp["first_step"] if i == 0 else _NO_SPAN:
                            with sp["input"]:
                                batch = (producer.get() if i else
                                         self.data.batch(gstep, global_batch))
                            with sp["h2d"]:
                                batch = jax.device_put(batch, data_sharding)
                            with sp["dispatch"]:
                                train_state, loss, *stats = step(
                                    train_state, batch, self._lr(w, epoch))
                                in_flight.append(loss)
                                if len(in_flight) > IN_FLIGHT:
                                    jax.block_until_ready(
                                        in_flight.popleft())
                            if i == 0:
                                jax.block_until_ready((train_state, loss))
                                first_s = time.perf_counter() - t0
                        if i == 0 and n_steps > 1:
                            producer = _Producer(
                                self.data, global_batch, step0 + 1,
                                step0 + n_steps, sp["prefetch"], ready)
                        epoch += epochs_per_step
                        if i % log_every == 0 or i == n_steps - 1:
                            with sp["loss_sync"]:
                                loss, stats = jax.device_get((loss, stats))
                                losses.append((gstep, epoch, float(loss)))
                                for st in stats:
                                    _record_stats(self.registry, st)
                    steps_done.inc()
                    samples_done.inc(global_batch)
            finally:
                if producer is not None:
                    producer.close()
            with sp["drain"]:
                jax.block_until_ready(train_state)
            seconds = time.perf_counter() - t0
            step_s = ((seconds - first_s) / (n_steps - 1) if n_steps > 1
                      else float("nan"))

            state = {**train_state,
                     "step": jnp.asarray(step0 + n_steps, jnp.int32),
                     "epoch": jnp.asarray(epoch, jnp.float32)}
            with sp["save"]:
                save_s = self.ckpt.save(step0 + n_steps, state,
                                        meta={"w": w, "epoch": epoch})
        return SegmentRecord(w=w, devices=devices, steps=n_steps,
                             epochs=epoch, lr=lr0, losses=losses,
                             seconds=seconds, first_step_seconds=first_s,
                             step_seconds=step_s, restore_seconds=restore_s,
                             save_seconds=save_s, state=state)
