"""The elastic loop's producer (``core/elastic.py``): after a segment's first
step, one thread makes the later steps' batches ahead of the loop, in step
order and never past the segment, and the loop trains on exactly what the
serial loop would."""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.configs.resnet110 import smoke_config
from repro.core.elastic import ElasticTrainer
from repro.data.synthetic import CifarLike
from repro.models.resnet import ResNetModel
from repro.optim.optimizers import sgd

M = 8            # images a worker
SIZE = 256       # images in the dataset
LR = 0.05
LOG_EVERY = 3


class BatchError(RuntimeError):
    pass


class Recording:
    """``CifarLike`` with a record of every call of ``batch`` as (step, rows,
    thread id), and of the steps the loop had finished at each call when
    ``done`` is the trainer's ``elastic.steps`` counter.  ``fail_at`` makes ``batch`` raise ``BatchError`` for that
    step; ``poison_at`` returns a batch the step cannot take; ``slow_at``
    takes a second over that step's batch."""

    def __init__(self, fail_at=None, poison_at=None, slow_at=None):
        self.inner = CifarLike(size=SIZE, seed=0)
        self.size = SIZE
        self.calls = []
        self.fail_at = fail_at
        self.poison_at = poison_at
        self.slow_at = slow_at
        self.done = None
        self.ahead = []

    def batch(self, step, rows):
        self.calls.append((step, rows, threading.get_ident()))
        if self.done is not None:
            self.ahead.append(step - self.done.n)
        if step == self.slow_at:
            time.sleep(1.0)
        if step == self.fail_at:
            raise BatchError(step)
        b = self.inner.batch(step, rows)
        if step == self.poison_at:
            b = {**b, "labels": np.array(["?"] * rows, dtype=object)}
        return b

    def steps(self):
        return [s for s, _, _ in self.calls]

    def producers(self):
        """The threads other than the caller's that called ``batch``."""
        return {t for _, _, t in self.calls} - {threading.get_ident()}


def trainer(ckpt_dir, data):
    return ElasticTrainer(ResNetModel(smoke_config()), sgd(), data,
                          CheckpointStore(ckpt_dir), base_lr_1w=LR,
                          m_per_worker=M, dataset_size=SIZE)


def alive(data):
    """The producer threads of ``data``'s calls that still run."""
    return [t for t in threading.enumerate()
            if t.ident in data.producers() and t.is_alive()]


def serial(tr, n_steps):
    """The serial loop: the same jitted step from ``step_for``, a batch
    made inline before each step.  -> (loss of each step, final state)."""
    step, rep, data_sharding = tr.step_for(1)
    state = tr.fresh_state()
    train_state = jax.device_put(
        {"params": state["params"], "opt": state["opt"]}, rep)
    data = CifarLike(size=SIZE, seed=0)
    losses = []
    for gstep in range(n_steps):
        batch = jax.device_put(data.batch(gstep, M), data_sharding)
        train_state, loss = step(train_state, batch, LR)
        losses.append(float(loss))
    return losses, train_state


def test_batch_called_once_per_step_in_order_from_a_producer(tmp_path):
    data = Recording()
    tr = trainer(str(tmp_path / "ckpt"), data)
    data.done = tr.registry.counter("elastic.steps")
    caller = threading.get_ident()
    tr.train_segment(1, 7, resume=False, log_every=LOG_EVERY)
    tr.train_segment(1, 5, resume=True, log_every=LOG_EVERY)
    assert data.steps() == list(range(12))
    # No batch is made more than two steps ahead of the loop.
    assert 0 <= min(data.ahead) and max(data.ahead) <= 2
    assert all(rows == M for _, rows, _ in data.calls)
    for first, n in ((0, 7), (7, 5)):
        threads = [t for s, _, t in data.calls if first <= s < first + n]
        assert threads[0] == caller
        assert caller not in threads[1:]
        # One producer a segment.
        assert len(set(threads[1:])) == 1
    assert data.producers() and not alive(data)


@pytest.mark.parametrize("resumed", [False, True])
def test_losses_and_state_bit_identical_to_the_serial_loop(resumed,
                                                           tmp_path):
    tr = trainer(str(tmp_path / "ckpt"), Recording())
    if resumed:
        first = tr.train_segment(1, 4, resume=False, log_every=LOG_EVERY)
        rec = tr.train_segment(1, 8, resume=True, log_every=LOG_EVERY)
        steps = 12
        logged = first.losses + rec.losses
    else:
        rec = tr.train_segment(1, 10, resume=False, log_every=LOG_EVERY)
        steps = 10
        logged = rec.losses
    losses, state = serial(tr, steps)
    assert [(g, l) for g, _, l in logged] == [(g, losses[g])
                                              for g, _, _ in logged]
    for a, b in zip(jax.tree_util.tree_leaves(
                        {"params": rec.state["params"],
                         "opt": rec.state["opt"]}),
                    jax.tree_util.tree_leaves(state), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batch_error_surfaces_at_its_step_with_its_type(tmp_path):
    data = Recording(fail_at=4)
    tr = trainer(str(tmp_path / "ckpt"), data)
    with pytest.raises(BatchError):
        tr.train_segment(1, 9, resume=False, log_every=LOG_EVERY)
    # Steps before the failing one ran; none after it.
    assert tr.registry.counters()["elastic.steps"] == 4
    assert max(data.steps()) <= 4 + 2
    assert data.producers() and not alive(data)
    assert tr.ckpt.latest_step() is None


def test_failing_step_stops_the_producer(tmp_path):
    # Step 3's batch is made, but the loop cannot place it on the device.
    data = Recording(poison_at=3)
    tr = trainer(str(tmp_path / "ckpt"), data)
    with pytest.raises(TypeError):
        tr.train_segment(1, 20, resume=False, log_every=LOG_EVERY)
    assert tr.registry.counters()["elastic.steps"] == 3
    # The producer ran at most two batches past the failing step, and then
    # stopped: nothing is made after the segment has raised.
    made = data.steps()
    assert made == list(range(len(made))) and max(made) <= 3 + 2
    assert data.producers() and not alive(data)
    assert data.steps() == made


def test_one_step_segment_starts_no_thread(tmp_path, monkeypatch):
    started = []
    start = threading.Thread.start

    def spy(self):
        started.append(self.name)
        start(self)

    data = Recording()
    tr = trainer(str(tmp_path / "ckpt"), data)
    monkeypatch.setattr(threading.Thread, "start", spy)
    tr.train_segment(1, 1, resume=False, log_every=LOG_EVERY)
    tr.train_segment(1, 1, resume=True, log_every=LOG_EVERY)
    assert started == []
    assert data.steps() == [0, 1]
    assert {t for _, _, t in data.calls} == {threading.get_ident()}
    assert tr.registry.timers()["elastic.prefetch"]["count"] == 0
    assert tr.registry.counters()["elastic.prefetch_ready"] == 0


@pytest.mark.parametrize("lengths", [(1, 6), (5, 2, 9)])
def test_prefetch_span_and_ready_counter_per_segment(lengths, tmp_path):
    tr = trainer(str(tmp_path / "ckpt"), Recording())
    for i, n in enumerate(lengths):
        before = tr.registry.counters().get("elastic.prefetch_ready", 0)
        tr.train_segment(1, n, resume=bool(i), log_every=LOG_EVERY)
        timers = tr.registry.timers()
        assert timers["elastic.prefetch"]["count"] == sum(
            k - 1 for k in lengths[:i + 1])
        ready = tr.registry.counters()["elastic.prefetch_ready"] - before
        assert 0 <= ready <= n - 1
    assert tr.registry.counters()["elastic.steps"] == sum(lengths)


def test_a_batch_not_yet_made_is_not_counted_ready(tmp_path):
    # The loop asks for step 4's batch well within the second the producer
    # takes over it.
    tr = trainer(str(tmp_path / "ckpt"), Recording(slow_at=4))
    tr.train_segment(1, 8, resume=False, log_every=LOG_EVERY)
    assert tr.registry.counters()["elastic.prefetch_ready"] <= 8 - 2
    assert tr.registry.timers()["elastic.input"]["total_s"] >= 0.5


def test_order_holds_under_fast_thread_switching(tmp_path):
    # The interpreter switches threads every microsecond: the producer and
    # the loop interleave as finely as they can, and the batches still
    # reach their steps in order, each made once.
    data = Recording()
    tr = trainer(str(tmp_path / "ckpt"), data)
    tr.train_segment(1, 1, resume=False, log_every=LOG_EVERY)   # compile
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        rec = tr.train_segment(1, 40, resume=True, log_every=1)
    finally:
        sys.setswitchinterval(interval)
    assert data.steps() == list(range(41))
    losses, _ = serial(tr, 41)
    assert [l for _, _, l in rec.losses] == losses[1:]
    assert data.producers() and not alive(data)
