"""The elastic loop's spans and counters (``core/elastic.py``): each part of a
segment is a ``jax.profiler`` annotation named ``elastic.<part>`` and a
timer of the same name in the trainer's ``telemetry.Registry``, the
producer's ``elastic.prefetch`` among them; the profiler leaves the
computation bit for bit as it is."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.checkpoint.store import CheckpointStore
from repro.configs.resnet110 import smoke_config
from repro.core import telemetry
from repro.core.elastic import SPANS, ElasticTrainer
from repro.data.synthetic import CifarLike
from repro.models.resnet import ResNetModel
from repro.optim.optimizers import sgd

M = 8            # images a worker
STEPS = 12       # steps of the traced segment
LOG_EVERY = 5
PER_SEGMENT = ("segment", "init_state", "restore", "place", "first_step",
               "drain", "save")
PER_STEP = ("input", "h2d", "dispatch")


def trainer(ckpt_dir, grad_exchange=None):
    return ElasticTrainer(ResNetModel(smoke_config()), sgd(),
                          CifarLike(size=256, seed=0),
                          CheckpointStore(ckpt_dir), base_lr_1w=0.05,
                          m_per_worker=M, dataset_size=256,
                          grad_exchange=grad_exchange)


def _run(ckpt_dir, trace_dir=None):
    """A one-step segment, then a resumed segment of ``STEPS`` steps, under
    the profiler when ``trace_dir`` is given.  -> (trainer, record)."""
    tr = trainer(ckpt_dir)
    tr.train_segment(1, 1, resume=False, log_every=LOG_EVERY)
    if trace_dir is None:
        return tr, tr.train_segment(1, STEPS, log_every=LOG_EVERY)
    with jax.profiler.trace(trace_dir):
        return tr, tr.train_segment(1, STEPS, log_every=LOG_EVERY)


def _host_spans(trace_dir):
    """[(start_ns, end_ns, name, stats)] of the ``elastic.*`` host events."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("elastic."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return sorted(out)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    d = tmp_path_factory.mktemp("tracing")
    off = _run(str(d / "off"))
    on = _run(str(d / "on"), str(d / "trace"))
    return {"off": off, "on": on, "spans": _host_spans(str(d / "trace"))}


def test_one_step_span_per_step_numbered_by_the_global_step(runs):
    spans = runs["spans"]
    steps = [s for s in spans if s[2] == "elastic.step"]
    assert [int(s[3]["step_num"]) for s in steps] == list(range(1, 1 + STEPS))
    for s0, e0, _, _ in steps:
        inside = [n for s, e, n, _ in spans if s0 <= s and e <= e0]
        for part in PER_STEP:
            assert inside.count("elastic." + part) == 1, (part, inside)


def test_loss_syncs_and_segment_spans(runs):
    _, rec = runs["on"]
    names = [n for _, _, n, _ in runs["spans"]]
    assert names.count("elastic.loss_sync") == len(rec.losses)
    for part in PER_SEGMENT:
        assert names.count("elastic." + part) == 1, part
    assert set(names) == {"elastic." + p for p in SPANS}
    (seg,) = [s for s in runs["spans"] if s[2] == "elastic.segment"]
    assert (int(seg[3]["w"]), int(seg[3]["steps"])) == (1, STEPS)
    # Every other span lies inside the segment.
    assert all(seg[0] <= s and e <= seg[1] for s, e, _, _ in runs["spans"])


def test_prefetch_spans_lie_in_the_segment_after_its_first_step(runs):
    spans = runs["spans"]
    (seg,) = [s for s in spans if s[2] == "elastic.segment"]
    (first,) = [s for s in spans if s[2] == "elastic.first_step"]
    prefetch = [s for s in spans if s[2] == "elastic.prefetch"]
    # One for each step after the first, none past the segment's last.
    assert len(prefetch) == STEPS - 1
    for s, e, _, _ in prefetch:
        assert seg[0] <= s and e <= seg[1]
        assert not (first[0] <= s and e <= first[1])
        assert s >= first[1]


def test_first_step_nests_in_the_segments_first_step(runs):
    spans = runs["spans"]
    (first,) = [s for s in spans if s[2] == "elastic.first_step"]
    step1 = [s for s in spans if s[2] == "elastic.step"][0]
    assert step1[0] <= first[0] and first[1] <= step1[1]
    inside = [n for s, e, n, _ in spans if first[0] <= s and e <= first[1]]
    assert sorted(inside) == sorted(["elastic.first_step"] + [
        "elastic." + p for p in PER_STEP])


def test_registry_counts_steps_samples_and_log_steps(runs):
    tr, rec = runs["off"]
    reg = tr.registry
    n = 1 + STEPS
    # The second segment at the same w reuses the step: one build.
    counters = reg.counters()
    # Steps whose prefetched batch was made when the loop asked for it: at
    # most one per step after each segment's first.
    assert 0 <= counters["elastic.prefetch_ready"] <= STEPS - 1
    assert counters == {"elastic.samples": n * M,
                        "elastic.step_builds": 1, "elastic.steps": n,
                        "elastic.prefetch_ready":
                            counters["elastic.prefetch_ready"]}
    timers = reg.timers()
    counts = {k: v["count"] for k, v in timers.items()}
    assert counts == {
        "elastic.segment": 2, "elastic.init_state": 2, "elastic.restore": 1,
        "elastic.place": 2, "elastic.first_step": 2, "elastic.step": n,
        "elastic.input": n, "elastic.h2d": n, "elastic.dispatch": n,
        "elastic.loss_sync": 1 + len(rec.losses), "elastic.drain": 2,
        "elastic.save": 2, "elastic.prefetch": STEPS - 1}
    assert all(v["total_s"] > 0 for v in timers.values())
    # A part takes no longer than the whole that holds it.
    assert timers["elastic.step"]["total_s"] <= \
        timers["elastic.segment"]["total_s"]
    assert sum(timers["elastic." + p]["total_s"] for p in PER_STEP) <= \
        timers["elastic.step"]["total_s"]


def test_profiler_leaves_losses_and_state_bit_identical(runs):
    (_, off), (_, on) = runs["off"], runs["on"]
    assert off.losses == on.losses
    for a, b in zip(jax.tree_util.tree_leaves(off.state),
                    jax.tree_util.tree_leaves(on.state), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shared_registry_accumulates_across_trainers(tmp_path):
    reg = telemetry.Registry()
    for i in range(2):
        tr = trainer(str(tmp_path / "ckpt"))
        tr.registry = reg
        tr.train_segment(1, 2, resume=bool(i), log_every=LOG_EVERY)
    counters = reg.counters()
    assert 0 <= counters["elastic.prefetch_ready"] <= 2
    assert counters == {"elastic.samples": 4 * M,
                        "elastic.step_builds": 2, "elastic.steps": 4,
                        "elastic.prefetch_ready":
                            counters["elastic.prefetch_ready"]}
    assert reg.timers()["elastic.prefetch"]["count"] == 2
    assert reg.timers()["elastic.segment"]["count"] == 2
    assert reg.timers()["elastic.restore"]["count"] == 1


def test_registry_set_after_construction_takes_effect(tmp_path):
    tr = trainer(str(tmp_path / "ckpt"))
    reg = tr.registry = telemetry.Registry()
    tr.train_segment(1, 1, resume=False)
    assert reg.counters()["elastic.steps"] == 1


def test_span_times_into_its_timer():
    reg = telemetry.Registry()
    sp = reg.span("a")
    for _ in range(3):
        with sp:
            pass
    with reg.span("b", step=True)(step_num=4):
        pass
    t = reg.timers()
    assert (t["a"]["count"], t["b"]["count"]) == (3, 1)
    assert t["a"]["total_s"] >= 0.0


@pytest.mark.parametrize("exchange", [None, "ring", "doubling_halving"])
def test_step_module_is_named_jit_train_step(exchange, tmp_path):
    # The benchmark finds the step's runs on the device by this name
    # (benchmarks/chip/tracefile.py, ``step_runs``).
    tr = trainer(str(tmp_path / "ckpt"), grad_exchange=exchange)
    step, rep, data = tr.step_for(1)
    state = tr.fresh_state()
    batch = CifarLike(size=256, seed=0).batch(0, M)
    text = step.lower({"params": state["params"], "opt": state["opt"]},
                      batch, jnp.float32(0.1)).as_text()
    assert "module @jit_train_step" in text
