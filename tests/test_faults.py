"""Fault injection + elastic recovery (PR 10): fault-model registry and
schedule determinism, the zero-fault bit-identity gate (60-job goldens +
1000-job sha256 with the fault machinery threaded through), cross-engine
parity under churn, ClusterState fault-lifecycle invariants (deterministic
and hypothesis), checkpoint-age-dependent lost work, the failure-aware
policy's goodput edge, and the hardened CheckpointStore (atomic sidecars,
corrupt-snapshot fallback) wired through ElasticTrainer."""
import dataclasses
import json
import os
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from test_placement import (FLAT_PLACED, FRAG, GOLDEN_1000JOB_SHA256,
                            GOLDEN_60JOB_JCT_HOURS, _trace_sha256)

from repro.checkpoint.store import CheckpointStore
from repro.collectives.cost import ClusterModel, NodeSpec
from repro.core import faults as F
from repro.core import placement as P
from repro.core import scheduler as S
from repro.core import telemetry as tele
from repro.core.elastic import ElasticTrainer
from repro.core.jobs import WORKLOAD_PATTERNS, make_workload, \
    synthetic_workload
from repro.core.simulator import simulate
from repro.optim.optimizers import sgd


# --------------------------------------------------------------------------
# Registry + validation
# --------------------------------------------------------------------------

def test_fault_registry_round_trip():
    assert F.registered_fault_models() == (
        "churn", "drain", "kill", "none", "rack", "stragglers")
    assert isinstance(F.get_fault_model("none"), F.NoFaults)
    assert F.get_fault_model("kill_1800").t == 1800.0
    assert F.get_fault_model("churn_3").n == 3
    assert F.get_fault_model("drain_900").t == 900.0
    assert F.get_fault_model("stragglers_2").k == 2
    assert F.get_fault_model("rack_7000").t == 7000.0
    # instances pass through
    model = F.StochasticChurn(5)
    assert F.get_fault_model(model) is model
    for bad, match in [("bogus", "unknown fault model"),
                       ("churn", "needs an integer"),
                       ("churn_x", "must be an integer"),
                       ("none_3", "takes no parameter"),
                       ("kill_0", "must be >= 1"),
                       (7, "must be a non-empty string")]:
        with pytest.raises(ValueError, match=match):
            F.get_fault_model(bad)


def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        F.FaultEvent(0.0, "explode", 0)
    with pytest.raises(ValueError, match="degrade factor"):
        F.FaultEvent(0.0, "degrade", 0, factor=0.0)
    with pytest.raises(ValueError, match="degrade factor"):
        F.FaultEvent(0.0, "degrade", 0, factor=1.5)
    assert F.FaultEvent(0.0, "degrade", 0, factor=0.5).factor == 0.5


def test_checkpoint_policy_lost_progress():
    cp = F.CheckpointPolicy(interval=300.0)
    assert cp.lost_progress(0.0) == 0.0
    assert cp.lost_progress(-5.0) == 0.0
    assert cp.lost_progress(250.0) == 250.0   # no checkpoint yet
    assert cp.lost_progress(300.0) == 0.0     # exactly at a checkpoint
    assert cp.lost_progress(650.0) == 50.0
    with pytest.raises(ValueError, match="interval must be > 0"):
        F.CheckpointPolicy(interval=0.0)


def test_cluster_model_fault_validation():
    with pytest.raises(ValueError, match="faults without placement"):
        ClusterModel(capacity=64, faults="churn_3")
    with pytest.raises(ValueError, match="checkpoint_interval without"):
        ClusterModel(capacity=64, checkpoint_interval=100.0)
    with pytest.raises(ValueError, match="checkpoint_interval must be > 0"):
        dataclasses.replace(FRAG, faults="churn_3",
                            checkpoint_interval=-1.0)
    # model/cluster combinations that cannot work are rejected up front
    with pytest.raises(ValueError, match="single-node"):
        ClusterModel(capacity=8, placement="packed", faults="churn_3")
    with pytest.raises(ValueError, match="at.*least one at full speed"):
        dataclasses.replace(FRAG, faults="stragglers_4")
    with pytest.raises(ValueError, match="survivors"):
        ClusterModel(capacity=8, placement="packed", faults="rack_100")
    # a valid combination constructs fine
    assert dataclasses.replace(FRAG, faults="churn_3").faults == "churn_3"


# --------------------------------------------------------------------------
# Schedule determinism
# --------------------------------------------------------------------------

ALL_SPECS = ("none", "kill_1800", "churn_6", "drain_1800", "stragglers_2",
             "rack_7000")


@pytest.mark.parametrize("spec", ALL_SPECS)
def test_schedule_is_pure_and_sorted(spec):
    """Same (cluster, seed, horizon) -> bit-identical schedule on every
    call — both engines build the tape independently and must agree."""
    model = F.get_fault_model(spec)
    a = model.schedule(FRAG, 7, 20_000.0)
    b = model.schedule(FRAG, 7, 20_000.0)
    assert a == b
    assert list(e.t for e in a) == sorted(e.t for e in a)
    for e in a:
        assert 0 <= e.node < len(FRAG.node_specs())


def test_churn_schedule_varies_with_seed():
    model = F.get_fault_model("churn_6")
    assert model.schedule(FRAG, 7, 20_000.0) != \
        model.schedule(FRAG, 8, 20_000.0)


# --------------------------------------------------------------------------
# Zero-fault bit-identity: the fault machinery threaded through with an
# empty schedule must not move a single completion time.
# --------------------------------------------------------------------------

FLAT_NOFAULT = dataclasses.replace(FLAT_PLACED, faults="none")


@pytest.mark.parametrize("strat", sorted(GOLDEN_60JOB_JCT_HOURS))
def test_zero_fault_preserves_60job_golden_values(strat):
    jobs = synthetic_workload(60, 500.0, 0)
    res = simulate(jobs, strategy=strat, cluster=FLAT_NOFAULT)
    assert res.avg_jct_hours == GOLDEN_60JOB_JCT_HOURS[strat], strat
    assert res.evictions == 0


@pytest.mark.parametrize("pattern", sorted(WORKLOAD_PATTERNS))
def test_zero_fault_1000job_sha256(pattern):
    jobs = make_workload(pattern, 1000, 250.0, 0)
    res = simulate(jobs, strategy="precompute", cluster=FLAT_NOFAULT)
    assert _trace_sha256(res) == GOLDEN_1000JOB_SHA256[pattern], pattern


# --------------------------------------------------------------------------
# Engine parity + trajectory determinism under faults
# --------------------------------------------------------------------------

CHURN = dataclasses.replace(FRAG, faults="churn_3", fault_seed=5,
                            checkpoint_interval=200.0)


def test_churn_engine_parity_every_policy():
    """Identical seeds give identical trajectories on both engines, for
    every registry entry (future policies are gated automatically)."""
    jobs = make_workload("mixed_maxw", 20, 500.0, 7)
    for strat in S.registered_policies().values():
        fast = simulate(jobs, strategy=strat, cluster=CHURN)
        again = simulate(jobs, strategy=strat, cluster=CHURN)
        assert fast.completion_times == again.completion_times, strat
        ref = simulate(jobs, strategy=strat, cluster=CHURN,
                       engine="reference")
        assert fast.completion_times == ref.completion_times, strat
        assert fast.evictions == ref.evictions, strat
        assert fast.migrations == ref.migrations, strat
        assert fast.rejected == ref.rejected, strat


@pytest.mark.parametrize("spec", ["kill_2000", "drain_2000", "rack_7000",
                                  "stragglers_1"])
def test_fault_kind_engine_parity(spec):
    cluster = dataclasses.replace(FRAG, faults=spec, fault_seed=3)
    jobs = make_workload("mixed_maxw", 16, 400.0, 2)
    for strat in ("srtf", "pack_srtf", "recovery_aware"):
        fast = simulate(jobs, strategy=strat, cluster=cluster)
        ref = simulate(jobs, strategy=strat, cluster=cluster,
                       engine="reference")
        assert fast.completion_times == ref.completion_times, (spec, strat)
        assert fast.evictions == ref.evictions, (spec, strat)


def test_scheduled_kill_evicts_and_recovers():
    """A kill while gangs are running evicts them (telemetry agrees on
    the count), yet every job still completes — evicted gangs re-enter
    through admission and finish after the node returns."""
    cluster = dataclasses.replace(FRAG, faults="kill_2000", fault_seed=0,
                                  checkpoint_interval=200.0)
    jobs = make_workload("mixed_maxw", 16, 400.0, 2)
    res = simulate(jobs, strategy="srtf", cluster=cluster,
                   telemetry=tele.Telemetry())
    assert res.evictions > 0
    assert len(res.completion_times) == 16
    roll = res.telemetry.rollup()
    assert roll["n_evictions"] == res.evictions
    assert roll["n_faults"] == 2          # the kill and the recover
    assert 0.0 <= roll["goodput"] <= 1.0
    # lost work costs goodput: the same trace without faults scores 1.0
    clean = simulate(jobs, strategy="srtf",
                     cluster=dataclasses.replace(cluster, faults="none"),
                     telemetry=tele.Telemetry())
    assert res.telemetry.goodput < clean.telemetry.goodput


def test_eviction_rolls_back_to_last_checkpoint():
    """Tighter checkpoints lose less work: the same kill under a smaller
    checkpoint_interval never scores lower goodput."""
    jobs = make_workload("mixed_maxw", 16, 400.0, 2)
    goodput = {}
    for interval in (50.0, 1000.0):
        cluster = dataclasses.replace(FRAG, faults="kill_2000",
                                      fault_seed=0,
                                      checkpoint_interval=interval)
        res = simulate(jobs, strategy="srtf", cluster=cluster,
                       telemetry=tele.Telemetry())
        goodput[interval] = res.telemetry.goodput
    assert goodput[50.0] >= goodput[1000.0]


# --------------------------------------------------------------------------
# ClusterState fault lifecycle: invariants under kill/drain/recover
# --------------------------------------------------------------------------

def test_fail_node_evicts_and_zeroes_capacity():
    state = P.ClusterState((NodeSpec(8), NodeSpec(8)))
    state.assign(P.Placement(1, ((0, 4), (1, 4))))   # spanning gang
    state.assign(P.Placement(2, ((1, 2),)))
    victims = state.fail_node(0)
    assert victims == [1]                 # only the gang touching node 0
    assert state.free[0] == 0             # dead node holds nothing
    assert state.free[1] == 6             # node-1 slots of the victim
    assert 2 in state.placements          # survivor untouched
    state.check_invariants(16)
    state.recover_node(0)
    assert state.free[0] == 8
    state.check_invariants(16)


def test_release_on_failed_node_does_not_resurrect_gpus():
    """Regression (satellite 2): releasing a gang that held slots on a
    failed node must not credit the dead node's GPUs back."""
    state = P.ClusterState((NodeSpec(8), NodeSpec(8)))
    state.assign(P.Placement(1, ((0, 4), (1, 4))))
    state.ok[0] = False                   # node dies with the gang live
    state.free[0] = 0
    state._refresh_mask()
    state.release(1)
    assert state.free[0] == 0             # dead node stays empty
    assert state.free[1] == 8             # healthy slots come back
    state.check_invariants(16)
    # releasing an already-released job is a no-op
    assert state.release(1) is None


def test_engine_tolerates_redundant_incidents():
    """Stochastic churn can draw the same node twice with overlapping
    outages: a second kill (or drain) of a down node is a no-op."""
    eng = P.PlacementEngine(
        ClusterModel(capacity=16, gpus_per_node=8,
                     inter_node_beta=1.0 / 1.25e8, placement="packed"))
    assert eng.fail(0) == []
    assert eng.fail(0) == []              # already dead: no-op
    eng.drain(1)
    eng.drain(1)                          # already draining: no-op
    eng.recover(0)
    eng.recover(1)
    eng.state.check_invariants(16)


def test_drain_keeps_running_gangs_but_blocks_new_ones():
    state = P.ClusterState((NodeSpec(8), NodeSpec(8)))
    state.assign(P.Placement(1, ((0, 8),)))
    state.drain_node(1)
    assert 1 in state.placements          # running gang stays
    assert state.free[1] == 8             # GPUs still physically free...
    assert int(state.avail.sum()) == 0    # ...but closed to placement
    strat = P.get_placement("packed")
    state.recover_node(1)
    assert strat.place(state, 4) == ((1, 4),)
    state.check_invariants(16)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 3),
                          st.integers(1, 12)),
                min_size=1, max_size=50))
def test_fault_lifecycle_invariants_property(ops):
    """Hypothesis: arbitrary interleavings of place / release / kill /
    recover / degrade never oversubscribe a node, never leave GPUs on a
    dead node, and conserve grants against surviving capacity."""
    nodes = (NodeSpec(8), NodeSpec(8), NodeSpec(4), NodeSpec(4))
    state = P.ClusterState(nodes)
    strat = P.get_placement("best_fit")
    live, jid = [], 0
    for action, node, w in ops:
        node = node % len(nodes)
        if action == 0 and w <= int(state.avail.sum()):
            state.assign(P.Placement(jid, strat.place(state, w)))
            live.append(jid)
            jid += 1
        elif action == 1 and live:
            state.release(live.pop(0))
        elif action == 2 and state.ok[node]:
            dead = state.fail_node(node)
            live = [j for j in live if j not in dead]
        elif action == 3:
            state.recover_node(node)
        elif action == 4:
            state.set_speed_mult(node, 0.5)
        state.check_invariants(24)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_simulation_survives_churn_property(seed):
    """Hypothesis: across whole churned traces every job either
    completes or is explicitly rejected — nothing is lost in a crash."""
    cluster = dataclasses.replace(FRAG, faults="churn_2", fault_seed=seed,
                                  checkpoint_interval=200.0)
    jobs = make_workload("mixed_maxw", 12, 300.0, seed % 1000)
    res = simulate(jobs, strategy="precompute", cluster=cluster)
    assert len(res.completion_times) + len(res.rejected) == 12


# --------------------------------------------------------------------------
# The failure-aware policy: goodput is the score that shows the win
# --------------------------------------------------------------------------

def test_recovery_aware_beats_blind_srtf_on_goodput():
    """The robustness acceptance row: under stochastic churn the
    failure-aware policy (gangs clamped to healthy full-speed nodes)
    holds more goodput than blind srtf, whose node-spanning rings die
    wholesale with every node."""
    cluster = dataclasses.replace(FRAG, capacity=64, gpus_per_node=8,
                                  faults="churn_6", fault_seed=7,
                                  checkpoint_interval=200.0)
    jobs = make_workload("mixed_maxw", 114, 500.0, 0)
    score = {}
    for strat in ("srtf", "recovery_aware"):
        res = simulate(jobs, strategy=strat, cluster=cluster,
                       telemetry=tele.Telemetry())
        score[strat] = res.telemetry.goodput
    assert score["recovery_aware"] > score["srtf"], score


def test_recovery_aware_is_plain_srtf_on_flat_cluster():
    """Without a placement engine there is nothing to route around: the
    failure-aware policy must rank exactly like srtf."""
    jobs = synthetic_workload(40, 500.0, 3)
    a = simulate(jobs, 64, "recovery_aware")
    b = simulate(jobs, 64, "recovery_aware", engine="reference")
    assert a.completion_times == b.completion_times


# --------------------------------------------------------------------------
# CheckpointStore hardening (satellite 1) + lost-work integration
# --------------------------------------------------------------------------

def _corrupt(path: str, keep: int = 40) -> None:
    """Truncate a file to ``keep`` bytes — a torn write."""
    with open(path, "rb") as f:
        head = f.read(keep)
    with open(path, "wb") as f:
        f.write(head)


def test_save_leaves_no_tmp_files():
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(3, {"x": jnp.ones(4)}, meta={"w": 2})
        names = sorted(os.listdir(d))
        assert names == ["ckpt_0000000003.json", "ckpt_0000000003.npz"]


def test_restore_falls_back_past_corrupt_snapshot():
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        template = {"x": jnp.zeros(4)}
        store.save(5, {"x": jnp.full(4, 5.0)})
        store.save(9, {"x": jnp.full(4, 9.0)})
        _corrupt(os.path.join(d, "ckpt_0000000009.npz"))
        assert store.latest_step() == 5   # torn snapshot is not a target
        state, _, _ = store.restore(template)
        np.testing.assert_array_equal(np.asarray(state["x"]),
                                      np.full(4, 5.0))
        # an explicit step is trusted: corruption there raises
        with pytest.raises(Exception):
            store.restore(template, step=9)


def test_restore_with_all_snapshots_corrupt_raises():
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(1, {"x": jnp.ones(2)})
        _corrupt(os.path.join(d, "ckpt_0000000001.npz"))
        assert store.latest_step() is None
        with pytest.raises(FileNotFoundError, match="no readable"):
            store.restore({"x": jnp.zeros(2)})


def test_corrupt_manifest_degrades_to_empty_meta():
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(2, {"x": jnp.ones(2)}, meta={"w": 8})
        mpath = os.path.join(d, "ckpt_0000000002.json")
        with open(mpath, "w") as f:
            f.write("{not json")
        state, meta, _ = store.restore({"x": jnp.zeros(2)})
        assert meta == {}                 # arrays win; sidecar is advisory
        os.remove(mpath)                  # missing sidecar: same story
        _, meta, _ = store.restore({"x": jnp.zeros(2)})
        assert meta == {}


def test_steps_skips_foreign_files():
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(4, {"x": jnp.ones(2)})
        with open(os.path.join(d, "ckpt_stray.npz"), "w") as f:
            f.write("not a checkpoint")
        assert store.steps() == [4]
        assert store.latest_step() == 4


class _TinyModel:
    """Linear least squares — enough structure for save/restore."""

    def init(self, key):
        return {"w": jnp.zeros((3,))}

    def loss(self, params, batch, sh=None):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)


class _TinyData:
    size = 64

    def __init__(self):
        rng = np.random.default_rng(0)
        self._x = rng.normal(size=(64, 3))
        self._w = np.array([1.0, -2.0, 0.5])

    def batch(self, step, n):
        idx = (np.arange(n) + step * n) % self.size
        return {"x": self._x[idx], "y": self._x[idx] @ self._w}


def test_trainer_crash_rolls_back_exactly_checkpoint_policy_loss():
    """End-to-end lost-work model: train 12 steps (checkpoint), train 7
    more whose checkpoint is torn mid-write — restore lands back on step
    12, and the 7 lost steps equal CheckpointPolicy(interval=12)'s
    prediction for a crash at progress 19."""
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        tr = ElasticTrainer(_TinyModel(), sgd(), _TinyData(), store,
                            base_lr_1w=0.05, m_per_worker=8,
                            dataset_size=64)
        tr.train_segment(w=1, n_steps=12, resume=False, log_every=4)
        tr.train_segment(w=1, n_steps=7, resume=True, log_every=4)
        assert store.steps() == [12, 19]
        _corrupt(os.path.join(d, "ckpt_0000000019.npz"))   # the crash
        state, _, _ = store.restore(tr.fresh_state())
        resumed_at = int(state["step"])
        assert resumed_at == 12
        lost = 19 - resumed_at
        assert lost == F.CheckpointPolicy(interval=12.0).lost_progress(19.0)


def test_fault_events_reach_the_event_stream():
    """The structured event stream carries the new fault/evict/recover
    kinds with node + lost-work attribution."""
    cluster = dataclasses.replace(FRAG, faults="kill_2000", fault_seed=0,
                                  checkpoint_interval=200.0)
    jobs = make_workload("mixed_maxw", 16, 400.0, 2)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "events.jsonl")
        res = simulate(jobs, strategy="srtf", cluster=cluster,
                       telemetry=tele.Telemetry(sink=tele.JSONLSink(path)))
        with open(path) as f:
            events = [json.loads(line) for line in f]
    kinds = {e["kind"] for e in events}
    assert {"fault", "evict", "recover"} <= kinds
    evicts = [e for e in events if e["kind"] == "evict"]
    assert len(evicts) == res.evictions
    for e in evicts:
        assert e["node"] >= 0 and e["lost"] >= 0.0
