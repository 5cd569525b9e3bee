"""The reduction from a trace to the per-layer metrics, on small traces
recorded on a TPU v5e (``testdata/``), against a brute-force count on a
grid of time."""
import glob
import gzip
import json
import os

import numpy as np
import pytest

import harness
import tracefile

TRACES = sorted(glob.glob(os.path.join(harness.HERE, "testdata",
                                       "*.trace.json.gz")))


def _grid(intervals, lo, hi, step):
    t = np.arange(lo, hi, step)
    on = np.zeros(len(t), bool)
    for s, e in intervals:
        on |= (t >= s) & (t < e)
    return on


@pytest.fixture(params=TRACES, ids=[os.path.basename(p) for p in TRACES])
def recorded(request):
    with gzip.open(request.param, "rt") as f:
        return json.load(f)


def test_testdata_present():
    assert len(TRACES) >= 1


def test_idle_share_matches_a_grid(recorded):
    lo, hi = tracefile.window(recorded)
    step = (hi - lo) / 200_000
    for dev, ops in recorded["devices"].items():
        busy = _grid([(s, e) for s, e, *_ in ops], lo, hi, step).mean()
        ours = tracefile.busy_ns(recorded, dev, lo, hi) / (hi - lo)
        assert ours == pytest.approx(busy, abs=2e-3)


def test_step_device_time_matches_a_grid(recorded):
    lo, hi = tracefile.window(recorded)
    runs = tracefile.step_runs(recorded, "0", lo, hi)
    assert runs, "the recorded trace holds train-step runs"
    ops = [(s, e) for s, e, *_ in recorded["devices"]["0"]]
    ours = tracefile.step_busy_ns(recorded, "0", lo, hi)
    for (s, e), v in zip(runs[:5], ours[:5]):
        step = (e - s) / 100_000
        assert v / (e - s) == pytest.approx(
            _grid(ops, s, e, step).mean(), abs=2e-3)
        assert 0 < v <= e - s


def test_exposed_collective_matches_a_grid(recorded):
    lo, hi = tracefile.window(recorded)
    ops = recorded["devices"]["0"]
    coll = [(s, e) for s, e, *r in ops if tracefile.is_collective((s, e, *r))]
    comp = [(s, e) for s, e, *r in ops
            if not tracefile.is_collective((s, e, *r))]
    for (s, e), v in zip(tracefile.step_runs(recorded, "0", lo, hi)[:5],
                         tracefile.exposed_collective_ns(recorded, "0",
                                                         lo, hi)[:5]):
        step = (e - s) / 100_000
        want = (_grid(coll, s, e, step) & ~_grid(comp, s, e, step)).mean()
        assert v / (e - s) == pytest.approx(want, abs=2e-3)


def test_gaps_cover_the_idle_time_and_carry_labels(recorded):
    lo, hi = tracefile.window(recorded)
    gaps = tracefile.idle_gaps(recorded, "0", lo, hi)
    idle = (hi - lo - tracefile.busy_ns(recorded, "0", lo, hi)) / 1e9
    assert sum(g for _, g in gaps) == pytest.approx(idle, rel=1e-9)
    names = {name for *_, name in recorded["host"]} | {"loop"}
    assert {label for label, _ in gaps} <= names
    # A gap that begins inside an input span is labelled input.
    for s, e, name in recorded["host"]:
        if name != "input":
            continue
        for label, _ in tracefile.idle_gaps(recorded, "0", s, e):
            assert label == "input"


def test_intervals():
    assert tracefile.union([(0, 2), (1, 3), (5, 6)], 0, 10) == [(0, 3),
                                                                (5, 6)]
    assert tracefile.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    tr = {"devices": {"0": [[0, 4, "fusion", ""], [2, 6, "all-reduce.1", ""],
                            [8, 9, "all-reduce-start", ""]]},
          "modules": {"0": [[0, 10, "jit_train_step(1)"]]},
          "host": [[0, 1, "input"], [7, 10, "save"]]}
    assert tracefile.step_busy_ns(tr, "0", 0, 10) == [7]
    assert tracefile.exposed_collective_ns(tr, "0", 0, 10) == [3]
    assert tracefile.idle_gaps(tr, "0", 0, 10) == [("loop", 2e-9),
                                                   ("save", 1e-9)]
