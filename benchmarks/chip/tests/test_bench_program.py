"""The readers of the program's own spans (``programspans``): exact values on
a hand-made trace, the time grid on a recorded one, None against a program
or a trace without the spans, and the existing readers left as they were."""
import glob
import gzip
import json
import os
import shutil

import numpy as np
import pytest

import harness
import programspans
import record_trace
import tiny
import tracefile

NEW = ["h2d_ms", "dispatch_ms", "loss_sync_ms", "loop_self_ms"]
TESTDATA = os.path.join(harness.HERE, "testdata")
# Recorded by the benchmark before the program carried spans.
PLAIN = {"resnet110.w1.train": "resnet110.w1.train.trace.json.gz",
         "qwen2.5-3b-l4.w1.train": "qwen2.5-3b-l4.w1.train.trace.json.gz"}
PROGRAM = sorted(glob.glob(os.path.join(TESTDATA, "*.program.trace.json.gz")))


def _load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _run(tr, cell="resnet110.w1.train"):
    c = harness.cell(cell)
    cfg, mod = harness.config(c["config"])
    traffic = harness.traffic_mod.load(c["traffic"])
    return harness.Run(traffic=traffic, chips=1,
                       peak=harness.peaks("TPU v5 lite"),
                       flops_per_sample=harness.flops_per_sample(mod, cfg,
                                                                 traffic),
                       spans=harness.Spans(), window=(0.0, 1.0), segments=[],
                       restarts=[], trace=tr,
                       trace_window=tracefile.window(tr))


def _read(run, names):
    return {n: harness.metric_reader(n).reduce(run) for n in names}


@pytest.fixture
def own_checkout(monkeypatch, tmp_path):
    """A checkout of the harness's own, so that its work directory is
    neither shared with other tests nor left from an earlier run."""
    shutil.copy(os.path.join(harness.CHECKOUT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "CHECKOUT", str(tmp_path))


# Two steps of 100 ns, the first a log step; the chip runs [20, 40) and
# [130, 190).  Host spans: bench input [2, 12) and [102, 112), bench save
# from 195.  The third input at 200 closes the window [0, 200).
HAND = {
    "devices": {"0": [[20, 40, "fusion.1", ""], [130, 190, "fusion.2", ""]]},
    "modules": {"0": [[20, 40, "jit_train_step(1)"],
                      [130, 190, "jit_train_step(1)"]]},
    "host": [[0, 10, "input"], [100, 110, "input"], [195, 199, "save"],
             [200, 210, "input"]],
    "program": [
        [0, 95, "elastic.step"], [0, 12, "elastic.input"],
        [13, 18, "elastic.h2d"], [18, 30, "elastic.dispatch"],
        [31, 90, "elastic.loss_sync"],
        [100, 160, "elastic.step"], [100, 112, "elastic.input"],
        [114, 120, "elastic.h2d"], [120, 150, "elastic.dispatch"],
        [199, 250, "elastic.step"], [199, 211, "elastic.input"]],
}


def test_readers_on_a_hand_made_trace():
    tr = json.loads(json.dumps(HAND))
    lo, hi = tracefile.window(tr)
    assert (lo, hi) == (0, 200)
    assert programspans.window_steps(tr, lo, hi) == 2
    assert programspans.program_spans(tr, "elastic.h2d", lo, hi) == [
        (13, 18), (114, 120)]
    # Self time: 95 - (12 + 5 + 12 + 59) and 60 - (12 + 6 + 30); the third
    # step runs past the window.
    assert programspans.self_ns(tr, lo, hi) == [7, 12]
    got = _read(_run(tr), NEW)
    assert got == pytest.approx({"h2d_ms": 5.5e-6, "dispatch_ms": 21e-6,
                                 "loss_sync_ms": 29.5e-6,
                                 "loop_self_ms": 9.5e-6}, rel=1e-12)


def test_idle_by_span_on_a_hand_made_trace():
    tr = json.loads(json.dumps(HAND))
    got = programspans.idle_by_span(tr, "0", 0, 200)
    # The chip is idle in [0, 20), [40, 130) and [190, 200).
    assert got == {"bench.input": 10 + 10, "elastic.input": 2 + 2 + 1,
                   "elastic.step": 1 + 5 + 2, "elastic.h2d": 5 + 6,
                   "elastic.dispatch": 2 + 10, "elastic.loss_sync": 50,
                   None: 5 + 5, "bench.save": 4}
    assert sum(got.values()) == 200 - 20 - 60


def test_new_readers_give_none_without_program_spans(own_checkout):
    for cell, name in PLAIN.items():
        tr = _load(os.path.join(TESTDATA, name))
        assert "program" not in tr
        run = _run(tr, cell)
        assert _read(run, NEW) == dict.fromkeys(NEW)
        lo, hi = run.trace_window
        assert programspans.idle_by_span(tr, "0", lo, hi) is None
    untraced = _run(_load(os.path.join(TESTDATA, PLAIN["resnet110.w1.train"])))
    untraced.trace = None
    assert _read(untraced, NEW) == dict.fromkeys(NEW)


# The existing readers and the breakdown on the traces recorded before the
# program carried spans, as they read there.
EXISTING = {
    "resnet110.w1.train": {"device_idle_pct": 82.10763271573956,
                           "step_device_ms": 5.391762,
                           "mfu": 2.9663847003925548,
                           "exchange_exposed_ms": 0.0},
    "qwen2.5-3b-l4.w1.train": {"device_idle_pct": 0.461643610464868,
                               "step_device_ms": 578.898178,
                               "mfu": 32.668882129076366,
                               "exchange_exposed_ms": 0.0},
}


@pytest.mark.parametrize("cell", sorted(PLAIN))
def test_existing_readers_unchanged(cell, own_checkout):
    tr = _load(os.path.join(TESTDATA, PLAIN[cell]))
    run = _run(tr, cell)
    lo, hi = run.trace_window

    def breakdown():
        return (tracefile.top_ops(tr, lo, hi),
                tracefile.idle_gaps(tr, "0", lo, hi))

    before, gaps = _read(run, EXISTING[cell]), breakdown()
    assert before == pytest.approx(EXISTING[cell], rel=1e-12)
    _read(run, NEW)   # which adds an empty "program" key to the trace
    assert _read(run, EXISTING[cell]) == before
    assert breakdown() == gaps


def _grid(intervals, lo, hi, step):
    t = np.arange(lo, hi, step)
    on = np.zeros(len(t), bool)
    for s, e in intervals:
        on |= (t >= s) & (t < e)
    return t, on


def test_program_testdata_present():
    assert PROGRAM


@pytest.mark.parametrize("path", PROGRAM,
                         ids=[os.path.basename(p) for p in PROGRAM])
def test_idle_by_span_matches_a_grid(path):
    tr = _load(path)
    lo, hi = tracefile.window(tr)
    got = programspans.idle_by_span(tr, "0", lo, hi)
    step = (hi - lo) / 200_000
    t, busy = _grid([(s, e) for s, e, *_ in tr["devices"]["0"]], lo, hi,
                    step)
    spans = [(s, e, "bench." + n) for s, e, n in tr["host"]] + \
        [tuple(p) for p in tr["program"]]
    label = np.full(len(t), None, object)
    width = np.full(len(t), np.inf)
    for s, e, n in spans:
        inside = (t >= s) & (t < e) & (e - s < width)
        label[inside], width[inside] = n, e - s
    idle = ~busy
    for name in set(got) | set(label[idle]):
        want = np.sum(idle & (label == name)) * step
        assert got.get(name, 0.0) == pytest.approx(want, abs=4e-3 * (hi - lo))
    assert sum(got.values()) == pytest.approx(
        hi - lo - tracefile.busy_ns(tr, "0", lo, hi), rel=1e-9)


@pytest.mark.parametrize("path", PROGRAM,
                         ids=[os.path.basename(p) for p in PROGRAM])
def test_program_spans_explain_the_hosts_step(path):
    """Steps are covered by their spans, the loop's own time is small, and
    the chip is seldom idle with no span open on the host."""
    tr = _load(path)
    cell = os.path.basename(path).split(".program.")[0]
    cov = record_trace.coverage(tr)
    assert cov["step_span_cover"] >= 0.98
    assert cov["loop_self_over_period"] <= 0.10
    assert cov["idle_no_span_share"] < 0.05
    got = _read(_run(tr, cell), NEW)
    assert all(v is not None and v >= 0 for v in got.values())


@pytest.mark.parametrize("path", PROGRAM,
                         ids=[os.path.basename(p) for p in PROGRAM])
def test_new_metrics_read_something_in_the_cells_they_list(path):
    """A cell lists a new metric only where its traced steps hold the span
    the metric reads: the Qwen cell's traced steps hold no log step."""
    tr = _load(path)
    cell = os.path.basename(path).split(".program.")[0]
    listed = {m["name"] for m in harness.benchmark()["per_layer"]
              if m["name"] in NEW and cell in m.get("workloads", [cell])}
    got = _read(_run(tr, cell), NEW)
    assert listed and all(got[n] > 0 for n in listed), got
    held = {n for _, _, n in tr["program"]}
    assert ("loss_sync_ms" in listed) == ("elastic.loss_sync" in held)


def test_traced_run_reports_the_new_metrics(own_checkout):
    out = tiny.run("resnet110.w1.train", trace=True)
    for name in NEW:
        assert out["metrics"][name]["unit"] == "ms"
        assert out["metrics"][name]["value"] >= 0


def test_program_without_spans_reports_none_of_them(monkeypatch,
                                                    own_checkout):
    """A program that carries no spans (as before it did) runs as before,
    and the new metrics are left out of the result."""
    import contextlib

    from repro.core import telemetry

    class Silent(telemetry.Span):
        __slots__ = ()

        def __init__(self, timer, annotation):
            super().__init__(timer, lambda *a, **k: contextlib.nullcontext())

    monkeypatch.setattr(telemetry, "Span", Silent)
    out = tiny.run("resnet110.w1.train", trace=True)
    assert out["correct"] is True
    assert not set(NEW) & set(out["metrics"])
    assert "device_idle_pct" in out["metrics"]


def test_cut_keeps_whole_steps():
    tr = json.loads(json.dumps(HAND))
    short = record_trace.cut(tr, 1, 1)
    assert tracefile.window(short) == (100, 200)
    assert [p[2] for p in short["program"]][:1] == ["elastic.step"]
    assert programspans.self_ns(short, 100, 200) == [12]
