"""The DeepSeek-V2-Lite cell at tiny sizes on the CPU: its FLOP count
against XLA's, its parameter count, a run through the harness that reads
finite numbers and fails under the control and a planted fault, and the
readers of the name scopes (``scopes``) on a hand-made trace, on a profile
recorded on a TPU v5e and against traces that carry no scopes."""
import copy
import glob
import gzip
import json
import math
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import harness
import scopes
import tiny
import tracefile

CELL = "deepseek-v2-lite-l5.w1.train"
SCOPED = ["moe_experts_expected_roofline", "moe_dispatch_ms", "mla_ms"]
TESTDATA = os.path.join(harness.HERE, "testdata")


def _cfg(**kw):
    cfg, mod = harness.config("deepseek-v2-lite-l5")
    cfg = copy.deepcopy(cfg)
    experts = kw.pop("experts", None)
    cfg.update(kw)
    if experts is not None:
        cfg["published"] = dict(cfg["published"], n_routed_experts=experts)
    return cfg, mod


def test_one_moe_layer_forward_flops_match_xla():
    """At the published widths, one MoE layer with one expert held of one
    and top-1: on the CPU XLA counts a ragged product over every group, and
    with one group it is one dense product, so every other term is
    checked."""
    cfg, mod = _cfg(num_hidden_layers=1, first_k_dense_replace=0,
                    n_routed_experts=1, num_experts_per_tok=1, experts=1)
    model, _ = mod.program(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seq = 256
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    xla = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0]).lower(
        params, tokens).cost_analysis()["flops"]
    ours = mod.forward_flops(cfg, seq)
    # XLA also counts the masked half of the score products (0.98% here)
    # and the norms, rotations, softmax and SiLU (about 0.2%); leaving out
    # the smallest projection, the latent's (1.8%), would push the ratio
    # over 1.03.
    assert 1.0 <= xla / ours <= 1.015, (xla, ours)


def test_cut_counts():
    cfg, mod = harness.config("deepseek-v2-lite-l5")
    leaves = jax.tree_util.tree_leaves(
        mod.param_spec(cfg), is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], str))
    assert sum(math.prod(leaf[1]) for leaf in leaves) == 535_060_992
    assert mod.held_per_token(cfg) == 0.75
    per_token = mod.flops_per_sample(cfg, 4096) / 4096
    assert per_token == pytest.approx(1.862e9, rel=1e-3)
    # An expert matmul pass over the expected 12,288 held rows is bound by
    # FLOPs, not bytes, on a v5e.
    rows = 4 * 4096 * 0.75
    peak = harness.peaks("TPU v5 lite")
    assert mod.expert_matmul_flops(cfg, rows) / peak["bf16_flops"] > \
        mod.expert_matmul_bytes(cfg, rows) / peak["hbm_bytes_per_s"]


def _tiny():
    cfg, _ = _cfg(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
                  moe_intermediate_size=32, vocab_size=256,
                  n_routed_experts=4, expert_offset=4, experts=16)
    return cfg


def _run(trace=False):
    cell = harness.cell(CELL)
    return harness.run(CELL, 2 ** 33 + 5, 2.0, trace, cfg=_tiny(),
                       traffic=tiny.traffic(cell), device=dict(tiny.CPU),
                       peak={})


@pytest.fixture
def own_checkout(monkeypatch, tmp_path):
    """A checkout of the harness's own, so that its work directory is
    neither shared with other tests nor left from an earlier run."""
    shutil.copy(os.path.join(harness.CHECKOUT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "CHECKOUT", str(tmp_path))


def test_sound_run_goes_end_to_end(own_checkout):
    out = _run(trace=True)
    assert set(out["checks"]) == set(harness.limits(CELL))
    assert all(math.isfinite(v["value"]) for v in out["checks"].values())
    # A CPU trace carries no scopes: the scoped metrics are left out.
    assert not set(SCOPED) & set(out["metrics"])


def _job(tmp_path):
    cell = harness.cell(CELL)
    return harness.Job(cell, 2 ** 33 + 5, spans=harness.Spans(),
                       ckpt_dir=str(tmp_path), cfg=_tiny(),
                       traffic=tiny.traffic(cell))


def test_control_fails(tmp_path):
    job = _job(tmp_path)
    ref = harness.reference_readings(job)
    ctl = harness.reference_readings(job, "fp8")
    assert not check.judge(check.numbers(ctl, ref), harness.limits(CELL))


def test_half_batch_fails(tmp_path, monkeypatch):
    from repro.models.transformer import TransformerModel

    real = TransformerModel.loss_and_stats

    def half(self, params, batch, sh=None):
        batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        return real(self, params, batch, sh)

    monkeypatch.setattr(TransformerModel, "loss_and_stats", half)
    job = _job(tmp_path)
    trainer, losses = job.first_steps()
    del trainer
    prog = job.program_readings(losses)
    ref = harness.reference_readings(job)
    assert not check.judge(check.numbers(prog, ref), harness.limits(CELL))


# ------------------------------------------------------------- scopes ----
# Two runs of the step, [0, 100) and [200, 300), on chip 0; the window is
# [0, 400).  Scoped ops: mla 10 + 20, moe.experts 30 (the second run's
# backward), moe.dispatch 5 and moe.route 7; one op outside every run.
HAND = {
    "devices": {"0": []},
    "modules": {"0": [[0, 100, "jit_train_step(1)"],
                      [200, 300, "jit_train_step(1)"]]},
    "host": [],
    "scoped": {
        "paths": ["jit(train_step)/while/body/mla/dot_general",
                  "jit(train_step)/transpose(jvp(moe.experts))/ragged_dot",
                  "jit(train_step)/moe.dispatch/sort",
                  "jit(train_step)/jvp(moe.route)/softmax",
                  "jit(train_step)/mlax/add"],
        "ops": {"0": [[0, 10, 0], [20, 25, 2], [30, 37, 3], [40, 45, 4],
                      [200, 220, 0], [250, 280, 1], [350, 360, 0]]}},
}


def _hand_run(tr):
    cell = harness.cell(CELL)
    cfg, mod = harness.config(cell["config"])
    traffic = harness.traffic_mod.load(cell["traffic"])
    return harness.Run(traffic=traffic, chips=1,
                       peak=harness.peaks("TPU v5 lite"),
                       flops_per_sample=harness.flops_per_sample(mod, cfg,
                                                                 traffic),
                       spans=harness.Spans(), window=(0.0, 1.0), segments=[],
                       restarts=[], trace=tr, trace_window=(0, 400))


def test_scope_readers_on_a_hand_made_trace():
    run = _hand_run(json.loads(json.dumps(HAND)))
    assert scopes.has_scope(HAND["scoped"]["paths"][1], ["moe.experts"])
    assert not scopes.has_scope(HAND["scoped"]["paths"][4], ["mla"])
    assert scopes.per_step_ms(run, ["mla"]) == pytest.approx(15e-6)
    assert scopes.per_step_ms(run, ["moe.route", "moe.dispatch",
                                    "moe.combine"]) == pytest.approx(6e-6)
    got = {n: harness.metric_reader(n).reduce(run) for n in SCOPED}
    assert got["mla_ms"] == pytest.approx(15e-6)
    assert got["moe_dispatch_ms"] == pytest.approx(6e-6)
    cfg, mod = harness.config("deepseek-v2-lite-l5")
    peak = harness.peaks("TPU v5 lite")
    least = 4 * mod.expert_matmul_flops(cfg, 12288) / peak["bf16_flops"]
    assert got["moe_experts_expected_roofline"] == pytest.approx(
        100 * least / 15e-9)


RECORDED = os.path.join(TESTDATA, CELL + ".scoped.xplane.pb.gz")


def test_scope_readers_on_a_recorded_profile(tmp_path):
    """A profile of one run of the cell's step on a TPU v5e, cut to chip 0's
    "XLA Modules" and "XLA Ops" lines with each op's name and JAX path:
    ``scopes.load`` finds every scope the program opens in the ops' event
    metadata, on the clock ``ProfileData`` gives the same events, and each
    reader's time is the union of its ops inside the step's runs."""
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(RECORDED, "rb") as f:
        (d / "t.xplane.pb").write_bytes(f.read())
    sc = scopes.load(str(tmp_path))
    (plane,) = [p for p in ProfileData.from_file(str(d / "t.xplane.pb")).planes
                if p.name == "/device:TPU:0"]
    lines = {line.name: list(line.events) for line in plane.lines}
    runs = [[e.start_ns, e.start_ns + e.duration_ns, e.name]
            for e in lines["XLA Modules"] if "train_step" in e.name]
    ops = {(e.start_ns, e.start_ns + e.duration_ns) for e in lines["XLA Ops"]}
    assert runs and sc["ops"]["0"]
    assert {(s, e) for s, e, _ in sc["ops"]["0"]} <= ops
    paths = sc["paths"]
    for name in ("mla", "moe.route", "moe.dispatch", "moe.experts",
                 "moe.combine"):
        assert any(scopes.has_scope(p, [name]) for p in paths), name
    tr = {"devices": {"0": []}, "modules": {"0": runs}, "host": [],
          "scoped": sc}
    run = _hand_run(tr)
    run.trace_window = (runs[0][0], runs[-1][1])
    got = {n: harness.metric_reader(n).reduce(run) for n in SCOPED}
    assert None not in got.values(), got

    def union_ms(names):
        inside = [scopes.has_scope(p, names) for p in paths]
        total = 0
        for lo, hi, _ in runs:
            end = lo
            for s, e in sorted((max(s, lo), min(e, hi)) for s, e, i
                               in sc["ops"]["0"]
                               if inside[i] and s < hi and e > lo):
                total += max(e - max(s, end), 0)
                end = max(end, e)
        return total / len(runs) / 1e6

    assert got["mla_ms"] == pytest.approx(union_ms(["mla"]), rel=1e-6)
    assert got["moe_dispatch_ms"] == pytest.approx(
        union_ms(["moe.route", "moe.dispatch", "moe.combine"]), rel=1e-6)
    step_ms = np.mean([e - s for s, e, _ in runs]) / 1e6
    assert got["mla_ms"] + got["moe_dispatch_ms"] < step_ms
    # The grouped matmuls run as custom calls with no JAX path; the
    # experts' time holds them.
    kernels = [i for i, p in enumerate(paths) if p.startswith("ragged-dot")]
    assert kernels
    matmul_ms = sum(e - s for s, e, i in sc["ops"]["0"] if i in kernels) / 1e6
    assert scopes.per_step_ms(run, ["moe.experts"]) > matmul_ms > 0
    assert 0 < got["moe_experts_expected_roofline"] <= 100


def test_scope_readers_give_none_without_scopes(own_checkout):
    for path in glob.glob(os.path.join(TESTDATA, "*.trace.json.gz")):
        with gzip.open(path, "rt") as f:
            tr = json.load(f)
        run = _hand_run(tr)
        assert {n: harness.metric_reader(n).reduce(run) for n in SCOPED} \
            == dict.fromkeys(SCOPED)
    run = _hand_run(json.loads(json.dumps(HAND)))
    run.trace = None
    assert harness.metric_reader("mla_ms").reduce(run) is None
