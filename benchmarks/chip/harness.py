"""One run of one benchmark cell: set-up, the measured window, the traced
sub-window, and the check against the plain reference.

The window drives the program's own elastic loop,
``ElasticTrainer.train_segment``; the harness writes no step loop.  It
injects the data source and the checkpoint store and times them at that
boundary, so its spans are its own.  Everything a cell needs is found by
name: the cell in ``BENCHMARK.json``, its configuration under ``configs/``,
its traffic under ``traffic/``, its limits under ``limits/`` and its
per-layer metrics under ``metrics/``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import gzip
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

import check
import refopt
import tracefile as tracing
import traffic as traffic_mod
import weights

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


# ------------------------------------------------------------- lookups ----
def benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    for c in benchmark()["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> tuple[dict, object]:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "config_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "configs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return cfg, mod


def limits(cell_name: str) -> dict:
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return json.load(f)["limits"]


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def flops_per_sample(mod, cfg: dict, traffic: dict) -> float:
    if "seq_len" in traffic:
        return mod.flops_per_sample(cfg, traffic["seq_len"])
    return mod.flops_per_sample(cfg)


# --------------------------------------------------------------- spans ----
class Spans:
    """The harness's spans, on the host clock and in the profiler's trace."""

    def __init__(self):
        self.events: list[tuple[str, float, float, dict]] = []

    @contextlib.contextmanager
    def span(self, name: str, **info):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        finally:
            self.events.append((name, t0, time.perf_counter(), info))

    def of(self, name: str, lo: float = -np.inf, hi: float = np.inf):
        return [e for e in self.events if e[0] == name and lo <= e[1] <= hi]


class TimedData:
    """The injected data source: the generator inside an ``input`` span, and
    the hook that opens and closes the profiler at fixed global steps."""

    def __init__(self, gen, spans: Spans, on_step=None):
        self.gen = gen
        self.size = gen.size
        self.spans = spans
        self.on_step = on_step

    def batch(self, step: int, batch_size: int) -> dict:
        if self.on_step is not None:
            self.on_step(step)
        with self.spans.span("input", step=step, rows=batch_size):
            return self.gen.batch(step, batch_size)


class MemoryStore:
    """A checkpoint store that keeps snapshots in host memory, with the
    program store's semantics (every leaf copied to the host on save).  For
    states too large to write to disk on every run.  It keeps the newest
    snapshot and those of the steps the check reads."""

    def __init__(self, keep=(1, 3)):
        self.saved: dict[int, dict] = {}
        self.keep = keep

    def save(self, step: int, state: dict, meta=None) -> float:
        import jax

        t0 = time.perf_counter()
        for s in [s for s in self.saved if s not in self.keep]:
            del self.saved[s]
        self.saved[step] = jax.tree_util.tree_map(np.asarray, state)
        return time.perf_counter() - t0

    def latest_step(self):
        return max(self.saved) if self.saved else None

    def restore(self, template, step=None):
        t0 = time.perf_counter()
        return (self.saved[self.latest_step() if step is None else step],
                {}, time.perf_counter() - t0)


class TimedStore:
    """The injected checkpoint store, with ``save`` and ``restore`` spans."""

    def __init__(self, inner, spans: Spans):
        self.inner = inner
        self.spans = spans
        self.treedefs: dict[int, object] = {}

    def latest_step(self):
        return self.inner.latest_step()

    def save(self, step: int, state: dict, meta=None) -> float:
        import jax

        with self.spans.span("save", step=step):
            self.treedefs[step] = jax.tree_util.tree_structure(state)
            return self.inner.save(step, state, meta)

    def restore(self, template, step=None):
        with self.spans.span("restore"):
            return self.inner.restore(template, step)

    def snapshot(self, step: int) -> dict:
        """The state saved at ``step``, as host arrays."""
        import jax

        treedef = self.treedefs[step]
        template = jax.tree_util.tree_unflatten(
            treedef, [0] * treedef.num_leaves)
        return self.inner.restore(template, step)[0]


class SeededModel:
    """The program's model with the benchmark's weights: ``init`` ignores
    the key it is given and returns the weights made from ``--seed``."""

    def __init__(self, model, make_params):
        self._model = model
        self._make = make_params

    def init(self, key=None):
        return self._make()

    def loss(self, params, batch, sh=None):
        return self._model.loss(params, batch, sh)

    def __getattr__(self, name):
        return getattr(self._model, name)


# ---------------------------------------------------------- precisions ----
def exact(x):
    return x


def fp8(x):
    """float8 e4m3 operands (saturating), the control's precision: the
    nearest below the bfloat16 that the configurations state."""
    import jax.numpy as jnp

    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    return jnp.clip(x, -top, top).astype(jnp.float8_e4m3fn).astype(x.dtype)


# ----------------------------------------------------------------- run ----
@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""
    traffic: dict
    chips: int
    peak: dict
    flops_per_sample: float
    spans: Spans
    window: tuple[float, float]
    segments: list[dict]
    restarts: list[tuple[float, float]]
    trace: dict | None = None
    trace_window: tuple[float, float] | None = None


class Job:
    """The program's trainer for one cell, built from the seed, with the
    injected data source and store."""

    def __init__(self, cell: dict, seed: int, *, spans: Spans, ckpt_dir: str,
                 on_step=None, cfg=None, traffic=None):
        from repro.checkpoint.store import CheckpointStore

        self.cfg, self.mod = config(cell["config"])
        if cfg is not None:
            self.cfg = cfg
        self.traffic = traffic or traffic_mod.load(cell["traffic"])
        self.seed = seed
        self.spans = spans
        self.gen = traffic_mod.generator(self.mod.INPUT, seed, self.cfg,
                                         self.traffic)
        self.data = TimedData(self.gen, spans, on_step)
        inner = (MemoryStore() if self.traffic["checkpoint"] == "memory"
                 else CheckpointStore(ckpt_dir))
        self.store = TimedStore(inner, spans)
        self.model, self.opt = self.mod.program(self.cfg)
        self.make_params = weights.maker(self.mod.param_spec(self.cfg))
        words = weights.seed_words(seed)
        import jax
        theirs = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        ours = jax.eval_shape(self.make_params, words)
        if jax.tree_util.tree_structure(theirs) != \
                jax.tree_util.tree_structure(ours) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(theirs),
                    jax.tree_util.tree_leaves(ours))):
            raise RuntimeError("the program's parameters no longer have the "
                               "layout the benchmark's weights are made in")
        self.seeded = SeededModel(self.model,
                                  lambda: self.make_params(words))

    def trainer(self):
        from repro.core.elastic import ElasticTrainer

        return ElasticTrainer(self.seeded, self.opt, self.data, self.store,
                              base_lr_1w=self.cfg["base_lr_1w"],
                              m_per_worker=self.traffic["m"],
                              dataset_size=self.gen.size)

    def first_steps(self):
        """The three steps that set-up drives and the reference follows,
        through the window's own call and feed.  -> (trainer, losses)."""
        import jax

        ws = traffic_mod.first_steps(self.traffic)
        log_every = self.traffic["log_every"]
        tr = self.trainer()
        losses = []
        for i, (w, n) in enumerate(((ws[0], 1), (ws[1], 2))):
            if i and self.traffic.get("restart"):
                tr = self.trainer()
            r = tr.train_segment(w, n, resume=bool(i), log_every=log_every)
            losses += [l for _, _, l in r.losses]
            if jax.device_count() >= w and r.devices != w:
                raise RuntimeError(f"a segment at w={w} ran on {r.devices}")
            del r   # its final state would stay on the device
        return tr, losses

    def program_readings(self, losses) -> dict:
        import jax

        params0 = jax.tree_util.tree_map(
            np.asarray, self.make_params(weights.seed_words(self.seed)))
        s1, s3 = self.store.snapshot(1), self.store.snapshot(3)
        return {"losses": losses,
                "grad1": refopt.first_grad(self.cfg["optimizer"], s1["opt"]),
                "change3": [np.asarray(a, np.float64) - b for a, b in zip(
                    jax.tree_util.tree_leaves(s3["params"]),
                    jax.tree_util.tree_leaves(params0))]}


def reference_readings(job: Job, variant: str = "reference") -> dict:
    """The plain reference over the same three steps.  ``variant`` puts a
    control or a planted fault in the program's place: ``fp8`` computes in
    the precision below the configuration's, ``half`` leaves out half of
    each batch, ``no_exchange`` trains each step at w > 1 on the first
    worker's rows alone."""
    import jax

    cfg, traffic, opt_cfg = job.cfg, job.traffic, job.cfg["optimizer"]
    quant = fp8 if variant == "fp8" else exact
    params = job.make_params(weights.seed_words(job.seed))
    params0 = jax.tree_util.tree_map(np.asarray, params)
    opt = refopt.init(opt_cfg, params)
    losses, grad1 = [], None
    m = traffic["m"]
    for i, w in enumerate(traffic_mod.first_steps(traffic)):
        batch = job.gen.batch(i, m * w)
        if variant == "half":
            batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        elif variant == "no_exchange" and w > 1:
            batch = {k: v[:m] for k, v in batch.items()}
        loss, grads = job.mod.reference_grad(cfg, params, batch, quant)
        params, opt = refopt.update(opt_cfg, params, opt, grads,
                                    cfg["base_lr_1w"] * w)
        losses.append(loss)
        if i == 0:
            grad1 = refopt.first_grad(opt_cfg, opt)
    change3 = [np.asarray(a, np.float64) - b for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(params0))]
    return {"losses": losses, "grad1": grad1, "change3": change3}


def check_devices(chips: int) -> tuple[dict, dict]:
    """Refuse to run without an accelerator, with too few chips, or on a
    chip whose peaks are unknown.  -> (device report, peaks)."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    kind = devs[0].device_kind
    return ({"platform": devs[0].platform, "kind": kind,
             "count": len(devs)}, peaks(kind))


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peak = None
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, device: dict | None = None,
        peak: dict | None = None, cfg=None, traffic=None,
        keep_trace: str | None = None) -> dict:
    """One run of a cell.  -> the result object (the last line printed).
    The caller checks the devices; tests pass ``device`` and ``peak``."""
    t_start = time.perf_counter() if t_start is None else t_start
    c = cell(cell_name)
    work = os.path.join(CHECKOUT, ".bench_work")
    ckpt_dir = os.path.join(work, "ckpt")
    trace_dir = os.path.join(work, "trace")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    spans = Spans()
    tracer = Tracer(trace_dir) if trace else None
    job = Job(c, seed, spans=spans, ckpt_dir=ckpt_dir, cfg=cfg,
              traffic=traffic, on_step=tracer.on_step if tracer else None)
    tr_traffic = job.traffic
    if tracer:
        tracer.steps = tuple(tr_traffic["trace_steps"])
    try:
        trainer, losses = job.first_steps()
        segs = traffic_mod.window_segments(tr_traffic, seconds)
        log_every = tr_traffic["log_every"]
        records = []
        if tr_traffic.get("restart"):
            # The window opens as the last set-up segment's save begins, so
            # it holds one restart before each of its segments.
            t_open = spans.of("save")[-1][1]
            del trainer
            for seg in segs:
                r = job.trainer().train_segment(seg["w"], seg["steps"],
                                                resume=True,
                                                log_every=log_every)
                records.append(_record(r))
                del r
        else:
            (seg,) = segs
            r = trainer.train_segment(seg["w"], seg["steps"], resume=True,
                                      log_every=log_every)
            records.append(_record(r))
            del r, trainer
            t_open = [e for e in spans.of("input")
                      if e[3]["step"] == 3][-1][1]
        t_close = spans.of("save")[-1][1]
        setup_s = t_open - t_start
    finally:
        if tracer:
            tracer.stop()
    peak_mem = memory_peak_bytes(c["chips"])

    samples = sum(s["steps"] * s["w"] * tr_traffic["m"] for s in segs)
    restarts = _restarts(spans, t_open, t_close) \
        if tr_traffic.get("restart") else []
    window_s = t_close - t_open
    result: dict = {"correct": False, "attempted": sum(s["steps"]
                                                       for s in segs),
                    "failed": 0}
    e2e = {"samples_per_s": {"value": samples / window_s,
                             "unit": "samples/s"},
           "setup_s": {"value": setup_s, "unit": "s"}}
    if restarts:
        e2e["restart_s"] = {"value": float(np.mean(
            [b - a for a, b in restarts])), "unit": "s"}

    run_info = Run(traffic=tr_traffic,
                   chips=c["chips"], peak=peak or {},
                   flops_per_sample=flops_per_sample(job.mod, job.cfg,
                                                     tr_traffic),
                   spans=spans, window=(t_open, t_close), segments=records,
                   restarts=restarts)
    dev = dict(device or {})
    dev["memory_peak_bytes"] = peak_mem
    breakdown = None
    if trace:
        run_info.trace = tracing.load(trace_dir)
        if keep_trace:
            with gzip.open(keep_trace, "wt") as f:
                json.dump(run_info.trace, f)
        lo, hi = tracing.window(run_info.trace)
        run_info.trace_window = (lo, hi)
        metrics = {}
        for m in benchmark()["per_layer"]:
            if cell_name not in m.get("workloads", [cell_name]):
                continue
            v = metric_reader(m["name"]).reduce(run_info)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        used = [str(i) for i in range(c["chips"])]
        dev["busy_s"] = float(np.mean([tracing.busy_ns(run_info.trace, d,
                                                       lo, hi)
                                       for d in used])) / 1e9
        dev["window_s"] = (hi - lo) / 1e9
        gaps = sorted(tracing.idle_gaps(run_info.trace, "0", lo, hi),
                      key=lambda g: -g[1])[:10]
        breakdown = {"device_ops": tracing.top_ops(run_info.trace, lo, hi),
                     "idle_gaps": [list(g) for g in gaps]}
    else:
        metrics = e2e

    # The check: after the window, with the program's state freed.
    prog = job.program_readings(losses)
    job.store.inner = None
    gc.collect()
    ref = reference_readings(job)
    nums = check.numbers(prog, ref)
    lim = limits(cell_name)
    result["correct"] = check.judge(nums, lim)
    result["metrics"] = metrics
    result["device"] = dev
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": nums[k], "limit": lim[k]} for k in lim}
    result["readings"] = nums
    shutil.rmtree(work, ignore_errors=True)
    return result


def _record(r) -> dict:
    return {"w": r.w, "steps": r.steps, "devices": r.devices,
            "first_step_s": r.first_step_seconds}


def _restarts(spans: Spans, lo: float, hi: float):
    """Each restart in the window: from a segment's save beginning to the
    next segment's first step being ready, which is when the loop asks for
    its second batch."""
    saves = [e[1] for e in spans.of("save", lo, hi)]
    out = []
    inputs = spans.of("input", lo, hi)
    for a, b in zip(saves, saves[1:] + [np.inf]):
        later = [e[1] for e in inputs if a < e[1] < b]
        if len(later) >= 2:
            out.append((a, later[1]))
    return out


class Tracer:
    """Opens the profiler when the loop asks for the batch of the first
    traced step and closes it at the last, from inside the data source."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.steps = (0, 0)
        self.on = False

    def on_step(self, step: int):
        import jax

        if step == self.steps[0] and not self.on:
            jax.profiler.start_trace(self.dir)
            self.on = True
        elif step == self.steps[1] and self.on:
            self.stop()

    def stop(self):
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on = False


def report(result: dict) -> None:
    """The compared numbers, each beside its limit, as the last lines on
    standard error; then the result as the last line on standard output."""
    print("readings " + json.dumps(result.pop("readings")), file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
