"""Tiny sizes of each configuration and traffic, for runs on the CPU."""
import copy

import harness
import traffic as traffic_mod

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def config(name: str) -> dict:
    cfg, _ = harness.config(name)
    cfg = copy.deepcopy(cfg)
    if name == "resnet110":
        cfg.update(depth=8)
    else:
        cfg.update(num_hidden_layers=1, hidden_size=128,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                   intermediate_size=256, vocab_size=512)
    return cfg


def traffic(cell: dict) -> dict:
    tr = copy.deepcopy(traffic_mod.load(cell["traffic"]))
    if "seq_len" in tr:
        tr.update(m=2, seq_len=64)
    else:
        tr["m"] = 8
    for seg in tr["segments"]:
        if "steps_per_s" in seg:
            seg["steps_per_s"] = 5
        else:
            seg["steps"] = 4
    if "cycles_per_s" in tr:
        tr["cycles_per_s"] = 0.5
    tr["trace_steps"] = [4, 7]
    return tr


def run(cell_name: str, seed: int = 2 ** 33 + 5, trace: bool = False):
    """One tiny run of a cell through the harness's own entry, past the
    look for a chip."""
    cell = harness.cell(cell_name)
    return harness.run(cell_name, seed, 2.0, trace,
                       cfg=config(cell["config"]), traffic=traffic(cell),
                       device=dict(CPU), peak={})


# The resize cell is built and rehearsed here but not yet in BENCHMARK.json
# (it is not proven on four chips); tests add it to a copy of the checkout.
RESIZE = {"name": "resnet110.resize-4-2", "config": "resnet110",
          "traffic": "resize-4-2.m128", "chips": 4, "why": "x"}
RESIZE_METRICS = [
    {"name": n, "unit": u, "better": "lower", "source": "host_clock",
     "layer": "x", "moves": "samples_per_s",
     "workloads": ["resnet110.resize-4-2"]}
    for n, u in (("exchange_exposed_ms", "ms"), ("save_s", "s"),
                 ("restore_s", "s"), ("first_step_s", "s"))]


def checkout_with_resize(root) -> str:
    """A copy of the checkout whose BENCHMARK.json also holds the resize
    cell and its metrics; -> its path."""
    import json
    import os
    import shutil

    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(harness.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.CHECKOUT, "src"),
               os.path.join(root, "src"))
    b = harness.benchmark()
    b["workloads"].append(RESIZE)
    b["per_layer"] += RESIZE_METRICS
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return str(root)


def job(cell_name: str, ckpt_dir: str, seed: int = 2 ** 33 + 5):
    cell = RESIZE if cell_name == RESIZE["name"] else harness.cell(cell_name)
    return harness.Job(cell, seed, spans=harness.Spans(), ckpt_dir=ckpt_dir,
                       cfg=config(cell["config"]), traffic=traffic(cell))
