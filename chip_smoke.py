"""Smoke test of the elastic trainer on a TPU: the published ResNet-110.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one four-chip host

One chip: train ResNet-110 (depth 110, stage widths 16/32/64, 32x32x3
inputs, 10 classes) through ``ElasticTrainer`` at w = 1 with per-worker batch
m = 128, SGD momentum 0.9 and weight decay 1e-4, on CIFAR-shaped data and
weights made from fixed seeds.  The segment checkpoints; a fresh trainer,
with a fresh jit, resumes from the checkpoint and trains on.

Four chips: one step at w = 4 from the same state and batch with XLA's
all-reduce, the ring and doubling-halving exchanges, which must agree; then
a save at w = 4 and a resume at w = 2 on two of the chips, the LR rescaled
by eq. 7.

Every check that fails raises.  Without a TPU the script exits non-zero
before training.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
M = 128                 # per-worker batch (paper §5)
BASE_LR_1W = 0.01       # ResNet-110's first LR, batch 128 (He et al. §4.2)
RESTART_STEPS = (20, 10)
EXCHANGES = (None, "ring", "doubling_halving")
EXCHANGE_ATOL = 2e-3    # as tests/test_collectives_shardmap.py


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def trainer(cfg, ckpt_dir: str, *, m: int = M, grad_exchange=None):
    from repro.checkpoint.store import CheckpointStore
    from repro.core.elastic import ElasticTrainer
    from repro.data.synthetic import CifarLike
    from repro.models.resnet import ResNetModel
    from repro.optim.optimizers import sgd

    return ElasticTrainer(ResNetModel(cfg), sgd(momentum=0.9,
                                                weight_decay=1e-4),
                          CifarLike(seed=SEED), CheckpointStore(ckpt_dir),
                          base_lr_1w=BASE_LR_1W, m_per_worker=m,
                          grad_exchange=grad_exchange)


def leaves(tree) -> list[np.ndarray]:
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def devices_of(state) -> list[int]:
    import jax
    leaf = jax.tree_util.tree_leaves(state["params"])[0]
    return sorted(d.id for d in leaf.sharding.device_set)


def report(name: str, r) -> None:
    print(f"{name}: w={r.w} devices={r.devices} ids={devices_of(r.state)} "
          f"lr={r.lr!r} steps={r.steps}")
    print(f"{name}: first_step_s={r.first_step_seconds!r} "
          f"step_s={r.step_seconds!r} save_s={r.save_seconds!r} "
          f"restore_s={r.restore_seconds!r}")
    print(f"{name}: losses={[(s, l) for s, _, l in r.losses]}")
    check(r.devices == r.w, f"{name} ran on {r.devices} devices, w={r.w}")
    check(all(math.isfinite(l) for _, _, l in r.losses),
          f"{name} losses finite")


def restart_phase(cfg, *, m: int = M, steps=RESTART_STEPS) -> None:
    """w = 1: train, checkpoint, resume in a fresh trainer."""
    import jax

    n1, n2 = steps
    with tempfile.TemporaryDirectory() as d:
        r1 = trainer(cfg, d, m=m).train_segment(w=1, n_steps=n1,
                                                resume=False, log_every=5)
        report("segment1", r1)

        tr2 = trainer(cfg, d, m=m)
        restored, _, _ = tr2.ckpt.restore(tr2.fresh_state())
        saved = leaves(r1.state)
        check(all(a.dtype == b.dtype and a.shape == b.shape
                  and a.tobytes() == b.tobytes()
                  for a, b in zip(saved, leaves(restored)))
              and len(saved) == len(leaves(restored)),
              "restored state equals the saved state bit for bit")
        print(f"restore: {len(saved)} leaves bit-identical")

        r2 = tr2.train_segment(w=1, n_steps=n2, resume=True, log_every=5)
        report("segment2", r2)
        check(r2.losses[0][0] == n1 and int(r2.state["step"]) == n1 + n2,
              "step counter continues from the checkpoint")

        # The restart's first loss is the saved weights' loss on that batch,
        # not the initial weights'.
        loss = jax.jit(tr2.model.loss)
        batch = tr2.data.batch(n1, m)
        at_saved = float(loss(restored["params"], batch))
        at_init = float(loss(tr2.fresh_state()["params"], batch))
        first = r2.losses[0][2]
        print(f"continuity: restart_loss={first!r} saved_weights_loss="
              f"{at_saved!r} initial_weights_loss={at_init!r}")
        check(abs(first - at_saved) < abs(first - at_init) / 10,
              "loss after the restart continues from the checkpoint")


def hlo_collectives(text: str) -> dict[str, int]:
    ops = re.findall(r" (all-reduce|reduce-scatter|all-gather|"
                     r"collective-permute)(?:-start)?\(", text)
    return {op: ops.count(op) for op in sorted(set(ops))}


def exchange_phase(cfg, *, m: int = M) -> None:
    """w = 4: XLA all-reduce vs ring vs doubling-halving, then 4 -> 2."""
    import jax

    with tempfile.TemporaryDirectory() as d:
        runs = {}
        for ex in EXCHANGES:
            name = ex or "xla"
            tr = trainer(cfg, os.path.join(d, name), m=m, grad_exchange=ex)
            r = tr.train_segment(w=4, n_steps=1, resume=False, log_every=1)
            report(f"w4_{name}", r)
            step, _, data_sharding = tr.step_for(4)
            batch = jax.device_put(tr.data.batch(0, 4 * m), data_sharding)
            state = {"params": r.state["params"], "opt": r.state["opt"]}
            ops = hlo_collectives(
                step.lower(state, batch, r.lr).compile().as_text())
            print(f"w4_{name}: hlo collectives {ops}")
            if ex is None:
                check(ops.get("all-reduce", 0) > 0, "XLA step all-reduces")
            else:
                check(ops.get("collective-permute", 0) > 0,
                      f"{name} step exchanges by collective-permute")
            runs[name] = (tr, r)

        ref = leaves(runs["xla"][1].state["params"])
        for name in ("ring", "doubling_halving"):
            got = leaves(runs[name][1].state["params"])
            diff = max(float(np.max(np.abs(a - b))) for a, b in zip(ref, got))
            print(f"agreement: {name} vs xla max_abs_diff={diff!r}")
            check(all(np.allclose(a, b, atol=EXCHANGE_ATOL)
                      for a, b in zip(ref, got)),
                  f"{name} step agrees with XLA's all-reduce")

        tr4, _ = runs["xla"]
        r4 = tr4.train_segment(w=4, n_steps=4, resume=True, log_every=1)
        report("resize_w4", r4)
        r2 = trainer(cfg, tr4.ckpt.dir, m=m).train_segment(
            w=2, n_steps=4, resume=True, log_every=1)
        report("resize_w2", r2)
        check(r2.losses[0][0] == 5, "w=2 resumes at the w=4 checkpoint")
        check(math.isclose(r2.lr, r4.lr / 2), "eq. 7 halves the LR at w=2")
        check(len(devices_of(r2.state)) == 2, "w=2 state on two chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    from repro.configs.resnet110 import CONFIG
    from repro.launch.compile_cache import use_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX sees {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    print(f"device: kind={devs[0].device_kind!r} count={len(devs)}")
    print(f"compile cache: {use_compile_cache()}")
    print(f"model: {CONFIG.name} depth={CONFIG.depth} width={CONFIG.width} "
          f"image={CONFIG.image_size} classes={CONFIG.num_classes} m={M}")

    if args.chips == 4:
        exchange_phase(CONFIG)
    else:
        restart_phase(CONFIG)
    for d in devs[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
