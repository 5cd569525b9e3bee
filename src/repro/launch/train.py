"""End-to-end training driver.

Trains any ``--arch`` (full or ``--smoke`` reduced config) on the synthetic
token pipeline with AdamW + warmup-cosine, checkpointing through the elastic
store.  ``--workers`` sets the data-parallel worker count the scheduler
allocated: per-worker batch m stays fixed, global batch = m * workers, LR
linearly rescaled (paper eq. 7).  When ``--workers`` devices exist the step
runs on a data mesh of that many, batch sharded: GSPMD inserts XLA's
all-reduce, or ``--grad-exchange ring|doubling_halving`` runs the paper's
explicit algorithm under shard_map.  With fewer devices the global batch
runs on one.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 100 --workers 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.checkpoint.store import CheckpointStore
from repro.data.synthetic import TokenStream
from repro.engine.steps import make_data_parallel_step, init_train_state
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.models.registry import build_model
from repro.optim.optimizers import adamw
from repro.optim.schedule import warmup_cosine, rescale_lr


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--m-per-worker", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4,
                    help="base LR at 1 worker (eq. 7 scales it)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-exchange", default=None,
                    choices=[None, "ring", "doubling_halving"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    opt = adamw()
    data = TokenStream(cfg.vocab_size, args.seq, seed=0)
    global_batch = args.m_per_worker * args.workers
    base_lr = rescale_lr(args.lr, args.workers, 1)
    sched = warmup_cosine(base_lr, warmup=min(20, args.steps // 5 + 1),
                          total=args.steps)

    # one device per worker when that many exist, else the whole global
    # batch on one device
    n = args.workers if jax.device_count() >= args.workers else 1
    jitted, rep, data_sharding = make_data_parallel_step(
        model, opt, make_data_mesh(n), args.grad_exchange)
    print(f"{args.workers} worker(s) on {n} device(s)")

    state = init_train_state(model, opt)
    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    step0 = 0
    if store and args.resume and store.latest_step() is not None:
        state, meta, secs = store.restore(state)
        step0 = store.latest_step()
        print(f"restored step {step0} in {secs:.2f}s (meta={meta})")
    state = jax.device_put(state, rep)

    t0 = time.perf_counter()
    first_loss = None
    for i in range(step0, step0 + args.steps):
        batch = jax.device_put(data.batch(i, global_batch), data_sharding)
        state, loss, *_ = jitted(state, batch, jnp.float32(sched(i)))
        if first_loss is None:
            first_loss = float(loss)
        if i % args.log_every == 0 or i == step0 + args.steps - 1:
            dt = time.perf_counter() - t0
            tok_s = (i - step0 + 1) * global_batch * args.seq / max(dt, 1e-9)
            print(f"step {i:5d} loss {float(loss):.4f} lr {sched(i):.2e} "
                  f"tok/s {tok_s:,.0f}", flush=True)
    if store:
        secs = store.save(step0 + args.steps, state,
                          meta={"workers": args.workers})
        print(f"checkpointed step {step0 + args.steps} in {secs:.2f}s")
    return first_loss, float(loss)


if __name__ == "__main__":
    main()
