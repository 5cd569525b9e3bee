"""The readings a cell's limits are set from, on the chip at the cell's own
size: for each seed, the numbers that sound runs of the program give
against the reference; for the first few seeds also those of the control
(the reference in float8) and of each planted fault (the reference put in
the program's place with half of each batch, and, where a step runs at
w > 1, with the first worker's rows alone).  A state left unchanged reads 1
on ``change_gap`` by construction and needs no run.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 101,102,... --faulty 3 > readings.jsonl

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faulty", type=int, default=3,
                    help="read the control and the faults on this many of "
                         "the seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(CHECKOUT, "src")]
    import harness
    import check
    import traffic as traffic_mod

    cell = harness.cell(args.workload)
    harness.check_devices(cell["chips"])
    import jax
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(dir=CHECKOUT) as d:
            job = harness.Job(cell, seed, spans=harness.Spans(), ckpt_dir=d)
            trainer, losses = job.first_steps()
            del trainer
            prog = job.program_readings(losses)
        job.store.inner = None
        gc.collect()
        ref = harness.reference_readings(job)
        row = {"seed": seed, "program": check.numbers(prog, ref),
               "ref_losses": ref["losses"], "prog_losses": prog["losses"]}
        if i < args.faulty:
            variants = ["fp8", "half"]
            if max(traffic_mod.first_steps(job.traffic)) > 1:
                variants.append("no_exchange")
            for v in variants:
                row[v] = check.numbers(harness.reference_readings(job, v),
                                       ref)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
