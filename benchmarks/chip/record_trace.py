"""Record a traced run's plain trace with the program's spans, cut to a few
steps, for the tests (``testdata/<cell>.program.trace.json.gz``).

    python3 benchmarks/chip/record_trace.py --workload <name> --seed <n> \
        --seconds <s> --out <path.trace.json.gz> [--steps 12]

One ``--trace 1`` run of the benchmark on the chip, whose kept trace also
holds the ``"program"`` key (``programspans``).  The kept trace starts at
the traced window's second step, since the first step's enclosing spans
opened before the profiler did.  It prints, for the whole traced window,
the share of it that ``elastic.step`` spans cover, the share of the chip's
idle time with no span open, and the loop's own time against the step
period.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def cut(tr: dict, first: int, n: int) -> dict:
    """``tr`` from the input span of its ``first`` traced step to that of
    step ``first + n``: whatever overlaps that stretch."""
    starts = sorted(s for s, _, name in tr["host"] if name == "input")
    lo, hi = starts[first], starts[first + n]

    def keep(events):
        return [ev for ev in events if ev[1] > lo and ev[0] < hi]

    return {"devices": {d: keep(ops) for d, ops in tr["devices"].items()},
            "modules": {d: keep(m) for d, m in tr["modules"].items()},
            "host": [ev for ev in tr["host"] if lo <= ev[0] <= hi],
            "program": keep(tr["program"])}


def coverage(tr: dict) -> dict:
    """What the program's spans account for in the traced window."""
    import programspans
    import tracefile

    lo, hi = tracefile.window(tr)
    steps = tracefile.union(((s, e) for s, e, n in tr["program"]
                             if n == "elastic.step"), lo, hi)
    idle = programspans.idle_by_span(tr, "0", lo, hi)
    self_ns = programspans.self_ns(tr, lo, hi)
    period = (hi - lo) / programspans.window_steps(tr, lo, hi)
    return {"window_ms": (hi - lo) / 1e6,
            "step_span_cover": tracefile.covered(steps) / (hi - lo),
            "idle_no_span_share": idle.get(None, 0.0) / sum(idle.values()),
            "idle_ms_by_span": {str(k): v / 1e6 for k, v in idle.items()},
            "loop_self_over_period": sum(self_ns) / len(self_ns) / period}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    import programspans
    import run
    import tracefile

    plain = tracefile.load
    tracefile.load = lambda d: dict(plain(d), program=programspans.load(d))
    out = os.path.abspath(args.out)
    run.main(["--workload", args.workload, "--seed", args.seed,
              "--seconds", args.seconds, "--trace", "1",
              "--keep-trace", out])
    with gzip.open(out, "rt") as f:
        tr = json.load(f)
    print("whole window " + json.dumps(coverage(tr)), file=sys.stderr)
    short = cut(tr, 1, args.steps)
    print("kept " + json.dumps(coverage(short)), file=sys.stderr)
    with gzip.open(out, "wt") as f:
        json.dump(short, f)


if __name__ == "__main__":
    main()
