"""The chip benchmark of the elastic trainer: one run of one cell.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  It exits non-zero, and prints no result, without an accelerator,
with fewer chips than the cell needs, on a chip not in ``peaks.json``, or
where the program under ``src/`` is missing.  Otherwise its last line on
standard output is the result object, and its last lines on standard error
are the numbers that decided ``correct``, each beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the traced run's plain trace here, "
                         "as gzip JSON")
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(CHECKOUT, "src")]
    import harness

    cell = harness.cell(args.workload)
    device, peak = harness.check_devices(cell["chips"])

    import jax
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # Cache every program, however quickly it compiled, so that only a
    # cell's first run in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T0, device=device,
                         peak=peak, keep_trace=args.keep_trace)
    harness.report(result)


if __name__ == "__main__":
    main()
