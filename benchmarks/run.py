"""Benchmark harness — one module per paper table + perf benches.

Prints ``name,us_per_call,derived`` CSV lines; ``--json out.json``
additionally writes the same rows as machine-readable JSON
(``{name: {us_per_call, derived}}``).

  PYTHONPATH=src python -m benchmarks.run                    # all tables
  PYTHONPATH=src python -m benchmarks.run table3             # one table
  PYTHONPATH=src python -m benchmarks.run scheduler --json out.json
"""
from __future__ import annotations

import json
import sys
import time


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            raise SystemExit("--json requires an output path")
        del argv[i:i + 2]
    want = argv or ["table1", "table2", "table3", "roofline"]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_scheduler, roofline, table1_profiling,
                            table2_stop_restart, table3_scheduler_sim)
    mods = {"table1": table1_profiling, "table2": table2_stop_restart,
            "table3": table3_scheduler_sim, "roofline": roofline,
            "scheduler": bench_scheduler}
    unknown = [n for n in want if n not in mods]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}; "
                         f"choose from {sorted(mods)}")
    rows: dict[str, dict] = {}

    def emit(line: str) -> None:
        print(line, flush=True)
        name, us, derived = line.split(",", 2)
        rows[name] = {"us_per_call": float(us), "derived": derived}

    print("name,us_per_call,derived")
    for name in want:
        t0 = time.perf_counter()
        mods[name].main(csv=emit)
        emit(f"{name}/wall_s,{(time.perf_counter() - t0) * 1e6:.0f},done")
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(rows, fh, indent=2, sort_keys=True)
            fh.write("\n")


if __name__ == "__main__":
    main()
