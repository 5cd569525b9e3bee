"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
plain form, which is also the form of the recorded trace under ``testdata/``:

    {"devices": {"<id>": [[start_ns, end_ns, name, category], ...]},
     "modules": {"<id>": [[start_ns, end_ns, name], ...]},
     "host":    [[start_ns, end_ns, name], ...]}

``devices`` holds the operations that ran on each chip ("XLA Ops" and
"Async XLA Ops" lines; the category is "async" for the latter), each named
by its HLO instruction (``%fusion.12``, ``%all-reduce.3``); ``modules`` the
compiled programs they belong to ("XLA Modules" lines); ``host`` the
benchmark's own spans (``bench.*`` annotations).  The profiler puts host
and device events on one clock to within about a millisecond.  Everything
else here is plain arithmetic on intervals of that form.
"""
from __future__ import annotations

import glob
import os

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(paths[-1])
    out: dict = {"devices": {}, "modules": {}, "host": []}
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    cat = "async" if line.name.startswith("Async") else ""
                    out["devices"].setdefault(dev, []).extend(
                        [e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split(" = ", 1)[0], cat]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    out["modules"][dev] = [
                        [e.start_ns, e.start_ns + e.duration_ns, e.name]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.start_ns,
                                            e.start_ns + e.duration_ns,
                                            e.name[len("bench."):]])
    out["host"].sort()
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of merged intervals ``a`` that merged intervals ``b`` leave."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(tr: dict) -> tuple[float, float]:
    """The traced window: from the first to the last input span the
    benchmark opened while tracing, so it holds whole step periods."""
    starts = [s for s, _, name in tr["host"] if name == "input"]
    if len(starts) < 2:
        raise ValueError("trace holds fewer than two input spans")
    return float(min(starts)), float(max(starts))


def is_collective(op) -> bool:
    return any(c in op[2] for c in COLLECTIVE)


def busy_ns(tr: dict, dev: str, lo: float, hi: float) -> float:
    return covered(union(((s, e) for s, e, *_ in tr["devices"].get(dev, [])),
                         lo, hi))


def step_runs(tr: dict, dev: str, lo: float, hi: float,
              program: str = "train_step") -> list[tuple[float, float]]:
    """Executions of the train step's program that lie wholly in the
    window."""
    return [(s, e) for s, e, name in tr["modules"].get(dev, [])
            if program in name and s >= lo and e <= hi]


def step_busy_ns(tr: dict, dev: str, lo: float, hi: float) -> list[float]:
    """Device busy time of each train-step execution in the window."""
    ops = [(s, e) for s, e, *_ in tr["devices"].get(dev, [])]
    return [covered(union(ops, s, e)) for s, e in step_runs(tr, dev, lo, hi)]


def exposed_collective_ns(tr: dict, dev: str, lo: float, hi: float
                          ) -> list[float]:
    """For each train-step execution in the window: the time collectives
    ran on this chip while no other operation ran on it."""
    ops = tr["devices"].get(dev, [])
    out = []
    for s, e in step_runs(tr, dev, lo, hi):
        coll = union(((a, b) for a, b, *r in ops
                      if is_collective((a, b, *r))), s, e)
        comp = union(((a, b) for a, b, *r in ops
                      if not is_collective((a, b, *r))), s, e)
        out.append(covered(subtract(coll, comp)))
    return out


def idle_gaps(tr: dict, dev: str, lo: float, hi: float
              ) -> list[tuple[str, float]]:
    """Every stretch of the window in which the chip ran nothing, labelled
    by the innermost benchmark span open on the host when it began
    (``loop`` where none was: dispatch, syncs and tracing in the program)."""
    busy = union(((s, e) for s, e, *_ in tr["devices"].get(dev, [])), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    out = []
    for s, e in gaps:
        label = "loop"
        best = None
        for hs, he, name in tr["host"]:
            if hs <= s < he and (best is None or he - hs < best):
                best, label = he - hs, name
        out.append((label, (e - s) / 1e9))
    return out


def top_ops(tr: dict, lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """The device operations that took most time in the window, summed over
    chips and over every run of the same operation."""
    total: dict[str, float] = {}
    for ops in tr["devices"].values():
        for s, e, name, _ in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[name] = total.get(name, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]
