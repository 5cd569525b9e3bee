"""The program's own spans in a traced run, and the numbers read from them.

The elastic loop (``src/repro/core/elastic.py``) marks each part of a
segment with a ``jax.profiler`` annotation named ``elastic.<part>``: the
segment, its set-up (``init_state``, ``restore``, ``place``,
``first_step``), each step (``step``, numbered by the global step) and the
step's parts (``input``, ``h2d``, ``dispatch``, ``loss_sync`` at log
steps), ``drain`` and ``save``.  They share the profiler's clock with the
device's operations and with the benchmark's ``bench.*`` spans.

A traced run's plain trace (``tracefile.load``) holds only the benchmark's
spans; ``spans_of`` reads the program's from the same profile into the
trace's ``"program"`` key, in the form ``[[start_ns, end_ns, name], ...]``
(the form a recorded trace under ``testdata/`` keeps them in).  A program
that carries no such spans gives an empty list, and every reader here then
returns None.
"""
from __future__ import annotations

import glob
import os

import tracefile

PREFIX = "elastic."


def load(trace_dir: str) -> list[list]:
    """The ``elastic.*`` host events of the profile under ``trace_dir``,
    sorted by start."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend([e.start_ns, e.start_ns + e.duration_ns, e.name]
                       for e in line.events if e.name.startswith(PREFIX))
    out.sort()
    return out


def spans_of(run) -> list[list] | None:
    """The program's spans in a traced run, or None where it was not traced
    or the program carries none.  A recorded trace keeps them under
    ``"program"``; otherwise they are read once from the profile the
    harness wrote in its work directory, which lives until the run ends."""
    if run.trace is None:
        return None
    if "program" not in run.trace:
        import harness

        run.trace["program"] = load(os.path.join(harness.CHECKOUT,
                                                 ".bench_work", "trace"))
    return run.trace["program"] or None


def program_spans(tr: dict, name: str, lo: float, hi: float
                  ) -> list[tuple[float, float]]:
    """The spans ``name`` (``elastic.<part>``) that start in [lo, hi)."""
    return [(s, e) for s, e, n in tr.get("program", [])
            if n == name and lo <= s < hi]


def window_steps(tr: dict, lo: float, hi: float) -> int:
    """Steps whose input began in [lo, hi): the steps ``mfu`` counts."""
    return sum(1 for s, _, n in tr["host"] if n == "input" and lo <= s < hi)


def self_ns(tr: dict, lo: float, hi: float) -> list[float]:
    """For each ``elastic.step`` span wholly in [lo, hi]: its duration less
    the union of the ``elastic.*`` spans inside it, the loop's own time."""
    prog = tr.get("program", [])
    out = []
    for s, e, n in prog:
        if n != PREFIX + "step" or s < lo or e > hi:
            continue
        kids = [(a, b) for a, b, m in prog
                if m != n and s <= a and b <= e]
        out.append((e - s) - tracefile.covered(tracefile.union(kids, s, e)))
    return out


def _pieces(spans, lo: float, hi: float):
    """[lo, hi) cut where a span opens or closes, each piece labelled by the
    innermost (shortest) span open in it, or None."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    spans = sorted(spans)
    out, open_, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= a:
            open_.append(spans[j])
            j += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        label = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ \
            else None
        out.append((a, b, label))
    return out


def idle_by_span(tr: dict, dev: str, lo: float, hi: float
                 ) -> dict[str | None, float] | None:
    """The chip's idle time in [lo, hi), in ns, split by the innermost
    ``bench.*`` or ``elastic.*`` span open on the host at each instant
    (None: no span open).  None where the trace holds no program spans."""
    if not tr.get("program"):
        return None
    spans = [(s, e, "bench." + n) for s, e, n in tr["host"]] + \
        [(s, e, n) for s, e, n in tr["program"]]
    busy = tracefile.union(((s, e) for s, e, *_ in tr["devices"].get(dev, [])),
                           lo, hi)
    idle = tracefile.subtract([(lo, hi)], busy)
    pieces = _pieces(spans, lo, hi)
    out: dict[str | None, float] = {}
    k = 0
    for s, e in idle:
        while pieces[k][1] <= s:
            k += 1
        i = k
        while i < len(pieces) and pieces[i][0] < e:
            a, b, label = pieces[i]
            out[label] = out.get(label, 0.0) + min(b, e) - max(a, s)
            i += 1
    return out


def per_window_step_ms(run, name: str) -> float | None:
    """Host ms per window step inside the spans ``name`` that start in the
    traced window."""
    if spans_of(run) is None:
        return None
    lo, hi = run.trace_window
    n = window_steps(run.trace, lo, hi)
    if not n:
        return None
    return sum(e - s for s, e in program_spans(run.trace, name, lo, hi)) \
        / n / 1e6
