"""Share of the traced window in which no operation ran on a chip, averaged
over the chips the cell uses (device trace)."""
import tracefile


def reduce(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = [tracefile.busy_ns(run.trace, str(d), lo, hi)
            for d in range(run.chips)]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
