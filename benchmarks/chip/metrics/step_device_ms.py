"""Device busy time of one run of the jitted train step, averaged over the
runs that lie wholly in the traced window, on chip 0 (device trace)."""
import tracefile


def reduce(run):
    if run.trace is None:
        return None
    per_step = tracefile.step_busy_ns(run.trace, "0", *run.trace_window)
    if not per_step:
        return None
    return sum(per_step) / len(per_step) / 1e6
