"""Per run of the train step, the time collectives ran on chip 0 while no
other operation ran there: the gradient exchange the step could not hide
(device trace)."""
import tracefile


def reduce(run):
    if run.trace is None:
        return None
    per_step = tracefile.exposed_collective_ns(run.trace, "0",
                                               *run.trace_window)
    if not per_step:
        return None
    return sum(per_step) / len(per_step) / 1e6
