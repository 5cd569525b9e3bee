"""Seconds from a fresh segment's start to its first step being ready, as
the elastic loop records it (``SegmentRecord.first_step_seconds``): the
fresh step's trace, the compile cache's load, and the first step."""


def reduce(run):
    if not run.restarts or not run.segments:
        return None
    vals = [s["first_step_s"] for s in run.segments]
    return sum(vals) / len(vals)
