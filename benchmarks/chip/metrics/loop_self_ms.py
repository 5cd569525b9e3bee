"""The elastic loop's own host ms a step: each ``elastic.step`` span wholly
in the traced window less the ``elastic.*`` spans inside it, averaged
(the program's spans, host clock).  Small where the named parts explain
the host's step."""
import programspans


def reduce(run):
    if programspans.spans_of(run) is None:
        return None
    per_step = programspans.self_ns(run.trace, *run.trace_window)
    if not per_step:
        return None
    return sum(per_step) / len(per_step) / 1e6
