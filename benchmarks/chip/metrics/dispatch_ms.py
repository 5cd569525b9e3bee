"""Host ms per step in the call of the jitted train step until it returns
(``elastic.dispatch``), over the traced window (the program's span, host
clock).  It grows where the host waits on the device's queue.

The profiler's own cost falls mostly here: on ResNet-110 at w = 1 (TPU
v5e) a traced step's dispatch takes about 17 ms against 4.6 ms with the
profiler off.  Compare it only with other traced runs."""
import programspans


def reduce(run):
    return programspans.per_window_step_ms(run, "elastic.dispatch")
