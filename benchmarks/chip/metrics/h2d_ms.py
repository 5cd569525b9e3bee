"""Host ms per step in the elastic loop's copy of the batch to the device
(``elastic.h2d``: ``jax.device_put`` of the batch), over the traced window
(the program's span, host clock)."""
import programspans


def reduce(run):
    return programspans.per_window_step_ms(run, "elastic.h2d")
