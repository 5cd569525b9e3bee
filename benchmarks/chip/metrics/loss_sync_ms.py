"""Host ms per step in the elastic loop's reads of the loss at log steps
(``elastic.loss_sync``: ``float(loss)``, which waits for the step), over the
traced window (the program's span, host clock).  0 where no log step falls
in the window, as in the traced steps of ``qwen2.5-3b-l4.w1.train``, which
is why that cell does not list it."""
import programspans


def reduce(run):
    return programspans.per_window_step_ms(run, "elastic.loss_sync")
