"""Device ms a run of the train step spends taking tokens to the held
experts and back: the operations under the name scopes ``moe.route`` (the
float32 router, its top-k and balance loss), ``moe.dispatch`` (the sort of
the assignments and the gather into the experts' buffer) and
``moe.combine`` (the gather back and the gates' sum), in the forward pass,
its recomputation and the backward, on chip 0 (device trace)."""
import scopes


def reduce(run):
    return scopes.per_step_ms(run, ("moe.route", "moe.dispatch",
                                    "moe.combine"))
