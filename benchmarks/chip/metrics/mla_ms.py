"""Device ms a run of the train step spends in latent attention: the
operations under the name scope ``mla`` (projections, YaRN rotation,
latent up-projection, attention), in the forward pass, its recomputation
and the backward, on chip 0 (device trace)."""
import scopes


def reduce(run):
    return scopes.per_step_ms(run, ("mla",))
