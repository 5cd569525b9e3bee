"""Seconds per call of the injected checkpoint store's ``restore`` in the
window (the harness's own span, host clock)."""


def reduce(run):
    spans = run.spans.of("restore", *run.window)
    if not run.restarts or not spans:
        return None
    return sum(e[2] - e[1] for e in spans) / len(spans)
