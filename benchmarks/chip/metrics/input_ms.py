"""Host time per step inside the injected data source's ``batch`` call, over
the window (the harness's own span, host clock)."""


def reduce(run):
    lo, hi = run.window
    spans = run.spans.of("input", lo, hi)
    if not spans:
        return None
    return 1e3 * sum(e[2] - e[1] for e in spans) / len(spans)
