"""Model FLOP/s utilization over the traced window: the forward and
backward FLOPs of the samples whose steps began in it, counted from the
model's shapes (recomputation not counted), over the window, the chips and
the chip's peak bf16 rate."""


def reduce(run):
    if run.trace is None or "bf16_flops" not in run.peak:
        return None
    starts = sorted(s for s, _, name in run.trace["host"] if name == "input")
    if len(starts) < 2 or len(run.traffic["segments"]) != 1:
        return None
    lo, hi = run.trace_window
    rows = run.traffic["m"] * run.traffic["segments"][0]["w"]
    flops = (len(starts) - 1) * rows * run.flops_per_sample
    return 100.0 * flops / ((hi - lo) / 1e9) / (run.chips
                                                 * run.peak["bf16_flops"])
