"""The held experts' matmuls against their roofline at the expected load:
the least time the chip could take for the FLOPs and bytes those matmuls
would execute in a step if routing were even, the larger of FLOPs over the
peak bf16 rate and bytes over the HBM bandwidth (``peaks.json``), over the
device time of the operations under the name scope ``moe.experts`` (device
trace, chip 0), in %.

FLOPs and bytes come from the configuration's shapes
(``expert_matmul_flops``, ``expert_matmul_bytes``): every MoE layer's three
matmuls at the held assignments a step is expected to bring (tokens times
top-k times held over routed experts), in the forward pass, its
recomputation and the backward's two products.  It is not the measured
load: the step's own count, the counter ``moe.assignments_held``, is in
the trainer's registry, which a run's result does not carry, and routing
that sends the held experts more or fewer than the expected share moves
the work but not this count.  The scope's time also holds the SwiGLU
product and the masks over the whole static buffer, so what the buffer's
padding costs shows here as a lower share."""
import scopes

NAME = "moe_experts_expected_roofline"


def reduce(run):
    ms = scopes.per_step_ms(run, ("moe.experts",))
    if not ms or "bf16_flops" not in run.peak:
        return None
    cfg, mod = scopes.cell_config(NAME)
    tr = run.traffic
    tokens = tr["m"] * tr["segments"][0]["w"] * tr["seq_len"]
    rows = tokens * mod.held_per_token(cfg)
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least_s = layers * max(
        mod.expert_matmul_flops(cfg, rows) / run.peak["bf16_flops"],
        mod.expert_matmul_bytes(cfg, rows) / run.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
