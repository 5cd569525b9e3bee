"""DeepSeek-V2-Lite cut to its dense layer and 4 of its 26 MoE layers, 8 of
its 64 routed experts and an eighth of its vocabulary: how the program
builds it, the weights the benchmark gives it, its plain reference and its
FLOPs.

The reference follows DeepSeek-V2 as published (arXiv:2405.04434 and the
model's own code): latent attention without q-LoRA (queries of 128 plain
and 64 rotary dims a head; keys and values up from a 512-wide latent under
an RMSNorm, one 64-wide rotary key shared by the heads; values 128 wide),
YaRN rotary scaling and its softmax scale, a SwiGLU dense layer, then MoE
layers with a float32 softmax router over all 64 experts, a greedy top-6
whose gates are not renormalised, 2 shared experts and the sequence-wise
balance loss; RMSNorm, an untied head.  It is float32 at the highest
matmul precision, imports nothing of the program, and is computed a
sequence at a time.  The held experts are computed densely on every token
and masked by the top-6, where the program sorts the assignments; what the
56 absent experts would add is left out of both.  Two choices of storage
are the program's: a norm's gain is stored as ``w`` and applied as
``1 + w``, and the rotary dims are rotated as two halves where the
published code pairs interleaved dims (a permutation of the weights).
"""
from __future__ import annotations

import json
import math

INPUT = "tokens"


def _dims(cfg):
    return {
        "n": cfg["num_hidden_layers"], "nd": cfg["first_k_dense_replace"],
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
        "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "fd": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
        "e": cfg["published"]["n_routed_experts"],
        "eh": cfg["n_routed_experts"], "off": cfg["expert_offset"],
        "k": cfg["num_experts_per_tok"], "fs": cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"], "v": cfg["vocab_size"]}


def program(cfg):
    """The program's model and optimizer for this configuration."""
    import dataclasses

    from repro.configs.base import Yarn
    from repro.configs.deepseek_v2_lite import CONFIG
    from repro.models.registry import build_model
    from repro.optim.optimizers import adamw

    x = _dims(cfg)
    y = cfg["rope_scaling"]
    model_cfg = dataclasses.replace(
        CONFIG, name=cfg["name"], n_layers=x["n"], d_model=x["d"],
        n_heads=x["h"], n_kv_heads=x["h"], d_ff=x["f"], vocab_size=x["v"],
        rope_theta=float(cfg["rope_theta"]), kv_lora_rank=x["r"],
        qk_nope_head_dim=x["dn"], qk_rope_head_dim=x["dr"],
        v_head_dim=x["dv"],
        rope_yarn=Yarn(factor=float(y["factor"]),
                       original_max_position=y[
                           "original_max_position_embeddings"],
                       beta_fast=float(y["beta_fast"]),
                       beta_slow=float(y["beta_slow"]),
                       mscale=float(y["mscale"]),
                       mscale_all_dim=float(y["mscale_all_dim"])),
        n_experts=x["e"], top_k=x["k"], moe_dropless=True,
        experts_held=x["eh"], expert_offset=x["off"],
        n_shared_experts=cfg["n_shared_experts"],
        aux_loss_coef=cfg["aux_loss_alpha"], first_dense_layers=x["nd"],
        dense_d_ff=x["fd"], tie_embeddings=cfg["tie_word_embeddings"])
    o = cfg["optimizer"]
    return build_model(model_cfg), adamw(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                         weight_decay=o["weight_decay"])


def param_spec(cfg) -> dict:
    """Leaves ("normal", shape, std) with std 1/sqrt(fan-in of the
    contraction); stored gains zero; layers stacked, the dense one apart."""
    x = _dims(cfg)
    d, h, r, dn, dr, dv = x["d"], x["h"], x["r"], x["dn"], x["dr"], x["dv"]
    if cfg["tie_word_embeddings"]:
        raise ValueError("the reference has an untied output head")

    def attn(n):
        return {"wq": ("normal", (n, d, h, dn + dr), d ** -0.5),
                "wkv_a": ("normal", (n, d, r + dr), d ** -0.5),
                "kv_norm": ("zeros", (n, r)),
                "wkv_b": ("normal", (n, r, h, dn + dv), r ** -0.5),
                "wo": ("normal", (n, h, dv, d), (h * dv) ** -0.5)}

    def swiglu(lead, f):
        return {"wi_gate": ("normal", lead + (d, f), d ** -0.5),
                "wi_up": ("normal", lead + (d, f), d ** -0.5),
                "wo": ("normal", lead + (f, d), f ** -0.5)}

    def norms(n):
        return {"ln1": {"scale": ("zeros", (n, d))},
                "ln2": {"scale": ("zeros", (n, d))}}

    nd, nm = x["nd"], x["n"] - x["nd"]
    return {
        "embed": ("normal", (x["v"], d), d ** -0.5),
        "unembed": ("normal", (x["v"], d), d ** -0.5),
        "final_norm": {"scale": ("zeros", (d,))},
        "dense_layers": {**norms(nd), "attn": attn(nd),
                         "mlp": swiglu((nd,), x["fd"])},
        "layers": {**norms(nm), "attn": attn(nm), "moe": {
            "router": ("normal", (nm, d, x["e"]), d ** -0.5),
            **swiglu((nm, x["eh"]), x["f"]),
            "shared": swiglu((nm,), x["fs"])}},
    }


# ---------------------------------------------------------------- FLOPs ---
def _attn_params(x) -> int:
    d, h = x["d"], x["h"]
    return (d * h * (x["dn"] + x["dr"]) + d * (x["r"] + x["dr"])
            + x["r"] * h * (x["dn"] + x["dv"]) + h * x["dv"] * d)


def held_per_token(cfg) -> float:
    """Expected assignments a token sends to the held experts: top-k of
    the published count, over a router that spreads evenly."""
    x = _dims(cfg)
    return x["k"] * x["eh"] / x["e"]


def matmul_params_per_token(cfg) -> float:
    """Matmul parameters a token meets: attention and the dense FFN, the
    router, the shared experts, the expected held experts, the head."""
    x = _dims(cfg)
    d, nd, nm = x["d"], x["nd"], x["n"] - x["nd"]
    dense = _attn_params(x) + 3 * d * x["fd"]
    moe = (_attn_params(x) + d * x["e"] + 3 * d * x["fs"]
           + held_per_token(cfg) * 3 * d * x["f"])
    return nd * dense + nm * moe + x["v"] * d


def _attn_flops(cfg, seq_len: int) -> float:
    """Forward FLOPs of causal attention's two products over a sequence."""
    x = _dims(cfg)
    return (x["n"] * 2 * x["h"] * (x["dn"] + x["dr"] + x["dv"])
            * seq_len * (seq_len + 1) / 2)


def forward_flops(cfg, seq_len: int) -> float:
    return 2.0 * matmul_params_per_token(cfg) * seq_len \
        + _attn_flops(cfg, seq_len)


def flops_per_sample(cfg, seq_len: int) -> float:
    """Forward and backward FLOPs of one sequence (three times the
    forward), with the held experts at their expected load; remat not
    counted."""
    return 3.0 * forward_flops(cfg, seq_len)


# The held experts' three matmuls of one MoE layer run four times a step:
# the forward, its recomputation (the layer scan rematerialises its body)
# and the backward's two products (the input's and the weights').
EXPERT_PASSES = 4


def expert_matmul_flops(cfg, held_rows: float) -> float:
    """Executed FLOPs of one MoE layer's held-expert matmuls in a step, for
    ``held_rows`` assignments to the held experts."""
    x = _dims(cfg)
    return EXPERT_PASSES * 3 * 2.0 * held_rows * x["d"] * x["f"]


def expert_matmul_bytes(cfg, held_rows: float) -> float:
    """Bytes those matmuls must move in bfloat16: each pass of each matmul
    reads its rows and the held weights and writes its rows, and the
    SwiGLU product reads two rows and writes one in each of the three
    passes that form it."""
    x = _dims(cfg)
    d, f, eh = x["d"], x["f"], x["eh"]
    per_pass = 3 * (held_rows * (d + f) + eh * d * f)
    return 2.0 * (EXPERT_PASSES * per_pass + 3 * 3 * held_rows * f)


# ------------------------------------------------------------ reference ---
def _yarn(cfg):
    """YaRN as DeepSeek-V2's code computes it: -> (inverse frequencies,
    cos/sin magnitude, softmax scale)."""
    import numpy as np

    x = _dims(cfg)
    y, base, dim = cfg["rope_scaling"], float(cfg["rope_theta"]), x["dr"]
    f, orig = float(y["factor"]), y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    def mscale(m):
        return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / f
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    mag = mscale(y["mscale"]) / mscale(y["mscale_all_dim"])
    scale = (x["dn"] + x["dr"]) ** -0.5 * mscale(y["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), mag, scale


def _seq_logits_aux(cfg, params, tokens, quant):
    """Logits of one sequence [S] and its balance loss summed over the MoE
    layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    hi = jax.lax.Precision.HIGHEST
    s = tokens.shape[0]
    dn, r, e, eh, k = x["dn"], x["r"], x["e"], x["eh"], x["k"]

    def mm(spec, a, b):
        return jnp.einsum(spec, quant(a), quant(b), precision=hi)

    def rms(v, w):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * (1 + w)

    inv, mag, scale = _yarn(cfg)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(mag * np.cos(ang))[:, None]
    sin = jnp.asarray(mag * np.sin(ang))[:, None]
    half = x["dr"] // 2

    def rope(v):                                   # [S, heads, dr]
        v1, v2 = v[..., :half], v[..., half:]
        return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], -1)

    # Queries in blocks of 512, each block's scores recomputed for the
    # backward pass, so that a 4,096-token sequence fits beside the
    # benchmark's copies of the weights and the optimizer's moments.
    qb = min(s, 512)
    kpos = jnp.arange(s)

    def attention(v, a):
        q = mm("sd,dhk->shk", v, a["wq"])
        kva = mm("sd,dr->sr", v, a["wkv_a"])
        kv = mm("sr,rhk->shk", rms(kva[:, :r], a["kv_norm"]), a["wkv_b"])
        qpe, kpe = rope(q[..., dn:]), rope(kva[:, None, r:])[:, 0]

        @jax.checkpoint
        def block(i):
            def rows(t):
                return jax.lax.dynamic_slice_in_dim(t, i * qb, qb, axis=0)

            sc = (mm("qhk,phk->hqp", rows(q[..., :dn]), kv[..., :dn])
                  + mm("qhk,pk->hqp", rows(qpe), kpe)) * scale
            seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
            pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return mm("hqp,phk->qhk", pr, kv[..., dn:])

        o = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, x["h"],
                                                             x["dv"])
        return mm("shk,hkd->sd", o, a["wo"])

    def swiglu(v, p):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", v, p["wi_gate"]))
                  * mm("sd,df->sf", v, p["wi_up"]), p["wo"])

    def dense_layer(v, p):
        v = v + attention(rms(v, p["ln1"]["scale"]), p["attn"])
        return v + swiglu(rms(v, p["ln2"]["scale"]), p["mlp"]), None

    def moe_layer(carry, p):
        v, aux = carry
        v = v + attention(rms(v, p["ln1"]["scale"]), p["attn"])
        y = rms(v, p["ln2"]["scale"])
        m = p["moe"]
        probs = jax.nn.softmax(mm("sd,de->se", y, m["router"]), axis=-1)
        gate, eid = jax.lax.top_k(probs, k)
        chosen = jax.nn.one_hot(eid, e, dtype=jnp.float32)      # [S,K,E]
        weight = jnp.einsum("sk,ske->se", gate, chosen)[
            :, x["off"]:x["off"] + eh]
        hid = jax.nn.silu(mm("sd,edf->sef", y, m["wi_gate"])) \
            * mm("sd,edf->sef", y, m["wi_up"])
        routed = jnp.einsum("sed,se->sd", mm("sef,efd->sed", hid, m["wo"]),
                            weight, precision=hi)
        frac = chosen.sum((0, 1)) * e / (s * k)
        aux = aux + jnp.sum(frac * probs.mean(0))
        return (v + routed + swiglu(y, m["shared"]), aux), None

    v = params["embed"][tokens]
    v, _ = jax.lax.scan(jax.checkpoint(dense_layer), v,
                        params["dense_layers"])
    (v, aux), _ = jax.lax.scan(jax.checkpoint(moe_layer), (v, 0.0),
                               params["layers"])
    logits = mm("sd,vd->sv", rms(v, params["final_norm"]["scale"]),
                params["unembed"])
    return logits, aux


def _seq_loss_sum(cfg, params, tokens, labels, quant):
    """Summed next-token cross-entropy of one sequence [S], plus the
    sequence's balance loss times its length and the loss's weight."""
    import jax
    import jax.numpy as jnp

    logits, aux = _seq_logits_aux(cfg, params, tokens, quant)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (jnp.sum(lse - gold)
            + cfg["aux_loss_alpha"] * tokens.shape[0] * aux)


_GRAD = {}


def reference_grad(cfg, params, batch, quant):
    """Mean cross-entropy over every token of the batch plus the weighted
    mean balance loss, and its gradient, one sequence at a time.  Each
    sequence's gradient is summed on the host, so that the device holds one
    sequence's gradient beside the weights and the optimizer's moments."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = (quant.__name__, json.dumps(cfg, sort_keys=True))
    if key not in _GRAD:
        _GRAD[key] = jax.jit(jax.value_and_grad(
            lambda p, t, y: _seq_loss_sum(cfg, p, t, y, quant)))
    fn = _GRAD[key]
    tokens, labels = batch["tokens"], batch["labels"]
    total, grads = 0.0, None
    for i in range(tokens.shape[0]):
        s, g = fn(params, jnp.asarray(tokens[i]), jnp.asarray(labels[i]))
        total += float(s)
        g = jax.tree_util.tree_map(np.asarray, g)
        grads = g if grads is None else jax.tree_util.tree_map(
            np.add, grads, g)
    count = tokens.size
    return total / count, jax.tree_util.tree_map(
        lambda g: g / np.float32(count), grads)


def reference_logits(cfg, params, tokens):
    """The reference's logits of one sequence [S], for tests at small
    sizes."""
    return _seq_logits_aux(cfg, params, tokens, lambda a: a)[0]
