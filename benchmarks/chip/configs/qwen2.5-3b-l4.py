"""Qwen2.5-3B cut to 4 of its 36 layers and an eighth of its vocabulary:
how the program builds it, the weights the benchmark gives it, its plain
reference and its FLOPs.

The reference follows the Qwen2 decoder as published (RMSNorm, GQA with
biases on q, k and v, rotary embeddings on the two halves of each head,
SwiGLU, tied embeddings) in float32 at the highest matmul precision, and
imports nothing of the program.  One choice of storage is the program's:
a norm's gain is stored as ``w`` and applied as ``1 + w``.
"""
from __future__ import annotations

import json

INPUT = "tokens"


def program(cfg):
    """The program's model and optimizer for this configuration."""
    import dataclasses

    from repro.configs.qwen25_3b import CONFIG
    from repro.models.registry import build_model
    from repro.optim.optimizers import adamw

    model_cfg = dataclasses.replace(
        CONFIG, name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])
    o = cfg["optimizer"]
    return build_model(model_cfg), adamw(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                         weight_decay=o["weight_decay"])


def _dims(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def param_spec(cfg) -> dict:
    """Leaves ("normal", shape, std) with std 1/sqrt(fan-in of the
    contraction); biases and stored gains zero; layers stacked."""
    n, d, h, hk, dh, f, v = _dims(cfg)
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the reference ties the output head to the embedding")
    return {
        "embed": ("normal", (v, d), d ** -0.5),
        "final_norm": {"scale": ("zeros", (d,))},
        "layers": {
            "ln1": {"scale": ("zeros", (n, d))},
            "ln2": {"scale": ("zeros", (n, d))},
            "attn": {"wq": ("normal", (n, d, h, dh), d ** -0.5),
                     "wk": ("normal", (n, d, hk, dh), d ** -0.5),
                     "wv": ("normal", (n, d, hk, dh), d ** -0.5),
                     "wo": ("normal", (n, h, dh, d), (h * dh) ** -0.5),
                     "bq": ("zeros", (n, h, dh)),
                     "bk": ("zeros", (n, hk, dh)),
                     "bv": ("zeros", (n, hk, dh))},
            "mlp": {"wi_gate": ("normal", (n, d, f), d ** -0.5),
                    "wi_up": ("normal", (n, d, f), d ** -0.5),
                    "wo": ("normal", (n, f, d), f ** -0.5)},
        },
    }


def matmul_params(cfg) -> int:
    n, d, h, hk, dh, f, v = _dims(cfg)
    per_layer = d * h * dh * 2 + d * hk * dh * 2 + 3 * d * f
    return n * per_layer + v * d


def flops_per_sample(cfg, seq_len: int) -> float:
    """Forward and backward FLOPs of one sequence: 6 per matmul parameter
    per token (the tied head included), and causal attention's two
    products over the keys each query sees, times three."""
    n, d, h, hk, dh, f, v = _dims(cfg)
    attn = n * 3 * 2 * 2 * h * dh * seq_len * (seq_len + 1) / 2
    return 6.0 * matmul_params(cfg) * seq_len + attn


def forward_flops(cfg, seq_len: int) -> float:
    n, d, h, hk, dh, f, v = _dims(cfg)
    attn = n * 2 * 2 * h * dh * seq_len * (seq_len + 1) / 2
    return 2.0 * matmul_params(cfg) * seq_len + attn


# ------------------------------------------------------------ reference ---
def _seq_loss_sum(cfg, params, tokens, labels, quant):
    """Summed next-token cross-entropy of one sequence [S]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, d, h, hk, dh, f, v = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hi = jax.lax.Precision.HIGHEST
    s = tokens.shape[0]

    def mm(spec, a, b):
        return jnp.einsum(spec, quant(a), quant(b), precision=hi)

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)

    half = dh // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.asarray(np.cos(ang))[:, None], jnp.asarray(np.sin(ang))[:, None]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((s, s), bool))
    group = jnp.arange(h) * hk // h

    def layer(x, p):
        a = p["attn"]
        y = rms(x, p["ln1"]["scale"])
        q = rope(mm("sd,dhk->shk", y, a["wq"]) + a["bq"])
        k = rope(mm("sd,dhk->shk", y, a["wk"]) + a["bk"])[:, group]
        val = (mm("sd,dhk->shk", y, a["wv"]) + a["bv"])[:, group]
        sc = mm("qhk,phk->hqp", q, k) / np.sqrt(dh)
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = mm("hqp,phk->qhk", pr, val)
        x = x + mm("shk,hkd->sd", o, a["wo"])
        y = rms(x, p["ln2"]["scale"])
        m = p["mlp"]
        g = jax.nn.silu(mm("sd,df->sf", y, m["wi_gate"])) \
            * mm("sd,df->sf", y, m["wi_up"])
        return x + mm("sf,fd->sd", g, m["wo"]), None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rms(x, params["final_norm"]["scale"])
    logits = mm("sd,vd->sv", x, params["embed"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


_GRAD = {}


def reference_grad(cfg, params, batch, quant):
    """Mean cross-entropy over every token of the batch and its gradient,
    one sequence at a time so that any batch fits."""
    import jax
    import jax.numpy as jnp

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
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    count = tokens.size
    return total / count, jax.tree_util.tree_map(lambda g: g / count, grads)
