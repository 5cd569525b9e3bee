"""Mixture-of-Experts FFNs: a capacity dispatch (``moe_ffn``) and a
drop-free expert share (``moe_dropless``).

Capacity dispatch, group-local and sort-based.

GShard-style semantics: each batch row is a dispatch *group* with capacity
C = ceil(S * top_k * cf / E).  Within a group, token->expert assignments are
sorted (a local [S*K] sort — never a cross-shard global sort) and gathered
into a static [B, E, C, D] buffer.  FLOPs stay proportional to *active*
experts, and the [B,S,.] -> [B,E,C,.] resharding (batch on ``data``, experts
on ``model``) is exactly the expert-parallel dispatch all-to-all, inserted
by GSPMD at the sharding constraint.  Avoids both the O(T*E*C) one-hot mask
(OOM at 128 experts x 1M tokens) and global sorts.  Router load-balance aux
loss follows Switch Transformer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import mlp
from repro.models.spec import TensorSpec as TS


def moe_specs(cfg: ModelConfig, n: int) -> dict:
    Lx, D, F, E = n, cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": TS((Lx, D, E), ("layers", "embed", None)),
        "wi_gate": TS((Lx, E, D, F), ("layers", "experts", "embed", "mlp")),
        "wi_up": TS((Lx, E, D, F), ("layers", "experts", "embed", "mlp")),
        "wo": TS((Lx, E, F, D), ("layers", "experts", "mlp", "embed")),
    }


def expert_only_specs(param_specs: dict):
    """Subtree of per-expert weights (for active-param accounting)."""
    out = {}

    def walk(tree, path):
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + (k,))
        else:
            if "experts" in (tree.axes or ()):
                out["/".join(path)] = tree

    walk(param_specs, ())
    return out


def group_capacity(group_tokens: int, cfg: ModelConfig) -> int:
    c = int(group_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # >=8, rounded up to 8


def moe_ffn(cfg: ModelConfig, p, x, sh):
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    dt = x.dtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    SK = S * K
    C = group_capacity(S, cfg)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(dt)
                        ).astype(jnp.float32)                        # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, K)                              # [B,S,K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance loss.
    frac = jnp.mean(jax.nn.one_hot(eid[..., 0], E, dtype=jnp.float32),
                    axis=(0, 1))
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

    # ---- group-local sorted dispatch ------------------------------------
    flat_e = eid.reshape(B, SK)
    order = jnp.argsort(flat_e, axis=-1, stable=True)                # [B,SK]
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    # per-group expert boundaries via batched searchsorted
    bounds = jax.vmap(lambda row: jnp.searchsorted(
        row, jnp.arange(E + 1), side="left"))(sorted_e)              # [B,E+1]
    counts = bounds[:, 1:] - bounds[:, :-1]                          # [B,E]
    offsets = bounds[:, :-1]
    slot = offsets[:, :, None] + jnp.arange(C)[None, None, :]        # [B,E,C]
    valid = jnp.arange(C)[None, None, :] < counts[:, :, None]
    slot = jnp.clip(slot, 0, SK - 1)
    src = jnp.take_along_axis(order, slot.reshape(B, E * C), axis=-1)
    src_tok = src // K                                               # [B,E*C]

    gx = jnp.take_along_axis(x, src_tok[..., None], axis=1)          # [B,EC,D]
    gx = gx.reshape(B, E, C, D) * valid[..., None].astype(dt)
    gx = sh(gx, "batch", "experts", "capacity", "embed")  # dispatch a2a

    # ---- expert FFN (gated silu) ----------------------------------------
    g = jax.nn.silu(jnp.einsum("becd,edf->becf", gx, p["wi_gate"].astype(dt)))
    u = jnp.einsum("becd,edf->becf", gx, p["wi_up"].astype(dt))
    eo = jnp.einsum("becf,efd->becd", g * u, p["wo"].astype(dt))     # [B,E,C,D]
    eo = sh(eo, "batch", "experts", "capacity", "embed")

    # ---- combine (gather-based: NO scatter) ------------------------------
    # Each token GATHERS its k expert outputs via the inverse sort
    # permutation.  A scatter-add combine forces GSPMD to replicate the
    # [B,S,D] f32 output across the data axis (8.6 GB all-reduces per layer
    # at train_4k); the gather keeps every index batch-local and everything
    # batch-sharded (EXPERIMENTS.md §Perf, pair B).
    inv = jnp.argsort(order, axis=-1)                     # rank of asgn i
    slot = inv - jnp.take_along_axis(offsets, flat_e, axis=-1)   # [B,SK]
    live = slot < C                                        # dropped if over
    slot = jnp.clip(slot, 0, C - 1)
    idx = flat_e * C + slot                                # [B,SK] into E*C
    eo_flat = eo.reshape(B, E * C, D)
    gathered = jnp.take_along_axis(eo_flat, idx[..., None], axis=1)  # [B,SK,D]
    w = (gate.reshape(B, SK) * live.astype(jnp.float32)).astype(dt)
    out = (gathered * w[..., None]).reshape(B, S, K, D).sum(axis=2)
    return out.astype(dt), aux


# --------------------------------------------------------------------------
# Drop-free expert share (DeepSeek-V2 MoE; model-configs guide §4).
#
# The layer is told which experts it holds: ``experts_held`` of the
# ``n_experts``, from ``expert_offset`` on (0 held: all).  Every token is
# routed over all experts (a float32 softmax and a greedy top-k), and every
# assignment that lands on a held expert is computed, however uneven the
# routing, so no token is dropped.  The held assignments, sorted by expert,
# run as one grouped matmul (``jax.lax.ragged_dot``) whose groups are the
# experts' loads, so the matmuls' work follows the tokens routed here.  The
# buffer is static: tokens × top_k rows, the most that can land here, of
# which the rows past the held assignments are outside every group.  Its
# price is the gathers, masks and activations over the whole buffer, a
# memory pass of tokens × top_k × d_model elements each, whatever the load.
# The shared experts run on every token once, and the sequence-wise
# balance loss is over the full router.  On one chip the share runs without
# its exchange: what the absent experts would add is left out.


def dropless_specs(cfg: ModelConfig, n: int) -> dict:
    Lx, D, F, E = n, cfg.d_model, cfg.d_ff, cfg.n_experts
    held = cfg.experts_held or E
    s = {
        "router": TS((Lx, D, E), ("layers", "embed", None)),
        "wi_gate": TS((Lx, held, D, F), ("layers", "experts", "embed", "mlp")),
        "wi_up": TS((Lx, held, D, F), ("layers", "experts", "embed", "mlp")),
        "wo": TS((Lx, held, F, D), ("layers", "experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        s["shared"] = {
            "wi_gate": TS((Lx, D, Fs), ("layers", "embed", "mlp")),
            "wi_up": TS((Lx, D, Fs), ("layers", "embed", "mlp")),
            "wo": TS((Lx, Fs, D), ("layers", "mlp", "embed"))}
    return s


@jax.custom_vjp
def _permute(x, idx, inv):
    """x[idx] for a permutation idx of x's rows, whose inverse is inv: the
    backward pass gathers by inv where a plain gather's would scatter."""
    return x.at[idx].get(mode="promise_in_bounds")


def _permute_fwd(x, idx, inv):
    return _permute(x, idx, inv), inv


def _permute_bwd(inv, g):
    return g.at[inv].get(mode="promise_in_bounds"), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def route(cfg: ModelConfig, router, x):
    """Float32 softmax over all experts and a greedy top-k.  x [B, S, D] ->
    (gates [B, S, K], expert ids [B, S, K], sequence-wise balance loss)."""
    E, K = cfg.n_experts, cfg.top_k
    S = x.shape[1]
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, K)   # not renormalised, as published
    # DeepSeek-V2's sequence-wise balance loss: each expert's share of a
    # sequence's assignments (times E / K) against its mean probability.
    picked = jnp.sum(jax.nn.one_hot(eid, E, dtype=jnp.float32), axis=(1, 2))
    frac = picked * (E / (S * K))                                    # [B,E]
    aux = jnp.mean(jnp.sum(frac * jnp.mean(probs, axis=1), axis=-1))
    return gate, eid, aux


def moe_dropless(cfg: ModelConfig, p, x):
    """x [B, S, D] -> (out [B, S, D], balance loss, held expert loads
    [experts_held] int32)."""
    dt = x.dtype
    B, S, D = x.shape
    T, K = B * S, cfg.top_k
    held = cfg.experts_held or cfg.n_experts
    with jax.named_scope("moe.route"):
        gate, eid, aux = route(cfg, p["router"], x)
    with jax.named_scope("moe.dispatch"):
        local = eid.reshape(T * K) - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        loads = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)                           # [held]
        order = jnp.argsort(key, stable=True)  # held ones first, by expert
        inv = jnp.argsort(order)
        live = (jnp.arange(T * K) < jnp.sum(loads))[:, None]
        xk = jnp.repeat(x.reshape(T, D), K, axis=0)                # [T*K,D]
        rows = jnp.where(live, _permute(xk, order, inv), 0)
    with jax.named_scope("moe.experts"):
        g = jax.lax.ragged_dot(rows, p["wi_gate"].astype(dt), loads)
        u = jax.lax.ragged_dot(rows, p["wi_up"].astype(dt), loads)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["wo"].astype(dt), loads)
        # Rows outside every group are not written by every backend.
        y = jnp.where(live, y, 0)
    with jax.named_scope("moe.combine"):
        ya = _permute(y, inv, order).reshape(T, K, D)
        w = jnp.where(mine, gate.reshape(T * K), 0).astype(dt)
        out = jnp.einsum("tkd,tk->td", ya, w.reshape(T, K))
    out = out.reshape(B, S, D)
    if "shared" in p:
        out = out + mlp(cfg, p["shared"], x)
    return out, aux, loads
