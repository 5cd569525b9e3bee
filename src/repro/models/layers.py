"""Shared neural-net layers (pure JAX, bf16 compute / f32 params).

Attention is *query-chunked* (flash-style at the XLA level): scores are only
ever materialized for one query block at a time, so 32k prefill never builds
an S x S tensor.  This XLA path is what every model runs and what the
dry-run lowers.  The Pallas kernel in ``repro.kernels.swa_attention``
implements the same block for the TPU but is not wired into it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.sharding.rules import ShardingRules, default_rules

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Unroll mode: XLA's cost_analysis counts while-loop bodies ONCE, so the
# dry-run lowers small *unrolled* depth variants to measure true per-layer
# flops/bytes/collective deltas (launch/dryrun.py).  Production path always
# scans (compile-time hygiene).
_UNROLL = False


@contextlib.contextmanager
def unroll_mode(on: bool = True):
    global _UNROLL
    prev = _UNROLL
    _UNROLL = on
    try:
        yield
    finally:
        _UNROLL = prev


def unrolled() -> bool:
    return _UNROLL


def remat_policy():
    """Activation-checkpoint policy for the layer scan.

    REPRO_REMAT_POLICY=full (default): save nothing, recompute everything —
    minimal memory.  =dots: keep matmul outputs (no recompute of the MXU
    work) — the compute-vs-memory knob exercised in EXPERIMENTS.md §Perf.
    """
    name = os.environ.get("REPRO_REMAT_POLICY", "full")
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def scan_layers(body, carry, xs, checkpoint_body: bool = True):
    """lax.scan over stacked layer params, or a python loop in unroll mode.

    Returns (carry, ys) where ys leaves are stacked along axis 0 (or None).
    """
    body_fn = (jax.checkpoint(body, policy=remat_policy())
               if checkpoint_body else body)
    if not _UNROLL:
        return jax.lax.scan(body_fn, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body_fn(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ys)
    else:
        ys = None
    return carry, ys


class Sharder:
    """Threads ``with_sharding_constraint`` hints through model code.

    Outside a mesh (CPU smoke tests) it is a no-op, so the same model code
    serves 1-device tests and 512-device dry-runs.
    """

    def __init__(self, mesh: Mesh | None = None,
                 rules: ShardingRules | None = None):
        self.mesh = mesh
        self.rules = rules or default_rules()

    def __call__(self, x, *axes):
        if self.mesh is None:
            return x
        spec = self.rules.spec_for(axes, x.shape, self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


NO_SHARD = Sharder()


# ---------------------------------------------------------------- norms ----
def rmsnorm(x, w, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x, w, b, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def apply_norm(cfg, x, p, prefix=""):
    if cfg.norm == "layernorm":
        return layernorm(x, p[prefix + "scale"], p[prefix + "bias"])
    return rmsnorm(x, p[prefix + "scale"])


# ----------------------------------------------------------------- rope ----
def rope_freqs(d_half: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, d_half, dtype=np.float32) / d_half))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(d_half: int, theta: float, yarn) -> np.ndarray:
    """YaRN's inverse frequencies (DeepSeek-V2's published rotary code):
    frequencies that turn fewer than ``beta_slow`` times over the original
    context are divided by ``factor``, those that turn more than
    ``beta_fast`` times are kept, and a linear ramp blends between."""
    dim = 2 * d_half

    def turns_dim(turns):
        return (dim * math.log(yarn.original_max_position
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(d_half, dtype=np.float32) - lo) / (hi - lo),
                   0, 1)
    extra = rope_freqs(d_half, theta)
    return (extra / yarn.factor * ramp + extra * (1 - ramp)).astype(
        np.float32)


def apply_rope(x, positions, theta: float, sections: tuple[int, ...] | None = None,
               freqs=None, mag: float = 1.0):
    """Rotate ``x [..., S, H, D]`` by ``positions``.

    positions: ``[B, S]`` int for standard RoPE, or ``[B, S, 3]`` for M-RoPE
    with ``sections`` (t, h, w) splitting the half-dim (Qwen2-VL style).
    ``freqs`` replaces the inverse frequencies of ``theta`` (YaRN), and
    ``mag`` scales the cosines and sines.
    """
    d = x.shape[-1]
    d_half = d // 2
    if freqs is None:
        freqs = rope_freqs(d_half, theta)
    freqs = jnp.asarray(freqs)                                # [d_half]
    if sections is None:
        ang = positions[..., None].astype(jnp.float32) * freqs  # [B,S,d_half]
    else:
        assert positions.ndim == 3 and sum(sections) == d_half
        parts, off = [], 0
        for i, sec in enumerate(sections):
            parts.append(positions[..., i, None].astype(jnp.float32)
                         * freqs[off:off + sec])
            off += sec
        ang = jnp.concatenate(parts, axis=-1)                 # [B,S,d_half]
    cos = mag * jnp.cos(ang)[:, :, None, :]                   # [B,S,1,d_half]
    sin = mag * jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d_half], x[..., d_half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention ----
def repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def chunked_attention(q, k, v, *, causal: bool = True,
                      window: int | None = None, q_chunk: int = 1024,
                      q_offset: int = 0, scale: float | None = None):
    """softmax(QK^T * scale) V without materializing [S, S].

    q, k: [B, Sq|Sk, H, D]; v: [B, Sk, H, Dv] (KV already GQA-repeated;
    Dv may differ from D).  ``scale`` defaults to 1/sqrt(D).
    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    ``window``: sliding-window size (key j visible to query i iff
    i - window < j <= i).
    """
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    if _UNROLL:
        # cost-measurement mode (dry-run depth variants): chunking does not
        # change flop/byte totals, so use one full-width chunk — the
        # unrolled-chunk HLO otherwise makes XLA's compile time explode.
        q_chunk = sq
    q_chunk = min(q_chunk, sq)
    n_chunks = -(-sq // q_chunk)
    pad = n_chunks * q_chunk - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = q.reshape(b, n_chunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)

    kpos = jnp.arange(sk)

    @jax.checkpoint  # recompute scores in backward: never store [S, S]
    def one_chunk(ci, qc):
        # qc: [B, Qc, H, D]
        qpos = q_offset + ci * q_chunk + jnp.arange(q_chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((q_chunk, sk), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    if n_chunks == 1:
        out = one_chunk(0, qs[0])
    else:
        if _UNROLL:
            out = jnp.stack([one_chunk(i, qs[i]) for i in range(n_chunks)])
        else:
            out = jax.lax.map(lambda args: one_chunk(args[0], args[1]),
                              (jnp.arange(n_chunks), qs))
        out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * q_chunk, h,
                                                   dv)
        out = out[:, :sq] if pad else out
    return out


def decode_attention(q, k_cache, v_cache, pos, *, window: int | None = None,
                     repeated: bool = False, scale: float | None = None):
    """One-token attention against a cache.

    q: [B, 1, H, D]; caches: [B, S, Hkv, D|Dv] (GQA-repeated already iff
    ``repeated``); pos: [B] int32 — number of valid tokens already in the
    cache (the new token occupies slot ``pos``).  ``scale`` defaults to
    1/sqrt(D).
    """
    b, s, hkv, d = k_cache.shape
    h = q.shape[2]
    if repeated:
        k, v = k_cache, v_cache
    else:
        k = repeat_kv(k_cache, h // hkv)
        v = repeat_kv(v_cache, h // hkv)
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale  # [B,H,1,S]
    kpos = jnp.arange(s)[None, :]                        # [1,S]
    valid = kpos <= pos[:, None]
    if window is not None:
        valid &= kpos > (pos[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------- mlps -----
def mlp(cfg, p, x):
    """Gated (silu/geglu) or plain (gelu) MLP from a layer param dict."""
    if cfg.activation in ("silu", "geglu"):
        act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
        g = act(jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype)))
        u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
        return jnp.einsum("bsf,fd->bsd", g * u, p["wo"].astype(x.dtype))
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
                    + p["wi_bias"].astype(x.dtype))
    return (jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
            + p["wo_bias"].astype(x.dtype))


# ----------------------------------------------------------- embeddings ----
def embed_tokens(embedding, tokens, scale: float | None = None):
    x = jnp.take(embedding, tokens, axis=0).astype(jnp.bfloat16)
    if scale is not None:
        x = x * jnp.asarray(scale, dtype=x.dtype)
    return x


def lm_logits(x, out_embedding):
    """x [B,S,D] @ [V,D]^T -> [B,S,V] in f32."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                      out_embedding.astype(jnp.float32))


def softmax_cross_entropy(logits, labels, mask=None):
    """Mean CE over valid positions; logits [B,S,V] f32, labels [B,S]."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
