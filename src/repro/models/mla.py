"""Multi-head latent attention (MLA, DeepSeek-V2 §2.1), without q-LoRA.

Queries come straight from ``wq``: per head ``qk_nope_head_dim`` plain
dims and ``qk_rope_head_dim`` rotary ones.  Keys and values come up from a
``kv_lora_rank``-wide latent: ``wkv_a`` maps the hidden state to the latent
and to one rotary key shared by every head, an RMSNorm is applied on the
latent, and ``wkv_b`` maps it to each head's plain key and its value
(``v_head_dim`` wide, which may differ from the key's width).  Rotary
positions use YaRN (``rope_yarn``, required), and the softmax scale is
``qk_head_dim ** -0.5`` times YaRN's ``mscale`` squared, as published.
The rotary dims are rotated as two halves, where the published code pairs
interleaved dims: a fixed permutation of the weights' columns.

Decoding keeps the latent and the rotary key, ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer, and up-projects the cache at
each step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.spec import TensorSpec as TS


def mla_specs(cfg: ModelConfig, n: int) -> dict:
    Lx, D, H, R = n, cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": TS((Lx, D, H, Dn + Dr), ("layers", "embed", "heads", "head_dim")),
        "wkv_a": TS((Lx, D, R + Dr), ("layers", "embed", None)),
        "kv_norm": TS((Lx, R), ("layers", None), init="zeros"),
        "wkv_b": TS((Lx, R, H, Dn + Dv), ("layers", None, "heads", "head_dim")),
        "wo": TS((Lx, H, Dv, D), ("layers", "heads", "head_dim", "embed")),
    }


def rope_tables(cfg: ModelConfig):
    """-> (inverse frequencies, cos/sin magnitude, softmax scale)."""
    d_half = cfg.qk_rope_head_dim // 2
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_yarn
    mag = (L.yarn_mscale(y.factor, y.mscale)
           / L.yarn_mscale(y.factor, y.mscale_all_dim))
    scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return L.yarn_freqs(d_half, cfg.rope_theta, y), mag, scale


def cache_specs(cfg: ModelConfig, n: int, batch: int, seq: int,
                dtype) -> dict:
    axes = ("layers", "batch", "cache_seq", None)
    return {"ckv": TS((n, batch, seq, cfg.kv_lora_rank), axes, dtype=dtype,
                      init="zeros"),
            "kpe": TS((n, batch, seq, cfg.qk_rope_head_dim), axes,
                      dtype=dtype, init="zeros")}


def attention(cfg: ModelConfig, p, x, positions, sh, *, cache=None,
              pos=None):
    """x [B, S, D] -> (out [B, S, D], new cache).  ``cache``: (ckv [B, T,
    R], kpe [B, T, Dr]) for decoding one token at ``pos`` [B]."""
    with jax.named_scope("mla"):
        dt = x.dtype
        R, Dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        H = cfg.n_heads
        freqs, mag, scale = rope_tables(cfg)
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        q_pe = L.apply_rope(q[..., Dn:], positions, cfg.rope_theta,
                            freqs=freqs, mag=mag)
        q = jnp.concatenate([q[..., :Dn], q_pe], axis=-1)
        q = sh(q, "batch", "seq", "heads", "head_dim")
        kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
        ckv = L.rmsnorm(kv_a[..., :R], p["kv_norm"])
        kpe = L.apply_rope(kv_a[..., None, R:], positions, cfg.rope_theta,
                           freqs=freqs, mag=mag)[:, :, 0]
        new_cache = None
        if cache is not None:
            ckv_cache, kpe_cache = cache
            bidx = jnp.arange(x.shape[0])
            ckv_cache = ckv_cache.at[bidx, pos].set(
                ckv[:, 0].astype(ckv_cache.dtype))
            kpe_cache = kpe_cache.at[bidx, pos].set(
                kpe[:, 0].astype(kpe_cache.dtype))
            new_cache = (ckv_cache, kpe_cache)
            ckv, kpe = ckv_cache.astype(dt), kpe_cache.astype(dt)
        kv = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b"].astype(dt))
        kpe = jnp.broadcast_to(kpe[:, :, None, :],
                               kpe.shape[:2] + (H, kpe.shape[-1]))
        k = jnp.concatenate([kv[..., :Dn], kpe], axis=-1)
        v = kv[..., Dn:]
        if cache is not None:
            attn = L.decode_attention(q, k, v, pos, repeated=True,
                                      scale=scale)
        else:
            # 512 queries a chunk: at 4 x 4,096 tokens and 16 heads a
            # chunk's float32 scores take 0.5 GiB, where 1,024 would hold
            # 0.75 GB more of a v5e's 16 GB through the backward pass.
            attn = L.chunked_attention(q, k, v, causal=True, scale=scale,
                                       q_chunk=512)
        attn = sh(attn, "batch", "seq", "heads", "head_dim")
        out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(dt))
    return out, new_cache
