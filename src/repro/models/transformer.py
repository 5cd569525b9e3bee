"""Decoder-only transformer family: dense GQA (qwen2.5-*, gemma, h2o-danube),
MoE (qwen3-moe, dbrx), latent attention with leading dense layers and a
drop-free expert share (deepseek-v2-lite) and VLM backbone (qwen2-vl,
M-RoPE + vision stub).

Layers are scanned over stacked parameters (MaxText-style) to bound HLO size
and compile time; the layer body is rematerialized for training.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.models import layers as L
from repro.models import mla
from repro.models import moe as moe_lib
from repro.models.spec import TensorSpec as TS, init_params


def _norm_specs(cfg, shape, axes):
    if cfg.norm == "layernorm":
        return {"scale": TS(shape, axes, init="ones"),
                "bias": TS(shape, axes, init="zeros")}
    return {"scale": TS(shape, axes, init="zeros")}


def attn_specs(cfg: ModelConfig, n: int) -> dict:
    Lx, D, H, Hk, Dh = (n, cfg.d_model, cfg.pad_heads_to or cfg.n_heads,
                        cfg.n_kv_heads, cfg.d_head)
    s: dict = {
        "wq": TS((Lx, D, H, Dh), ("layers", "embed", "heads", "head_dim")),
        "wk": TS((Lx, D, Hk, Dh), ("layers", "embed", "kv_heads", "head_dim")),
        "wv": TS((Lx, D, Hk, Dh), ("layers", "embed", "kv_heads", "head_dim")),
        "wo": TS((Lx, H, Dh, D), ("layers", "heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias or cfg.norm == "layernorm":  # whisper has proj biases
        s["bq"] = TS((Lx, H, Dh), ("layers", "heads", "head_dim"), init="zeros")
        s["bk"] = TS((Lx, Hk, Dh), ("layers", "kv_heads", "head_dim"),
                     init="zeros")
        s["bv"] = TS((Lx, Hk, Dh), ("layers", "kv_heads", "head_dim"),
                     init="zeros")
    return s


def mlp_specs(cfg: ModelConfig, n: int, d_ff: int | None = None) -> dict:
    Lx, D, F = n, cfg.d_model, d_ff or cfg.d_ff
    if cfg.activation in ("silu", "geglu"):
        return {"wi_gate": TS((Lx, D, F), ("layers", "embed", "mlp")),
                "wi_up": TS((Lx, D, F), ("layers", "embed", "mlp")),
                "wo": TS((Lx, F, D), ("layers", "mlp", "embed"))}
    return {"wi": TS((Lx, D, F), ("layers", "embed", "mlp")),
            "wi_bias": TS((Lx, F), ("layers", "mlp"), init="zeros"),
            "wo": TS((Lx, F, D), ("layers", "mlp", "embed")),
            "wo_bias": TS((Lx, D), ("layers", "embed"), init="zeros")}


def attention(cfg: ModelConfig, p, x, positions, sh, *,
              window: int | None, cache=None, pos=None,
              memory=None, causal: bool = True, layer_axis: bool = False):
    """Full attention sub-layer (optionally cross-attention via ``memory``).

    cache: (k_cache, v_cache) [B, S, Hkv, Dh] for decode; pos [B].
    Returns (out, new_cache).
    """
    dt = x.dtype
    kv_src = memory if memory is not None else x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.rope_theta and not (memory is not None):
        sections = None
        if cfg.mrope:
            # Qwen2-VL uses (16, 24, 24) on d_half=64; scale proportionally.
            half = cfg.d_head // 2
            t = half // 4
            hw = (half - t) // 2
            sections = (half - 2 * hw, hw, hw)
        q = L.apply_rope(q, positions, cfg.rope_theta, sections)
        k = L.apply_rope(k, positions, cfg.rope_theta, sections)
    q = sh(q, "batch", "seq", "heads", "head_dim")
    # Padded heads (pad_heads_to): extra Q heads exist only so the head dim
    # divides the model axis.  They keep the ORIGINAL q->kv group mapping
    # for real heads (via an explicit gather) and are hard-masked to zero
    # output, so forward AND gradients are identical to the unpadded model.
    H_real, H_pad = cfg.n_heads, (cfg.pad_heads_to or cfg.n_heads)
    head_map = jnp.asarray(
        [min(h, H_real - 1) * cfg.n_kv_heads // H_real
         for h in range(H_pad)], jnp.int32)
    new_cache = None
    if cache is not None:
        k_cache, v_cache = cache
        bidx = jnp.arange(k.shape[0])
        k_cache = k_cache.at[bidx, pos].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, pos].set(v[:, 0].astype(v_cache.dtype))
        new_cache = (k_cache, v_cache)
        attn = L.decode_attention(
            q, jnp.take(k_cache.astype(dt), head_map, axis=2),
            jnp.take(v_cache.astype(dt), head_map, axis=2),
            pos, window=window, repeated=True)
    else:
        attn = L.chunked_attention(q, jnp.take(k, head_map, axis=2),
                                   jnp.take(v, head_map, axis=2),
                                   causal=causal, window=window)
    if H_pad != H_real:
        mask = (jnp.arange(H_pad) < H_real).astype(dt)
        attn = attn * mask[None, None, :, None]
    attn = sh(attn, "batch", "seq", "heads", "head_dim")
    out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(dt))
    return out, new_cache


class TransformerModel:
    """dense | moe | vlm decoder-only LM."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------ specs ----
    def _layer_specs(self, n: int, moe: bool) -> dict:
        cfg = self.cfg
        D = cfg.d_model
        layer: dict = {"ln1": _norm_specs(cfg, (n, D), ("layers", "embed")),
                       "attn": (mla.mla_specs(cfg, n) if cfg.is_mla
                                else attn_specs(cfg, n)),
                       "ln2": _norm_specs(cfg, (n, D), ("layers", "embed"))}
        if moe and cfg.moe_dropless:
            layer["moe"] = moe_lib.dropless_specs(cfg, n)
        elif moe:
            layer["moe"] = moe_lib.moe_specs(cfg, n)
        else:
            layer["mlp"] = mlp_specs(cfg, n, cfg.dense_d_ff)
        return layer

    def param_specs(self) -> dict:
        cfg = self.cfg
        nd, D, V = cfg.first_dense_layers, cfg.d_model, cfg.vocab_size
        p = {"embed": TS((V, D), ("vocab", "embed"), init="embed"),
             "final_norm": _norm_specs(cfg, (D,), ("embed",)),
             "layers": self._layer_specs(cfg.n_layers - nd, cfg.is_moe)}
        if nd:
            p["dense_layers"] = self._layer_specs(nd, False)
        if not cfg.tie_embeddings:
            p["unembed"] = TS((V, D), ("vocab", "embed"), init="embed")
        return p

    def expert_param_specs(self):
        return moe_lib.expert_only_specs(self.param_specs())

    def init(self, key):
        return init_params(key, self.param_specs())

    # --------------------------------------------------------- positions ---
    def _positions(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        if not cfg.mrope:
            pos = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
            return jnp.broadcast_to(pos, (batch_size, seq_len))
        # M-RoPE: vision patches get (t=0, h, w) grid coords, text tokens get
        # t = h = w = running position (Qwen2-VL §2.1).
        P = min(cfg.n_frontend_tokens, seq_len)
        g = max(1, int(math.isqrt(P)))
        i = np.arange(seq_len)
        t = np.where(i < P, 0, i - P + g)
        h = np.where(i < P, np.minimum(i, P - 1) // g, i - P + g)
        w = np.where(i < P, np.minimum(i, P - 1) % g, i - P + g)
        pos3 = np.stack([t, h, w], axis=-1).astype(np.int32)  # [S,3]
        return jnp.broadcast_to(jnp.asarray(pos3)[None], (batch_size, seq_len, 3))

    def _decode_positions(self, pos):
        cfg = self.cfg
        if not cfg.mrope:
            return pos[:, None]
        P = cfg.n_frontend_tokens
        g = max(1, int(math.isqrt(P)))
        txt = pos - P + g
        return jnp.stack([txt, txt, txt], axis=-1)[:, None]  # [B,1,3]

    # ----------------------------------------------------------- embed -----
    def _embed(self, params, batch):
        cfg = self.cfg
        scale = math.sqrt(cfg.d_model) if cfg.name.startswith("gemma") else None
        x = L.embed_tokens(params["embed"], batch["tokens"], scale)
        if cfg.frontend == "vision" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            P = min(pe.shape[1], x.shape[1])
            x = jax.lax.dynamic_update_slice(x, pe[:, :P], (0, 0, 0))
        return x

    def _unembed(self, params):
        return params["embed"] if self.cfg.tie_embeddings else params["unembed"]

    # ---------------------------------------------------------- forward ----
    def _layer(self, params_i, x, positions, sh, window, cache_i=None,
               pos=None):
        """-> (x, balance loss, held expert loads or None, new cache)."""
        cfg = self.cfg
        h = L.apply_norm(cfg, x, params_i["ln1"])
        if cfg.is_mla:
            attn_out, new_cache = mla.attention(
                cfg, params_i["attn"], h, positions, sh, cache=cache_i,
                pos=pos)
        else:
            attn_out, new_cache = attention(
                cfg, params_i["attn"], h, positions, sh,
                window=window, cache=cache_i, pos=pos)
        x = x + attn_out
        h = L.apply_norm(cfg, x, params_i["ln2"])
        loads = None
        if "mlp" in params_i:
            ffn_out, aux = L.mlp(cfg, params_i["mlp"], h), 0.0
        elif cfg.moe_dropless:
            ffn_out, aux, loads = moe_lib.moe_dropless(cfg, params_i["moe"],
                                                       h)
        else:
            ffn_out, aux = moe_lib.moe_ffn(cfg, params_i["moe"], h, sh)
        return x + ffn_out, aux, loads, new_cache

    def _trunk(self, params, batch, sh, window):
        """The layers over the embedded tokens, then the final norm.
        -> (x, summed balance loss, held expert loads [layers, held] or
        None)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        x = sh(x, "batch", "seq", "embed")
        positions = self._positions(*batch["tokens"].shape)
        window = window if window is not None else cfg.sliding_window

        def body(carry, params_i):
            x, aux = carry
            x, aux_i, loads, _ = self._layer(params_i, x, positions, sh,
                                             window)
            return (x, aux + aux_i), loads

        carry = (x, 0.0)
        if "dense_layers" in params:
            carry, _ = L.scan_layers(body, carry, params["dense_layers"])
        (x, aux), loads = L.scan_layers(body, carry, params["layers"])
        return L.apply_norm(cfg, x, params["final_norm"]), aux, loads

    def forward(self, params, batch, sh=L.NO_SHARD, *, window=None):
        """Teacher-forced logits over the whole sequence. Returns (logits, aux)."""
        x, aux, _ = self._trunk(params, batch, sh, window)
        logits = L.lm_logits(x, self._unembed(params))
        return sh(logits, "batch", "seq", "vocab"), aux

    def loss(self, params, batch, sh=L.NO_SHARD):
        return self.loss_and_stats(params, batch, sh)[0]

    @property
    def step_stats(self) -> bool:
        """Whether ``loss_and_stats`` reports anything: the held experts'
        loads of the drop-free MoE."""
        return self.cfg.moe_dropless

    def loss_and_stats(self, params, batch, sh=L.NO_SHARD):
        """-> (loss, {"moe_assignments_held": [MoE layers, held] int32}
        for the drop-free MoE, else {})."""
        x, aux, loads = self._trunk(params, batch, sh, None)
        logits = sh(L.lm_logits(x, self._unembed(params)),
                    "batch", "seq", "vocab")
        ce = L.softmax_cross_entropy(logits, batch["labels"])
        stats = {} if loads is None else {"moe_assignments_held": loads}
        return ce + self.cfg.aux_loss_coef * aux, stats

    # ------------------------------------------------------------ serve ----
    def cache_specs(self, shape: InputShape, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        n = cfg.n_layers
        B, S = shape.global_batch, shape.seq_len
        if cfg.is_mla:
            return mla.cache_specs(cfg, n, B, S, dtype)
        kv = (n, B, S, cfg.n_kv_heads, cfg.d_head)
        axes = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
        return {"k": TS(kv, axes, dtype=dtype, init="zeros"),
                "v": TS(kv, axes, dtype=dtype, init="zeros")}

    def prefill(self, params, batch, sh=L.NO_SHARD, *, window=None):
        """Prefill logits (cache write-out elided in the benchmark shape —
        the assigned prefill shape measures the forward; see engine.serve
        for the cache-building variant)."""
        logits, _ = self.forward(params, batch, sh, window=window)
        return logits

    def decode_step(self, params, cache, batch, sh=L.NO_SHARD, *,
                    window=None):
        """One-token decode against a cache. batch: tokens [B,1], pos [B]."""
        cfg = self.cfg
        x = self._embed(params, batch)
        pos = batch["pos"]
        positions = self._decode_positions(pos)
        window = window if window is not None else cfg.sliding_window

        def body(x, xs):
            params_i, cache_i = xs
            x, _, _, new_cache = self._layer(params_i, x, positions, sh,
                                             window, cache_i=cache_i,
                                             pos=pos)
            return x, new_cache

        names = ("ckv", "kpe") if cfg.is_mla else ("k", "v")
        nd = cfg.first_dense_layers
        parts = []
        for key, lo, hi in (("dense_layers", 0, nd),
                            ("layers", nd, cfg.n_layers)):
            if lo == hi:
                continue
            caches = tuple(cache[n] if (lo, hi) == (0, cfg.n_layers)
                           else cache[n][lo:hi] for n in names)
            x, new = L.scan_layers(body, x, (params[key], caches),
                                   checkpoint_body=False)
            parts.append(new)
        x = L.apply_norm(cfg, x, params["final_norm"])
        logits = L.lm_logits(x, self._unembed(params))
        return logits, {n: jnp.concatenate([p[i] for p in parts])
                        if len(parts) > 1 else parts[0][i]
                        for i, n in enumerate(names)}

    # ------------------------------------------------------------ inputs ---
    def input_specs(self, shape: InputShape) -> dict:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = ("batch", "seq")
        if shape.kind == "train":
            d = {"tokens": TS((B, S), tok, dtype=jnp.int32),
                 "labels": TS((B, S), tok, dtype=jnp.int32)}
        elif shape.kind == "prefill":
            d = {"tokens": TS((B, S), tok, dtype=jnp.int32)}
        else:
            d = {"tokens": TS((B, 1), tok, dtype=jnp.int32),
                 "pos": TS((B,), ("batch",), dtype=jnp.int32)}
        if cfg.frontend == "vision" and shape.kind != "decode":
            d["patch_embeds"] = TS((B, cfg.n_frontend_tokens, cfg.d_model),
                                   ("batch", "patch", "embed"),
                                   dtype=jnp.bfloat16)
        return d
