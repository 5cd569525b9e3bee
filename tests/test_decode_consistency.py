"""Decode-vs-forward consistency: teacher-forced forward logits must match
step-by-step KV-cache/SSM-state decode logits — the strongest correctness
check for every cache implementation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.shapes import InputShape
from repro.models import spec as pspec
from repro.models.registry import build_model

S = 24


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma-2b", "mamba2-780m",
                                  "jamba-v0.1-52b", "qwen3-moe-30b-a3b",
                                  "deepseek-v2-lite"])
def test_decode_matches_forward(arch):
    import dataclasses
    cfg = get_smoke_config(arch)
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=None)
    if cfg.is_moe:
        # ample capacity: capacity-MoE drops tokens in teacher-forced mode
        # but never at single-token decode (inherent train/serve skew); this
        # test targets the cache logic, not the drop policy.
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, S)), jnp.int32)

    fwd_logits, _ = model.forward(params, {"tokens": tokens})

    dshape = InputShape("d", S, 2, "decode")
    cache = pspec.init_params(jax.random.PRNGKey(1),
                              model.cache_specs(dshape))
    decode = jax.jit(model.decode_step)
    step_logits = []
    for t in range(S):
        batch = {"tokens": tokens[:, t:t + 1],
                 "pos": jnp.full((2,), t, jnp.int32)}
        logits, cache = decode(params, cache, batch)
        step_logits.append(logits[:, 0])
    got = jnp.stack(step_logits, axis=1)

    # bf16 activations; compare in relative terms on the logits
    err = float(jnp.max(jnp.abs(got - fwd_logits)))
    scale = float(jnp.max(jnp.abs(fwd_logits))) + 1e-6
    assert err / scale < 0.08, (arch, err, scale)
    # argmax agreement is the serving-level contract (hybrid stacks more
    # bf16 noise through mamba+moe layers; its exact check is the f32 test)
    agree = float(jnp.mean((jnp.argmax(got, -1)
                            == jnp.argmax(fwd_logits, -1)).astype(
                                jnp.float32)))
    floor = 0.9 if cfg.family == "hybrid" else 0.95
    assert agree > floor, (arch, agree)


def test_jamba_decode_exact_in_f32(monkeypatch):
    """With f32 activations and caches, hybrid decode must match the
    teacher-forced forward to ~1e-5 — proves the cache logic is exact and
    the bf16 disagreement above is pure rounding."""
    import dataclasses
    import repro.models.layers as L

    def embed_f32(embedding, tokens, scale=None):
        x = jnp.take(embedding, tokens, axis=0).astype(jnp.float32)
        return x * scale if scale is not None else x

    monkeypatch.setattr(L, "embed_tokens", embed_f32)
    cfg = dataclasses.replace(get_smoke_config("jamba-v0.1-52b"),
                              capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, S)), jnp.int32)
    fwd, _ = model.forward(params, {"tokens": tokens})
    dshape = InputShape("d", S, 2, "decode")
    cache = pspec.init_params(jax.random.PRNGKey(1),
                              model.cache_specs(dshape))
    cache = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), cache)
    decode = jax.jit(model.decode_step)
    outs = []
    for t in range(S):
        logits, cache = decode(params, cache,
                               {"tokens": tokens[:, t:t + 1],
                                "pos": jnp.full((2,), t, jnp.int32)})
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - fwd))) < 1e-4


def test_whisper_decode_matches_forward():
    cfg = get_smoke_config("whisper-base")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, S)), jnp.int32)
    frames = jnp.asarray(rng.normal(size=(2, cfg.n_frontend_tokens,
                                          cfg.d_model)) * 0.1, jnp.bfloat16)

    fwd_logits, _ = model.forward(params, {"tokens": tokens,
                                           "frames": frames})
    enc = model.encode(params, frames)

    dshape = InputShape("d", S, 2, "decode")
    cache = pspec.init_params(jax.random.PRNGKey(1),
                              model.cache_specs(dshape))
    cache["enc"] = enc.astype(cache["enc"].dtype)
    decode = jax.jit(model.decode_step)
    outs = []
    for t in range(S):
        batch = {"tokens": tokens[:, t:t + 1],
                 "pos": jnp.full((2,), t, jnp.int32)}
        logits, cache = decode(params, cache, batch)
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(got - fwd_logits)))
    scale = float(jnp.max(jnp.abs(fwd_logits))) + 1e-6
    assert err / scale < 0.08, (err, scale)


def test_sliding_window_decode_matches_forward():
    """SWA (danube-style) forward/decode agreement with the window active."""
    cfg = get_smoke_config("h2o-danube-1.8b")
    assert cfg.sliding_window
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, S)), jnp.int32)
    fwd_logits, _ = model.forward(params, {"tokens": tokens})
    dshape = InputShape("d", S, 2, "decode")
    cache = pspec.init_params(jax.random.PRNGKey(1),
                              model.cache_specs(dshape))
    decode = jax.jit(model.decode_step)
    outs = []
    for t in range(S):
        batch = {"tokens": tokens[:, t:t + 1],
                 "pos": jnp.full((2,), t, jnp.int32)}
        logits, cache = decode(params, cache, batch)
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(got - fwd_logits)))
    scale = float(jnp.max(jnp.abs(fwd_logits))) + 1e-6
    assert err / scale < 0.08, (err, scale)
