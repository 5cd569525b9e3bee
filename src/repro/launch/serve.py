"""Batched serving driver: prefill a prompt batch, then greedy-decode.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.shapes import InputShape
from repro.data.synthetic import TokenStream
from repro.launch.compile_cache import use_compile_cache
from repro.models import spec as pspec
from repro.models.registry import build_model, decode_window


def serve(cfg, *, batch: int, prompt_len: int, new_tokens: int,
          params=None, greedy: bool = True, log: bool = True):
    model = build_model(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    cache_len = prompt_len + new_tokens
    shape = InputShape("serve", cache_len, batch, "decode")
    cache = pspec.init_params(jax.random.PRNGKey(1), model.cache_specs(shape))
    window = decode_window(cfg, cache_len)

    data = TokenStream(cfg.vocab_size, prompt_len, seed=3)
    prompts = jnp.asarray(data.batch(0, batch)["tokens"])      # [B, P]

    decode = jax.jit(
        lambda p, c, b: model.decode_step(p, c, b, window=window))

    # prefill by stepping the decoder over the prompt (cache-building path;
    # the chunked prefill fast path is exercised by model.prefill in tests)
    t0 = time.perf_counter()
    tok = prompts[:, 0:1]
    out_tokens = [tok]
    for t in range(cache_len - 1):
        batch_t = {"tokens": tok,
                   "pos": jnp.full((batch,), t, jnp.int32)}
        logits, cache = decode(params, cache, batch_t)
        if t + 1 < prompt_len:
            tok = prompts[:, t + 1:t + 2]       # teacher-forced prompt
        else:
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
            out_tokens.append(tok)
        if len(out_tokens) - 1 >= new_tokens:
            break
    dt = time.perf_counter() - t0
    gen = jnp.concatenate(out_tokens[1:], axis=1)
    if log:
        print(f"generated {gen.shape} in {dt:.2f}s "
              f"({batch * new_tokens / dt:.1f} tok/s)")
    return np.asarray(gen), dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args(argv)
    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    gen, dt = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                    new_tokens=args.new_tokens)
    print("sample:", gen[0][:16])


if __name__ == "__main__":
    main()
