"""Jitted public wrappers around the Pallas kernels.

Each wrapper compiles the Mosaic kernel for the TPU.  A caller off the TPU
(the CPU tests) passes ``interpret=True`` to run the kernel body through the
Pallas interpreter instead; nothing picks that on its own.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import fused_update as _fu
from repro.kernels import rmsnorm as _rms
from repro.kernels import swa_attention as _swa


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def swa_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool = False):
    """Sliding-window flash attention. q/k/v: [BH, S, D]."""
    return _swa.swa_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)


@partial(jax.jit, static_argnames=("momentum", "weight_decay", "nesterov",
                                   "block", "interpret"))
def fused_sgd_update(params_flat, grads_flat, mu_flat, lr, *,
                     momentum: float = 0.9, weight_decay: float = 1e-4,
                     nesterov: bool = False, block: int = 65536,
                     interpret: bool = False):
    return _fu.fused_sgd_update(params_flat, grads_flat, mu_flat, lr,
                                momentum=momentum, weight_decay=weight_decay,
                                nesterov=nesterov, block=block,
                                interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """Fused RMSNorm (gain = 1 + w). x: [..., D]; w: [D]."""
    return _rms.rmsnorm(x, w, eps=eps, block_rows=block_rows,
                        interpret=interpret)
