"""Optimizers (pure JAX, optax-style but self-contained).

SGD+momentum is the paper's optimizer (ResNet/CIFAR); AdamW serves the LLM
architectures.  States are pytrees mirroring the params, so checkpointing
and elastic restarts treat them uniformly.  These jnp updates are what the
train step runs.  The fused Pallas kernel (repro.kernels.fused_update)
implements the same SGD update on a flat buffer but is not on the train
step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[..., tuple[Any, Any]]  # (grads, state, params, lr)
    name: str = "opt"


def sgd(momentum: float = 0.9, weight_decay: float = 1e-4,
        nesterov: bool = False) -> Optimizer:
    def init(params):
        return {"mu": jax.tree_util.tree_map(jnp.zeros_like, params)}

    def update(grads, state, params, lr):
        def upd(g, mu, p):
            g = g + weight_decay * p
            mu_new = momentum * mu + g
            step = (g + momentum * mu_new) if nesterov else mu_new
            return p - lr * step, mu_new

        flat = jax.tree_util.tree_map(upd, grads, state["mu"], params)
        new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                            is_leaf=lambda t: isinstance(t, tuple))
        new_mu = jax.tree_util.tree_map(lambda t: t[1], flat,
                                        is_leaf=lambda t: isinstance(t, tuple))
        return new_params, {"mu": new_mu}

    return Optimizer(init, update, "sgd")


def adamw(b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.1) -> Optimizer:
    def init(params):
        z = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"m": z,
                "v": jax.tree_util.tree_map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, lr):
        t = state["t"] + 1
        c1 = 1.0 - b1 ** t.astype(jnp.float32)
        c2 = 1.0 - b2 ** t.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m_new = b1 * m + (1 - b1) * g
            v_new = b2 * v + (1 - b2) * g * g
            mhat = m_new / c1
            vhat = v_new / c2
            step = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p
            return p - lr * step, m_new, v_new

        flat = jax.tree_util.tree_map(upd, grads, state["m"], state["v"],
                                      params)
        pick = lambda i: jax.tree_util.tree_map(
            lambda tup: tup[i], flat, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"m": pick(1), "v": pick(2), "t": t}

    return Optimizer(init, update, "adamw")
