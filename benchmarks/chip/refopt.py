"""Plain reference optimizers, as the configuration states them.

SGD with momentum and weight decay added to the gradient (the paper's
ResNet optimizer), and AdamW with decoupled weight decay and bias
correction.  ``first_grad`` reads the gradient an optimizer received at its
first step back from its state after that step; it serves the program's
state and the reference's alike.
"""
from __future__ import annotations

import numpy as np


def init(opt: dict, params):
    import jax
    import jax.numpy as jnp

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    if opt["name"] == "sgd":
        return {"mu": zeros}
    if opt["name"] == "adamw":
        return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like,
                                                        params), "t": 0}
    raise ValueError(opt["name"])


def update(opt: dict, params, state, grads, lr: float):
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    if opt["name"] == "sgd":
        mom, wd = opt["momentum"], opt["weight_decay"]
        mu = tm(lambda m, g, p: mom * m + g + wd * p, state["mu"], grads,
                params)
        return tm(lambda p, m: p - lr * m, params, mu), {"mu": mu}
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    t = state["t"] + 1
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = tm(lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                       + wd * p), params, m, v)
    return new, {"m": m, "v": v, "t": t}


def first_grad(opt: dict, state) -> list[np.ndarray]:
    """Leaves of the first gradient, from the state after one step (for SGD
    that is the gradient with the weight decay term, as the update used)."""
    import jax

    if opt["name"] == "sgd":
        tree = state["mu"]
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
    return [np.asarray(x) / (1 - opt["b1"])
            for x in jax.tree_util.tree_leaves(state["m"])]
