"""Step builders: train_step / prefill / decode_step closures over a model,
optimizer and Sharder — the functions the launcher jits with in/out
shardings and the dry-run lowers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import Sharder, NO_SHARD
from repro.optim.optimizers import Optimizer


def make_train_step(model, optimizer: Optimizer, sh: Sharder = NO_SHARD,
                    grad_exchange: str | None = None, axis: str = "data",
                    microbatches: int = 1):
    """(state {params, opt}, batch, lr) -> (state, loss).

    grad_exchange: None => implicit GSPMD reduction (production path);
    "ring"/"doubling_halving" are only valid inside shard_map (the
    paper-faithful explicit path, see examples/explicit_allreduce.py).

    microbatches > 1: gradient accumulation — the global batch is split
    into k sequential microbatches inside a lax.scan, bounding live
    activations to one microbatch (the memory-roofline knob for big-model
    training; EXPERIMENTS.md §Perf).
    """

    def grads_of(params, batch):
        return jax.value_and_grad(lambda p: model.loss(p, batch, sh))(params)

    def train_step(state, batch, lr):
        params = state["params"]
        if microbatches == 1:
            loss, grads = grads_of(params, batch)
        else:
            k = microbatches
            mb = jax.tree_util.tree_map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mbatch):
                acc, lsum = carry
                loss_i, g_i = grads_of(params, mbatch)
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), acc, g_i)
                return (acc, lsum + loss_i), None

            (grads, lsum), _ = jax.lax.scan(body, (zeros, 0.0), mb)
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            loss = lsum / k
        if grad_exchange:
            from repro.collectives.xla import exchange_tree
            grads = exchange_tree(grads, axis, grad_exchange)
            n = jax.lax.axis_size(axis)
            grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            loss = jax.lax.pmean(loss, axis)   # report the global-batch loss
        new_params, new_opt = optimizer.update(grads, state["opt"],
                                               state["params"], lr)
        return {"params": new_params, "opt": new_opt}, loss

    return train_step


def make_data_parallel_step(model, optimizer: Optimizer, mesh,
                            grad_exchange: str | None = None):
    """Jit the train step over a 1-D ``"data"`` mesh.

    The batch is sharded over ``"data"`` and the state replicated.  With
    ``grad_exchange=None`` GSPMD inserts XLA's all-reduce; ``"ring"`` /
    ``"doubling_halving"`` run the paper's explicit exchange under
    shard_map.  -> (jitted step, replicated sharding, batch sharding).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    step = make_train_step(model, optimizer, grad_exchange=grad_exchange)
    if grad_exchange:
        step = jax.shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data"), P()),
                             out_specs=(P(), P()), check_vma=False)
    jitted = jax.jit(step, in_shardings=(rep, data, rep),
                     out_shardings=(rep, rep))
    return jitted, rep, data


def make_prefill(model, sh: Sharder = NO_SHARD, window: int | None = None):
    def prefill(params, batch):
        return model.prefill(params, batch, sh, window=window)

    return prefill


def make_decode_step(model, sh: Sharder = NO_SHARD,
                     window: int | None = None):
    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch, sh, window=window)

    return decode_step


def init_train_state(model, optimizer: Optimizer, key=None) -> dict:
    params = model.init(key if key is not None else jax.random.PRNGKey(0))
    return {"params": params, "opt": optimizer.init(params)}


def train_state_specs(model, optimizer: Optimizer) -> dict:
    """TensorSpec tree for the full train state (params + optimizer state),
    used by the dry-run to build shardings/abstract values without
    allocating.  Optimizer state mirrors param specs; scalar counters are
    plain TensorSpecs with no axes."""
    from repro.models.spec import TensorSpec as TS

    pspecs = model.param_specs()
    if optimizer.name == "sgd":
        opt = {"mu": pspecs}
    elif optimizer.name == "adamw":
        opt = {"m": pspecs, "v": pspecs, "t": TS((), (), dtype=jnp.int32,
                                                 init="zeros")}
    else:
        raise ValueError(optimizer.name)
    return {"params": pspecs, "opt": opt}
