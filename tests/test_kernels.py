"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles.
Every call passes interpret=True, which runs the Pallas body on the CPU;
tests/test_tpu_compile.py compiles the same kernels for a TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,s,d,window,causal", [
    (2, 256, 64, None, True),
    (2, 256, 64, 128, True),
    (1, 384, 128, 96, True),
    (3, 128, 128, None, False),
    (1, 130, 32, 64, True),          # non-multiple seq (padding path)
    (2, 64, 256, 32, True),          # gemma-style d=256
])
def test_swa_attention_sweep(bh, s, d, window, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(s + d), 3)
    q = jax.random.normal(k1, (bh, s, d), dtype)
    k = jax.random.normal(k2, (bh, s, d), dtype)
    v = jax.random.normal(k3, (bh, s, d), dtype)
    got = ops.swa_attention(q, k, v, causal=causal, window=window,
                            block_q=64, block_k=64, interpret=True)
    want = ref.swa_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_swa_attention_block_shape_invariance():
    """BlockSpec tile sizes must not change results."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, 256, 64), jnp.float32)
    k = jax.random.normal(k2, (2, 256, 64), jnp.float32)
    v = jax.random.normal(k3, (2, 256, 64), jnp.float32)
    outs = [ops.swa_attention(q, k, v, window=100, block_q=bq, block_k=bk,
                              interpret=True)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-5, atol=1e-5)


def test_swa_window_blocks_are_skipped_semantically():
    """With a tiny window, far-away K must have zero influence."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 256, 32), jnp.float32)
    k = jax.random.normal(k2, (1, 256, 32), jnp.float32)
    v = jax.random.normal(k3, (1, 256, 32), jnp.float32)
    base = ops.swa_attention(q, k, v, window=16, block_q=64, block_k=64,
                             interpret=True)
    k2_, v2_ = k.at[:, :128].set(99.0), v.at[:, :128].set(-99.0)
    pert = ops.swa_attention(q, k2_, v2_, window=16, block_q=64, block_k=64,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(pert[:, 192:]),
                               np.asarray(base[:, 192:]), rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 5000), lr=st.floats(1e-4, 1.0),
       momentum=st.floats(0.0, 0.99))
def test_fused_update_property(n, lr, momentum):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n), 3)
    p = jax.random.normal(k1, (n,), jnp.float32)
    g = jax.random.normal(k2, (n,), jnp.float32)
    mu = jax.random.normal(k3, (n,), jnp.float32)
    got = ops.fused_sgd_update(p, g, mu, lr, momentum=momentum, block=512,
                               interpret=True)
    want = ref.fused_sgd_update_ref(p, g, mu, lr, momentum=momentum)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("n,block", [(65536, 65536), (100001, 4096),
                                     (7, 8)])
def test_fused_update_shapes(n, block, nesterov):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    p = jax.random.normal(k1, (n,), jnp.float32)
    g = jax.random.normal(k2, (n,), jnp.float32)
    mu = jax.random.normal(k3, (n,), jnp.float32)
    got = ops.fused_sgd_update(p, g, mu, 0.1, nesterov=nesterov, block=block,
                               interpret=True)
    want = ref.fused_sgd_update_ref(p, g, mu, 0.1, nesterov=nesterov)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)


def test_fused_update_equals_sgd_optimizer_step():
    """The kernel is a drop-in for the jnp SGD update on a flat buffer."""
    from repro.optim.optimizers import sgd
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    params = {"a": jax.random.normal(k1, (33,)),
              "b": jax.random.normal(k2, (17,))}
    grads = jax.tree_util.tree_map(lambda x: x * 0.1, params)
    state = opt.init(params)
    new_params, new_state = opt.update(grads, state, params, 0.05)

    flat_p = jnp.concatenate([params["a"], params["b"]])
    flat_g = jnp.concatenate([grads["a"], grads["b"]])
    flat_mu = jnp.zeros_like(flat_p)
    got_p, got_mu = ops.fused_sgd_update(flat_p, flat_g, flat_mu, 0.05,
                                         momentum=0.9, weight_decay=1e-4,
                                         block=32, interpret=True)
    want_p = jnp.concatenate([new_params["a"], new_params["b"]])
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,block_rows", [
    ((4, 128, 512), 256), ((1, 7, 64), 4), ((300, 1024), 128),
    ((2, 2048), 2048)])
def test_rmsnorm_sweep(shape, block_rows, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(shape)))
    x = jax.random.normal(k1, shape, dtype)
    w = jax.random.normal(k2, (shape[-1],), jnp.float32) * 0.1
    got = ops.rmsnorm(x, w, block_rows=block_rows, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_matches_model_layer():
    """The kernel is a drop-in for repro.models.layers.rmsnorm."""
    from repro.models.layers import rmsnorm as layer_rmsnorm
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (3, 17, 256), jnp.float32)
    w = jax.random.normal(k2, (256,), jnp.float32) * 0.1
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, w, interpret=True)),
                               np.asarray(layer_rmsnorm(x, w)),
                               rtol=1e-5, atol=1e-5)
