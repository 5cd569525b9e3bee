"""The analytic FLOP counts that ``mfu`` reads, against XLA's own count at
sizes where the program has no loop (XLA counts a loop's body once, so
ResNet-110's scanned blocks and a many-layer transformer cannot be checked
this way)."""
import jax
import jax.numpy as jnp
import pytest

import harness


def _xla_flops(fn, *args) -> float:
    return jax.jit(fn).lower(*args).cost_analysis()["flops"]


def test_resnet8_forward_flops_match_xla():
    cfg, mod = harness.config("resnet110")
    cfg = dict(cfg, depth=8)
    model, _ = mod.program(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = 4
    images = jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.float32)
    xla = _xla_flops(model.apply, params, images) / batch
    ours = mod.forward_flops(cfg)
    # XLA also counts the group norms, ReLUs and residual adds, 3.8% of the
    # work at these widths; leaving out the smallest convolution (the stem,
    # 1.8%) would push the ratio over 1.05, counting any work twice under 1.
    assert 1.0 <= xla / ours <= 1.05, (xla, ours)


def test_resnet110_counts():
    cfg, mod = harness.config("resnet110")
    # In-bounds taps only: 229.3 M multiply-adds a forward pass, against
    # 252.9 M when the products with the zero padding are counted.
    assert mod.forward_flops(cfg) == 2 * 229_295_424
    assert mod.flops_per_sample(cfg) == 3 * mod.forward_flops(cfg)


def test_qwen_one_layer_forward_flops_match_xla():
    cfg, mod = harness.config("qwen2.5-3b-l4")
    cfg = dict(cfg, num_hidden_layers=1)
    model, _ = mod.program(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seq = 256
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    xla = _xla_flops(lambda p, t: model.forward(p, {"tokens": t})[0],
                     params, tokens)
    ours = mod.forward_flops(cfg, seq)
    # XLA also counts the masked half of the score products (0.45% here)
    # and the norms, rotary embedding, softmax and SiLU (about 0.2%);
    # leaving out the smallest matmul (k's projection, 0.45%) would push the
    # ratio over 1.008.
    assert 1.0 <= xla / ours <= 1.008, (xla, ours)


def test_qwen_cut_counts():
    cfg, mod = harness.config("qwen2.5-3b-l4")
    leaves = jax.tree_util.tree_leaves(
        mod.param_spec(cfg), is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], str))
    total = sum(int(jnp.prod(jnp.array(leaf[1]))) for leaf in leaves)
    assert total == 347_205_632
    # Everything but the norms' gains and the q, k, v biases is a matmul.
    assert mod.matmul_params(cfg) == total - (2 * 4 + 1) * 2048 \
        - 4 * (2048 + 2 * 256)
    per_token = mod.flops_per_sample(cfg, 4096) / 4096
    assert per_token == pytest.approx(2.284e9, rel=1e-3)
