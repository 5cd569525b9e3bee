"""Sharding-rule resolution: divisibility fallback, head_dim secondary
fallback, decode cache rules, and the no-duplicate-mesh-axis invariant."""
import os
import subprocess
import sys

from _hypothesis_compat import given, settings, strategies as st

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# rules resolution itself needs a Mesh object; build tiny abstract meshes in
# a subprocess-free way using jax's mesh_utils on 1 device is impossible for
# 16x16 — so use jax.sharding.Mesh over a numpy array of fake devices? Mesh
# requires real devices; we therefore test via AbstractMesh.
import jax
import numpy as np

from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.sharding.rules import default_rules


def mesh_1pod():
    return AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def mesh_2pod():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                        axis_types=(AxisType.Auto,) * 3)


RULES = default_rules()


def spec(axes, shape, mesh=None):
    return RULES.spec_for(axes, shape, mesh or mesh_1pod())


def test_batch_sharded_on_data():
    assert spec(("batch", "seq"), (256, 4096)) == P("data", None)


def test_batch_multi_pod():
    s = spec(("batch", "seq"), (256, 4096), mesh_2pod())
    assert s == P(("pod", "data"), None)


def test_batch_one_replicated():
    assert spec(("batch", "seq"), (1, 524288), mesh_2pod()) == P(None, None)


def test_heads_divisible():
    assert spec(("layers", "embed", "heads", "head_dim"),
                (36, 2048, 16, 128)) == P(None, None, "model", None)


def test_heads_40_falls_back_to_head_dim():
    """qwen2.5-14b: 40 heads don't divide 16 -> shard head_dim instead."""
    assert spec(("layers", "embed", "heads", "head_dim"),
                (48, 5120, 40, 128)) == P(None, None, None, "model")


def test_kv_heads_small_replicate():
    """kv=2 < 16 and q-heads divisible: kv weights replicate (GQA Megatron
    convention), no head_dim fallback."""
    assert spec(("layers", "embed", "kv_heads", "head_dim"),
                (36, 2048, 2, 128)) == P(None, None, None, None)


def test_vocab_non_divisible_replicates():
    assert spec(("vocab", "embed"), (51865, 512)) == P(None, None)
    assert spec(("vocab", "embed"), (151936, 2048)) == P("model", None)


def test_experts_sharded():
    assert spec(("layers", "experts", "embed", "mlp"),
                (48, 128, 2048, 768)) == P(None, "model", None, None)


def test_decode_cache_rules():
    # decode rules shard cache_seq over whatever axes batch leaves free
    rules = default_rules({"cache_seq": ("pod", "data", "model")})
    m = mesh_2pod()
    # decode_32k: batch 128 takes pod+data, cache_seq gets model
    s = rules.spec_for(("layers", "batch", "cache_seq", "kv_heads",
                        "head_dim"), (48, 128, 32768, 8, 128), m)
    assert s == P(None, ("pod", "data"), "model", None, None)
    # long_500k: batch 1 unshardable, cache_seq takes everything
    s = rules.spec_for(("layers", "batch", "cache_seq", "kv_heads",
                        "head_dim"), (48, 1, 524288, 8, 128), m)
    assert s == P(None, None, ("pod", "data", "model"), None, None)


def test_fsdp_profile():
    rules = default_rules({"embed": ("data",)})
    s = rules.spec_for(("layers", "embed", "mlp"), (36, 2048, 11008),
                       mesh_1pod())
    assert s == P(None, "data", "model")
    # activations: batch wins the data axis, embed then replicates
    s = rules.spec_for(("batch", "seq", "embed"), (256, 4096, 2048),
                       mesh_1pod())
    assert s == P("data", None, None)


AXES_POOL = ["batch", "seq", "embed", "heads", "kv_heads", "head_dim",
             "mlp", "vocab", "experts", "layers", "cache_seq", None]


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(AXES_POOL),
                          st.integers(1, 4096)), min_size=1, max_size=5))
def test_no_mesh_axis_used_twice(dims):
    """PartitionSpec invariant: a mesh axis may appear at most once."""
    axes = tuple(a for a, _ in dims)
    shape = tuple(s for _, s in dims)
    s = RULES.spec_for(axes, shape, mesh_2pod())
    flat = []
    for part in s:
        if part is None:
            continue
        if isinstance(part, tuple):
            flat.extend(part)
        else:
            flat.append(part)
    assert len(flat) == len(set(flat)), (axes, shape, s)
    # and every sharded dim divides evenly
    m = mesh_2pod()
    for part, size in zip(s, shape):
        if part is None:
            continue
        total = 1
        for ax in (part if isinstance(part, tuple) else (part,)):
            total *= m.shape[ax]
        assert size % total == 0, (axes, shape, s)
