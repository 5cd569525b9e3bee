"""DeepSeek-V2-Lite through the program's transformer, at small sizes on the
CPU: latent attention with YaRN, the leading dense layer and the drop-free
expert share against the benchmark's plain float32 reference
(``benchmarks/chip/configs/deepseek-v2-lite-l5.py``, which imports nothing
of the program); the shares of a layer adding up to the uncut layer; no
token dropped however the routing leans; and the loop's expert counters."""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.checkpoint.store import CheckpointStore
from repro.configs import get_smoke_config
from repro.core.elastic import ElasticTrainer
from repro.data.synthetic import TokenStream
from repro.models import moe as moe_lib
from repro.models.spec import init_params
from repro.optim.optimizers import adamw

CHIP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "benchmarks", "chip", "configs")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "deepseek_v2_lite_l5", os.path.join(CHIP, "deepseek-v2-lite-l5.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def small_cfg(held: int = 8, offset: int = 4, experts: int = 16) -> dict:
    """The benchmark's configuration at CPU size: every width cut, the
    kinds of layer, the routing and YaRN kept."""
    with open(os.path.join(CHIP, "deepseek-v2-lite-l5.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
               moe_intermediate_size=32, vocab_size=256,
               n_routed_experts=held, expert_offset=offset)
    cfg["published"] = dict(cfg["published"], n_routed_experts=experts)
    return cfg


@pytest.fixture
def f32(monkeypatch):
    """Run the program in float32, so that it meets the float32 reference
    to round-off (its bf16 agreement is the chip benchmark's check)."""
    def embed_f32(embedding, tokens, scale=None):
        return jnp.take(embedding, tokens, axis=0).astype(jnp.float32)

    monkeypatch.setattr(L, "embed_tokens", embed_f32)


def _params(model, seed=0):
    """The program's initial weights, with every stored gain and the
    router's spread made non-trivial."""
    params = model.init(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [p + 0.1 * jax.random.normal(k, p.shape) for p, k in
              zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("held,offset,seq", [(16, 0, 24), (8, 4, 24),
                                              (8, 4, 1024)],
                         ids=["all_experts", "a_share", "two_query_blocks"])
def test_logits_and_gradients_match_the_reference(f32, held, offset, seq):
    cfg = small_cfg(held, offset)
    model, _ = REF.program(cfg)
    params = _params(model)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, seq)),
                         jnp.int32)
    logits, _ = model.forward(params, {"tokens": tokens})
    for i in range(2):
        want = REF.reference_logits(cfg, params, tokens[i])
        assert _rel(logits[i], want) < 1e-5
    batch = {"tokens": tokens, "labels": labels}
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    ref_loss, ref_grads = REF.reference_grad(cfg, params, batch,
                                             lambda a: a)
    assert abs(float(loss) - ref_loss) < 1e-5 * abs(ref_loss)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        want = ref_grads
        for p in path:
            want = want[p.key]
        assert _rel(g, want) < 1e-4, jax.tree_util.keystr(path)


def _layer(held, offset, experts=16, top_k=6, shared=2):
    cfg = dataclasses.replace(
        get_smoke_config("deepseek-v2-lite"), n_experts=experts,
        top_k=top_k, experts_held=held, expert_offset=offset,
        n_shared_experts=shared)
    return cfg, moe_lib.dropless_specs(cfg, 1)


def _one(p):
    return jax.tree_util.tree_map(lambda a: a[0], p)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each: their routed parts, with the
    shared experts counted once, give the layer that holds all sixteen."""
    full_cfg, full_specs = _layer(0, 0)
    full = _one(init_params(jax.random.PRNGKey(0), full_specs))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, full_cfg.d_model))
    want, aux_full, loads_full = moe_lib.moe_dropless(full_cfg, full, x)
    # What every share computes alike: the shared experts (a share whose
    # routed experts are zero gives them alone).
    cfg0, _ = _layer(2, 0)
    shared = moe_lib.moe_dropless(cfg0, dict(full, **{
        k: jnp.zeros_like(full[k][:2]) for k in ("wi_gate", "wi_up", "wo")}),
        x)[0]
    total, loads = shared, []
    for i in range(8):
        cfg, _ = _layer(2, 2 * i)
        p = dict(full, **{k: full[k][2 * i:2 * i + 2]
                          for k in ("wi_gate", "wi_up", "wo")})
        out, aux, held = moe_lib.moe_dropless(cfg, p, x)
        assert float(aux) == pytest.approx(float(aux_full), rel=1e-6)
        total = total + (out - shared)
        loads.append(held)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(loads),
                                  np.asarray(loads_full))
    assert int(np.sum(loads_full)) == 2 * 12 * 6


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    """A router that sends every token to expert 5 first: the share that
    holds experts 4-7 computes all 24 of those assignments, where the
    capacity dispatch would drop all but its capacity."""
    cfg, specs = _layer(4, 4, shared=0)
    p = _one(init_params(jax.random.PRNGKey(0), specs))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                  (2, 12, cfg.d_model)))
    p["router"] = p["router"].at[:, 5].set(10.0)
    out, _, loads = moe_lib.moe_dropless(cfg, p, x)
    gate, eid, _ = moe_lib.route(cfg, p["router"], x)
    assert bool(jnp.all(eid[..., 0] == 5))
    assert int(loads[1]) == 24
    # Every assignment to experts 4-7, computed densely.
    h = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["wi_gate"]))
         * jnp.einsum("bsd,edf->bsef", x, p["wi_up"]))
    y = jnp.einsum("bsef,efd->bsed", h, p["wo"])
    w = jnp.sum(jnp.where((eid[..., None] == 4 + jnp.arange(4)),
                          gate[..., None], 0.0), axis=2)           # [B,S,4]
    want = jnp.einsum("bsed,bse->bsd", y, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_loop_counts_held_assignments_at_log_steps(tmp_path):
    cfg = dataclasses.replace(get_smoke_config("deepseek-v2-lite"),
                              experts_held=2, expert_offset=1)
    from repro.models.registry import build_model

    data = TokenStream(cfg.vocab_size, 16, seed=0)
    tr = ElasticTrainer(build_model(cfg), adamw(), data,
                        CheckpointStore(str(tmp_path)), base_lr_1w=1e-3,
                        m_per_worker=2, dataset_size=1000)
    rec = tr.train_segment(1, 5, resume=False, log_every=2)
    assert [s for s, _, _ in rec.losses] == [0, 2, 4]
    held = tr.registry.counters()["moe.assignments_held"]
    # Three log steps, two MoE layers, 32 tokens each, top-2 of 4 experts:
    # at most every assignment, and some land on the two held here.
    assert 0 < held <= 3 * 2 * 32 * 2
    assert tr.registry.peaks()["moe.busiest_expert_ratio"] >= 1.0
