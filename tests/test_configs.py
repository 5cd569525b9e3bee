"""The assigned architecture table, asserted exactly."""
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import Yarn

EXPECTED = {
    "qwen2.5-3b": dict(family="dense", n_layers=36, d_model=2048, n_heads=16,
                       n_kv_heads=2, d_ff=11008, vocab_size=151936,
                       qkv_bias=True),
    "qwen2-vl-2b": dict(family="vlm", n_layers=28, d_model=1536, n_heads=12,
                        n_kv_heads=2, d_ff=8960, vocab_size=151936,
                        mrope=True),
    "h2o-danube-1.8b": dict(family="dense", n_layers=24, d_model=2560,
                            n_heads=32, n_kv_heads=8, d_ff=6912,
                            vocab_size=32000, sliding_window=4096),
    "mamba2-780m": dict(family="ssm", n_layers=48, d_model=1536,
                        vocab_size=50280, ssm_state=128, d_ff=0),
    "jamba-v0.1-52b": dict(family="hybrid", n_layers=32, d_model=4096,
                           n_heads=32, n_kv_heads=8, d_ff=14336,
                           vocab_size=65536, n_experts=16, top_k=2,
                           attn_every=8),
    "qwen3-moe-30b-a3b": dict(family="moe", n_layers=48, d_model=2048,
                              n_heads=32, n_kv_heads=4, d_ff=768,
                              vocab_size=151936, n_experts=128, top_k=8),
    "gemma-2b": dict(family="dense", n_layers=18, d_model=2048, n_heads=8,
                     n_kv_heads=1, d_ff=16384, vocab_size=256000, d_head=256,
                     activation="geglu"),
    "dbrx-132b": dict(family="moe", n_layers=40, d_model=6144, n_heads=48,
                      n_kv_heads=8, d_ff=10752, vocab_size=100352,
                      n_experts=16, top_k=4),
    "whisper-base": dict(family="audio", n_layers=6, d_model=512, n_heads=8,
                         n_kv_heads=8, d_ff=2048, vocab_size=51865,
                         encoder_layers=6),
    "qwen2.5-14b": dict(family="dense", n_layers=48, d_model=5120,
                        n_heads=40, n_kv_heads=8, d_ff=13824,
                        vocab_size=152064, qkv_bias=True),
    "deepseek-v2-lite": dict(family="moe", n_layers=27, d_model=2048,
                             n_heads=16, d_ff=1408, vocab_size=102400,
                             kv_lora_rank=512, qk_nope_head_dim=128,
                             qk_rope_head_dim=64, v_head_dim=128,
                             n_experts=64, top_k=6, n_shared_experts=2,
                             first_dense_layers=1,
                             dense_d_ff=10944, rope_theta=10_000.0,
                             rope_yarn=Yarn(40.0, 4096, 32.0, 1.0, 0.707,
                                            0.707),
                             tie_embeddings=False),
}


def test_all_ten_archs_present():
    assert set(ARCH_IDS) == set(EXPECTED)


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_config_numbers(arch):
    cfg = get_config(arch)
    for k, v in EXPECTED[arch].items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    assert cfg.source  # every config cites its source


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_smoke_configs_are_reduced(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 4
    assert cfg.d_model <= 512
    assert cfg.vocab_size <= 512
    if cfg.is_moe:
        assert cfg.n_experts <= 4


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen2.5-14b", "gemma-2b",
                                  "mamba2-780m", "jamba-v0.1-52b",
                                  "dbrx-132b", "qwen3-moe-30b-a3b",
                                  "deepseek-v2-lite"])
def test_param_counts_in_expected_range(arch):
    """Full-config parameter counts should be near the advertised sizes."""
    bounds = {"qwen2.5-3b": (2.5e9, 4e9), "qwen2.5-14b": (12e9, 16e9),
              "gemma-2b": (2e9, 3.2e9), "mamba2-780m": (0.6e9, 1.0e9),
              "jamba-v0.1-52b": (45e9, 60e9), "dbrx-132b": (110e9, 145e9),
              "qwen3-moe-30b-a3b": (25e9, 35e9),
              "deepseek-v2-lite": (15.5e9, 16e9)}
    n = get_config(arch).param_count()
    lo, hi = bounds[arch]
    assert lo <= n <= hi, (arch, n)


def test_moe_active_params():
    cfg = get_config("qwen3-moe-30b-a3b")
    active = cfg.active_param_count()
    total = cfg.param_count()
    assert active < total * 0.25          # 8/128 experts active + shared
    assert 2e9 <= active <= 5e9           # "a3b" = ~3B active
