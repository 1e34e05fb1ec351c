"""Assigned-architecture config conformance (the table in the brief)."""
import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config, get_smoke, input_specs
from repro.configs.base import shape_applicable

EXPECTED = {
    # arch: (L, d_model, H, kv, d_ff, vocab)
    "deepseek-moe-16b": (28, 2048, 16, 16, 10944, 102400),
    "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
    "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
    "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
    "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
    "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
    "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
    "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
    "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_config_matches_assignment(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = EXPECTED[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v


def test_arch_details():
    ds = get_config("deepseek-moe-16b")
    assert ds.moe.num_experts == 64 and ds.moe.top_k == 6
    assert ds.moe.num_shared_experts == 2
    mx = get_config("mixtral-8x22b")
    assert mx.moe.num_experts == 8 and mx.moe.top_k == 2
    assert mx.swa_window > 0
    assert get_config("whisper-tiny").enc_layers == 4
    assert get_config("qwen3-8b").qk_norm
    assert get_config("xlstm-350m").block_pattern == ("mlstm", "slstm")
    assert get_config("recurrentgemma-2b").block_pattern == \
        ("rglru", "rglru", "attn")
    assert get_config("internvl2-2b").num_patches == 256


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_config_same_family(arch):
    full, smoke = get_config(arch), get_smoke(arch)
    assert full.family == smoke.family
    assert (full.moe is None) == (smoke.moe is None)
    assert smoke.param_count() < full.param_count() / 100


def test_long500k_applicability():
    """Sub-quadratic archs run long_500k; pure full-attention archs skip."""
    runs = {a for a in ARCH_IDS
            if shape_applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert runs == {"mixtral-8x22b", "xlstm-350m", "recurrentgemma-2b"}


def test_param_counts_roughly_match_names():
    # analytic counts should be in the ballpark the model names claim
    assert 14e9 < get_config("deepseek-moe-16b").param_count() < 20e9
    assert 120e9 < get_config("mixtral-8x22b").param_count() < 160e9
    assert 60e9 < get_config("deepseek-67b").param_count() < 75e9
    assert 2.5e9 < get_config("llama3.2-3b").param_count() < 4.5e9
    assert 0.25e9 < get_config("xlstm-350m").param_count() < 0.6e9
    # MoE active << total
    ds = get_config("deepseek-moe-16b")
    assert ds.active_param_count() < 0.3 * ds.param_count()


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_complete(arch, shape):
    cfg = get_config(arch)
    specs = input_specs(cfg, SHAPES[shape])
    kinds = {"train": {"tokens", "labels"}, "prefill": {"tokens"},
             "decode": {"token"}}[SHAPES[shape].kind]
    assert kinds <= set(specs)
    for s in specs.values():
        assert all(d > 0 for d in s.shape)
