"""Config registry: exact published specs, param counts, layer layouts."""
import pytest

from repro.configs import (ALL_ARCHS, SHAPES, count_active_params,
                           count_params, get_config, shape_applicable,
                           smoke_config)
from repro.configs.base import ATTN, FF_MOE, MLA, SSM

EXPECTED_ARCHS = {
    "mamba2-1.3b", "granite-moe-3b-a800m", "deepseek-v2-236b",
    "seamless-m4t-large-v2", "starcoder2-7b", "yi-9b", "minitron-4b",
    "yi-6b", "jamba-v0.1-52b", "chameleon-34b", "granite-4.0-h-micro",
}


def test_all_eleven_archs_registered():
    assert set(ALL_ARCHS) == EXPECTED_ARCHS


@pytest.mark.parametrize("arch", sorted(EXPECTED_ARCHS))
def test_param_count_matches_published_size(arch):
    cfg = get_config(arch)
    n = count_params(cfg)
    assert cfg.expected_params > 0
    err = abs(n - cfg.expected_params) / cfg.expected_params
    assert err < 0.10, f"{arch}: {n/1e9:.2f}B vs expected {cfg.expected_params/1e9:.2f}B"


@pytest.mark.parametrize("arch", sorted(EXPECTED_ARCHS))
def test_tree_count_equals_analytic(arch):
    from repro.models import param_count
    cfg = get_config(arch)
    assert param_count(cfg) == count_params(cfg)


def test_exact_published_dims():
    c = get_config("deepseek-v2-236b")
    assert (c.num_layers, c.d_model, c.num_heads) == (60, 5120, 128)
    assert c.mla.kv_lora_rank == 512 and c.moe.num_experts == 160
    assert c.moe.experts_per_token == 6 and c.moe.num_shared_experts == 2
    c = get_config("yi-9b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (48, 4096, 32, 4, 11008, 64000)
    c = get_config("starcoder2-7b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (32, 4608, 36, 4, 18432, 49152)
    c = get_config("mamba2-1.3b")
    assert c.ssm.d_state == 128 and c.num_layers == 48 and c.d_model == 2048
    c = get_config("granite-moe-3b-a800m")
    assert c.moe.num_experts == 40 and c.moe.experts_per_token == 8
    c = get_config("jamba-v0.1-52b")
    assert c.moe.num_experts == 16 and c.moe.experts_per_token == 2
    c = get_config("seamless-m4t-large-v2")
    assert c.enc_layers == 24 and c.vocab_size == 256_206
    c = get_config("chameleon-34b")
    assert c.qk_norm and c.d_model == 8192
    c = get_config("granite-4.0-h-micro")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.resolved_head_dim, c.d_ff, c.vocab_size) == \
        (40, 2048, 32, 8, 64, 8192, 100_352)
    assert [i for i in range(40) if c.mixer_at(i) == "attn"] == [5, 15, 25, 35]
    assert (c.ssm.d_state, c.ssm.head_dim, c.ssm.expand, c.ssm.num_groups,
            c.ssm.conv_width, c.ssm.chunk) == (128, 64, 2, 1, 4, 256)
    assert (c.position_embedding_type, c.attention_multiplier,
            c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == ("nope", 0.015625, 12.0, 0.22, 8.0)
    assert c.tie_embeddings and c.moe is None


def test_moe_active_params():
    c = get_config("granite-moe-3b-a800m")
    assert count_active_params(c) < 1.0e9          # "a800m"
    c = get_config("deepseek-v2-236b")
    assert 18e9 < count_active_params(c) < 25e9    # ~21B active


def test_jamba_layer_layout():
    c = get_config("jamba-v0.1-52b")
    mixers = [c.mixer_at(i) for i in range(8)]
    assert mixers.count(ATTN) == 1 and mixers.count(SSM) == 7
    ffs = [c.ff_at(i) for i in range(8)]
    assert ffs.count(FF_MOE) == 4
    assert c.layer_period() == 8 and c.scan_layers() == (0, 32)


def test_deepseek_first_dense_layer():
    c = get_config("deepseek-v2-236b")
    assert c.ff_at(0) != FF_MOE and c.ff_at(1) == FF_MOE
    assert c.mixer_at(0) == MLA
    assert c.scan_layers() == (1, 59)


def test_long_context_applicability():
    long = SHAPES["long_500k"]
    runnable = [a for a in ALL_ARCHS if shape_applicable(get_config(a), long)[0]]
    assert sorted(runnable) == ["granite-4.0-h-micro", "jamba-v0.1-52b",
                                "mamba2-1.3b"]
    # all other shapes apply to every arch
    for s in ("train_4k", "prefill_32k", "decode_32k"):
        for a in ALL_ARCHS:
            assert shape_applicable(get_config(a), SHAPES[s])[0]


def test_cell_grid_is_40():
    from repro.launch.cells import all_cells
    cells = all_cells()
    # four shapes for each of the 11 archs (40 for the first ten)
    assert len(cells) == 44
    # long_500k is skipped for the 8 pure full-attention archs -> 36 runnable
    runnable = [c for c in cells if c[2]]
    assert len(runnable) == 36


@pytest.mark.parametrize("arch", sorted(EXPECTED_ARCHS))
def test_smoke_config_is_structurally_faithful(arch):
    full, small = get_config(arch), smoke_config(arch)
    assert small.family == full.family
    assert (small.moe is None) == (full.moe is None)
    assert (small.ssm is None) == (full.ssm is None)
    assert (small.mla is None) == (full.mla is None)
    assert (small.enc_layers > 0) == (full.enc_layers > 0)
    assert small.layer_period() == full.layer_period()
    assert count_params(small) < 2_000_000


def test_padded_vocab():
    c = get_config("mamba2-1.3b")
    assert c.padded_vocab % 256 == 0 and c.padded_vocab >= c.vocab_size
    assert c.padded_vocab % 16 == 0
