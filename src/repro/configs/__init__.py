"""Architecture configs (one module per assigned architecture).

``get_config(arch)`` returns the full published config; ``smoke_config(arch)``
returns a structurally identical but tiny variant for CPU smoke tests — same
family, mixer layout, MoE/MLA/SSM structure, but small widths / few layers /
tiny vocab.  Full configs are only ever lowered via ShapeDtypeStructs
(launch/dryrun.py); they are never materialized on this container.
"""
import dataclasses

from repro.configs.base import (ATTN, FF_GELU, FF_MOE, FF_NONE, FF_RELU2,
                                FF_SWIGLU, MLA, SSM, MLAConfig, ModelConfig,
                                MoEConfig, SHAPES, ShapeConfig, SSMConfig,
                                count_active_params, count_params, get_config,
                                list_archs, register, shape_applicable)

# populate the registry
from repro.configs import (chameleon_34b, deepseek_v2_236b,  # noqa: F401
                           granite_4_0_h_micro, granite_moe_3b_a800m,
                           jamba_v0_1_52b, mamba2_1_3b, minitron_4b,
                           seamless_m4t_large_v2, starcoder2_7b, yi_6b, yi_9b)

ALL_ARCHS = list_archs()


def smoke_config(arch: str, *, layers_per_period: int = 1) -> ModelConfig:
    """Tiny structurally-faithful variant of ``arch`` for CPU smoke tests."""
    cfg = get_config(arch)
    period = cfg.layer_period()
    num_layers = max(2, period * layers_per_period)
    prefix = cfg.moe.first_dense if cfg.moe else 0
    num_layers += prefix

    kw = dict(
        name=cfg.name + "-smoke",
        num_layers=num_layers,
        d_model=64,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=128,
        expected_params=0.0,
    )
    if cfg.num_heads:
        kw.update(num_heads=4, num_kv_heads=2 if cfg.num_kv_heads < cfg.num_heads else 4,
                  head_dim=16)
    if cfg.mla is not None:
        kw.update(mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16),
                  num_heads=4, num_kv_heads=4, head_dim=16)
    if cfg.moe is not None:
        kw.update(moe=dataclasses.replace(
            cfg.moe, num_experts=4,
            experts_per_token=min(2, cfg.moe.experts_per_token),
            d_ff_expert=32))
    if cfg.ssm is not None:
        kw.update(ssm=dataclasses.replace(
            cfg.ssm, d_state=16, head_dim=16, num_groups=1, chunk=8))
    if cfg.enc_layers:
        kw.update(enc_layers=2)
    return cfg.with_(**kw)


__all__ = [
    "ATTN", "MLA", "SSM", "FF_SWIGLU", "FF_GELU", "FF_RELU2", "FF_MOE",
    "FF_NONE", "ModelConfig", "MoEConfig", "MLAConfig", "SSMConfig",
    "ShapeConfig", "SHAPES", "ALL_ARCHS", "get_config", "smoke_config",
    "list_archs", "count_params", "count_active_params", "shape_applicable",
    "register",
]
