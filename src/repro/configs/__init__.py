"""Architecture config registry.

``get_config("arctic-480b")`` returns the full assigned config;
``get_reduced_config(name)`` returns a same-family reduced config for CPU
smoke tests (few layers, narrow widths, few experts, tiny vocab).
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                RGLRUConfig, ShapeConfig, SHAPES, SSMConfig,
                                YarnConfig, shape_applicable)

from repro.configs import (arctic_480b, deepseek_v2_lite_16b, gemma_2b,
                           llama3_405b, mamba2_780m, minicpm_2b,
                           musicgen_large, qwen2_vl_2b, recurrentgemma_2b,
                           starcoder2_3b)

_REGISTRY: dict[str, ModelConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (arctic_480b, deepseek_v2_lite_16b, qwen2_vl_2b, musicgen_large,
              minicpm_2b, gemma_2b, llama3_405b, starcoder2_3b,
              recurrentgemma_2b, mamba2_780m)
}

ARCH_NAMES = tuple(sorted(_REGISTRY))


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    return _REGISTRY[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {tuple(SHAPES)}")
    return SHAPES[name]


def all_cells(include_skipped: bool = False):
    """Yield (arch, shape, runnable, reason) for all 40 assigned cells."""
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, reason = shape_applicable(cfg, shape)
            if ok or include_skipped:
                yield arch, sname, ok, reason


def get_reduced_config(name: str) -> ModelConfig:
    """Same-family tiny config: one scan group, narrow dims, tiny vocab."""
    cfg = get_config(name)
    kw: dict = dict(
        name=cfg.name + "-reduced",
        num_layers=min(cfg.num_layers, 3),
        d_model=128,
        vocab_size=256,
    )
    if cfg.family != "ssm":
        kw.update(num_heads=4, num_kv_heads=min(cfg.num_kv_heads, 2) or 1,
                  head_dim=32, d_ff=256)
    if cfg.pos_embed == "mrope":
        kw["mrope_sections"] = (4, 6, 6)   # half of reduced head_dim = 16
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2),
            expert_d_ff=64,
            shared_d_ff=64 if cfg.moe.num_shared_experts else 0)
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=32, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32)
        kw["head_dim"] = 32
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, d_inner=256, head_dim=32, state_dim=16, chunk_size=16)
    if cfg.rglru is not None:
        kw["rglru"] = dataclasses.replace(
            cfg.rglru, lru_width=128, window_size=32, scan_chunk=16)
        kw["num_layers"] = 3   # one (rec, rec, attn) group
    if cfg.first_dense_layers:
        kw["first_dense_d_ff"] = 128
    if cfg.sliding_window:
        kw["sliding_window"] = 32
    return dataclasses.replace(cfg, **kw)


__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "SSMConfig", "RGLRUConfig",
    "YarnConfig",
    "ShapeConfig", "SHAPES", "ARCH_NAMES", "get_config", "get_shape",
    "get_reduced_config", "all_cells", "shape_applicable",
]
