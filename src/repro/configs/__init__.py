"""Arch registry: importing this package registers all assigned archs."""
from repro.configs.base import (ARCH_REGISTRY, SHAPES, ModelConfig,
                                ShapeConfig, cells_for, get_arch)
from repro.configs import (  # noqa: F401
    command_r_35b, granite_4_0_h_small, internlm2_20b, mamba2_780m, mixtral_8x7b, phi3_5_moe,
    qwen1_5_32b, qwen2_0_5b, qwen2_vl_2b, recurrentgemma_9b,
    seamless_m4t_large_v2)

ALL_ARCHS = tuple(sorted(ARCH_REGISTRY))

__all__ = ["ARCH_REGISTRY", "ALL_ARCHS", "SHAPES", "ModelConfig",
           "ShapeConfig", "cells_for", "get_arch"]
