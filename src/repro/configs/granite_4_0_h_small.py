"""Granite-4.0-H-Small (32B-A9B) — Mamba-2 and NoPE attention in a
published layer order, each layer followed by a mixture of 72 experts
(10 a token) plus a shared expert [hf: ibm-granite/granite-4.0-h-small
config.json, model_type granitemoehybrid; Mamba-2: arXiv:2405.21060].

Attention at layers 5, 15, 25 and 35 (32 query and 8 KV heads of 128,
no rotary embedding, scores scaled by 1/128); Mamba-2 elsewhere (128
heads of 64, state 128, one group, conv 4 with a bias, chunk 256);
embeddings ×12, each residual branch ×0.22, logits ÷16, RMSNorm eps
1e-5; weights in bfloat16, as published.

``STAGE0`` is what one chip holds of a deployment of 32: four pipeline
stages of ten layers (one whole period of the layer list, 9 Mamba and
1 attention) × 8-way expert parallelism in each stage.  It is stage 0,
expert rank 0: layers 0–9, experts 0–8 of the router's 72.
"""
import dataclasses

from repro.configs.base import ModelConfig, register

LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = register(ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    num_layers=40,
    layer_types=LAYER_TYPES,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    num_experts=72,
    num_experts_per_tok=10,
    shared_expert_d_ff=1536,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_ngroups=1,
    ssm_conv=4,
    ssm_chunk=256,
    norm_eps=1e-5,
    position_embedding="nope",
    attn_scale=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_embeddings=True,
    param_dtype="bfloat16",
    source="[hf: ibm-granite/granite-4.0-h-small; arXiv:2405.21060]",
))


def share(cfg: ModelConfig, *, stages: int, stage: int, expert_ranks: int,
          rank: int) -> ModelConfig:
    """One chip's share: the layers of pipeline stage ``stage`` of
    ``stages`` and the experts of rank ``rank`` of ``expert_ranks``; the
    router keeps its published width and experts per token."""
    per_stage = cfg.num_layers // stages
    held = cfg.num_experts // expert_ranks
    return dataclasses.replace(
        cfg, name=f"{cfg.name}.stage{stage}",
        num_layers=per_stage,
        layer_types=cfg.layer_types[stage * per_stage:
                                    (stage + 1) * per_stage],
        num_held_experts=held, expert_offset=rank * held)


STAGE0 = register(share(CONFIG, stages=4, stage=0, expert_ranks=8,
                        rank=0))
