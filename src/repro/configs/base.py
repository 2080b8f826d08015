"""Model/arch configuration system.

One flat frozen dataclass covers every assigned family (dense / moe /
ssm / hybrid / enc-dec / vlm / audio); per-arch files instantiate it
with the exact published numbers and register under their ``--arch`` id.

``smoke()`` returns the reduced same-family config every architecture's
CPU smoke test runs (few layers, narrow width, tiny vocab); the FULL
config is exercised only through the dry-run (ShapeDtypeStruct only).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

ARCH_REGISTRY = {}


def register(cfg: "ModelConfig") -> "ModelConfig":
    ARCH_REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> "ModelConfig":
    # populate the registry on first use
    from repro import configs  # noqa: F401  (imports all arch modules)
    if name not in ARCH_REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None          # default d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 1e6
    mrope_sections: Optional[Tuple[int, ...]] = None  # qwen2-vl M-RoPE
    sliding_window: Optional[int] = None    # mixtral SWA
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    act: str = "silu"                       # silu (SwiGLU) | gelu (GeGLU)
    parallel_block: bool = False            # command-r parallel attn+ffn
    tie_embeddings: bool = False
    logit_softcap: Optional[float] = None

    # moe: the router scores all ``num_experts``; this chip holds and
    # computes experts [expert_offset, expert_offset + held_experts)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25
    num_held_experts: Optional[int] = None  # None: every expert
    expert_offset: int = 0
    shared_expert_d_ff: int = 0             # 0: no shared expert

    # ssm (mamba2 SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_ngroups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 64

    # hybrid (recurrentgemma: every `attn_period`-th block is local attn)
    lru_width: Optional[int] = None
    attn_period: int = 3
    local_window: int = 2048

    # a stack served from its layer list (granite-4.0-h): one kind per
    # layer, "mamba" (Mamba-2 mixer) or "attention", each followed by
    # the MoE; the recurrent state lives in arena pages
    layer_types: Optional[Tuple[str, ...]] = None

    # published constants; the defaults leave every other config as is
    norm_eps: float = 1e-6
    position_embedding: str = "rope"        # rope | nope
    attn_scale: Optional[float] = None      # default head_dim ** -0.5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0             # logits are divided by it
    param_dtype: str = "float32"

    # encoder-decoder
    enc_layers: int = 0

    # modality frontend stub: None | "vision" | "audio"
    modality: Optional[str] = None

    # source annotation [source; verified-tier]
    source: str = ""

    # ---- derived -----------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def held_experts(self) -> int:
        return (self.num_experts if self.num_held_experts is None
                else self.num_held_experts)

    @property
    def attn_scale_(self) -> float:
        return (self.head_dim_ ** -0.5 if self.attn_scale is None
                else self.attn_scale)

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (see DESIGN.md §Arch-applicability)."""
        return (self.family in ("ssm", "hybrid")
                or self.sliding_window is not None)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so TP-16 sharding divides."""
        return -(-self.vocab_size // 256) * 256

    def param_count(self) -> int:
        """Analytic parameter count (drives MODEL_FLOPS = 6·N·D)."""
        d, f, v = self.d_model, self.d_ff, self.padded_vocab
        hd = self.head_dim_
        per_attn = (self.num_heads * hd * d
                    + 2 * self.num_kv_heads * hd * d
                    + self.num_heads * hd * d)
        per_mlp = 3 * d * f
        n = 0
        if self.layer_types is not None:
            di, ns, g = self.d_inner, self.ssm_state, self.ssm_ngroups
            per_mamba = (d * (2 * di + 2 * g * ns + self.ssm_nheads)
                         + di * d)
            per_moe = (self.held_experts * per_mlp + d * self.num_experts
                       + 3 * d * self.shared_expert_d_ff)
            n_attn = self.layer_types.count("attention")
            n += (n_attn * per_attn + (self.num_layers - n_attn) * per_mamba
                  + self.num_layers * per_moe)
        elif self.family == "ssm":
            di, ns = self.d_inner, self.ssm_state
            per = (d * (2 * di + 2 * self.ssm_ngroups * ns + self.ssm_nheads)
                   + di * d)
            n += self.num_layers * per
        elif self.family == "hybrid":
            lw = self.lru_width or d
            n_attn = self.num_layers // self.attn_period
            n_rec = self.num_layers - n_attn
            per_rec = d * lw * 2 + lw * d + 2 * lw  # in/out proj + gates
            n += n_attn * per_attn + n_rec * per_rec + self.num_layers * per_mlp
        else:
            layers = self.num_layers + self.enc_layers
            n += layers * per_attn
            if self.num_experts:
                n += self.num_layers * (self.num_experts * per_mlp
                                        + d * self.num_experts)
            else:
                n += layers * per_mlp
            if self.is_encdec:
                n += self.num_layers * per_attn  # cross attention
        n += v * d * (1 if self.tie_embeddings else 2)
        return n

    def active_param_count(self) -> int:
        """MoE: params touched per token (6·N_active·D)."""
        if not self.num_experts:
            return self.param_count()
        full = self.param_count()
        moe = (self.num_layers * self.held_experts * 3 * self.d_model
               * self.d_ff)
        active = self.num_layers * self.num_experts_per_tok * 3 \
            * self.d_model * self.d_ff
        return full - moe + active

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests.  A layer list
        keeps both kinds (its first attention layer and the Mamba layer
        before it), a held share smaller than the router's width, and
        the shared expert."""
        if self.layer_types is not None:
            a = self.layer_types.index("attention")
            return dataclasses.replace(
                self, name=self.name + "-smoke", num_layers=3,
                layer_types=("mamba", "attention", "mamba") if a else
                ("attention", "mamba", "mamba"),
                d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                d_ff=64, vocab_size=512, num_experts=8,
                num_experts_per_tok=3, num_held_experts=4,
                expert_offset=0, shared_expert_d_ff=96,
                ssm_state=32, ssm_headdim=16, ssm_chunk=16,
                attn_scale=1 / 32, param_dtype="float32")
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, self.attn_period + 1
                           if self.family == "hybrid" else 2),
            d_model=128,
            num_heads=4,
            num_kv_heads=max(1, min(self.num_kv_heads, 2)),
            d_ff=256,
            head_dim=32,
            vocab_size=512,
            num_experts=min(self.num_experts, 4),
            ssm_state=min(self.ssm_state, 32),
            ssm_headdim=16 if self.ssm_state else self.ssm_headdim,
            ssm_chunk=16,
            lru_width=128 if self.lru_width else None,
            local_window=64 if self.family == "hybrid" else self.local_window,
            sliding_window=64 if self.sliding_window else None,
            enc_layers=min(self.enc_layers, 2),
            mrope_sections=(4, 6, 6) if self.mrope_sections else None,
        )


# ---- input shape sets (assigned; seq_len × global_batch) -------------------
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def cells_for(cfg: ModelConfig):
    """The (arch × shape) cells this arch runs (skips per DESIGN.md)."""
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.sub_quadratic:
        names.append("long_500k")
    return [SHAPES[n] for n in names]
