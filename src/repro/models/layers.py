"""Transformer building blocks: norms, RoPE/M-RoPE, GQA flash attention
(causal / sliding-window / cross), gated MLPs.

Attention is a blockwise online-softmax scan over KV (pure-jnp flash):
memory is O(S·block) instead of O(S²), which is what lets prefill_32k
and train_4k lower without materializing score matrices.  Each scan
body is rematerialized, so autodiff recomputes block scores backward —
flash-attention backward complexity, in plain JAX.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import Spec

_NEG = -1e30

# Analysis mode: fully unroll inner scans so compiled-HLO cost analysis
# counts every iteration (XLA counts a while body once).  Set by
# launch/dryrun.py around lowering; never on in training/tests.
_ANALYSIS_UNROLL = False


def set_analysis_unroll(v: bool):
    global _ANALYSIS_UNROLL
    _ANALYSIS_UNROLL = bool(v)


def scan_unroll():
    return _ANALYSIS_UNROLL


# ---- norms -----------------------------------------------------------------

def norm_spec(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": Spec((d,), ("embed",), "ones"),
                "bias": Spec((d,), ("embed",), "zeros")}
    return {"scale": Spec((d,), ("embed",), "ones")}


def apply_norm(cfg: ModelConfig, p, x, eps: Optional[float] = None):
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        var = (xf ** 2).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


# ---- RoPE / M-RoPE ---------------------------------------------------------

def rope_freqs(cfg: ModelConfig):
    hd = cfg.head_dim_
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))


def apply_rope(cfg: ModelConfig, x, positions):
    """x: (B, S, H, D).  positions: (B, S) int32, or (3, B, S) for M-RoPE
    (temporal/height/width sections, Qwen2-VL §2.1)."""
    inv = rope_freqs(cfg)  # (D/2,)
    if cfg.mrope_sections is not None:
        # frequency slot j rotates by the position stream (temporal /
        # height / width) that owns its section.
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[None],
                                         (3,) + positions.shape)
        sec = jnp.concatenate([
            jnp.full((n,), i, jnp.int32)
            for i, n in enumerate(cfg.mrope_sections)])  # (D/2,)
        pos = positions[sec]  # (D/2, B, S)
        ang = pos.transpose(1, 2, 0).astype(jnp.float32) * inv[None, None, :]
    else:
        ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---- blockwise flash attention (pure jnp) ----------------------------------

def flash_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                    q_offset=0, kv_valid_len=None, block: int = 512,
                    block_q: int = 4096, scale: Optional[float] = None):
    """q: (B, S, Hq, D); k, v: (B, T, Hkv, D).  GQA via head grouping.

    ``q_offset``: global position of q[0] relative to k[0] (decode /
    chunked prefill).  ``window``: sliding-window width (None = full).
    ``kv_valid_len``: (B,) valid kv length (padding mask).
    ``scale``: score scale (None = D ** -0.5).
    Long sequences are additionally blocked over q (``block_q``) so the
    live score/accumulator tensors stay O(block_q·block), not O(S·block)
    — prefill_32k peaked at 61 GiB/chip without it.
    """
    B, S, Hq, D = q.shape
    if S > block_q:
        nq = -(-S // block_q)
        pad = nq * block_q - S
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
        qb = qp.reshape(B, nq, block_q, Hq, D).transpose(1, 0, 2, 3, 4)
        offs = q_offset + jnp.arange(nq, dtype=jnp.int32) * block_q

        def one(args):
            qi, oi = args
            return flash_attention(qi, k, v, causal=causal, window=window,
                                   q_offset=oi, kv_valid_len=kv_valid_len,
                                   block=block, block_q=S, scale=scale)

        out = jax.lax.map(one, (qb, offs))
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * block_q, Hq, D)
        return out[:, :S]
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block = min(block, T)
    nblocks = -(-T // block)
    pad = nblocks * block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblocks, block, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblocks, block, Hkv, D).transpose(1, 0, 2, 3, 4)

    stage_dt = jnp.float32 if q.dtype == jnp.float32 else jnp.bfloat16
    qg = (q.reshape(B, S, Hkv, G, D)
          * (D ** -0.5 if scale is None else scale)).astype(stage_dt)
    qpos = q_offset + jnp.arange(S, dtype=jnp.int32)

    def body(carry, inp):
        m, l, acc = carry
        i, kblk, vblk = inp
        kpos = i * block + jnp.arange(block, dtype=jnp.int32)
        s = jnp.einsum("bshgd,bthd->bhgst", qg, kblk.astype(stage_dt),
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((S, block), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        mask &= (kpos < T)[None, :]
        mask = mask[None, None, None]  # (1, 1, 1, S, block)
        if kv_valid_len is not None:
            mask = mask & (kpos[None, None, None, None, :]
                           < kv_valid_len[:, None, None, None, None])
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(-1)
        acc_new = alpha[..., None] * acc + jnp.einsum(
            "bhgst,bthd->bhgsd", p.astype(stage_dt), vblk.astype(stage_dt),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, S), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, S, D), jnp.float32)
    idx = jnp.arange(nblocks, dtype=jnp.int32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, a0),
        (idx, kb, vb), unroll=_ANALYSIS_UNROLL)
    out = acc / (l[..., None] + 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D)


# ---- attention block --------------------------------------------------------

def attn_specs(cfg: ModelConfig):
    """(heads, head_dim) stored MERGED: heads×hd is divisible by the
    16-way model axis for every assigned arch even when the head count
    (40, 14, 12…) is not — jit in_shardings demands exact divisibility."""
    hd, d = cfg.head_dim_, cfg.d_model
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    s = {
        "wq": Spec((d, nq), ("embed", "heads")),
        "wk": Spec((d, nkv), ("embed", "heads")),
        "wv": Spec((d, nkv), ("embed", "heads")),
        "wo": Spec((nq, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = Spec((nq,), ("heads",), "zeros")
        s["bk"] = Spec((nkv,), ("heads",), "zeros")
        s["bv"] = Spec((nkv,), ("heads",), "zeros")
    return s


def qkv_project(cfg: ModelConfig, p, x, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim_
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.position_embedding == "rope":
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, positions)
    return q, k, v


def attn_out(p, o, dtype):
    B, S = o.shape[:2]
    return o.astype(dtype).reshape(B, S, -1) @ p["wo"].astype(dtype)


# ---- gated MLP ---------------------------------------------------------------

def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": Spec((d, f), ("embed", "mlp")),
        "w_up": Spec((d, f), ("embed", "mlp")),
        "w_down": Spec((f, d), ("mlp", "embed")),
    }


def apply_mlp(cfg: ModelConfig, p, x):
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = act(x @ p["w_gate"].astype(x.dtype))
    u = x @ p["w_up"].astype(x.dtype)
    return (g * u) @ p["w_down"].astype(x.dtype)
