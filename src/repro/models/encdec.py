"""Encoder-decoder assembly (seamless-m4t): bidirectional encoder over
stubbed frame embeddings + causal decoder with cross-attention.

Decode caches: paged self-attention KV (grows per generated token, on
the allocator) + dense cross-attention KV (computed once at prefill
from the encoder output — fixed size, so it stays a plain tensor)."""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as Lyr
from repro.models import params as Prm
from repro.models.params import Spec
from repro.models.transformer import Caches, unembed
from repro.paged import kv_cache as KV
from repro.parallel.sharding import constrain


class EncDecCaches(NamedTuple):
    self_kv: Optional[KV.PagedKV]     # decoder self-attn, paged
    cross_k: Optional[Any]            # (Ld, B, Se, Hkv, hd)
    cross_v: Optional[Any]
    enc_valid: Optional[Any]          # (B,) encoder valid lengths


def enc_block_specs(cfg: ModelConfig):
    return {"norm1": Lyr.norm_spec(cfg), "attn": Lyr.attn_specs(cfg),
            "norm2": Lyr.norm_spec(cfg), "ffn": Lyr.mlp_specs(cfg)}


def dec_block_specs(cfg: ModelConfig):
    return {"norm1": Lyr.norm_spec(cfg), "attn": Lyr.attn_specs(cfg),
            "norm_x": Lyr.norm_spec(cfg), "xattn": Lyr.attn_specs(cfg),
            "norm2": Lyr.norm_spec(cfg), "ffn": Lyr.mlp_specs(cfg)}


def encdec_specs(cfg: ModelConfig):
    v, d = cfg.padded_vocab, cfg.d_model
    s = {
        "embed": Spec((v, d), ("vocab", "embed")),
        "enc_in": Spec((d, d), ("embed", None)),  # frame-embedding adapter
        "enc_blocks": Prm.stack(enc_block_specs(cfg), cfg.enc_layers),
        "enc_norm": Lyr.norm_spec(cfg),
        "dec_blocks": Prm.stack(dec_block_specs(cfg), cfg.num_layers),
        "final_norm": Lyr.norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = Spec((d, v), ("embed", "vocab"))
    return s


def encode(cfg, params, src_embeds, remat_policy="full",
           dtype=jnp.bfloat16):
    """src_embeds: (B, Se, D) stubbed modality frontend output."""
    x = (src_embeds.astype(dtype) @ params["enc_in"].astype(dtype))
    B, Se, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

    def body(x, p_l):
        x = constrain(x, "batch", "seq", "act_embed")
        h = Lyr.apply_norm(cfg, p_l["norm1"], x)
        q, k, v = Lyr.qkv_project(cfg, p_l["attn"], h, pos)
        o = Lyr.flash_attention(q, k, v, causal=False)
        x = x + Lyr.attn_out(p_l["attn"], o, x.dtype)
        h = Lyr.apply_norm(cfg, p_l["norm2"], x)
        return x + Lyr.apply_mlp(cfg, p_l["ffn"], h), None

    from repro.models.transformer import _remat
    x, _ = jax.lax.scan(_remat(body, remat_policy), x,
                        params["enc_blocks"], unroll=Lyr.scan_unroll())
    return Lyr.apply_norm(cfg, params["enc_norm"], x)


def _cross_kv(cfg, p_l, enc_out):
    """Cross-attention K/V from encoder output (no RoPE)."""
    B, S, _ = enc_out.shape
    k = enc_out @ p_l["wk"].astype(enc_out.dtype)
    v = enc_out @ p_l["wv"].astype(enc_out.dtype)
    if cfg.qkv_bias:
        k = k + p_l["bk"].astype(enc_out.dtype)
        v = v + p_l["bv"].astype(enc_out.dtype)
    hd = cfg.head_dim_
    return (k.reshape(B, S, cfg.num_kv_heads, hd),
            v.reshape(B, S, cfg.num_kv_heads, hd))


def decode_stack(cfg, params, tokens, enc_out, mode,
                 caches: EncDecCaches, remat_policy="full",
                 dtype=jnp.bfloat16, return_hidden: bool = False):
    """Decoder over target tokens.  mode train/prefill: full causal pass
    (cross-attn against enc_out); decode: one token vs caches."""
    B, S = tokens.shape
    x = params["embed"].astype(dtype)[tokens]
    kv = caches.self_kv
    page_table = None if kv is None else kv.page_table
    seq_lens = None if kv is None else kv.seq_lens
    if mode == "decode":
        pos = kv.seq_lens[:, None]
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(carry, inp):
        x, heap = carry
        x = constrain(x, "batch", "seq", "act_embed")
        p_l, i, cross = inp
        h = Lyr.apply_norm(cfg, p_l["norm1"], x)
        q, k, v = Lyr.qkv_project(cfg, p_l["attn"], h, pos)
        if mode == "decode":
            heap = KV.append1_at(heap, i, page_table, seq_lens, k, v)
            o = KV.paged_attend1(KV.layer_of(heap, i), page_table,
                                 seq_lens + 1, q)
        else:
            o = Lyr.flash_attention(q, k, v, causal=True)
            if mode == "prefill":
                heap = KV.prefill_write_at(heap, i, page_table, k, v)
        x = x + Lyr.attn_out(p_l["attn"], o, x.dtype)

        # cross attention
        h = Lyr.apply_norm(cfg, p_l["norm_x"], x)
        qx = h @ p_l["xattn"]["wq"].astype(h.dtype)
        if cfg.qkv_bias:
            qx = qx + p_l["xattn"]["bq"].astype(h.dtype)
        qx = qx.reshape(h.shape[0], h.shape[1], cfg.num_heads, cfg.head_dim_)
        if mode == "decode":
            kx, vx = cross
        else:
            kx, vx = _cross_kv(cfg, p_l["xattn"], enc_out)
        ox = Lyr.flash_attention(qx, kx, vx, causal=False,
                                 kv_valid_len=caches.enc_valid)
        x = x + Lyr.attn_out(p_l["xattn"], ox, x.dtype)

        h = Lyr.apply_norm(cfg, p_l["norm2"], x)
        x = x + Lyr.apply_mlp(cfg, p_l["ffn"], h)
        return (x, heap), (kx, vx)

    from repro.models.transformer import _remat
    heap = None if kv is None else kv.layers
    cross_xs = ((caches.cross_k, caches.cross_v) if mode == "decode"
                else (None, None))
    (x, heap), (ck, cv) = jax.lax.scan(
        _remat(body, remat_policy), (x, heap),
        (params["dec_blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32),
         cross_xs), unroll=Lyr.scan_unroll())
    new_kv = None if kv is None else kv._replace(layers=heap)
    new = EncDecCaches(self_kv=new_kv, cross_k=ck, cross_v=cv,
                       enc_valid=caches.enc_valid)
    if mode == "prefill":
        x = x[:, -1:]  # only the last position's logits are consumed
    if return_hidden:
        return Lyr.apply_norm(cfg, params["final_norm"], x), new
    return unembed(cfg, params, x), new
