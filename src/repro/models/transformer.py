"""Decoder-only LM assembly: dense / MoE / SSM / hybrid stacks.

Layers are *scanned* (params stacked on a leading 'layers' axis) so a
64-layer model traces one layer body — compile time and HLO size stay
flat with depth, and the FSDP all-gathers pipeline across the scan.
``mode`` selects the path:

    train    — full-sequence mixing, no cache
    prefill  — full-sequence mixing + write paged-KV / final states
    decode   — one token against the caches (paged attention / O(1)
               recurrences)

Caches ride through the scan as per-layer xs/ys (KVLayer arrays are
stacked on the same leading axis as params).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as Lyr
from repro.models import moe as Moe
from repro.models import params as Prm
from repro.models import rglru as Rgl
from repro.models import ssm as Ssm
from repro.models.params import Spec
from repro.paged import kv_cache as KV
from repro.parallel.sharding import constrain


class Caches(NamedTuple):
    """Decode-time state, all stacked over their layer population."""
    kv: Optional[KV.PagedKV] = None      # attention layers
    ssm_h: Optional[Any] = None          # (Lr, B, H, P, N) f32
    ssm_conv: Optional[Any] = None       # (Lr, B, W-1, conv_dim)
    # a layer-list model: each slot's state pages in the page heap,
    # (B, Mamba layers, pages a layer) int32, -1 = none; and the tokens
    # routed to each held expert, (layers, held) int32, summed on device
    state_table: Optional[Any] = None
    expert_tokens: Optional[Any] = None


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def _ffn_specs(cfg: ModelConfig):
    if cfg.num_experts:
        return Moe.moe_specs(cfg)
    return Lyr.mlp_specs(cfg)


def block_specs(cfg: ModelConfig):
    if cfg.family == "ssm":
        return {"norm": Lyr.norm_spec(cfg), "mixer": Ssm.ssm_specs(cfg)}
    s = {"norm1": Lyr.norm_spec(cfg), "attn": Lyr.attn_specs(cfg),
         "ffn": _ffn_specs(cfg)}
    if not cfg.parallel_block:
        s["norm2"] = Lyr.norm_spec(cfg)
    return s


def hybrid_triple_specs(cfg: ModelConfig):
    rec = {"norm1": Lyr.norm_spec(cfg), "mixer": Rgl.rglru_specs(cfg),
           "norm2": Lyr.norm_spec(cfg), "ffn": Lyr.mlp_specs(cfg)}
    att = {"norm1": Lyr.norm_spec(cfg), "attn": Lyr.attn_specs(cfg),
           "norm2": Lyr.norm_spec(cfg), "ffn": Lyr.mlp_specs(cfg)}
    return {"rec1": rec, "rec2": rec, "attn": att}


def pattern_block_specs(cfg: ModelConfig, kind: str):
    """One layer of a layer-list stack: its mixer, then the MoE."""
    mixer = ({"mixer": Ssm.ssm_specs(cfg)} if kind == "mamba"
             else {"attn": Lyr.attn_specs(cfg)})
    return {"norm1": Lyr.norm_spec(cfg), **mixer,
            "norm2": Lyr.norm_spec(cfg), "ffn": Moe.moe_specs(cfg)}


# a layer list's kinds and the params key each kind's stack is under
PATTERN_KINDS = {"mamba": "mamba", "attention": "attn"}


def lm_specs(cfg: ModelConfig):
    v, d = cfg.padded_vocab, cfg.d_model
    s = {"embed": Spec((v, d), ("vocab", "embed")),
         "final_norm": Lyr.norm_spec(cfg)}
    if cfg.layer_types is not None:
        for kind, key in PATTERN_KINDS.items():
            n = cfg.layer_types.count(kind)
            if n:
                s[key] = Prm.stack(pattern_block_specs(cfg, kind), n)
    elif cfg.family == "hybrid":
        ntri, tail = divmod(cfg.num_layers, cfg.attn_period)
        s["triples"] = Prm.stack(hybrid_triple_specs(cfg), ntri)
        if tail:
            rec = hybrid_triple_specs(cfg)["rec1"]
            s["tail"] = Prm.stack(rec, tail)
    else:
        s["blocks"] = Prm.stack(block_specs(cfg), cfg.num_layers)
    if not cfg.tie_embeddings:
        s["lm_head"] = Spec((d, v), ("embed", "vocab"))
    if cfg.param_dtype != "float32":
        dt = jnp.dtype(cfg.param_dtype)
        s = jax.tree.map(lambda x: x._replace(dtype=dt), s,
                         is_leaf=lambda x: isinstance(x, Spec))
    return s


def num_attn_layers(cfg: ModelConfig) -> int:
    if cfg.layer_types is not None:
        return cfg.layer_types.count("attention")
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_period
    return cfg.num_layers


def num_rec_layers(cfg: ModelConfig) -> int:
    """Layers whose state lives in dense per-slot arrays (a layer-list
    model keeps its recurrent state in pages: none)."""
    if cfg.layer_types is not None:
        return 0
    if cfg.family == "ssm":
        return cfg.num_layers
    if cfg.family == "hybrid":
        return cfg.num_layers - cfg.num_layers // cfg.attn_period
    return 0


# ---------------------------------------------------------------------------
# sub-blocks (single layer)
# ---------------------------------------------------------------------------

def _attn_mix(cfg, p, x, positions, mode, kvl, page_table, seq_lens,
              window):
    q, k, v = Lyr.qkv_project(cfg, p, x, positions)
    # windowed layers use ring page tables when the table is smaller
    # than the sequence needs (window-bounded KV — pages recycle).
    ring = (window is not None and page_table is not None
            and mode in ("decode", "prefill"))
    if mode == "decode":
        kvl = KV.append1(kvl, page_table, seq_lens, k, v, ring=ring)
        o = KV.paged_attend1(kvl, page_table, seq_lens + 1, q,
                             window=window, ring=ring)
    else:
        o = Lyr.flash_attention(q, k, v, causal=True, window=window)
        if mode == "prefill":
            kvl = KV.prefill_write1(kvl, page_table, k, v, ring=ring)
    return Lyr.attn_out(p, o, x.dtype), kvl


def _ffn(cfg, p, x, mode="train"):
    if cfg.num_experts:
        y, aux, _ = Moe.apply_moe(cfg, p, x, no_drop=(mode != "train"))
        return y, aux
    return Lyr.apply_mlp(cfg, p, x), jnp.float32(0.0)


def dense_block(cfg, p, x, positions, mode, kvl, page_table, seq_lens):
    window = cfg.sliding_window
    if cfg.parallel_block:
        h = Lyr.apply_norm(cfg, p["norm1"], x)
        a, kvl = _attn_mix(cfg, p["attn"], h, positions, mode, kvl,
                           page_table, seq_lens, window)
        f, aux = _ffn(cfg, p["ffn"], h, mode)
        return x + a + f, kvl, aux
    h = Lyr.apply_norm(cfg, p["norm1"], x)
    a, kvl = _attn_mix(cfg, p["attn"], h, positions, mode, kvl,
                       page_table, seq_lens, window)
    x = x + a
    h = Lyr.apply_norm(cfg, p["norm2"], x)
    f, aux = _ffn(cfg, p["ffn"], h, mode)
    return x + f, kvl, aux


def ssm_block(cfg, p, x, mode, cache):
    h = Lyr.apply_norm(cfg, p["norm"], x)
    y, new_cache = Ssm.apply_ssm_layer(
        cfg, p["mixer"], h, cache if mode == "decode" else None)
    return x + y, new_cache


def rec_block(cfg, p, x, mode, cache):
    h = Lyr.apply_norm(cfg, p["norm1"], x)
    y, new_cache = Rgl.apply_rglru_layer(
        cfg, p["mixer"], h, cache if mode == "decode" else None)
    x = x + y
    h = Lyr.apply_norm(cfg, p["norm2"], x)
    return x + Lyr.apply_mlp(cfg, p["ffn"], h), new_cache


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def _remat(fn, policy):
    if policy == "none":
        return fn
    pol = None if policy == "full" else getattr(
        jax.checkpoint_policies, policy)
    return jax.checkpoint(fn, policy=pol, prevent_cse=False)


def uniform_stack(cfg, params, x, positions, mode, caches: Caches,
                  remat_policy="full"):
    """dense / moe / ssm: scan over the stacked blocks."""
    kv = caches.kv

    if cfg.family == "ssm":
        def body(carry, inp):
            x, aux = carry
            x = constrain(x, "batch", "seq", "act_embed")
            p_l, (h_l, conv_l) = inp
            cache = (h_l, conv_l) if mode == "decode" else None
            x, new_cache = ssm_block(cfg, p_l, x, mode, cache)
            return (x, aux), new_cache
        # non-decode: dummy per-layer placeholders (states still come back
        # stacked as ys, which is how prefill seeds the decode caches)
        xs_cache = ((caches.ssm_h, caches.ssm_conv) if mode == "decode"
                    else (jnp.zeros((cfg.num_layers, 1)),
                          jnp.zeros((cfg.num_layers, 1))))
        (x, aux), st = jax.lax.scan(
            _remat(body, remat_policy), (x, jnp.float32(0.0)),
            (params["blocks"], xs_cache), unroll=Lyr.scan_unroll())
        new = Caches(kv=None, ssm_h=st[0], ssm_conv=st[1])
        return x, aux, new

    page_table = None if kv is None else kv.page_table
    seq_lens = None if kv is None else kv.seq_lens

    def body(carry, inp):
        x, aux = carry
        x = constrain(x, "batch", "seq", "act_embed")
        p_l, kv_l = inp
        x, kv_l, a = dense_block(cfg, p_l, x, positions, mode, kv_l,
                                 page_table, seq_lens)
        return (x, aux + a), kv_l

    kv_xs = None if kv is None else kv.layers
    (x, aux), kv_layers = jax.lax.scan(
        _remat(body, remat_policy), (x, jnp.float32(0.0)),
        (params["blocks"], kv_xs), unroll=Lyr.scan_unroll())
    new_kv = None if kv is None else kv._replace(layers=kv_layers)
    return x, aux, Caches(kv=new_kv)


def hybrid_stack(cfg, params, x, positions, mode, caches: Caches,
                 remat_policy="full"):
    """recurrentgemma: scan over (rec, rec, attn) triples + rec tail."""
    kv = caches.kv
    ntri = cfg.num_layers // cfg.attn_period
    tail = cfg.num_layers - ntri * cfg.attn_period
    page_table = None if kv is None else kv.page_table
    seq_lens = None if kv is None else kv.seq_lens

    def triple_body(carry, inp):
        x = carry
        x = constrain(x, "batch", "seq", "act_embed")
        p_t, kv_l, (h1, c1), (h2, c2) = inp
        cache1 = (h1, c1) if mode == "decode" else None
        cache2 = (h2, c2) if mode == "decode" else None
        x, nc1 = rec_block(cfg, p_t["rec1"], x, mode, cache1)
        x, nc2 = rec_block(cfg, p_t["rec2"], x, mode, cache2)
        h = Lyr.apply_norm(cfg, p_t["attn"]["norm1"], x)
        a, kv_l = _attn_mix(cfg, p_t["attn"]["attn"], h, positions, mode,
                            kv_l, page_table, seq_lens, cfg.local_window)
        x = x + a
        h = Lyr.apply_norm(cfg, p_t["attn"]["norm2"], x)
        x = x + Lyr.apply_mlp(cfg, p_t["attn"]["ffn"], h)
        return x, (kv_l, nc1, nc2)

    def _dummy_rec(n):
        return (jnp.zeros((n, 1)), jnp.zeros((n, 1)))

    hs, cs = [], []
    if ntri > 0:
        rec_xs = ((caches.ssm_h[:ntri], caches.ssm_conv[:ntri]),
                  (caches.ssm_h[ntri:2 * ntri],
                   caches.ssm_conv[ntri:2 * ntri])
                  ) if mode == "decode" else (_dummy_rec(ntri),
                                              _dummy_rec(ntri))
        kv_xs = None if kv is None else kv.layers
        x, (kv_layers, nc1, nc2) = jax.lax.scan(
            _remat(triple_body, remat_policy), x,
            (params["triples"], kv_xs, rec_xs[0], rec_xs[1]),
            unroll=Lyr.scan_unroll())
        hs, cs = [nc1[0], nc2[0]], [nc1[1], nc2[1]]
    else:  # probe configs: tail-only stacks (no attention layers)
        kv_layers = None if kv is None else kv.layers
    if tail:
        def tail_body(carry, inp):
            x = carry
            p_l, (h_l, c_l) = inp
            cache = (h_l, c_l) if mode == "decode" else None
            x, nc = rec_block(cfg, p_l, x, mode, cache)
            return x, nc
        t_xs = ((caches.ssm_h[2 * ntri:], caches.ssm_conv[2 * ntri:])
                if mode == "decode" else _dummy_rec(tail))
        x, nct = jax.lax.scan(_remat(tail_body, remat_policy), x,
                              (params["tail"], t_xs),
                              unroll=Lyr.scan_unroll())
        hs.append(nct[0])
        cs.append(nct[1])

    new_kv = None if kv is None else kv._replace(layers=kv_layers)
    new = Caches(kv=new_kv, ssm_h=jnp.concatenate(hs, 0),
                 ssm_conv=jnp.concatenate(cs, 0))
    return x, jnp.float32(0.0), new


def _runs(layer_types):
    """(kind, first index within its kind, length) of each run of
    consecutive layers of one kind, in layer order."""
    runs, seen = [], {}
    for kind in layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in runs]


def pattern_stack(cfg, params, x, positions, mode, caches: Caches,
                  remat_policy="full"):
    """A stack driven by ``cfg.layer_types`` (granite-4.0-h).  Each layer:
    pre-norm → mixer (Mamba-2, or attention without rotary embedding at
    ``attn_scale``) → residual + ``residual_multiplier`` × branch →
    pre-norm → held-expert MoE plus the shared expert → residual +
    ``residual_multiplier`` × branch.

    Parameters are stacked per kind and each layer's are indexed inside
    a scan over its run of consecutive layers of one kind, so a kind's
    layer body is traced once per run and never unrolled.  In serving
    the page heap (``caches.kv.layers``: the attention layers' K/V and
    the Mamba layers' state pages) is a scan carry, read and written at
    the pages the slot tables name and never copied whole.  The prefill
    writes each Mamba layer's final SSD state and conv tail into the
    row's state pages; decode reads and writes them."""
    kv = caches.kv
    serving = mode != "train"
    rm = cfg.residual_multiplier
    heap = kv.layers if serving else None
    page_table = kv.page_table if serving else None
    seq_lens = kv.seq_lens if serving else None
    table = caches.state_table
    valid = None if table is None else table[:, 0, 0] >= 0

    def moe(p, x):
        h = Lyr.apply_norm(cfg, p["norm2"], x)
        f, aux, load = Moe.apply_moe(cfg, p["ffn"], h, no_drop=serving,
                                     valid=valid)
        return x + rm * f, aux, load

    def layer(kind, carry, i):
        x, heap, aux = carry
        x = constrain(x, "batch", "seq", "act_embed")
        p = jax.tree.map(lambda a: a[i], params[PATTERN_KINDS[kind]])
        h = Lyr.apply_norm(cfg, p["norm1"], x)
        if kind == "mamba":
            ids = None if table is None else table[:, i]
            state = (KV.read_state(cfg, heap, ids) if mode == "decode"
                     else None)
            y, (hf, conv) = Ssm.apply_ssm_layer(cfg, p["mixer"], h, state)
            if serving:   # an admission's row holds all its pages
                heap = KV.write_state(cfg, heap, ids, hf, conv,
                                      holes=mode == "decode")
        else:
            q, k, v = Lyr.qkv_project(cfg, p["attn"], h, positions)
            if mode == "decode":
                heap = KV.append1_at(heap, i, page_table, seq_lens, k, v)
                o = KV.paged_attend1(KV.layer_of(heap, i), page_table,
                                     seq_lens + 1, q, scale=cfg.attn_scale_,
                                     precision=jax.lax.Precision.HIGHEST)
            else:
                o = Lyr.flash_attention(q, k, v, causal=True,
                                        scale=cfg.attn_scale_)
                if mode == "prefill":
                    heap = KV.prefill_write_at(heap, i, page_table, k, v)
            y = Lyr.attn_out(p["attn"], o, x.dtype)
        x, a, load = moe(p, x + rm * y)
        return (x, heap, aux + a), load

    carry, loads = (x, heap, jnp.float32(0.0)), []
    for kind, i0, n in _runs(cfg.layer_types):
        body = _remat(functools.partial(layer, kind), remat_policy)
        carry, load = jax.lax.scan(body, carry,
                                   jnp.arange(i0, i0 + n, dtype=jnp.int32),
                                   unroll=Lyr.scan_unroll())
        loads.append(load)
    x, heap, aux = carry
    new = caches
    if serving:
        new = new._replace(kv=kv._replace(layers=heap))
    if caches.expert_tokens is not None:
        new = new._replace(expert_tokens=caches.expert_tokens
                           + jnp.concatenate(loads, 0))
    return x, aux, new


# ---------------------------------------------------------------------------
# top level
# ---------------------------------------------------------------------------

def embed(cfg, params, tokens, extra_embeds=None, dtype=jnp.bfloat16):
    x = params["embed"].astype(dtype)[tokens]
    if cfg.embedding_multiplier != 1:
        x = x * cfg.embedding_multiplier
    if extra_embeds is not None:
        x = x + extra_embeds.astype(dtype)
    return constrain(x, "batch", "seq", "act_embed")


def unembed(cfg, params, x):
    h = Lyr.apply_norm(cfg, params["final_norm"], x)
    w = (params["embed"].T if cfg.tie_embeddings
         else params["lm_head"]).astype(h.dtype)
    logits = constrain((h @ w).astype(jnp.float32),
                       "batch", "seq", "vocab")
    if cfg.logits_scaling != 1:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def forward(cfg: ModelConfig, params, tokens, positions=None,
            extra_embeds=None, mode="train", caches: Caches = Caches(),
            remat_policy="full", dtype=jnp.bfloat16,
            return_hidden: bool = False):
    """Returns (logits, aux_loss, new_caches) — or final-normed hidden
    states instead of logits when ``return_hidden`` (the chunked-CE
    training path avoids materializing (B, S, vocab) f32 logits)."""
    B, S = tokens.shape
    if positions is None:
        if mode == "decode":
            positions = caches.kv.seq_lens[:, None] if caches.kv is not None \
                else jnp.zeros((B, 1), jnp.int32)
        else:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    elif positions.ndim == 3 and positions.shape[1] == 3:
        # batch convention: M-RoPE positions arrive batch-first (B, 3, S)
        # so microbatch splitting is uniform; rope wants (3, B, S).
        positions = positions.transpose(1, 0, 2)
    x = embed(cfg, params, tokens, extra_embeds, dtype)
    stack = (pattern_stack if cfg.layer_types is not None
             else hybrid_stack if cfg.family == "hybrid" else uniform_stack)
    x, aux, new_caches = stack(cfg, params, x, positions, mode, caches,
                               remat_policy)
    if mode == "prefill":
        # only the last position's logits are consumed — unembedding all
        # S positions at 32k×(vocab) dominates prefill compute otherwise
        x = x[:, -1:]
    if return_hidden:
        return Lyr.apply_norm(cfg, params["final_norm"], x), aux, new_caches
    return unembed(cfg, params, x), aux, new_caches
