"""Public model facade: build_model(cfg) → Model.

Uniform API over all ten assigned architectures:

    m = build_model(get_arch("mixtral-8x7b"))
    params = m.init(key)
    loss, metrics = m.loss(params, batch)
    caches = m.make_decode_caches(batch=8, max_seq=1024)
    logits, caches = m.prefill(params, batch, caches)
    logits, caches = m.decode_step(params, tokens, caches)

Batches are dicts: LM families use {tokens, targets[, mm_embeds,
positions]}; enc-dec uses {src_embeds, tokens, targets}.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as ED
from repro.models import params as Prm
from repro.models import ssm as Ssm
from repro.models import transformer as TF
from repro.paged import kv_cache as KV
from repro.parallel.sharding import constrain


def _chunked_ce(cfg, params, h, targets, chunk: int = 512):
    """Cross-entropy over sequence chunks with vocab-sharded logits.

    Materializing (B, S, V) f32 logits dominates big-vocab training
    memory (2.3 GiB/chip on qwen1.5-32b×train_4k) and leaves the (D, V)
    head-gradient partial unsharded; chunking bounds live logits to
    (B, chunk, V/TP) and keeps the W-grad partial vocab-sharded."""
    B, S, D = h.shape
    # one reshard off the model axis (SP) before the chunk loop: slicing
    # a seq-sharded operand per chunk makes GSPMD re-gather h for every
    # chunk in fwd+bwd (observed 4.7e11 B/dev on qwen1.5×train_4k).
    h = constrain(h, "batch", None, None)
    w = (params["embed"].T if cfg.tie_embeddings
         else params["lm_head"]).astype(h.dtype)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=-1)
    nb = (S + pad) // chunk
    hb = h.reshape(B, nb, chunk, D).swapaxes(0, 1)
    tb = targets.reshape(B, nb, chunk).swapaxes(0, 1)

    def body(carry, inp):
        ce_sum, n_sum = carry
        hc, tc = inp
        logits = constrain((hc @ w).astype(jnp.float32),
                           "batch", None, "vocab")
        if cfg.logits_scaling != 1:
            logits = logits / cfg.logits_scaling
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(
                logits / cfg.logit_softcap)
        mask = (tc >= 0).astype(jnp.float32)
        labels = jnp.maximum(tc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return (ce_sum + ((logz - gold) * mask).sum(),
                n_sum + mask.sum()), None

    from repro.models.layers import scan_unroll
    (ce_sum, n_sum), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (jnp.float32(0.0), jnp.float32(0.0)), (hb, tb),
        unroll=scan_unroll())
    return ce_sum / jnp.maximum(n_sum, 1), n_sum


def kv_dtype_for(cfg: ModelConfig, seq_len: int, batch: int):
    """int8 KV pages when bf16 would blow the v5e HBM budget
    (qwen1.5-32b @ decode_32k — see DESIGN.md §Arch-applicability)."""
    hd = cfg.head_dim_
    layers = TF.num_attn_layers(cfg) + (cfg.num_layers if cfg.is_encdec
                                        else 0)
    bytes_bf16 = 2 * layers * batch * seq_len * cfg.num_kv_heads * hd * 2
    return jnp.int8 if bytes_bf16 > 2.5e12 else jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---- params ------------------------------------------------------------
    def specs(self):
        if self.cfg.is_encdec:
            return ED.encdec_specs(self.cfg)
        return TF.lm_specs(self.cfg)

    def init(self, key):
        return Prm.materialize(self.specs(), key)

    def abstract_params(self):
        return Prm.abstract(self.specs())

    def logical_axes(self):
        return Prm.logical_axes(self.specs())

    # ---- training ----------------------------------------------------------
    def loss(self, params, batch, remat_policy: str = "full",
             dtype=jnp.bfloat16):
        cfg = self.cfg
        targets = batch["targets"]
        if cfg.is_encdec:
            enc = ED.encode(cfg, params, batch["src_embeds"],
                            remat_policy, dtype)
            caches = ED.EncDecCaches(None, None, None, None)
            h, _ = ED.decode_stack(cfg, params, batch["tokens"], enc,
                                   "train", caches, remat_policy, dtype,
                                   return_hidden=True)
            aux = jnp.float32(0.0)
        else:
            h, aux, _ = TF.forward(
                cfg, params, batch["tokens"],
                positions=batch.get("positions"),
                extra_embeds=batch.get("mm_embeds"),
                mode="train", remat_policy=remat_policy, dtype=dtype,
                return_hidden=True)
        ce, n_tok = _chunked_ce(cfg, params, h, targets)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux, "tokens": n_tok}

    # ---- serving -----------------------------------------------------------
    def make_decode_caches(self, batch: int, max_seq: int,
                           kv_dtype=None, num_pages: Optional[int] = None,
                           abstract: bool = False,
                           window_ring: bool = False):
        """``window_ring``: windowed archs (SWA / hybrid local attn) get
        ring page tables bounded by the window — pages recycle through
        the allocator instead of growing with the sequence."""
        cfg = self.cfg
        page = KV.PAGE_SIZE
        pps = -(-max_seq // page)
        if window_ring:
            window = (cfg.sliding_window
                      or (cfg.local_window if cfg.family == "hybrid"
                          else None))
            if window:
                pps = min(pps, window // page + 2)
        if cfg.layer_types is not None:
            # the page heap holds the recurrent state too: float32
            if kv_dtype not in (None, jnp.float32):
                raise ValueError(
                    f"{cfg.name} keeps its recurrent state in the page "
                    f"heap, which must be float32, not {kv_dtype}")
            kv_dtype = jnp.float32
        kv_dtype = kv_dtype or kv_dtype_for(cfg, max_seq, batch)
        mk = KV.abstract_paged_kv if abstract else KV.init_paged_kv

        def paged(n_layers):
            np_total = num_pages or batch * pps
            return mk(n_layers, np_total, batch, pps, cfg.num_kv_heads,
                      cfg.head_dim_, kv_dtype, page)

        if cfg.is_encdec:
            return ED.EncDecCaches(
                self_kv=paged(cfg.num_layers),
                cross_k=None, cross_v=None, enc_valid=None)

        n_attn = TF.num_attn_layers(cfg)
        n_rec = TF.num_rec_layers(cfg)
        if cfg.layer_types is not None:
            return self._pattern_caches(batch, paged, kv_dtype, abstract)
        kv = paged(n_attn) if n_attn else None
        ssm_h = ssm_conv = None
        if n_rec:
            if cfg.family == "ssm":
                h_shape = (n_rec, batch, cfg.ssm_nheads, cfg.ssm_headdim,
                           cfg.ssm_state)
                c_shape = (n_rec, batch, cfg.ssm_conv - 1,
                           Ssm.conv_dim(cfg))
            else:  # hybrid RG-LRU
                r = cfg.lru_width or cfg.d_model
                h_shape = (n_rec, batch, r)
                c_shape = (n_rec, batch, 3, r)
            if abstract:
                ssm_h = jax.ShapeDtypeStruct(h_shape, jnp.float32)
                ssm_conv = jax.ShapeDtypeStruct(c_shape, jnp.bfloat16)
            else:
                ssm_h = jnp.zeros(h_shape, jnp.float32)
                ssm_conv = jnp.zeros(c_shape, jnp.bfloat16)
        return TF.Caches(kv=kv, ssm_h=ssm_h, ssm_conv=ssm_conv)

    def _pattern_caches(self, batch, paged, kv_dtype, abstract):
        """A layer-list model's caches: one page heap for the attention
        layers' K/V and the Mamba layers' state pages, each slot's
        state-page table, and the per-expert token counts."""
        cfg = self.cfg
        n_mamba = cfg.layer_types.count("mamba")
        shapes = {"state_table": ((batch, n_mamba,
                                   KV.state_pages_per_layer(cfg)), -1),
                  "expert_tokens": ((cfg.num_layers, cfg.held_experts), 0)}
        if abstract:
            arrs = {k: jax.ShapeDtypeStruct(sh, jnp.int32)
                    for k, (sh, _) in shapes.items()}
        else:
            arrs = {k: jnp.full(sh, v, jnp.int32)
                    for k, (sh, v) in shapes.items()}
        return TF.Caches(kv=paged(TF.num_attn_layers(cfg)), **arrs)

    def prefill(self, params, batch, caches, remat_policy: str = "full",
                dtype=jnp.bfloat16):
        """Full-sequence pass that populates the decode caches.
        Returns (last-position logits, caches ready for decode_step)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        S = tokens.shape[1]
        if cfg.is_encdec:
            enc = ED.encode(cfg, params, batch["src_embeds"],
                            remat_policy, dtype)
            caches = caches._replace(
                enc_valid=batch.get("src_valid"))
            logits, caches = ED.decode_stack(
                cfg, params, tokens, enc, "prefill", caches,
                remat_policy, dtype)
            kv = caches.self_kv._replace(
                seq_lens=caches.self_kv.seq_lens + S)
            return logits[:, -1], caches._replace(self_kv=kv)
        logits, _, new = TF.forward(
            cfg, params, tokens, positions=batch.get("positions"),
            extra_embeds=batch.get("mm_embeds"), mode="prefill",
            caches=caches, remat_policy=remat_policy, dtype=dtype)
        if new.kv is not None:
            new = new._replace(kv=new.kv._replace(
                seq_lens=new.kv.seq_lens + S))
        return logits[:, -1], new

    def decode_step(self, params, tokens, caches, dtype=jnp.bfloat16):
        """One token per sequence.  tokens: (B, 1).  Returns (logits
        (B, vocab), caches with seq_lens advanced)."""
        cfg = self.cfg
        if cfg.is_encdec:
            logits, new = ED.decode_stack(
                cfg, params, tokens, None, "decode", caches,
                remat_policy="none", dtype=dtype)
            kv = new.self_kv._replace(seq_lens=new.self_kv.seq_lens + 1)
            return logits[:, 0], new._replace(self_kv=kv)
        logits, _, new = TF.forward(
            cfg, params, tokens, mode="decode", caches=caches,
            remat_policy="none", dtype=dtype)
        if new.kv is not None:
            new = new._replace(kv=new.kv._replace(
                seq_lens=new.kv.seq_lens + 1))
        elif caches.kv is None and cfg.family == "ssm":
            pass  # ssm caches carry no seq_lens
        return logits[:, 0], new


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
