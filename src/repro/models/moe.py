"""Mixture-of-Experts FFN with capacity-based dispatch.

Token→expert routing reuses the allocator's lane-aggregation machinery
(``groups.masked_rank``): each (token, k) pair is an *allocation
request* against its expert's capacity-C buffer, ranked per expert in
one masked prefix-sum; rank ≥ C means the request fails and the token
is dropped for that expert — the exact failure semantics of a bulk
``Ouroboros.alloc``.  This keeps MoE fully shardable: the buffers are
dense (E, C, D) tensors (E over 'model' when divisible — phi3.5's 16
experts shard exactly; otherwise d_ff takes the TP axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import groups
from repro.models import layers as Lyr
from repro.models.params import Spec
from repro.parallel.sharding import constrain


def moe_specs(cfg: ModelConfig):
    """The router scores all ``num_experts``; the expert weights are the
    ``held_experts`` this chip computes; a shared expert is added when
    ``shared_expert_d_ff`` is set."""
    d, f, e, h = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    s = {
        "router": Spec((d, e), ("embed", None)),
        "w_gate": Spec((h, d, f), ("expert", "embed", "mlp")),
        "w_up": Spec((h, d, f), ("expert", "embed", "mlp")),
        "w_down": Spec((h, f, d), ("expert", "mlp", "embed")),
    }
    if cfg.shared_expert_d_ff:
        s["shared"] = Lyr.mlp_specs(cfg, cfg.shared_expert_d_ff)
    return s


def apply_moe(cfg: ModelConfig, p, x, no_drop: bool = False, valid=None):
    """x: (B, S, D) → (y, aux_loss, load).  The router scores every
    expert and keeps the top k, weighted by a softmax over the k chosen
    (the softmax over all experts renormalized over the top k: the same
    weights).  Only the held experts [expert_offset, expert_offset +
    held_experts) are dispatched and computed; their weighted part is
    returned, plus the shared expert once.  ``load``: (held,) int32,
    the (token, choice) pairs routed to each held expert from the rows
    where ``valid`` (B,) holds (all rows when None).

    Per-batch-row capacity (C = cf·S·K/E per row) so the dispatch
    buffer (B, held, C, D) shards over the data axes with zero dispatch
    collectives — a globally-ranked buffer defeats GSPMD and replicates
    terabytes.  ``no_drop`` (serving prefill and decode): C = S, which
    drops nothing since a token picks an expert at most once."""
    B, S, D = x.shape
    K, E = cfg.num_experts_per_tok, cfg.num_experts
    Eh, e0 = cfg.held_experts, cfg.expert_offset
    cap = S if no_drop else max(1, int(cfg.moe_capacity_factor
                                       * S * K / E))
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu

    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)       # (B, S, E)
    topw, topi = jax.lax.top_k(probs, K)
    topw = topw / (topw.sum(-1, keepdims=True) + 1e-9)

    local = topi.reshape(B, S * K) - e0           # (B, S·K)
    held = (local >= 0) & (local < Eh)
    flat_e = jnp.where(held, local, Eh)           # Eh: not held here
    flat_w = topw.reshape(B, S * K)
    # rank within (row, expert): lane-aggregated allocation per row
    onehot = (flat_e[..., None]
              == jnp.arange(Eh, dtype=jnp.int32)[None, None, :])
    inc = jnp.cumsum(onehot.astype(jnp.int32), axis=1)
    rank = jnp.take_along_axis(inc - onehot.astype(jnp.int32),
                               jnp.minimum(flat_e, Eh - 1)[..., None],
                               axis=2)[..., 0]
    keep = held & (rank < cap)                     # capacity = alloc success

    tok_of = jnp.arange(S * K, dtype=jnp.int32) // K
    src = x[:, tok_of]                             # static-index gather
    # Dispatch/combine as *vmapped* per-row scatter/gather: the batch
    # dim becomes a scatter batching dim, which GSPMD partitions along
    # 'data'.  A flat multi-index scatter is unpartitionable and gets
    # replicated with operand-shaped index tensors (observed 118 GiB
    # and 40 GiB u32 iotas per chip on mixtral×train_4k).

    def disp(e_b, r_b, keep_b, src_b):
        return jnp.zeros((Eh, cap, D), x.dtype).at[
            jnp.where(keep_b, e_b, Eh), r_b].set(src_b, mode="drop")

    buf = jax.vmap(disp)(flat_e, rank, keep, src)
    buf = constrain(buf, "batch", "expert", None, "act_embed")

    g = act(jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(x.dtype))
    g = constrain(g, "batch", "expert", None, "mlp")
    u = constrain(u, "batch", "expert", None, "mlp")
    y_e = jnp.einsum("becf,efd->becd", g * u, p["w_down"].astype(x.dtype))
    y_e = constrain(y_e, "batch", "expert", None, "act_embed")

    gathered = jax.vmap(
        lambda ye_b, e_b, r_b: ye_b.at[e_b, r_b].get(
            mode="fill", fill_value=0))(y_e, flat_e, rank)
    y = (gathered * (keep[..., None])
         * flat_w[..., None].astype(x.dtype)).reshape(B, S, K, D).sum(axis=2)
    if cfg.shared_expert_d_ff:
        y = y + Lyr.apply_mlp(cfg, p["shared"], x)

    # Switch-transformer load-balance loss: E * Σ_e f_e · P_e
    f_e = jnp.zeros(E, jnp.float32).at[
        jnp.where(keep, flat_e + e0, E).reshape(-1)].add(
        1.0, mode="drop") / (B * S * K)
    p_e = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e)
    rows = (jnp.ones((B, 1, 1), bool) if valid is None
            else valid[:, None, None])
    load = jnp.sum(onehot & rows, axis=(0, 1), dtype=jnp.int32)
    return y, aux, load
