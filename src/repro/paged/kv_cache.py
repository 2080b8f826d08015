"""Paged KV cache backed by the Ouroboros allocator.

The serving-side embodiment of the paper's technique: KV pages are
dynamically allocated per sequence from an Ouroboros heap (default
variant ``vl_chunk`` — the virtualized-list chunk allocator, which
claims chunks on demand with no init-time carve) and addressed through
a page table, vLLM-style but with the allocator running *on device* as
bulk transactions.

Layout: page heaps are stacked over attention layers — one page id
backs all layers' K/V slots for its 16-token span (page tables are
layer-invariant, as in vLLM).  Optional int8 quantization stores a per
(slot, head) scale — this is what makes qwen1.5-32b's decode_32k cell
fit v5e HBM (DESIGN.md §Arch-applicability).

Single-layer cores (``append1`` / ``prefill_write1`` / ``paged_attend1``)
are what the model's scan-over-layers consumes; the ``PagedKV``
container stacks them for the serving engine.  ``paged_attend1`` is the
GSPMD-shardable jnp decode attention (blockwise online softmax over
page-table gathers); kernels/paged_attention.py is the single-chip TPU
Pallas fast path validated against the same oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import HeapConfig, Ouroboros

PAGE_SIZE = 16  # tokens per KV page
_NEG = -1e30

# Analysis override: the dry-run sets this to the full table width so
# the page-block loop disappears and HLO cost analysis sees every flop
# (a while body is only counted once).  Execution memory profiles use
# the normal blocked path (override None).
_PB_OVERRIDE = None

# Dense-prefill fast path: when the page table is the canonical layout
# (page id = b·P + j, the engine's bulk-reservation order) a prefill KV
# write is a pure reshape — no scatter.  GSPMD cannot partition the
# general scatter into a fully-sharded heap and replicates it (observed
# ~46 GiB/chip extra on qwen1.5-32b×prefill_32k).  Enabled by the
# dry-run/serving launcher; the engine's arbitrary-id path keeps the
# scatter.
_DENSE_PREFILL = False


def set_page_block_override(v):
    global _PB_OVERRIDE
    _PB_OVERRIDE = v


def set_dense_prefill(v: bool):
    global _DENSE_PREFILL
    _DENSE_PREFILL = bool(v)


class KVLayer(NamedTuple):
    """One attention layer's page heap (the scan-over-layers unit)."""
    k: jnp.ndarray                  # (NP, page, Hkv, hd) kv_dtype
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray]  # (NP, page, Hkv) f32 — int8 KV only
    v_scale: Optional[jnp.ndarray]


class PagedKV(NamedTuple):
    layers: KVLayer                 # arrays stacked: (L, NP, page, Hkv, hd)
    page_table: jnp.ndarray         # (B, P) int32, -1 = hole
    seq_lens: jnp.ndarray           # (B,) int32 — tokens already cached

    @property
    def page(self) -> int:
        return self.layers.k.shape[2]


def init_paged_kv(num_layers: int, num_pages: int, batch: int,
                  max_pages_per_seq: int, num_kv_heads: int, head_dim: int,
                  kv_dtype=jnp.bfloat16, page: int = PAGE_SIZE) -> PagedKV:
    shape = (num_layers, num_pages, page, num_kv_heads, head_dim)
    quant = kv_dtype == jnp.int8
    return PagedKV(
        layers=KVLayer(
            k=jnp.zeros(shape, kv_dtype),
            v=jnp.zeros(shape, kv_dtype),
            k_scale=jnp.zeros(shape[:4], jnp.float32) if quant else None,
            v_scale=jnp.zeros(shape[:4], jnp.float32) if quant else None),
        page_table=jnp.full((batch, max_pages_per_seq), -1, jnp.int32),
        seq_lens=jnp.zeros(batch, jnp.int32),
    )


def abstract_paged_kv(num_layers, num_pages, batch, max_pages_per_seq,
                      num_kv_heads, head_dim, kv_dtype=jnp.bfloat16,
                      page: int = PAGE_SIZE) -> PagedKV:
    """ShapeDtypeStruct twin of ``init_paged_kv`` for the dry-run."""
    shape = (num_layers, num_pages, page, num_kv_heads, head_dim)
    quant = kv_dtype == jnp.int8
    sds = jax.ShapeDtypeStruct
    return PagedKV(
        layers=KVLayer(
            k=sds(shape, kv_dtype), v=sds(shape, kv_dtype),
            k_scale=sds(shape[:4], jnp.float32) if quant else None,
            v_scale=sds(shape[:4], jnp.float32) if quant else None),
        page_table=sds((batch, max_pages_per_seq), jnp.int32),
        seq_lens=sds((batch,), jnp.int32),
    )


def make_kv_allocator(num_pages: int, backend: str = "jnp",
                      lowering: str = "auto", num_shards: int = 1):
    """Ouroboros instance managing the page-id space.

    Each logical page is one 256 B region of a single-size-class heap;
    ``vl_chunk`` claims chunks lazily so the full page space is usable.
    offset//64 (words) ↔ page id.  Allocator state is the flat
    device-resident arena (core/arena.py) — the vl chunk queues, their
    next-pointer chains, bitmaps, and counters all live at fixed word
    offsets in it, so with ``backend="pallas"`` every page grant and
    release the engine issues is ONE fused kernel launch, segment walk
    included; ``lowering`` picks the kernel shape (whole-arena refs vs
    the blocked compiled lowering, DESIGN.md §8).  Backends and
    lowerings are bit-identical, so serving behaviour is invariant to
    both — which is also why the serving snapshot fingerprint
    (DESIGN.md §12) records this allocator's layout/geometry but NOT
    its backend/lowering: a snapshot taken on one restores onto the
    other mid-stream.

    ``num_shards > 1`` partitions the page space into that many
    independent arenas (core/shards.py, DESIGN.md §9): the heap is
    sized so EACH shard carries the per-shard page share plus its own
    vl segment overhead, the engine routes each sequence's grants to
    ``slot % num_shards`` via ``shard_hint``, and exhausted shards
    overflow to neighbors — page ids stay global either way.

    Returns (ouro, words_per_page, physical_pages).  Queue segments live
    in the same heap (the ouroboros property), so granted ids are a
    subset of [0, physical_pages) that skips segment-occupied chunks —
    size the KV page array with ``physical_pages``, never ``num_pages``
    (ids beyond the array would silently drop KV writes).

    >>> import jax.numpy as jnp
    >>> from repro.paged.kv_cache import make_kv_allocator
    >>> ouro, wpp, physical = make_kv_allocator(64)
    >>> state = ouro.init()
    >>> sizes = jnp.full(4, 256, jnp.int32)     # four page grants
    >>> state, offs = ouro.alloc(state, sizes, jnp.ones(4, bool))
    >>> page_ids = [int(o) // wpp for o in offs]
    >>> all(0 <= p < physical for p in page_ids)
    True
    """
    chunk = 4096
    pages_per_chunk = chunk // 256
    pages_per_shard = -(-num_pages // num_shards)
    data_chunks = -(-pages_per_shard // pages_per_chunk)
    # vl segments: one per size class (5) + chunk-queue chain growth
    # (1023 ids per segment) + headroom — per shard.
    seg_chunks = 5 + data_chunks // 1023 + 3
    cfg = HeapConfig(
        total_bytes=num_shards * (data_chunks + seg_chunks) * chunk,
        chunk_bytes=chunk, min_page_bytes=256)
    physical_pages = cfg.total_words // 64
    return (Ouroboros(cfg, "vl_chunk", backend, lowering,
                      num_shards=num_shards), 64, physical_pages)


def modality_page_quota(cfg, page_bytes: int = 256) -> int:
    """Arena pages of per-sequence state residency BEYOND the KV pages
    — the per-modality allocation policy (DESIGN.md §13).

    The paper's claim is ONE dynamic allocator for heterogeneous
    workloads, so every model family's per-sequence state rides the
    same Ouroboros arena the KV pages come from.  Attention KV grows
    page-by-page with the sequence (``make_kv_allocator``); what this
    helper sizes is the O(1)-per-sequence state the other families
    carry instead of (or on top of) KV:

    - ``ssm`` (mamba2): the SSD recurrent state — ``(nheads, headdim,
      state)`` f32 plus the ``(conv-1, conv_dim)`` bf16 convolution
      tail, per layer;
    - ``hybrid`` (recurrentgemma): the RG-LRU recurrence — ``(lru_width,)``
      f32 hidden plus the ``(3, lru_width)`` bf16 conv tail, per
      recurrent (non-attention) layer;
    - ``moe`` (mixtral, phi3.5): the routed expert activation buffers —
      ``top_k × d_ff`` bf16 per MoE layer;
    - dense / enc-dec / vlm: 0 (their per-sequence state is entirely
      KV pages).

    The serving engine grants this many pages per slot at admission
    (``slot_aux``) and frees them at retirement/eviction/cancel, so
    SSM and MoE traffic exercises the allocator even though their
    state tensors live in dense device arrays.

    >>> from repro.configs import get_arch
    >>> from repro.paged.kv_cache import modality_page_quota
    >>> modality_page_quota(get_arch("qwen2-0.5b").smoke())
    0
    >>> modality_page_quota(get_arch("mamba2-780m").smoke()) > 0
    True
    """
    if cfg.family == "ssm":
        conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
        per_layer = (cfg.ssm_nheads * cfg.ssm_headdim * cfg.ssm_state * 4
                     + (cfg.ssm_conv - 1) * conv_dim * 2)
        return -(-cfg.num_layers * per_layer // page_bytes)
    if cfg.family == "hybrid":
        r = cfg.lru_width or cfg.d_model
        n_rec = cfg.num_layers - cfg.num_layers // cfg.attn_period
        return -(-n_rec * (r * 4 + 3 * r * 2) // page_bytes)
    if cfg.num_experts:
        buf = cfg.num_layers * cfg.num_experts_per_tok * cfg.d_ff * 2
        return -(-buf // page_bytes)
    return 0


def scatter_grant_words(page_table, page_counts, lane_slot, lane_rank,
                        lane_offs, grant_ok, wpp: int):
    """Scatter freshly granted arena WORD offsets into the device page
    table — the mega-step path where the table is never materialized on
    the host: grants flow kernel → page id (``offset // wpp``) → table
    entirely on device.  Lane ``j`` lands at row ``lane_slot[j]``,
    column ``page_counts[slot] + lane_rank[j]`` (the slot's next free
    table slots, in grant order); lanes with ``grant_ok[j]`` False are
    dropped.

    >>> import jax.numpy as jnp
    >>> from repro.paged.kv_cache import scatter_grant_words
    >>> pt = jnp.full((2, 3), -1, jnp.int32)
    >>> pt = scatter_grant_words(
    ...     pt, jnp.array([1, 0]),                  # pages already mapped
    ...     jnp.array([0, 1]), jnp.array([0, 0]),   # lane slot / rank
    ...     jnp.array([128, 0]),                    # granted word offsets
    ...     jnp.array([True, True]), wpp=64)
    >>> pt.tolist()
    [[-1, 2, -1], [0, -1, -1]]
    """
    B, P = page_table.shape
    pages = (lane_offs // wpp).astype(jnp.int32)
    row = jnp.where(grant_ok, lane_slot, B)
    col = jnp.where(grant_ok, page_counts[lane_slot] + lane_rank, P)
    return page_table.at[row, col].set(pages, mode="drop")


def forwarding_page_map(fwd, wpp: int, max_span: int):
    """Expand a defrag :class:`~repro.core.defrag.Forwarding` table to
    page granularity: ``(src_pids, dst_pids)`` int32 arrays (−1 padded),
    one entry per migrated page (a multi-page extent contributes one
    entry per page).  ``max_span`` bounds pages per extent — the
    allocator's ``words_per_chunk // wpp``."""
    k = fwd.sizes // (wpp * 4)
    j = jnp.arange(max_span, dtype=jnp.int32)[None, :]
    ok = (fwd.src >= 0)[:, None] & (j < k[:, None])
    sp = jnp.where(ok, fwd.src[:, None] // wpp + j, -1)
    dp = jnp.where(ok, fwd.dst[:, None] // wpp + j, -1)
    return sp.reshape(-1), dp.reshape(-1)


def apply_forwarding(kv: PagedKV, fwd, wpp: int,
                     max_span: Optional[int] = None) -> PagedKV:
    """Apply a defrag forwarding table to the paged cache: move the
    migrated pages' K/V rows (and scales) to their new physical page
    ids and rewrite every matching page-table entry — after which
    reads through the table are word-identical to pre-defrag reads
    (tests/test_defrag.py pins this).

    ``max_span`` bounds pages per forwarded extent; by default it is
    derived from the concrete table (``None`` under tracing raises —
    pass the allocator's ``words_per_chunk // wpp`` there, as the
    engine does).

    >>> import jax.numpy as jnp
    >>> from repro.core.defrag import Forwarding
    >>> from repro.paged.kv_cache import apply_forwarding, init_paged_kv
    >>> kv = init_paged_kv(1, num_pages=4, batch=1, max_pages_per_seq=2,
    ...                    num_kv_heads=1, head_dim=2,
    ...                    kv_dtype=jnp.float32)
    >>> kv = kv._replace(
    ...     layers=kv.layers._replace(k=kv.layers.k.at[:, 3].set(7.0)),
    ...     page_table=kv.page_table.at[0, 0].set(3))
    >>> fwd = Forwarding(src=jnp.array([3 * 64], jnp.int32),
    ...                  dst=jnp.array([0], jnp.int32),
    ...                  sizes=jnp.array([256], jnp.int32))
    >>> kv2 = apply_forwarding(kv, fwd, wpp=64)
    >>> int(kv2.page_table[0, 0]), float(kv2.layers.k[0, 0, 0, 0, 0])
    (0, 7.0)
    """
    if max_span is None:
        try:
            max_span = max(1, int(jnp.max(fwd.sizes // (wpp * 4))))
        except jax.errors.ConcretizationTypeError as e:
            raise ValueError(
                "apply_forwarding needs an explicit max_span under jit "
                "tracing (the allocator's words_per_chunk // wpp)"
            ) from e
    sp, dp = forwarding_page_map(fwd, wpp, max_span)
    np_ = kv.layers.k.shape[1]
    moved = sp >= 0
    safe_sp = jnp.where(moved, sp, 0)
    safe_dp = jnp.where(moved, dp, np_)

    def relocate(heap):
        if heap is None:
            return None
        # unmoved lanes target row np_ (one past the end) and drop
        return heap.at[:, safe_dp].set(heap[:, safe_sp], mode="drop")

    layers = KVLayer(*(relocate(x) for x in kv.layers))
    key = jnp.where(moved, sp, jnp.int32(-2))
    hit = kv.page_table[:, :, None] == key[None, None, :]
    new = jnp.sum(jnp.where(hit, dp[None, None, :], 0), axis=-1)
    table = jnp.where(hit.any(-1), new, kv.page_table)
    return kv._replace(layers=layers, page_table=table)


def _quant(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-8
    return jnp.round(x / scale).astype(jnp.int8), scale[..., 0]


def _store(layer: KVLayer, idx, k_new, v_new) -> KVLayer:
    if layer.k_scale is not None:
        kq, ks = _quant(k_new.astype(jnp.float32))
        vq, vs = _quant(v_new.astype(jnp.float32))
        return KVLayer(
            k=layer.k.at[idx].set(kq, mode="drop"),
            v=layer.v.at[idx].set(vq, mode="drop"),
            k_scale=layer.k_scale.at[idx].set(ks, mode="drop"),
            v_scale=layer.v_scale.at[idx].set(vs, mode="drop"))
    return layer._replace(
        k=layer.k.at[idx].set(k_new.astype(layer.k.dtype), mode="drop"),
        v=layer.v.at[idx].set(v_new.astype(layer.v.dtype), mode="drop"))


def append1(layer: KVLayer, page_table, seq_lens, k_t, v_t,
            ring: bool = False) -> KVLayer:
    """Write one new token's K/V at position ``seq_lens`` per sequence.
    k_t, v_t: (B, 1, Hkv, hd).  Pages must already be mapped.
    ``ring``: windowed attention — table slot = page_index mod P, so a
    window-sized table serves unbounded sequences (page reuse)."""
    page = layer.k.shape[1]
    np_ = layer.k.shape[0]
    P = page_table.shape[1]
    pidx, slot = seq_lens // page, seq_lens % page
    if ring:
        pidx = pidx % P
    ids = jnp.take_along_axis(page_table, pidx[:, None], axis=1)[:, 0]
    idx = (jnp.where(ids >= 0, ids, np_), slot)
    return _store(layer, idx, k_t[:, 0], v_t[:, 0])


def prefill_write1(layer: KVLayer, page_table, k, v, pos0=0,
                   ring: bool = False) -> KVLayer:
    """Bulk-write a prefill segment (S tokens).  k, v: (B, S, Hkv, hd)."""
    B, S = k.shape[:2]
    page = layer.k.shape[1]
    np_ = layer.k.shape[0]
    P = page_table.shape[1]
    if (_DENSE_PREFILL and not ring and pos0 == 0 and np_ == B * P
            and S <= P * page):
        # canonical layout: page id = b·P + j  →  the heap IS the
        # reshaped K/V tensor (zero-scatter path).
        pad = P * page - S
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = kp.reshape(np_, page, *k.shape[2:])
        vp = vp.reshape(np_, page, *v.shape[2:])
        if layer.k_scale is not None:
            kq, ks = _quant(kp.astype(jnp.float32))
            vq, vs = _quant(vp.astype(jnp.float32))
            return KVLayer(k=kq, v=vq, k_scale=ks, v_scale=vs)
        return KVLayer(k=kp.astype(layer.k.dtype),
                       v=vp.astype(layer.v.dtype),
                       k_scale=None, v_scale=None)
    pos = pos0 + jnp.arange(S, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (B, S))
    pidx, slot = pos // page, pos % page
    if ring:
        pidx = pidx % P
    ids = jnp.take_along_axis(page_table, pidx, axis=1)
    idx = (jnp.where(ids >= 0, ids, np_), slot)
    return _store(layer, idx, k, v)


def paged_attend1(layer: KVLayer, page_table, kv_len, q, *,
                  window: Optional[int] = None, page_block: int = 16,
                  ring: bool = False):
    """Decode attention for one layer over the paged heap.

    q: (B, 1, Hq, hd); kv_len: (B,) valid tokens (incl. current).
    Blockwise online softmax over page-table gathers — O(page_block)
    live memory, GSPMD-shardable (heads on 'model', batch on 'data')."""
    B, _, Hq, D = q.shape
    NP, page, Hkv, _ = layer.k.shape
    P = page_table.shape[1]
    G = Hq // Hkv
    pb = min(_PB_OVERRIDE or page_block, P)
    nblk = -(-P // pb)
    pad = nblk * pb - P
    pt = jnp.pad(page_table, ((0, 0), (0, pad)), constant_values=-1)
    ptb = pt.reshape(B, nblk, pb).transpose(1, 0, 2)   # (nblk, B, pb)

    # staging dtype follows the cache: f32 caches (tests, oracles) stay
    # exact; bf16/int8 caches stage in bf16 (small dequant blocks) with
    # f32 accumulation via preferred_element_type below.
    stage_dt = (jnp.float32 if layer.k.dtype == jnp.float32
                else jnp.bfloat16)
    qg = (q[:, 0].reshape(B, Hkv, G, D) * (D ** -0.5)).astype(stage_dt)

    def body(carry, inp):
        m, l, acc = carry
        i, ids = inp                                   # ids: (B, pb)
        safe = jnp.maximum(ids, 0)
        k = layer.k[safe].astype(stage_dt)             # (B, pb, page, Hkv, D)
        v = layer.v[safe].astype(stage_dt)
        if layer.k_scale is not None:
            k = k * layer.k_scale[safe][..., None].astype(stage_dt)
            v = v * layer.v_scale[safe][..., None].astype(stage_dt)
        k = k.reshape(B, pb * page, Hkv, D)
        v = v.reshape(B, pb * page, Hkv, D)
        j = i * pb + jax.lax.broadcasted_iota(jnp.int32, (pb, page), 0)
        slot_of = jax.lax.broadcasted_iota(jnp.int32, (pb, page), 1)
        if ring:
            # ring table: slot j holds absolute page cur − ((cur−j) mod P)
            cur = (jnp.maximum(kv_len, 1) - 1)[:, None, None] // page
            abs_page = cur - ((cur - j[None]) % P)
            tok = (abs_page * page + slot_of[None]).reshape(B, -1)
            valid = (tok >= 0) & (tok < kv_len[:, None]) \
                & jnp.repeat(ids >= 0, page, axis=1)
        else:
            tok = (j * page + slot_of).reshape(-1)[None]  # absolute positions
            valid = (tok < kv_len[:, None]) \
                & jnp.repeat(ids >= 0, page, axis=1)
        if window is not None:
            valid &= tok > (kv_len[:, None] - 1 - window)
        s = jnp.einsum("bhgd,bthd->bhgt", qg, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, None, :], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(valid[:, None, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(-1)
        acc_new = alpha[..., None] * acc + jnp.einsum(
            "bhgt,bthd->bhgd", p.astype(stage_dt), v,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, D), jnp.float32)
    from repro.models.layers import scan_unroll
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nblk, dtype=jnp.int32), ptb),
        unroll=(min(nblk, 8) if scan_unroll() else 1))
    out = acc / (l[..., None] + 1e-30)
    return out.reshape(B, 1, Hq, D)
