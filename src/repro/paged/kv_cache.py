"""Paged KV cache backed by the Ouroboros allocator.

The serving-side embodiment of the paper's technique: KV pages are
dynamically allocated per sequence from an Ouroboros heap (default
variant ``vl_chunk`` — the virtualized-list chunk allocator, which
claims chunks on demand with no init-time carve) and addressed through
a page table, vLLM-style but with the allocator running *on device* as
bulk transactions.

Layout: page heaps are stacked over attention layers — one page id
backs all layers' K/V slots for its 16-token span (page tables are
layer-invariant, as in vLLM).  A page stores each token's ``Hkv·hd``
values in rows of 128 lanes, the TPU tile's width (``kv_row``): one row
``(page, Hkv·hd)`` where they fit it (qwen2's 2 × 64 heads fill it
exactly), else ``(page, Hkv·hd / 128, 128)`` (granite's 8 × 128).  The
heap is then stored as it is gathered and scattered, with no relayout.
Optional int8 quantization stores a per (slot, head) scale — this is
what makes qwen1.5-32b's decode_32k cell fit v5e HBM (DESIGN.md
§Arch-applicability).

The model's layer scan carries the stacked heap and the layer index:
``append1_at`` / ``prefill_write_at`` write one layer's tokens in place
and ``layer_of`` hands ``paged_attend1`` a layer without slicing it out.
``paged_attend1`` is the GSPMD-shardable jnp decode attention (blockwise
online softmax over page-table gathers); kernels/paged_attention.py is
the single-chip TPU Pallas fast path validated against the same oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import HeapConfig, Ouroboros

PAGE_SIZE = 16  # tokens per KV page
LANES = 128     # a TPU tile's minor dimension
_NEG = -1e30

# Analysis override: the dry-run sets this to the full table width so
# the page-block loop disappears and HLO cost analysis sees every flop
# (a while body is only counted once).  Execution memory profiles use
# the normal blocked path (override None).
_PB_OVERRIDE = None


def set_page_block_override(v):
    global _PB_OVERRIDE
    _PB_OVERRIDE = v


class KVLayer(NamedTuple):
    """Page heap arrays: stacked over attention layers in a
    :class:`PagedKV`, one layer's as :func:`layer_of` gives it."""
    k: jnp.ndarray                  # (L, NP, page, *kv_row) kv_dtype
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray]  # (L, NP, page, Hkv) f32 — int8 only
    v_scale: Optional[jnp.ndarray]


def kv_row(num_kv_heads: int, head_dim: int) -> tuple:
    """How the heap stores one token's K (or V): its ``Hkv·hd`` values
    in rows of ``LANES`` where they fill more than one, else as one row.

    >>> kv_row(2, 64), kv_row(8, 128), kv_row(2, 16)
    ((128,), (8, 128), (32,))
    """
    n = num_kv_heads * head_dim
    return (n // LANES, LANES) if n > LANES and n % LANES == 0 else (n,)


def page_rows(x):
    """K or V ``(..., Hkv, hd)`` as the heap stores a token
    (:func:`kv_row`)."""
    return x.reshape(x.shape[:-2] + kv_row(*x.shape[-2:]))


class PagedKV(NamedTuple):
    layers: KVLayer
    page_table: jnp.ndarray         # (B, P) int32, -1 = hole
    seq_lens: jnp.ndarray           # (B,) int32 — tokens already cached

    @property
    def page(self) -> int:
        return self.layers.k.shape[2]


def init_paged_kv(num_layers: int, num_pages: int, batch: int,
                  max_pages_per_seq: int, num_kv_heads: int, head_dim: int,
                  kv_dtype=jnp.bfloat16, page: int = PAGE_SIZE) -> PagedKV:
    shape = (num_layers, num_pages, page) + kv_row(num_kv_heads, head_dim)
    scales = (num_layers, num_pages, page, num_kv_heads)
    quant = kv_dtype == jnp.int8
    return PagedKV(
        layers=KVLayer(
            k=jnp.zeros(shape, kv_dtype),
            v=jnp.zeros(shape, kv_dtype),
            k_scale=jnp.zeros(scales, jnp.float32) if quant else None,
            v_scale=jnp.zeros(scales, jnp.float32) if quant else None),
        page_table=jnp.full((batch, max_pages_per_seq), -1, jnp.int32),
        seq_lens=jnp.zeros(batch, jnp.int32),
    )


def abstract_paged_kv(num_layers, num_pages, batch, max_pages_per_seq,
                      num_kv_heads, head_dim, kv_dtype=jnp.bfloat16,
                      page: int = PAGE_SIZE) -> PagedKV:
    """ShapeDtypeStruct twin of ``init_paged_kv`` for the dry-run."""
    shape = (num_layers, num_pages, page) + kv_row(num_kv_heads, head_dim)
    scales = (num_layers, num_pages, page, num_kv_heads)
    quant = kv_dtype == jnp.int8
    sds = jax.ShapeDtypeStruct
    return PagedKV(
        layers=KVLayer(
            k=sds(shape, kv_dtype), v=sds(shape, kv_dtype),
            k_scale=sds(scales, jnp.float32) if quant else None,
            v_scale=sds(scales, jnp.float32) if quant else None),
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


def state_geometry(cfg):
    """How a layer-list model's Mamba layer keeps its recurrent state in
    page rows: ``(heads_per_page, head_pages, conv_pages)``.

    A page id's row in the page heap is its K and V slots across the
    attention layers: ``2 · attention layers · PAGE_SIZE · kv_heads ·
    head_dim`` float32 words (128 KiB for granite-4.0-h's one attention
    layer per stage).  A state page holds ``heads_per_page`` SSD heads
    of ``(headdim, state)`` float32; a layer's conv tail, ``(conv - 1,
    conv_dim)``, takes ``conv_pages`` rows (DESIGN.md §13)."""
    from repro.models.ssm import conv_dim
    n_attn = cfg.layer_types.count("attention")
    row = 2 * n_attn * PAGE_SIZE * cfg.num_kv_heads * cfg.head_dim_
    head = cfg.ssm_headdim * cfg.ssm_state
    hpp = row // head if head else 0
    if not n_attn or row % head or cfg.ssm_nheads % hpp:
        raise ValueError(
            f"{cfg.name}: a page row of {row} words does not hold whole "
            f"SSD heads of {head} words for {cfg.ssm_nheads} heads")
    tail = (cfg.ssm_conv - 1) * conv_dim(cfg)
    return hpp, cfg.ssm_nheads // hpp, -(-tail // row)


def state_pages_per_layer(cfg) -> int:
    _, head_pages, conv_pages = state_geometry(cfg)
    return head_pages + conv_pages


def modality_page_quota(cfg) -> int:
    """Arena pages a slot holds beyond its KV pages: the pages that hold
    its recurrent state (DESIGN.md §13).

    The paper's claim is ONE dynamic allocator for heterogeneous state,
    so a model served from its layer list (granite-4.0-h) keeps each
    Mamba layer's SSD state and conv tail in pages of the same arena
    as its KV pages, in the same page heap: ``state_pages_per_layer``
    pages for each Mamba layer, granted at admission in the same
    transaction as the prompt's KV pages, read and written by every
    tick through the slot's state-page table, and freed with the KV
    pages at retirement, eviction or cancel.  Every other family holds
    no state pages: an MoE layer keeps no per-sequence state, and the
    ssm / hybrid families keep theirs in dense per-slot arrays.

    >>> from repro.configs import get_arch
    >>> from repro.paged.kv_cache import modality_page_quota
    >>> modality_page_quota(get_arch("qwen2-0.5b").smoke())
    0
    >>> modality_page_quota(get_arch("granite-4.0-h-small.stage0"))
    297
    """
    if cfg.layer_types is None:
        return 0
    return cfg.layer_types.count("mamba") * state_pages_per_layer(cfg)


def _state_slices(layers: KVLayer):
    """The heap arrays a state page spans, in head order: attention
    layer by layer, its K slot then its V slot — ``(field, layer)``."""
    return [(f, l) for l in range(layers.k.shape[0]) for f in ("k", "v")]


def read_state(cfg, layers: KVLayer, ids):
    """One Mamba layer's state for every row from its state pages
    ``ids`` (B, head_pages + conv_pages): ``(h, conv)``.  ``h`` is a
    tuple of head slices, one per heap array a page spans (each
    (B, H / slices, P, N) float32, in head order), gathered straight
    from the heap rows and never interleaved into one array; ``conv``
    is (B, conv - 1, conv_dim) float32.  A hole (-1) reads page 0."""
    from repro.models.ssm import conv_dim
    _, hp, _ = state_geometry(cfg)
    safe = jnp.maximum(ids, 0)
    B = ids.shape[0]
    h, tails = [], []
    for f, l in _state_slices(layers):
        arr = getattr(layers, f)
        h.append(arr[l, safe[:, :hp]].reshape(
            B, -1, cfg.ssm_headdim, cfg.ssm_state))
        tails.append(arr[l, safe[:, hp:]].reshape(B, -1))
    tail = (cfg.ssm_conv - 1) * conv_dim(cfg)
    conv = jnp.concatenate(tails, 1)[:, :tail].reshape(
        B, cfg.ssm_conv - 1, conv_dim(cfg))
    return tuple(h), conv


def write_state(cfg, layers: KVLayer, ids, h, conv,
                holes: bool = True) -> KVLayer:
    """Write one Mamba layer's state into its state pages ``ids``: ``h``
    as :func:`read_state` gives it, or whole (B, H, P, N); ``conv``
    (B, conv - 1, conv_dim).  Holes (-1) are dropped; ``holes=False``
    promises there are none (an admission's row), which lets a one-row
    write update the heap in place."""
    _, hp, cp = state_geometry(cfg)
    slices = _state_slices(layers)
    B, np_ = ids.shape[0], layers.k.shape[1]
    if not isinstance(h, tuple):
        h = tuple(jnp.split(h, len(slices), axis=1))
    row = layers.k.shape[2:]
    tail = conv.reshape(B, -1).astype(jnp.float32)
    n = len(slices) * cp * int(np.prod(row))
    tails = jnp.split(jnp.pad(tail, ((0, 0), (0, n - tail.shape[1]))),
                      len(slices), axis=1)
    dst = jnp.where(ids >= 0, ids, np_) if holes else ids
    mode = "drop" if holes else "promise_in_bounds"
    new = {}
    for (f, l), hs, ts in zip(slices, h, tails):
        a = new.get(f, getattr(layers, f))
        hs = hs.reshape((B, hp) + row).astype(a.dtype)
        ts = ts.reshape((B, cp) + row).astype(a.dtype)
        if B * cp == 1:
            # a one-row scatter becomes an update that rereads, so
            # copies, the heap: write the conv row with the heads
            a = a.at[l, dst].set(jnp.concatenate([hs, ts], 1), mode=mode)
        else:
            a = a.at[l, dst[:, :hp]].set(hs, mode=mode)
            a = a.at[l, dst[:, hp:]].set(ts, mode=mode)
        new[f] = a
    return layers._replace(**new)


class _LayerOf:
    """Attention layer ``a`` of a stacked heap array as
    :func:`paged_attend1` reads it: indexing gathers from the stacked
    array itself, so no layer slice is copied."""

    def __init__(self, heap, a):
        self.heap, self.a = heap, a
        self.shape, self.dtype = heap.shape[1:], heap.dtype

    def __getitem__(self, idx):
        return self.heap[self.a, idx]


def layer_of(layers: KVLayer, a) -> KVLayer:
    """Attention layer ``a`` (may be traced) of a stacked heap, for
    :func:`paged_attend1`."""
    return KVLayer(*(None if x is None else _LayerOf(x, a)
                     for x in layers))


def _token_rows(layers: KVLayer, a, page_table, pos, ring: bool):
    """Heap index of tokens at positions ``pos`` (B, S) in attention
    layer ``a``: ``(a, page id, slot)``.  A hole (-1) in the table
    points one past the last page, where :func:`_store` drops the
    write.  ``ring``: windowed attention — table slot = page index mod
    P, so a window-sized table serves unbounded sequences."""
    page, np_ = layers.k.shape[2], layers.k.shape[1]
    pidx = pos // page
    if ring:
        pidx = pidx % page_table.shape[1]
    ids = jnp.take_along_axis(page_table, pidx, axis=1)
    return a, jnp.where(ids >= 0, ids, np_), pos % page


def append1_at(layers: KVLayer, a, page_table, seq_lens, k_t, v_t,
               ring: bool = False) -> KVLayer:
    """Write one new token's K/V (B, 1, Hkv, hd) at position
    ``seq_lens`` of each sequence into attention layer ``a`` of the
    stacked heap, in place.  Pages must already be mapped."""
    idx = _token_rows(layers, a, page_table, seq_lens[:, None], ring)
    return _store(layers, idx, k_t, v_t)


def prefill_write_at(layers: KVLayer, a, page_table, k, v,
                     ring: bool = False) -> KVLayer:
    """Write a prompt's K/V (B, S, Hkv, hd), positions 0 … S-1, into
    attention layer ``a`` of the stacked heap, in place."""
    B, S = k.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return _store(layers, _token_rows(layers, a, page_table, pos, ring),
                  k, v)


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
    >>> int(kv2.page_table[0, 0]), float(kv2.layers.k[0, 0, 0, 0])
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


def _store(layers: KVLayer, idx, k_new, v_new) -> KVLayer:
    """Set the heap rows ``idx`` names to tokens of K/V ``(..., Hkv,
    hd)``, quantized per (token, head) on an int8 heap."""
    if layers.k_scale is not None:
        kq, ks = _quant(k_new.astype(jnp.float32))
        vq, vs = _quant(v_new.astype(jnp.float32))
        return KVLayer(
            k=layers.k.at[idx].set(page_rows(kq), mode="drop"),
            v=layers.v.at[idx].set(page_rows(vq), mode="drop"),
            k_scale=layers.k_scale.at[idx].set(ks, mode="drop"),
            v_scale=layers.v_scale.at[idx].set(vs, mode="drop"))
    return layers._replace(
        k=layers.k.at[idx].set(page_rows(k_new).astype(layers.k.dtype),
                               mode="drop"),
        v=layers.v.at[idx].set(page_rows(v_new).astype(layers.v.dtype),
                               mode="drop"))


def paged_attend1(layer: KVLayer, page_table, kv_len, q, *,
                  window: Optional[int] = None, page_block: int = 16,
                  ring: bool = False, scale: Optional[float] = None):
    """Decode attention for one layer over the paged heap.

    layer: one layer's heap, (NP, page, *kv_row) (:func:`layer_of`);
    q: (B, 1, Hq, hd); kv_len: (B,) valid tokens (incl. current);
    ``scale``: score scale (None = hd ** -0.5).  A float32 heap is read
    at ``HIGHEST`` precision: at the default, XLA on a TPU lowers the
    heap to bfloat16 whole, ahead of the gathers.
    Blockwise online softmax over page-table gathers — O(page_block)
    live memory, GSPMD-shardable (heads on 'model', batch on 'data')."""
    B, _, Hq, D = q.shape
    page = layer.k.shape[1]
    Hkv = int(np.prod(layer.k.shape[2:])) // D
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
    precision = (jax.lax.Precision.HIGHEST if stage_dt == jnp.float32
                 else None)
    qg = (q[:, 0].reshape(B, Hkv, G, D)
          * (D ** -0.5 if scale is None else scale)).astype(stage_dt)

    def body(carry, inp):
        m, l, acc = carry
        i, ids = inp                                   # ids: (B, pb)
        safe = jnp.maximum(ids, 0)
        # (B, pb, page, *kv_row) rows, split into heads once gathered
        k = layer.k[safe].astype(stage_dt).reshape(B, pb * page, Hkv, D)
        v = layer.v[safe].astype(stage_dt).reshape(B, pb * page, Hkv, D)
        if layer.k_scale is not None:
            ks = layer.k_scale[safe].reshape(B, pb * page, Hkv, 1)
            vs = layer.v_scale[safe].reshape(B, pb * page, Hkv, 1)
            k = k * ks.astype(stage_dt)
            v = v * vs.astype(stage_dt)
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
        s = jnp.einsum("bhgd,bthd->bhgt", qg, k, precision=precision,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, None, :], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(valid[:, None, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(-1)
        acc_new = alpha[..., None] * acc + jnp.einsum(
            "bhgt,bthd->bhgd", p.astype(stage_dt), v, precision=precision,
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
