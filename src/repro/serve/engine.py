"""Continuous-batching serving engine on the Ouroboros paged KV cache.

The end-to-end integration of the paper's allocator with a model
server: sequences arrive, get admitted into free batch slots, grow
their KV page-by-page out of the allocator (bulk device transactions —
one ``alloc`` per engine step covers every growing sequence, the
lane-aggregated pattern from DESIGN.md §2), and release every page on
completion.  Page churn across requests of different lengths is exactly
the fragmentation workload Ouroboros was built for; the default
``vl_chunk`` variant claims heap chunks lazily and reuses freed pages.

The decode loop is the fused tick (DESIGN.md §11): ONE jitted function
per tick that (a) computes per-slot page need from device-resident
``lens``/``active`` state, (b) runs the bulk grow as the single-
``pallas_call`` arena transaction (``Ouroboros.grow``), (c) scatters
granted pages into the device page table straight from the grant
words (``kv_cache.scatter_grant_words`` — no host-materialized table),
(d) runs the model forward with paged attention, and (e) greedily
samples + advances ``seq_lens``/last-token on device.  A decode tick
is a fixed small number of launches regardless of ``max_batch``; the
host syncs one tiny ``(B,)`` finished/failed flag vector per tick and
touches only control-plane decisions (admission, retirement, and the
defrag-retry on allocation failure, which stays host-side).  The
device page-table row is the one holder of a slot's KV page ids, from
admission to retirement.

Single-host reference implementation (the dry-run serve_step covers the
multi-pod path); everything device-side is jitted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.paged import kv_cache as KV


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (Lp,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


# 2: a slot's KV page ids live in the device page table alone (version
# 1 snapshots also carried host page lists, and the host decode loop)
SNAPSHOT_VERSION = 2


def _req_to_json(r: Request) -> dict:
    return {"uid": int(r.uid),
            "prompt": [int(t) for t in np.asarray(r.prompt)],
            "max_new_tokens": int(r.max_new_tokens),
            "eos_id": None if r.eos_id is None else int(r.eos_id),
            "out_tokens": [int(t) for t in r.out_tokens],
            "done": bool(r.done)}


def _req_from_json(d: dict) -> Request:
    return Request(
        uid=int(d["uid"]),
        prompt=np.asarray(d["prompt"], np.int32),
        max_new_tokens=int(d["max_new_tokens"]),
        eos_id=None if d["eos_id"] is None else int(d["eos_id"]),
        out_tokens=[int(t) for t in d["out_tokens"]],
        done=bool(d["done"]))


class MegaState(NamedTuple):
    """Device-resident per-slot decode state — the mega-step carry.

    The host keeps cheap integer mirrors (advanced from the per-tick
    flag vector) for stats and retirement, but the device arrays are
    the truth the fused tick computes from."""
    last_tok: jnp.ndarray     # (B,) int32 — token to decode this tick
    lens: jnp.ndarray         # (B,) int32 — tokens logically generated
    page_counts: jnp.ndarray  # (B,) int32 — KV pages mapped per slot
    active: jnp.ndarray       # (B,) bool
    budget: jnp.ndarray       # (B,) int32 — new tokens still allowed
    eos: jnp.ndarray          # (B,) int32 — eos id, −1 = none
    out_buf: jnp.ndarray      # (B, cap) int32 — generated tokens
    n_out: jnp.ndarray        # (B,) int32 — tokens in out_buf


def merge_rows(cfg, new_caches, old_caches, row_mask):
    """Keep only ``row_mask`` rows from a cache update.

    Structure-aware (never shape-guessing — num_layers can equal
    max_batch): page heaps are taken wholesale (rows outside the mask
    had their writes dropped on a table hole — heap rows stay
    disjoint); batch-first leaves merge on axis 0; layer-stacked state
    leaves (Lr, B, ...) merge on axis 1.  The mega-step's cache
    advance: mask = slots that advanced this tick."""
    mask = jnp.asarray(row_mask)

    def axis0(new, old):
        if new is None or old is None:
            return new
        sel = mask.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(sel, new, old)

    def axis1(new, old):
        if new is None or old is None:
            return new
        sel = mask.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(sel, new, old)

    def merge_kv(new_kv, old_kv):
        if new_kv is None:
            return None
        return new_kv._replace(
            layers=new_kv.layers,  # wholesale: disjoint heap rows
            page_table=axis0(new_kv.page_table, old_kv.page_table),
            seq_lens=axis0(new_kv.seq_lens, old_kv.seq_lens))

    old = old_caches
    if cfg.is_encdec:
        return new_caches._replace(
            self_kv=merge_kv(new_caches.self_kv, old.self_kv),
            cross_k=axis1(new_caches.cross_k, old.cross_k),
            cross_v=axis1(new_caches.cross_v, old.cross_v),
            enc_valid=(axis0(new_caches.enc_valid, old.enc_valid)
                       if new_caches.enc_valid is not None
                       else old.enc_valid))
    # a layer-list model's state pages are in the heap (rows that did
    # not advance were given no state pages, so wrote none); its slot
    # tables merge on axis 0 and its expert counts are taken wholesale
    return new_caches._replace(
        kv=merge_kv(new_caches.kv, old.kv),
        ssm_h=axis1(new_caches.ssm_h, old.ssm_h),
        ssm_conv=axis1(new_caches.ssm_conv, old.ssm_conv),
        state_table=axis0(new_caches.state_table, old.state_table))


def put_row(cfg, row_caches, caches, slot):
    """Write a one-row cache update back at batch row ``slot``.

    The admission prefill computes only the admitted row, through a
    view of the caches that holds the shared page heap and that slot's
    page-table row.  The sibling of :func:`merge_rows` over the same
    tree: the page heap is taken wholesale (the row wrote only into
    its own pages), the page table is kept (the prefill never changes
    it), batch-first leaves are set at ``[slot]`` and layer-stacked
    state leaves (Lr, B, ...) at ``[:, slot]``.  ``slot`` may be traced.
    Dtypes promote as in :func:`merge_rows`."""
    def put(row, old, axis):
        if row is None:
            return old
        dt = jnp.promote_types(row.dtype, old.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            old.astype(dt), row.astype(dt), slot, axis)

    def put_kv(row_kv, kv):
        if row_kv is None:
            return None
        return kv._replace(layers=row_kv.layers,
                           seq_lens=put(row_kv.seq_lens, kv.seq_lens, 0))

    if cfg.is_encdec:
        return caches._replace(
            self_kv=put_kv(row_caches.self_kv, caches.self_kv),
            cross_k=put(row_caches.cross_k, caches.cross_k, 1),
            cross_v=put(row_caches.cross_v, caches.cross_v, 1),
            enc_valid=put(row_caches.enc_valid, caches.enc_valid, 0))
    return caches._replace(
        kv=put_kv(row_caches.kv, caches.kv),
        ssm_h=put(row_caches.ssm_h, caches.ssm_h, 1),
        ssm_conv=put(row_caches.ssm_conv, caches.ssm_conv, 1),
        expert_tokens=row_caches.expert_tokens)


class ServingEngine:
    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_seq: int = 512, num_pages: Optional[int] = None,
                 kv_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                 sample: str = "greedy", alloc_backend: str = "jnp",
                 alloc_lowering: str = "auto", num_shards: int = 1,
                 rebalance_threshold: Optional[int] = None,
                 mega_step: bool = True, max_new_cap: int = 256,
                 defrag_threshold: Optional[float] = None,
                 defrag_check_interval: int = 1,
                 tracer: Optional[obs_trace.Tracer] = None):
        # Validate the allocator knobs before any expensive setup: a
        # typo like alloc_backend="palas" must fail here with the menu
        # of choices, not surface later (or worse, quietly behave like
        # a different configuration).
        from repro.core import BACKENDS, LOWERINGS
        if alloc_backend not in BACKENDS:
            raise ValueError(
                f"unknown alloc_backend {alloc_backend!r}; pick from "
                f"{BACKENDS}")
        if alloc_lowering not in LOWERINGS:
            raise ValueError(
                f"unknown alloc_lowering {alloc_lowering!r}; pick from "
                f"{LOWERINGS}")
        if not isinstance(num_shards, int) or num_shards < 1:
            raise ValueError(
                f"num_shards must be a positive int, got {num_shards!r}")
        if rebalance_threshold is not None:
            if num_shards == 1:
                raise ValueError(
                    "rebalance_threshold requires num_shards > 1")
            if (not isinstance(rebalance_threshold, int)
                    or rebalance_threshold < 1):
                raise ValueError(
                    f"rebalance_threshold must be None or a positive "
                    f"int (pages of max-min shard imbalance), got "
                    f"{rebalance_threshold!r}")
        if defrag_threshold is not None and not (
                0.0 < float(defrag_threshold) < 1.0):
            raise ValueError(
                f"defrag_threshold must be None or a frag_ratio in "
                f"(0, 1), got {defrag_threshold!r}")
        if not isinstance(defrag_check_interval, int) \
                or defrag_check_interval < 1:
            raise ValueError(
                f"defrag_check_interval must be a positive int (steps "
                f"between frag_ratio checks), got "
                f"{defrag_check_interval!r}")
        if not mega_step:
            raise ValueError(
                "mega_step=False asks for the host decode loop, which "
                "was removed: the fused tick is the engine's only "
                "decode loop")
        if not isinstance(max_new_cap, int) or max_new_cap < 1:
            raise ValueError(
                f"max_new_cap must be a positive int, got "
                f"{max_new_cap!r}")
        cfg = model.cfg
        self.model, self.params, self.cfg = model, params, cfg
        self.max_batch, self.max_seq = max_batch, max_seq
        self.page = KV.PAGE_SIZE
        self.pps = -(-max_seq // self.page)
        self.num_pages = num_pages or max_batch * self.pps
        assert sample == "greedy"
        self.compute_dtype = compute_dtype
        self.max_new_cap = max_new_cap
        self.defrag_threshold = (None if defrag_threshold is None
                                 else float(defrag_threshold))
        self.defrag_check_interval = defrag_check_interval
        # observability (DESIGN.md §14): engine phases emit trace
        # spans through the tracer — each span is always a profiler
        # annotation ``serve.<phase>``; NULL records no JSON events —
        # host-side readings publish through the metrics registry
        self.tracer = tracer if tracer is not None else obs_trace.NULL
        self.metrics = obs_metrics.MetricsRegistry()
        self.last_tick_compiled = False

        # --- the paper's allocator manages the page-id space -------------
        # alloc_state is the flat device-resident arena (core/arena.py:
        # one word image + one control block); alloc_backend="pallas"
        # makes every bulk grant/release below a single fused kernel
        # launch (vl segment walk included), bit-identical to "jnp".
        # num_shards > 1 splits the page space into independent arenas
        # (core/shards.py): each sequence slot homes on slot % S, and
        # exhausted shards overflow to neighbors inside the same single
        # kernel launch.
        self.num_shards = num_shards
        self.rebalance_threshold = rebalance_threshold
        self.page_bytes = 256  # logical bytes per page in the heap
        # per-modality page policy (DESIGN.md §13): a layer-list
        # model's recurrent state rides the SAME arena and page heap as
        # its KV pages — aux_pages per slot, granted at admission with
        # the prompt's KV pages and freed with them.  0 for every other
        # family, so those engines are sized as before.
        self.aux_pages = KV.modality_page_quota(cfg)
        self.ouro, self.wpp, physical_pages = KV.make_kv_allocator(
            self.num_pages + max_batch * self.aux_pages,
            backend=alloc_backend,
            lowering=alloc_lowering, num_shards=num_shards)
        self.alloc_state = self.ouro.init()
        self._shard_words = (self.ouro.layout.shard_words
                             if num_shards > 1
                             else self.ouro.cfg.total_words)

        # the page array is sized by the heap's PHYSICAL page space:
        # segment-occupied chunks make granted ids sparse in it.
        self.caches = model.make_decode_caches(
            max_batch, max_seq=max_seq, kv_dtype=kv_dtype,
            num_pages=physical_pages)
        if cfg.is_encdec:
            # cross-attention K/V of every slot at the FIXED encoder
            # length (max_seq frames): each admission writes its row
            xkv = (cfg.num_layers, max_batch, max_seq, cfg.num_kv_heads,
                   cfg.head_dim_)
            self.caches = self.caches._replace(
                cross_k=jnp.zeros(xkv, compute_dtype),
                cross_v=jnp.zeros(xkv, compute_dtype),
                enc_valid=jnp.zeros(max_batch, jnp.int32))
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        # the state pages each admitted slot holds; the device's
        # state-page table is their mirror (KV page ids live in the
        # device page table alone)
        self.slot_aux: List[List[int]] = [[] for _ in range(max_batch)]
        # a slot's state and KV pages are granted and freed in one
        # transaction of a fixed lane count, so every admission and
        # every retirement runs one program
        self._slot_lanes = (self.aux_pages + self.pps if self.aux_pages
                            else None)
        self.slot_len = np.zeros(max_batch, np.int64)  # host truth
        self.waiting: List[Request] = []
        self._uid = 0
        # admission ordinals: which active slot is YOUNGEST (the
        # eviction victim under exhaustion — it loses the least work)
        self._admit_ord = np.zeros(max_batch, np.int64)
        self._admit_counter = 0
        # the prefill argmaxes ON DEVICE: only the first token id
        # crosses the host boundary, never logits.  A named function:
        # the program shows up in a profile as ``jit_prefill``.  It
        # computes the admitted row alone, through a view holding the
        # shared heap and the slot's page-table row, and writes it back
        # at the traced ``slot`` (one program per prompt length, any
        # slot)
        def prefill(p, b, c, slot):
            kv = c.self_kv if cfg.is_encdec else c.kv
            view = c
            if kv is not None:
                kv = kv._replace(
                    page_table=jax.lax.dynamic_slice_in_dim(
                        kv.page_table, slot, 1),
                    seq_lens=jnp.zeros(1, jnp.int32))
                view = (c._replace(self_kv=kv) if cfg.is_encdec
                        else c._replace(kv=kv))
            if getattr(c, "state_table", None) is not None:
                view = view._replace(
                    state_table=jax.lax.dynamic_slice_in_dim(
                        c.state_table, slot, 1))
            tok, row = _tokens_of(model.prefill(
                p, b, view, remat_policy="none", dtype=compute_dtype))
            return tok, put_row(cfg, row, c, slot)

        self._prefill = jax.jit(prefill, donate_argnums=(2,))

        # --- device-resident slot state: the tick's carry ----------------
        B = max_batch
        self.mega_state = MegaState(
            last_tok=jnp.zeros(B, jnp.int32),
            lens=jnp.zeros(B, jnp.int32),
            page_counts=jnp.zeros(B, jnp.int32),
            active=jnp.zeros(B, bool),
            budget=jnp.zeros(B, jnp.int32),
            eos=jnp.full(B, -1, jnp.int32),
            out_buf=jnp.zeros((B, max_new_cap), jnp.int32),
            n_out=jnp.zeros(B, jnp.int32))
        # host mirrors, advanced from the per-tick flag vector — never
        # synced from device mid-flight
        self._pages_host = np.zeros(B, np.int64)
        self._nout_host = np.zeros(B, np.int64)
        self._fail_streak = np.zeros(B, np.int64)
        self._mega_fn = None
        self._mega = None

        from repro.kernels.ops import resolve_lowering
        mem_words = int(np.prod(self.alloc_state.mem.shape))
        ctl_words = int(np.prod(self.alloc_state.ctl.shape))
        self.stats = {"allocs": 0, "frees": 0, "steps": 0,
                      "alloc_failures": 0,
                      # observability: device words the arena occupies,
                      # and which transaction path actually runs
                      "arena_mem_words": mem_words,
                      "arena_ctl_words": ctl_words,
                      "alloc_backend": alloc_backend,
                      "alloc_lowering": (resolve_lowering(alloc_lowering)
                                         if alloc_backend == "pallas"
                                         else "none"),
                      # sharding observability: live pages per shard
                      # (shard_pages_live(), filled by
                      # refresh_frag_stats) and how many grants landed
                      # off their home shard (the overflow walk at work)
                      "num_shards": num_shards,
                      "shard_pages_live": [0] * num_shards,
                      "alloc_overflows": 0,
                      # defragmentation observability (DESIGN.md §10):
                      # transactions issued, waves run, pages moved
                      "alloc_txns": 0,
                      # graceful degradation (DESIGN.md §12): slots
                      # evicted + requeued when defrag could not
                      # reclaim enough pages
                      "evictions": 0,
                      # client abandonment (DESIGN.md §13): requests
                      # cancelled mid-stream or in the waiting queue
                      "cancels": 0,
                      # per-modality page policy: arena pages each
                      # admitted slot holds beyond KV (0 = dense), and
                      # the state pages granted and freed so far
                      "aux_pages_per_slot": self.aux_pages,
                      "state_pages_granted": 0,
                      "state_pages_freed": 0,
                      "defrag_waves": 0,
                      "rebalance_waves": 0,
                      "auto_defrag_waves": 0,
                      "pages_migrated": 0,
                      # decode-loop observability (DESIGN.md §11)
                      "launches_per_tick": None,
                      # jit first-call events observed by step(): how
                      # many of this process's ticks paid a compile
                      # (the replay harness splits its latency summary
                      # on exactly this signal — DESIGN.md §14)
                      "jit_first_calls": 0,
                      # admission prefill work: rows and token-rows
                      # the prefill programs computed, padding included
                      "prefill_rows": 0,
                      "prefill_tokens": 0}
        self._read_frag_stats()  # no slot holds a page yet

    def _compile_count(self) -> int:
        """Total jit-cache entries across the jitted callables a tick
        can dispatch — engine-owned programs plus the allocator's
        class-level transaction jits — grows exactly when a tick
        traced+compiled.  (The allocator jits are shared across
        Ouroboros instances, so another engine compiling in the same
        process can mark one of our ticks "compile" — a conservative
        misclassification: it only withholds that tick from the steady
        percentiles.)"""
        fns = [self._prefill, self._mega]
        fns += [getattr(self.ouro, nm, None) for nm in
                ("_alloc", "_free", "_alloc_sharded", "_free_sharded",
                 "_alloc_pinned", "_free_pinned")]
        return sum(fn._cache_size() for fn in fns
                   if fn is not None and hasattr(fn, "_cache_size"))

    # ---- request lifecycle -------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, eos_id=None) -> int:
        if max_new_tokens > self.max_new_cap:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} exceeds the tick's "
                f"device token buffer (max_new_cap={self.max_new_cap}); "
                f"raise max_new_cap at engine construction")
        self._uid += 1
        self.waiting.append(Request(self._uid, np.asarray(prompt, np.int32),
                                    max_new_tokens, eos_id))
        return self._uid

    def _kv(self):
        c = self.caches
        return c.self_kv if self.cfg.is_encdec else c.kv

    def _set_kv(self, kv):
        if self.cfg.is_encdec:
            self.caches = self.caches._replace(self_kv=kv)
        else:
            self.caches = self.caches._replace(kv=kv)

    def _bulk_alloc(self, homes: List[int],
                    lanes: Optional[int] = None) -> List[int]:
        """ONE allocator transaction granting one page per entry of
        ``homes`` (the requesting slot's home shard — grants overflow
        to neighbor shards when that shard is full).  Lanes from
        different slots coalesce into this single kernel launch: a
        decode step issues at most one transaction for the whole
        batch.  ``lanes`` fixes the transaction's width (default
        ``max(2 · max_batch, pages)``)."""
        n_pages = len(homes)
        lanes = max(self.max_batch * 2, n_pages, lanes or 0)
        sizes = jnp.full(lanes, self.page_bytes, jnp.int32)
        mask = jnp.arange(lanes) < n_pages
        home = np.zeros(lanes, np.int32)
        home[:n_pages] = homes
        self.stats["alloc_txns"] += 1
        with self.tracer.span("bulk_grow", pages=n_pages):
            if self.num_shards > 1:
                self.alloc_state, offs = self.ouro.alloc(
                    self.alloc_state, sizes, mask,
                    shard_hint=jnp.asarray(home))
            else:
                self.alloc_state, offs = self.ouro.alloc(
                    self.alloc_state, sizes, mask)
            offs = np.asarray(offs[:n_pages])
        ok = offs >= 0
        self.stats["allocs"] += int(ok.sum())
        self.stats["alloc_failures"] += int((~ok).sum())
        shard = offs[ok] // self._shard_words
        self.stats["alloc_overflows"] += int((shard != home[:n_pages][ok])
                                             .sum())
        return [int(o) // self.wpp if o >= 0 else -1 for o in offs]

    def _alloc_pages(self, homes: List[int],
                     lanes: Optional[int] = None) -> List[int]:
        """Bulk page grant with defragmentation recovery: if any lane
        fails, return this transaction's partial grants, run ONE
        defrag wave (migrating stragglers together and retiring the
        emptied chunks to the pool), and retry once — the paper-regime
        alternative to dying on a fragmented heap."""
        got = self._bulk_alloc(homes, lanes)
        if all(g >= 0 for g in got):
            return got
        self._bulk_free([g for g in got if g >= 0], lanes=lanes)
        self.defrag()
        return self._bulk_alloc(homes, lanes)

    def shard_pages_live(self) -> List[int]:
        """Live pages per shard, derived from where the page ids live:
        the device page table (KV pages) and ``slot_aux`` (state
        pages).  One small (B, P) device→host read — called on demand
        (rebalance checks, stat refreshes), never per tick."""
        pages = [p for aux in self.slot_aux for p in aux]
        kv = self._kv()
        if kv is not None:
            pt = np.asarray(kv.page_table)
            pages = np.concatenate([pt[pt >= 0], pages])
        live = np.zeros(self.num_shards, np.int64)
        np.add.at(live, np.asarray(pages, np.int64) * self.wpp
                  // self._shard_words, 1)
        return [int(x) for x in live]

    def _bulk_free(self, pages: List[int], count_stats: bool = True,
                   lanes: Optional[int] = None):
        if not pages:
            return
        lanes = max(self.max_batch * 2, len(pages), lanes or 0)
        offs = np.full(lanes, -1, np.int32)
        offs[:len(pages)] = np.asarray(pages, np.int32) * self.wpp
        with self.tracer.span("bulk_free", pages=len(pages)):
            sizes = jnp.full(lanes, self.page_bytes, jnp.int32)
            mask = jnp.asarray(offs >= 0)
            self.alloc_state = self.ouro.free(
                self.alloc_state, jnp.asarray(offs), sizes, mask)
        if count_stats:
            self.stats["frees"] += len(pages)

    def _grant_slot(self, slot: int, upto_tokens: int) -> bool:
        """Admission's ONE allocator transaction: the slot's state
        pages (``aux_pages``: a layer-list model's recurrent state,
        DESIGN.md §13) and the page-table pages covering
        ``upto_tokens`` positions (an admitted slot holds none yet).
        On failure the partial grants go back, so allocs and frees
        stay balanced."""
        need = 0
        if self._kv() is not None:  # attention-free: O(1) state only
            need = -(-upto_tokens // self.page)
        n = self.aux_pages + need
        if n == 0:
            return True
        span = (self.tracer.span("state_grant", slot=slot,
                                 pages=self.aux_pages)
                if self.aux_pages else contextlib.nullcontext())
        with span:
            got = self._alloc_pages([slot % self.num_shards] * n,
                                    self._slot_lanes)
        if any(g < 0 for g in got):
            self._bulk_free([g for g in got if g >= 0],
                            lanes=self._slot_lanes)
            return False
        if self.aux_pages:
            self._set_slot_aux(slot, got[:self.aux_pages])
            self.stats["state_pages_granted"] += self.aux_pages
        if need:
            self._map_granted(slot, got[self.aux_pages:])
        return True

    def _set_slot_aux(self, slot: int, pages: List[int]):
        """The slot's state pages, host list and device table row."""
        self.slot_aux[slot] = list(pages)
        tab = self.caches.state_table
        row = np.full(tab.shape[1:], -1, np.int32)
        if pages:
            row[:] = np.asarray(pages, np.int32).reshape(row.shape)
        self.caches = self.caches._replace(
            state_table=tab.at[slot].set(jnp.asarray(row)))

    def _free_slot_pages(self, slot: int, kv_pages: List[int]):
        """Return a slot's KV pages and state pages to the arena in ONE
        transaction (retirement, eviction, cancel)."""
        aux = self.slot_aux[slot]
        if not aux:
            self._bulk_free(kv_pages)
            return
        with self.tracer.span("state_free", slot=slot, pages=len(aux)):
            self._bulk_free(list(kv_pages) + aux, lanes=self._slot_lanes)
        self.stats["state_pages_freed"] += len(aux)
        self._set_slot_aux(slot, [])

    def _map_granted(self, slot: int, pages: List[int]):
        """Write freshly granted page ids into columns ``0..n-1`` of
        the slot's page-table row (one scatter)."""
        kv = self._kv()
        n = len(pages)
        pt = kv.page_table.at[jnp.asarray([slot] * n, jnp.int32),
                              jnp.asarray(list(range(n)), jnp.int32)].set(
            jnp.asarray(pages, jnp.int32))
        self._set_kv(kv._replace(page_table=pt))

    # ---- defragmentation (core/defrag.py, DESIGN.md §10) -------------------

    def defrag(self) -> int:
        """Run one defragmentation wave on the KV allocator and remap
        every engine-side page reference through the forwarding table
        (KV page heaps + page tables + slot page lists).  Returns the
        number of pages migrated.  Triggered automatically on
        allocation failure and past ``defrag_threshold``; also callable
        by operators between batches."""
        with self.tracer.span("defrag_wave"):
            self.alloc_state, fwd = self.ouro.defrag(self.alloc_state)
            moved = self._apply_forwarding(fwd)
        self.stats["defrag_waves"] += 1
        self.stats["pages_migrated"] += moved
        self.refresh_frag_stats()
        return moved

    def _maybe_auto_defrag(self):
        """Fire one defragmentation wave when ``frag_ratio`` exceeds
        the configured ``defrag_threshold`` (checked every
        ``defrag_check_interval`` steps; max over shards when sharded)
        — the proactive complement to the allocation-failure retry.
        Counted separately in ``stats["auto_defrag_waves"]``."""
        if self.defrag_threshold is None:
            return
        if self.stats["steps"] % self.defrag_check_interval:
            return
        fs = self.refresh_frag_stats()
        ratio = float(np.max(np.asarray(fs["frag_ratio"])))
        if ratio > self.defrag_threshold:
            self.defrag()
            self.stats["auto_defrag_waves"] += 1

    def _maybe_rebalance(self):
        """One cross-shard rebalance wave when per-shard live pages
        diverge beyond ``rebalance_threshold`` (pages, max − min)."""
        if self.num_shards == 1 or self.rebalance_threshold is None:
            return
        live = self.shard_pages_live()
        if max(live) - min(live) <= self.rebalance_threshold:
            return
        with self.tracer.span("rebalance_wave"):
            self.alloc_state, fwd = self.ouro.rebalance(self.alloc_state)
            moved = self._apply_forwarding(fwd)
        self.stats["rebalance_waves"] += 1
        self.stats["pages_migrated"] += moved
        self.refresh_frag_stats()

    def _apply_forwarding(self, fwd) -> int:
        """Remap every page reference the engine holds through a defrag
        forwarding table: KV page heaps move rows old→new, page tables
        rewrite ids, and ``slot_aux`` (with the state table) follows.
        Returns pages migrated."""
        if not (np.asarray(fwd.src) >= 0).any():
            return 0
        max_span = self.ouro.cfg.words_per_chunk // self.wpp
        kv = self._kv()
        if kv is not None:
            self._set_kv(KV.apply_forwarding(kv, fwd, self.wpp,
                                             max_span=max_span))
        # host-side tables remap through the SAME page expansion the
        # KV cache used (one source of truth for extent → page math)
        sp, dp = (np.asarray(x) for x in
                  KV.forwarding_page_map(fwd, self.wpp, max_span))
        mapping: Dict[int, int] = {int(s): int(d)
                                   for s, d in zip(sp, dp) if s >= 0}
        if self.aux_pages:
            # the state pages moved with their heap rows above; their
            # host lists and table follow
            for slot, pages in enumerate(self.slot_aux):
                self._set_slot_aux(slot, [mapping.get(p, p)
                                          for p in pages])
        return len(mapping)

    def refresh_frag_stats(self):
        """Recompute fragmentation observability into ``stats``:
        ``free_words``, ``largest_free_extent``, and ``frag_ratio``
        (1 − largest/total) — per shard when ``num_shards > 1`` — and
        ``shard_pages_live`` (:meth:`shard_pages_live`)."""
        self.stats["shard_pages_live"] = self.shard_pages_live()
        return self._read_frag_stats()

    def _read_frag_stats(self):
        fs = self.ouro.frag_stats(self.alloc_state)
        if self.num_shards > 1:
            self.stats["free_words"] = [
                int(x) for x in np.asarray(fs["free_words"])]
            self.stats["largest_free_extent"] = [
                int(x) for x in np.asarray(fs["largest_free_extent"])]
            self.stats["frag_ratio"] = [
                float(x) for x in np.asarray(fs["frag_ratio"])]
        else:
            self.stats["free_words"] = int(fs["free_words"])
            self.stats["largest_free_extent"] = int(
                fs["largest_free_extent"])
            self.stats["frag_ratio"] = float(fs["frag_ratio"])
        return fs

    # ---- observability (obs/, DESIGN.md §14) -------------------------------

    def drain_telemetry(self) -> dict:
        """Decode the arena's device-side telemetry words (the ctl
        accumulators every lowering updates in-kernel) into a host
        dict ``{field: np.ndarray}`` — per-class arrays carry a
        leading shard axis when ``num_shards > 1``.  A read, not a
        reset: the device words are monotonic."""
        from repro.obs import telemetry as OT
        lay = self.ouro.layout
        if self.num_shards > 1:
            lay = lay.shard
        return OT.decode(lay, np.asarray(self.alloc_state.ctl))

    def publish_metrics(self,
                        registry: Optional[
                            obs_metrics.MetricsRegistry] = None
                        ) -> obs_metrics.MetricsRegistry:
        """Publish every host-side reading through a metrics registry
        (``self.metrics`` unless one is passed): engine stat counters,
        fragmentation gauges, and the drained in-kernel telemetry
        words, labelled by size class / shard / walk attempt.  Returns
        the registry (export with ``to_prometheus()``/``to_json()``)."""
        reg = self.metrics if registry is None else registry
        counters = ("steps", "allocs", "frees", "alloc_failures",
                    "alloc_txns", "alloc_overflows", "evictions",
                    "cancels", "defrag_waves", "rebalance_waves",
                    "auto_defrag_waves", "pages_migrated",
                    "jit_first_calls", "prefill_rows", "prefill_tokens",
                    "state_pages_granted", "state_pages_freed")
        for k in counters:
            reg.counter(f"repro_engine_{k}_total",
                        f"engine stats[{k!r}]").set(float(self.stats[k]))
        load = self.expert_load()
        if load is not None:
            m = reg.counter("repro_engine_expert_tokens_total",
                            "tokens routed to each held expert",
                            labelnames=("layer", "expert"))
            for layer, row in enumerate(load):
                for e, n in enumerate(row):
                    m.labels(layer=layer, expert=self.cfg.expert_offset
                             + e).set(float(n))
        reg.gauge("repro_engine_waiting",
                  "requests queued for admission").set(
                      float(len(self.waiting)))
        reg.gauge("repro_engine_active_slots",
                  "batch slots decoding").set(
            float(sum(r is not None for r in self.slot_req)))
        self.refresh_frag_stats()
        for k in ("free_words", "largest_free_extent", "frag_ratio"):
            g = reg.gauge(f"repro_arena_{k}",
                          f"allocator frag_stats[{k!r}]",
                          labelnames=("shard",))
            v = self.stats[k]
            for s, x in enumerate(v if isinstance(v, list) else [v]):
                g.labels(shard=s).set(float(x))
        tele = self.drain_telemetry()
        per_class = {"t_alloc": "repro_alloc_granted_total",
                     "t_free": "repro_free_total",
                     "t_fail": "repro_alloc_failed_total",
                     "t_wrap": "repro_ring_wrap_total"}
        scalar = {"t_grow": "repro_segment_grow_total",
                  "t_shrink": "repro_segment_shrink_total",
                  "t_pool_wrap": "repro_pool_wrap_total"}
        for field, arr in tele.items():
            arr = np.atleast_2d(np.asarray(arr))   # (S, w)
            if field in per_class:
                m = reg.counter(per_class[field],
                                f"in-kernel ctl telemetry {field}",
                                labelnames=("shard", "size_class"))
                for s in range(arr.shape[0]):
                    for c in range(arr.shape[1]):
                        m.labels(shard=s, size_class=c).set(
                            float(arr[s, c]))
            elif field in scalar:
                m = reg.counter(scalar[field],
                                f"in-kernel ctl telemetry {field}",
                                labelnames=("shard",))
                for s in range(arr.shape[0]):
                    m.labels(shard=s).set(float(arr[s, 0]))
            elif field == "t_walk":
                m = reg.counter("repro_overflow_walk_served_total",
                                "lanes served per overflow-walk "
                                "attempt (in-kernel histogram)",
                                labelnames=("shard", "attempt"))
                for s in range(arr.shape[0]):
                    for a in range(arr.shape[1]):
                        m.labels(shard=s, attempt=a).set(
                            float(arr[s, a]))
        return reg

    def expert_load(self) -> Optional[np.ndarray]:
        """Tokens routed to each held expert, (layers, held) — summed on
        the device by every prefill and tick and read only here (None
        for a model that counts none)."""
        c = self.caches
        if getattr(c, "expert_tokens", None) is None:
            return None
        load = np.asarray(c.expert_tokens)
        self.stats["expert_tokens"] = load.tolist()
        return load

    def _admit(self):
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None or not self.waiting:
                continue
            req = self.waiting.pop(0)
            lp = len(req.prompt)
            if not self._grant_slot(slot, lp + 1):
                self.waiting.insert(0, req)  # heap full; retry later
                break
            batch = {"tokens": jnp.asarray(req.prompt[None])}
            if self.cfg.modality == "audio":
                # FIXED encoder length, so every slot's cross-KV row has
                # one shape.  The stub frontend is zeros; ``src_valid``
                # masks the padding out of cross attention
                # (kv_valid_len).
                batch["src_embeds"] = jnp.zeros(
                    (1, self.max_seq, self.cfg.d_model), jnp.float32)
                batch["src_valid"] = jnp.full(1, lp, jnp.int32)
            rows = int(batch["tokens"].shape[0])
            # from dispatch through the first-token read, so the span
            # ends once the device has finished the prefill
            with self.tracer.span("prefill", slot=slot, uid=req.uid,
                                  prompt_len=lp, rows=rows):
                tok_ids, self.caches = self._prefill(
                    self.params, batch, self.caches, np.int32(slot))
                first = int(np.asarray(tok_ids)[0])
            self.stats["prefill_rows"] += rows
            self.stats["prefill_tokens"] += rows * lp
            req.out_tokens.append(first)
            self.slot_req[slot] = req
            self.slot_len[slot] = lp + 1
            self._admit_counter += 1
            self._admit_ord[slot] = self._admit_counter
            with self.tracer.span("slot_push", uid=req.uid, slot=slot):
                self._mega_admit(slot, req, first)

    # ---- fused decode mega-step (DESIGN.md §11) ----------------------------

    def _mega_admit(self, slot: int, req: Request, first: int):
        """Push an admitted slot's control state to the device arrays.

        Page ids granted at admission already live in the device page
        table (the only id holder, pulled back once at retirement):
        ``_grant_slot`` mapped the pages covering ``slot_len``."""
        npages = (-(-int(self.slot_len[slot]) // self.page)
                  if self._kv() is not None else 0)
        self._pages_host[slot] = npages
        self._nout_host[slot] = 1
        self._fail_streak[slot] = 0
        ms = self.mega_state
        eos = -1 if req.eos_id is None else int(req.eos_id)
        self.mega_state = MegaState(
            last_tok=ms.last_tok.at[slot].set(first),
            lens=ms.lens.at[slot].set(int(self.slot_len[slot])),
            page_counts=ms.page_counts.at[slot].set(npages),
            active=ms.active.at[slot].set(True),
            budget=ms.budget.at[slot].set(req.max_new_tokens - 1),
            eos=ms.eos.at[slot].set(eos),
            out_buf=ms.out_buf.at[slot].set(0).at[slot, 0].set(first),
            n_out=ms.n_out.at[slot].set(1))

    def _build_mega(self):
        """Trace+compile the fused decode tick: grow → scatter →
        forward → sample → advance, ONE jitted function with the whole
        carry (arena, KV caches, slot state) donated."""
        cfg = self.cfg
        model = self.model
        ouro = self.ouro
        page, page_bytes, wpp = self.page, self.page_bytes, self.wpp
        B, S = self.max_batch, self.num_shards
        lanes = B  # decode grows ≤ 1 page per slot per tick
        cap = self.max_new_cap
        dtype = self.compute_dtype
        homes = jnp.arange(B, dtype=jnp.int32) % S
        has_kv = self._kv() is not None

        def mega(params, alloc_state, caches, ms):
            kv = caches.self_kv if cfg.is_encdec else caches.kv
            if has_kv:
                # (a) per-slot page need from device-resident state
                need = jnp.maximum(
                    -(-(ms.lens + 1) // page) - ms.page_counts, 0)
                need = jnp.where(ms.active, need, 0).astype(jnp.int32)
                # (b) bulk grow: ONE arena transaction for the batch
                alloc_state, offs, l_slot, l_rank, l_mask = ouro.grow(
                    alloc_state, need, page_bytes, lanes,
                    home=homes if S > 1 else None)
                ok = l_mask & (offs >= 0)
                granted = jnp.zeros(B + 1, jnp.int32).at[
                    jnp.where(l_mask, l_slot, B)].add(
                        ok.astype(jnp.int32))[:B]
                # a slot fails the tick when ANY of its pages did —
                # its partial grants are withheld from the table and
                # reclaimed by the host-side defrag-retry path
                failed = ms.active & (granted < need)
                grant_ok = ok & ~failed[l_slot]
                # (c) grants → device page table, straight from the
                # arena word offsets (no host-materialized table)
                kv = kv._replace(page_table=KV.scatter_grant_words(
                    kv.page_table, ms.page_counts, l_slot, l_rank,
                    offs, grant_ok, wpp))
                caches = (caches._replace(self_kv=kv) if cfg.is_encdec
                          else caches._replace(kv=kv))
                new_counts = ms.page_counts + jnp.where(failed, 0, need)
            else:  # attention-free family: O(1) state, nothing to grow
                failed = jnp.zeros(B, bool)
                offs = jnp.full(lanes, -1, jnp.int32)
                l_slot = jnp.zeros(lanes, jnp.int32)
                l_mask = jnp.zeros(lanes, bool)
                new_counts = ms.page_counts
            advance = ms.active & ~failed
            # (d) model forward with paged attention; failed/inactive
            # rows write to table holes (dropped) and their cache
            # advance is masked back out below
            view = caches
            if getattr(caches, "state_table", None) is not None:
                # a row that does not advance reads and writes no state
                view = caches._replace(state_table=jnp.where(
                    advance[:, None, None], caches.state_table, -1))
            logits, new_caches = model.decode_step(
                params, ms.last_tok[:, None], view, dtype=dtype)
            caches = merge_rows(cfg, new_caches, caches, advance)
            # (e) greedy sampling + seq/token advance, all on device
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            out_buf = ms.out_buf.at[
                jnp.where(advance, jnp.arange(B, dtype=jnp.int32), B),
                jnp.minimum(ms.n_out, cap - 1)].set(nxt, mode="drop")
            budget = ms.budget - advance.astype(jnp.int32)
            finished = advance & (
                (budget <= 0) | ((ms.eos >= 0) & (nxt == ms.eos)))
            ms2 = MegaState(
                last_tok=jnp.where(advance, nxt, ms.last_tok),
                lens=ms.lens + advance.astype(jnp.int32),
                page_counts=new_counts,
                active=ms.active & ~finished,
                budget=budget,
                eos=ms.eos,
                out_buf=out_buf,
                n_out=ms.n_out + advance.astype(jnp.int32))
            # the ONLY per-tick host sync: bit 0 finished, bit 1 failed
            flags = (finished.astype(jnp.uint8)
                     | (failed.astype(jnp.uint8) << 1))
            return alloc_state, caches, ms2, flags, offs, l_slot, l_mask

        self._mega_fn = mega
        self._mega = jax.jit(mega, donate_argnums=(1, 2, 3))

    def launches_per_tick(self) -> int:
        """``pallas_call`` launch count of ONE decode tick, read off
        the jaxprs (kernels/ops.count_pallas_calls — the same counter
        as the per-transaction and per-wave proofs) of the single fused
        tick program — constant in ``max_batch`` by construction.
        Recorded into ``stats["launches_per_tick"]``; benchmarks/
        common.launches_per_tick delegates here so fig8 records and
        engine stats can never disagree."""
        from repro.kernels.ops import count_pallas_calls
        if self._mega is None:
            self._build_mega()
        jx = jax.make_jaxpr(self._mega_fn)(
            self.params, self.alloc_state, self.caches, self.mega_state)
        n = count_pallas_calls(jx)
        self.stats["launches_per_tick"] = n
        return n

    def _step_mega(self) -> List[Request]:
        """One fused decode tick + control-plane follow-up: dispatch
        the mega-step, sync the (B,) flag vector, advance the host
        mirrors, reclaim/retry on allocation failure, retire finished
        slots (the only point page ids and tokens are pulled back)."""
        active = [s for s in range(self.max_batch)
                  if self.slot_req[s] is not None]
        if not active:
            return []
        if self._mega is None:
            self._build_mega()
        with self.tracer.span("decode", slots=len(active)):
            (self.alloc_state, self.caches, self.mega_state, flags,
             l_offs, l_slot, l_mask) = self._mega(
                self.params, self.alloc_state, self.caches,
                self.mega_state)
        with self.tracer.span("flag_sync"):
            flags = np.asarray(flags)      # the per-tick host sync
        fin = (flags & 1) > 0
        fail = (flags & 2) > 0
        has_kv = self._kv() is not None

        # host mirrors advance deterministically from the flags — the
        # grant count is recomputed with the SAME need formula the
        # device used, so allocs/frees stay exactly balanced
        grants = 0
        for s in active:
            if fail[s]:
                self.stats["alloc_failures"] += 1
                continue
            if has_kv:
                missing = (-(-(int(self.slot_len[s]) + 1) // self.page)
                           - int(self._pages_host[s]))
                grants += max(missing, 0)
                self._pages_host[s] += max(missing, 0)
            self.slot_len[s] += 1
            self._nout_host[s] += 1
        if has_kv:
            self.stats["alloc_txns"] += 1
            self.stats["allocs"] += grants

        if fail.any():
            self._recover_failed(fail, fin, l_offs, l_slot, l_mask)
        else:
            self._fail_streak[:] = 0

        finished = []
        for s in np.nonzero(fin)[0].tolist():
            req = self.slot_req[s]
            if req is not None:  # not evicted this tick
                with self.tracer.span("retire", uid=req.uid, slot=s,
                                      tokens=int(self._nout_host[s])):
                    finished.append(self._release_mega(s))
        return finished

    def _recover_failed(self, fail, fin, l_offs, l_slot, l_mask):
        """Alloc-failure path (host-side): pull
        the lane arrays (failure ticks only), return the failed slots'
        partial grants to the heap, run ONE defrag wave, and let the
        next tick retry.  Two consecutive failed retries mean defrag
        cannot reclaim enough — gracefully degrade by evicting the
        youngest active slot (its pages return to the heap, its
        request requeues and replays identically under greedy decode)
        instead of killing the server with ``MemoryError``."""
        offs_h = np.asarray(l_offs)
        slot_h = np.asarray(l_slot)
        mask_h = np.asarray(l_mask)
        leaked = mask_h & (offs_h >= 0) & fail[slot_h]
        self._free_offsets(offs_h[leaked])
        self.defrag()
        self._fail_streak[fail] += 1
        self._fail_streak[~fail] = 0
        if (self._fail_streak >= 2).any():
            # slots finishing THIS tick retire (and free) right after
            # this call — evicting one would double-release it, and
            # its pages come back anyway
            victim = self._youngest_active(
                exclude=set(int(s) for s in np.nonzero(fin)[0]))
            if victim is not None:
                self._evict_slot(victim)
                self._fail_streak[:] = 0

    def _free_offsets(self, offs_words):
        """Uncounted bulk free of raw word offsets (failure recovery:
        these grants were never counted as allocs either)."""
        if len(offs_words) == 0:
            return
        self._bulk_free([int(o) // self.wpp for o in offs_words],
                        count_stats=False)

    def _release_mega(self, slot: int) -> Request:
        """Retire one finished slot: pull its token row and page-table
        row from device (the only mid-flight device→host reads besides
        the flag vector), free the pages, and zero the slot's device
        state."""
        req = self.slot_req[slot]
        n = int(self._nout_host[slot])
        buf = np.asarray(self.mega_state.out_buf[slot])
        req.out_tokens = [int(x) for x in buf[:n]]
        req.done = True
        kv = self._kv()
        if kv is not None:
            row = np.asarray(kv.page_table[slot])
            self._free_slot_pages(slot, [int(p) for p in row[row >= 0]])
            pt = kv.page_table.at[slot].set(-1)
            sl = kv.seq_lens.at[slot].set(0)
            self._set_kv(kv._replace(page_table=pt, seq_lens=sl))
        else:
            self._free_slot_pages(slot, [])
        ms = self.mega_state
        self.mega_state = MegaState(
            last_tok=ms.last_tok.at[slot].set(0),
            lens=ms.lens.at[slot].set(0),
            page_counts=ms.page_counts.at[slot].set(0),
            active=ms.active.at[slot].set(False),
            budget=ms.budget.at[slot].set(0),
            eos=ms.eos.at[slot].set(-1),
            out_buf=ms.out_buf,
            n_out=ms.n_out.at[slot].set(0))
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        self._admit_ord[slot] = 0
        self._pages_host[slot] = 0
        self._nout_host[slot] = 0
        self._fail_streak[slot] = 0
        return req

    # ---- graceful degradation: evict + requeue under exhaustion ------------

    def _youngest_active(self, exclude=()) -> Optional[int]:
        """The eviction victim: the most recently admitted active slot
        (it loses the least generated work, and greedy decode replays
        its stream identically after re-admission)."""
        slots = [s for s in range(self.max_batch)
                 if self.slot_req[s] is not None and s not in exclude]
        if not slots:
            return None
        return max(slots, key=lambda s: int(self._admit_ord[s]))

    def _drop_slot(self, slot: int) -> Request:
        """Free EVERY page an active slot holds (KV + modality aux)
        back through the allocator and zero its slot state, host and
        device — the shared teardown under eviction (which requeues)
        and cancellation (which drops).  Allocs/frees stay balanced:
        the frees here are counted exactly like retirement frees.
        Returns the slot's request."""
        req = self.slot_req[slot]
        kv = self._kv()
        # mid-flight the device page-table row is the only page-id
        # holder
        row = (np.asarray(kv.page_table[slot]) if kv is not None
               else np.zeros(0, np.int32))
        self._free_slot_pages(slot, [int(p) for p in row[row >= 0]])
        ms = self.mega_state
        self.mega_state = MegaState(
            last_tok=ms.last_tok.at[slot].set(0),
            lens=ms.lens.at[slot].set(0),
            page_counts=ms.page_counts.at[slot].set(0),
            active=ms.active.at[slot].set(False),
            budget=ms.budget.at[slot].set(0),
            eos=ms.eos.at[slot].set(-1),
            out_buf=ms.out_buf,
            n_out=ms.n_out.at[slot].set(0))
        self._pages_host[slot] = 0
        self._nout_host[slot] = 0
        self._fail_streak[slot] = 0
        kv = self._kv()
        if kv is not None:
            self._set_kv(kv._replace(
                page_table=kv.page_table.at[slot].set(-1),
                seq_lens=kv.seq_lens.at[slot].set(0)))
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        self._admit_ord[slot] = 0
        return req

    def _evict_slot(self, slot: int):
        """Evict one active slot: free every page it holds back
        through the allocator, zero its slot state (host and device),
        and push its request to the FRONT of the waiting queue with
        its generated tokens discarded — re-admission replays the
        identical stream (greedy decode is deterministic), so one
        oversized burst degrades throughput instead of killing the
        server.  Counted in ``stats["evictions"]``."""
        with self.tracer.span("eviction", slot=slot):
            req = self._drop_slot(slot)
        req.out_tokens = []
        req.done = False
        self.waiting.insert(0, req)
        self.stats["evictions"] += 1

    def cancel(self, uid: int) -> bool:
        """Cancel a request — the client-abandonment path (DESIGN.md
        §13).  Three cases, all legal between any two steps:

        - uid still in the **waiting queue**: removed before it ever
          touches a slot;
        - uid **active in a slot**: every page the slot holds (KV +
          modality aux) is freed back through the allocator in bulk —
          allocs and frees stay balanced — and the slot opens for the
          next admission;
        - uid **already retired** (or never submitted): a no-op
          returning ``False``, never a ``KeyError`` — retirement
          legitimately races a client's hangup.

        Returns True iff the request was actually cancelled; counted
        in ``stats["cancels"]``."""
        for i, r in enumerate(self.waiting):
            if r.uid == uid:
                self.waiting.pop(i)
                self.stats["cancels"] += 1
                self.tracer.instant("cancel", uid=uid, where="waiting")
                return True
        for slot in range(self.max_batch):
            r = self.slot_req[slot]
            if r is not None and r.uid == uid:
                with self.tracer.span("cancel", uid=uid, slot=slot):
                    self._drop_slot(slot)
                self.stats["cancels"] += 1
                return True
        return False

    # ---- main loop -----------------------------------------------------------
    def step(self) -> List[Request]:
        """Admit, decode one token for all active slots (the fused
        tick), retire finished requests.  Returns requests finished
        this step.

        The whole step is one ``tick`` trace span whose category —
        ``"compile"`` when any engine jit traced this step,
        ``"steady"`` otherwise — is resolved at close from the jit
        cache sizes; ``last_tick_compiled`` exposes the same signal to
        the replay harness (DESIGN.md §14)."""
        ts = self.tracer.begin("tick", step=self.stats["steps"] + 1)
        pre = self._compile_count()
        with self.tracer.span("admission"):
            self._admit()
        self._maybe_rebalance()
        finished = self._step_mega()
        self.stats["steps"] += 1
        self._maybe_auto_defrag()
        grew = self._compile_count() - pre
        self.stats["jit_first_calls"] += grew
        self.last_tick_compiled = grew > 0
        self.tracer.complete(
            "tick", ts, cat="compile" if grew > 0 else "steady",
            step=self.stats["steps"], finished=len(finished))
        return finished

    def run_until_done(self, max_steps: int = 10000) -> List[Request]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.waiting and all(r is None for r in self.slot_req):
                break
        return out

    # ---- crash-safe serving: snapshot / restore (DESIGN.md §12) ------------

    def snapshot_fingerprint(self) -> dict:
        """The layout-validation contract (DESIGN.md §12): everything
        that decides how snapshot words are INTERPRETED — the arena
        layout rendering (the same ``describe()`` the golden-layout
        tests pin), allocator geometry, and engine geometry.  A
        snapshot restores only into an engine whose fingerprint
        matches exactly; allocator ``backend``/``lowering`` are
        deliberately absent (transactions are bit-identical across
        them, so a snapshot may restore onto a different one)."""
        kv = self._kv()
        lay = self.ouro.layout
        return {
            "snapshot_version": SNAPSHOT_VERSION,
            "arena_layout": lay.describe(),
            "variant": self.ouro.variant,
            "num_shards": self.num_shards,
            "wpp": self.wpp,
            "page_bytes": self.page_bytes,
            "page_tokens": self.page,
            "num_pages": self.num_pages,
            "arch": self.cfg.name,
            "max_batch": self.max_batch,
            "max_seq": self.max_seq,
            "max_new_cap": self.max_new_cap,
            "kv_dtype": (None if kv is None
                         else str(kv.layers.k.dtype)),
        }

    def _snapshot_tree(self):
        """The array half of a snapshot (also the restore template):
        arena slabs, KV caches, the tick's device carry and its host
        mirrors."""
        return {"arena_mem": self.alloc_state.mem,
                "arena_ctl": self.alloc_state.ctl,
                "caches": self.caches,
                "slot_len": np.asarray(self.slot_len),
                "mega": self.mega_state,
                "pages_host": np.asarray(self._pages_host),
                "nout_host": np.asarray(self._nout_host),
                "fail_streak": np.asarray(self._fail_streak)}

    def _snapshot_meta(self) -> dict:
        """The JSON half: fingerprint, request queue, host tables,
        stats counters (everything non-array a restart needs)."""
        meta = {
            "fingerprint": self.snapshot_fingerprint(),
            "uid": self._uid,
            "admit_counter": self._admit_counter,
            "admit_ord": [int(x) for x in self._admit_ord],
            "slot_reqs": [None if r is None else _req_to_json(r)
                          for r in self.slot_req],
            "waiting": [_req_to_json(r) for r in self.waiting],
            "slot_aux": [[int(p) for p in ps]
                         for ps in self.slot_aux],
            "stats": {k: v for k, v in self.stats.items()},
        }
        # round-trip now: catches an unserializable field at snapshot
        # time (not at some later restore) and deep-copies
        return json.loads(json.dumps(meta))

    def snapshot(self, directory: Optional[str] = None,
                 step: Optional[int] = None, keep: int = 3):
        """Capture the COMPLETE serving state at a step boundary:
        arena word image + control block (all shards), KV page heaps +
        page tables + ``seq_lens``, the mega-step carry and its host
        mirrors, the waiting queue and in-flight requests, and the
        stats block.  With ``directory``, writes an atomic committed
        checkpoint through ckpt/checkpoint.py (requests and the layout
        fingerprint ride the ``meta.json`` sidecar) and returns the
        committed path; otherwise returns the in-memory snapshot dict
        ``{"tree", "meta"}`` that :meth:`restore` accepts directly."""
        with self.tracer.span("snapshot",
                              to_disk=directory is not None):
            meta = self._snapshot_meta()
            if directory is not None:
                from repro.ckpt import checkpoint as CK
                return CK.save(self._snapshot_tree(), directory,
                               step=self.stats["steps"] if step is None
                               else step,
                               keep=keep, extra=meta)
            tree = jax.tree.map(lambda x: np.array(jax.device_get(x)),
                                self._snapshot_tree())
            return {"tree": tree, "meta": meta}

    def restore(self, source, step: Optional[int] = None):
        """Load a snapshot taken by :meth:`snapshot` — an in-memory
        snapshot dict, or a checkpoint directory (newest committed
        step unless ``step`` is given; a step swept by a concurrent
        retention falls back to the next-newest).  The snapshot's
        layout fingerprint is validated FIRST: a snapshot from a
        different ``ArenaLayout`` or engine geometry is rejected
        loudly with a ``ValueError`` naming the differing fields —
        never silently misinterpreted.  After restore, decoding
        resumes token-identically for every in-flight sequence.
        Returns the restored checkpoint step (None for in-memory
        snapshots)."""
        with self.tracer.span("restore"):
            if isinstance(source, str):
                from repro.ckpt import checkpoint as CK
                meta_rec, s = CK.read_meta(source, step)
                meta = meta_rec.get("extra")
                if meta is None or "fingerprint" not in meta:
                    raise ValueError(
                        f"checkpoint step {s} under {source!r} is not "
                        f"a serving-engine snapshot (no fingerprint "
                        f"sidecar)")
                self._validate_fingerprint(meta["fingerprint"])
                tree, s = CK.restore(self._snapshot_tree(), source,
                                     step=s)
                self._apply_snapshot(tree, meta)
                return s
            meta = source["meta"]
            self._validate_fingerprint(meta["fingerprint"])
            self._apply_snapshot(source["tree"], meta)
            return None

    def _validate_fingerprint(self, fp: dict):
        mine = self.snapshot_fingerprint()
        if fp != mine:
            diffs = sorted(k for k in set(fp) | set(mine)
                           if fp.get(k) != mine.get(k))
            raise ValueError(
                f"snapshot layout fingerprint mismatch on fields "
                f"{diffs} — refusing to restore: a snapshot from a "
                f"different ArenaLayout or engine geometry would be "
                f"silently misinterpreted (snapshot "
                f"{ {k: fp.get(k) for k in diffs} !r} vs engine "
                f"{ {k: mine.get(k) for k in diffs} !r})")

    def _apply_snapshot(self, tree, meta):
        """Install validated snapshot state (fingerprint already
        checked; every array leaf is additionally shape/dtype-checked
        against the live engine before anything is mutated)."""
        def check(path, new, old):
            new = jnp.asarray(np.asarray(new))
            old = jnp.asarray(old)
            if new.shape != old.shape or new.dtype != old.dtype:
                raise ValueError(
                    f"snapshot leaf {jax.tree_util.keystr(path)}: "
                    f"shape/dtype {new.shape}/{new.dtype} does not "
                    f"match the engine's {old.shape}/{old.dtype}")
            return new

        mapped = jax.tree_util.tree_map_with_path(
            check, tree, self._snapshot_tree())
        self.alloc_state = self.alloc_state._replace(
            mem=mapped["arena_mem"], ctl=mapped["arena_ctl"])
        self.caches = mapped["caches"]
        self.slot_len = np.asarray(mapped["slot_len"], np.int64).copy()
        self.mega_state = mapped["mega"]
        self._pages_host = np.asarray(mapped["pages_host"],
                                      np.int64).copy()
        self._nout_host = np.asarray(mapped["nout_host"],
                                     np.int64).copy()
        self._fail_streak = np.asarray(mapped["fail_streak"],
                                       np.int64).copy()
        self.slot_req = [None if d is None else _req_from_json(d)
                         for d in meta["slot_reqs"]]
        self.waiting = [_req_from_json(d) for d in meta["waiting"]]
        self.slot_aux = [[int(p) for p in ps] for ps in meta["slot_aux"]]
        self._uid = int(meta["uid"])
        self._admit_counter = int(meta["admit_counter"])
        self._admit_ord = np.asarray(meta["admit_ord"], np.int64)
        # counters restore; engine-identity fields (which backend /
        # lowering / launch count THIS process runs) stay fresh
        identity = {"arena_mem_words", "arena_ctl_words",
                    "alloc_backend", "alloc_lowering", "num_shards",
                    "launches_per_tick",
                    "aux_pages_per_slot", "jit_first_calls"}
        for k, v in meta["stats"].items():
            if k in self.stats and k not in identity:
                self.stats[k] = v
        self.refresh_frag_stats()


def _tokens_of(model_out):
    """(logits, caches) → (greedy token ids, caches): the argmax runs
    inside the jit so only (B,) int32 ids are ever fetched."""
    logits, caches = model_out
    return jnp.argmax(logits, -1).astype(jnp.int32), caches
