"""Compile the blocked allocator kernels for a described TPU v5e chip.

Interpret mode runs a kernel on the CPU and accepts what the chip's
compiler (Mosaic) refuses: scalar reads from VMEM, slices that break
the HBM tiling, primitives it cannot lower.  These tests compile the
kernels of the main path at real size for a ``v5e:2x2`` topology that
is described, not attached, so a refusal fails here instead of on the
chip:

- the blocked alloc and free of all six variants on the 32 MiB
  benchmark heap (``benchmarks/common.BENCH_HEAP``) with 1024 lanes,
  single-arena and with four shards;
- the vl_chunk blocked defrag wave at the serving engine's arena size
  (qwen2-0.5b, ``max_batch=16``, ``max_seq=4096``), single-arena and
  with four shards;
- the serving engine's fused decode tick for qwen2-0.5b at its
  published widths with the Pallas allocator, built from shapes only;
- the engine's admission prefill of one 2048-token prompt at the same
  widths (XLA alone: it holds no kernel);
- the same tick and a 512-token admission prefill, donated as the
  engine donates them: neither copies its bf16 page heap (a heap whose
  rows tile needs no relayout, and the layer scan carries it);
- the fused tick and a 1024-token admission prefill of granite-4.0-h's
  stage 0 (64 slots × 5120 tokens), whose page heap holds the KV and
  the recurrent state: neither program copies or converts the heap.

Each allocator or tick compile must hold a Mosaic kernel
(``tpu_custom_call``).  The topology is described only inside the
module fixture: the TPU library may be loaded by one process at a
time, so nothing here may touch it while the module is imported.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from benchmarks.common import BENCH_HEAP
from repro.core import VARIANTS, Ouroboros
from repro.kernels import alloc_txn_blocked as blk
from repro.kernels import defrag_txn
from repro.kernels import ops
from repro.paged.kv_cache import PAGE_SIZE, make_kv_allocator

LANES = 1024
MAX_BATCH, MAX_SEQ = 16, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compile_for(one_chip):
    """Compile ``fn`` for arguments of the given shapes on the described
    chip (donating the arguments ``donate`` names, as the engine does)
    with the persistent cache off (a TPU compile written to it could not be
    read back without a chip); returns the compiled program's text."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def place(a):   # an int32 shape, or a pytree of shaped values
        if isinstance(a, tuple) and all(isinstance(d, int) for d in a):
            return jax.ShapeDtypeStruct(a, jnp.int32, sharding=one_chip)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), a)

    def run(fn, *args, donate=()):
        placed = [place(a) for a in args]
        return jax.jit(fn, donate_argnums=donate).lower(
            *placed).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _arena_shapes(ouro):
    """(mem, ctl) shapes of a single or sharded arena."""
    st = jax.eval_shape(ouro.init)
    return st.mem.shape, st.ctl.shape


@pytest.mark.parametrize("num_shards", (1, 4))
@pytest.mark.parametrize("op", ("alloc", "free"))
@pytest.mark.parametrize("variant", VARIANTS)
def test_blocked_txn_compiles_for_v5e(compile_for, variant, op,
                                      num_shards):
    o = Ouroboros(BENCH_HEAP, variant, num_shards=num_shards)
    S, kind, fam = num_shards, o.kind, o.family
    if op == "alloc" and S == 1:
        def fn(mem, ctl, sizes, mask):
            return blk.arena_alloc_txn_blocked(BENCH_HEAP, kind, fam, mem,
                                               ctl, sizes, mask)
        lanes = 2
    elif op == "alloc":
        def fn(mem, ctl, sizes, mask, home):
            return blk.sharded_arena_alloc_txn_blocked(
                BENCH_HEAP, S, kind, fam, mem, ctl, sizes, mask, home,
                S - 1)
        lanes = 3
    elif S == 1:
        def fn(mem, ctl, offs, sizes, mask):
            return blk.arena_free_txn_blocked(BENCH_HEAP, kind, fam, mem,
                                              ctl, offs, sizes, mask)
        lanes = 3
    else:
        def fn(mem, ctl, offs, sizes, mask):
            return blk.sharded_arena_free_txn_blocked(
                BENCH_HEAP, S, kind, fam, mem, ctl, offs, sizes, mask)
        lanes = 3
    text = compile_for(fn, *_arena_shapes(o), *((LANES,),) * lanes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("num_shards", (1, 4))
def test_blocked_defrag_compiles_at_serving_arena(compile_for, num_shards):
    ouro, _, _ = make_kv_allocator(MAX_BATCH * (MAX_SEQ // PAGE_SIZE),
                                   num_shards=num_shards)
    moves = ouro._moves(None)

    def fn(mem, ctl, src, dst, sizes):
        if num_shards == 1:
            return defrag_txn.arena_defrag_txn_blocked(
                ouro.cfg, "chunk", "vl", mem, ctl, src, dst, sizes)
        return defrag_txn.sharded_arena_defrag_txn_blocked(
            ouro.cfg, num_shards, "chunk", "vl", mem, ctl, src, dst, sizes)

    text = compile_for(fn, *_arena_shapes(ouro), *((moves,),) * 3)
    assert "tpu_custom_call" in text


@pytest.fixture()
def full_width_engine(monkeypatch):
    """qwen2-0.5b's mega-step engine at published widths, built from
    shapes: parameters from ``eval_shape`` and abstract KV caches, so
    nothing full-size is allocated on this host."""
    from repro.configs import get_arch
    from repro.models import model as M
    from repro.serve.engine import ServingEngine

    caches = M.Model.make_decode_caches
    monkeypatch.setattr(M.Model, "make_decode_caches",
                        lambda self, *a, **k: caches(self, *a, **k,
                                                     abstract=True))
    model = M.build_model(get_arch("qwen2-0.5b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return ServingEngine(model, params, max_batch=MAX_BATCH,
                         max_seq=MAX_SEQ, alloc_backend="pallas",
                         alloc_lowering="blocked", mega_step=True,
                         max_new_cap=64)


def test_mega_tick_compiles_at_full_width(compile_for, full_width_engine,
                                          monkeypatch):
    """The fused decode tick (grow kernel + qwen2-0.5b forward) at
    published widths; the kernels are compiled, not interpreted, as
    they are on a chip."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    eng = full_width_engine
    eng._build_mega()
    carry = jax.eval_shape(lambda *a: a, eng.params, eng.alloc_state,
                           eng.caches, eng.mega_state)
    assert "tpu_custom_call" in compile_for(eng._mega_fn, *carry)


def test_admission_prefill_compiles_at_full_width(compile_for,
                                                  full_width_engine):
    """The admission prefill of one 2048-token prompt at published
    widths computes one row: the program's activations are
    ``(1, 2048, d_model)``, never ``(max_batch, 2048, d_model)``."""
    eng = full_width_engine
    lp, d = 2048, eng.cfg.d_model
    batch = {"tokens": jax.ShapeDtypeStruct((1, lp), jnp.int32)}
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    text = compile_for(eng._prefill, eng.params, batch, eng.caches, slot)
    assert f"[1,{lp},{d}]" in text
    assert f"[{MAX_BATCH},{lp},{d}]" not in text


@pytest.fixture()
def granite_engine(monkeypatch):
    """granite-4.0-h-small's stage-0 engine at the benchmark cell's
    geometry, built from shapes."""
    from repro.configs import get_arch
    from repro.models import model as M
    from repro.serve.engine import ServingEngine

    caches = M.Model.make_decode_caches
    monkeypatch.setattr(M.Model, "make_decode_caches",
                        lambda self, *a, **k: caches(self, *a, **k,
                                                     abstract=True))
    model = M.build_model(get_arch("granite-4.0-h-small.stage0"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return ServingEngine(model, params, max_batch=64, max_seq=5120,
                         kv_dtype=jnp.float32, alloc_backend="pallas",
                         alloc_lowering="blocked", mega_step=True,
                         max_new_cap=4096)


_HLO_DTYPES = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}


def _heap_copies(text, heap):
    """Lines of a compiled program that copy or convert the page heap,
    whole or one layer of it, or make it in a dtype other than its own
    (XLA on a TPU lowers a float32 heap that a default-precision
    product reads to bfloat16 whole; a heap whose rows do not tile is
    relaid out layer by layer)."""
    import re
    shapes = (heap.shape, heap.shape[1:])
    dims = "|".join(",".join(map(str, d)) for d in shapes)
    made = re.compile(r"= (\w+)\[(?:1,)?(?:%s)\]\{[^}]*\} ([\w-]+)\(" % dims)
    own = _HLO_DTYPES[jnp.dtype(heap.dtype).name]
    return [ln for ln in text.splitlines()
            for m in [made.search(ln)]
            if m and (m.group(2).startswith(("copy", "convert"))
                      or m.group(1) != own)]


def test_mega_tick_never_copies_the_heap(compile_for, full_width_engine,
                                         monkeypatch):
    """qwen2-0.5b's tick, donated as the engine donates it, appends and
    reads each layer's K/V in the stacked bf16 heap it was given and
    copies none of it."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    eng = full_width_engine
    eng._build_mega()
    carry = jax.eval_shape(lambda *a: a, eng.params, eng.alloc_state,
                           eng.caches, eng.mega_state)
    text = compile_for(eng._mega_fn, *carry, donate=(1, 2, 3))
    assert not _heap_copies(text, eng.caches.kv.layers.k)


def test_admission_prefill_never_copies_the_heap(compile_for,
                                                 full_width_engine):
    """A 512-token qwen2-0.5b admission writes the row's K/V pages in
    place."""
    eng = full_width_engine
    batch = {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)}
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    text = compile_for(eng._prefill, eng.params, batch, eng.caches, slot,
                       donate=(2,))
    assert not _heap_copies(text, eng.caches.kv.layers.k)


def test_granite_tick_compiles_and_never_copies_the_heap(
        compile_for, granite_engine, monkeypatch):
    """The tick reads and writes every slot's state pages and KV in the
    page heap it was given (5.2 GB here) and copies none of it."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    eng = granite_engine
    eng._build_mega()
    carry = jax.eval_shape(lambda *a: a, eng.params, eng.alloc_state,
                           eng.caches, eng.mega_state)
    text = compile_for(eng._mega_fn, *carry, donate=(1, 2, 3))
    assert "tpu_custom_call" in text
    assert not _heap_copies(text, eng.caches.kv.layers.k)


def test_granite_prefill_compiles_and_never_copies_the_heap(
        compile_for, granite_engine):
    """A 1024-token admission writes the row's state pages and KV pages
    in place."""
    eng = granite_engine
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1024), jnp.int32)}
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    text = compile_for(eng._prefill, eng.params, batch, eng.caches, slot,
                       donate=(2,))
    assert not _heap_copies(text, eng.caches.kv.layers.k)
