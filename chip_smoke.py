"""Bring-up check of the main path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs in one process, starts no other, and fails unless JAX's first
device is a TPU.  Two phases, each through the entry points a user
calls:

1. Allocator: the paper's §3 iteration (alloc -> write -> read-back
   check -> free) once per variant on the 32 MiB benchmark heap, 1024
   lanes of 1000 B, with the fused Pallas kernels on the blocked
   lowering beside the jnp oracle; offsets and every ``mem``/``ctl``
   word must match.  Then one churn and one defrag wave on vl_chunk,
   forwarding table included.
2. Serving: qwen2-0.5b at its published widths with random parameters
   from ``--seed``, ``max_batch=16``, ``max_seq=4096``, 16 requests of
   256- or 1024-token prompts and 64 new tokens, served twice: the
   fused decode tick with the Pallas allocator, and the same tick on
   the jnp allocator (the CLI default) as the reference.  Token streams
   must be identical per request, the allocator balanced and
   failure-free, and the compiled Pallas tick must hold a Mosaic kernel
   (``tpu_custom_call``).

Earlier lines print set-up facts (device, bytes, compile and wall
seconds); they are not measurements.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed check raises, so no result line is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N_LANES, LANE_BYTES = 1024, 1000
MAX_BATCH, MAX_SEQ, MAX_NEW = 16, 4096, 64
PROMPT_LENS = (256, 1024)


def _compiled_text(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def _mosaic(fn, *args):
    assert "tpu_custom_call" in _compiled_text(fn, *args), (
        "compiled program holds no Mosaic kernel: the Pallas path was "
        "not compiled for the chip")


def _words(state):
    import numpy as np
    return np.asarray(state.mem).copy(), np.asarray(state.ctl).copy()


def _same(what, a, b):
    import numpy as np
    for x, y in zip(a, b):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            raise AssertionError(f"{what}: pallas/blocked and jnp differ")


def allocator_phase():
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import BENCH_HEAP
    from repro.core import VARIANTS, Ouroboros

    sizes = jnp.full(N_LANES, LANE_BYTES, jnp.int32)
    mask = jnp.ones(N_LANES, bool)
    tags = jnp.arange(N_LANES, dtype=jnp.int32)
    for variant in VARIANTS:
        t0 = time.perf_counter()
        runs = []
        for backend, lowering in (("pallas", "blocked"), ("jnp", "auto")):
            o = Ouroboros(BENCH_HEAP, variant, backend, lowering)
            st = o.init()
            if backend == "pallas":
                _mosaic(lambda s, z, m: o.alloc(s, z, m), st, sizes, mask)
                _mosaic(lambda s, f, z, m: o.free(s, f, z, m), st,
                        tags, sizes, mask)
            st, offs = o.alloc(st, sizes, mask)
            offs = np.asarray(offs)
            after_alloc = _words(st)
            st = o.write_pattern(st, jnp.asarray(offs), sizes, tags)
            ok = np.asarray(o.check_pattern(st, jnp.asarray(offs), sizes,
                                            tags))
            st = o.free(st, jnp.asarray(offs), sizes, mask)
            runs.append((offs, after_alloc, _words(st), ok))
        (po, pa, pf, pok), (jo, ja, jf, _) = runs
        granted = po >= 0
        assert granted.any() and pok[granted].all(), (
            f"{variant}: read-back check failed")
        _same(f"{variant} offsets", [po], [jo])
        _same(f"{variant} arena after alloc", pa, ja)
        _same(f"{variant} arena after free", pf, jf)
        print(f"alloc {variant}: {int(granted.sum())}/{N_LANES} granted, "
              f"pallas/blocked == jnp, "
              f"{time.perf_counter() - t0:.2f} s", flush=True)

    # one churn, then one defrag wave, on the served variant
    t0 = time.perf_counter()
    runs = []
    odd = jnp.arange(N_LANES) % 2 == 1
    for backend, lowering in (("pallas", "blocked"), ("jnp", "auto")):
        o = Ouroboros(BENCH_HEAP, "vl_chunk", backend, lowering)
        st, offs = o.alloc(o.init(), sizes, mask)
        st = o.write_pattern(st, offs, sizes, tags)
        st = o.free(st, offs, sizes, odd)
        if backend == "pallas":
            _mosaic(lambda s: o.defrag(s), st)
        st, fwd = o.defrag(st)
        runs.append((_words(st), tuple(np.asarray(x) for x in fwd)))
    (pw, pfwd), (jw, jfwd) = runs
    _same("vl_chunk arena after defrag", pw, jw)
    _same("vl_chunk forwarding table", pfwd, jfwd)
    moved = int((pfwd[0] >= 0).sum())
    assert moved > 0, "the defrag wave moved nothing"
    print(f"defrag vl_chunk: {moved} pages moved, pallas/blocked == jnp, "
          f"{time.perf_counter() - t0:.2f} s", flush=True)


def _serve(model, params, prompts, **engine_kw):
    import jax
    from repro.serve.engine import ServingEngine

    t0 = time.perf_counter()
    eng = ServingEngine(model, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                        max_new_cap=MAX_NEW, **engine_kw)
    facts = {"kv_bytes": sum(x.nbytes for x in jax.tree.leaves(eng.caches)),
             "arena_bytes": sum(x.nbytes for x in
                                jax.tree.leaves(eng.alloc_state))}
    facts["launches_per_tick"] = eng.launches_per_tick()
    if eng.stats["alloc_backend"] == "pallas":
        tc = time.perf_counter()
        _mosaic(eng._mega_fn, eng.params, eng.alloc_state, eng.caches,
                eng.mega_state)
        facts["tick_compile_s"] = time.perf_counter() - tc
    for p in prompts:
        eng.submit(p, max_new_tokens=MAX_NEW)
    done, ticks = [], 0
    while eng.waiting or any(r is not None for r in eng.slot_req):
        ts = time.perf_counter()
        done.extend(eng.step())
        if ticks == 0:
            facts["first_step_s"] = time.perf_counter() - ts
        ticks += 1
    facts.update(ticks=ticks, wall_s=time.perf_counter() - t0,
                 tokens=sum(len(r.out_tokens) for r in done))
    streams = {r.uid: list(r.out_tokens) for r in done}
    return streams, dict(eng.stats), facts


def serving_phase(seed: int):
    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.models.model import build_model

    cfg = get_arch("qwen2-0.5b")          # published widths, full depth
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    pbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"serve qwen2-0.5b: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"head_dim {cfg.head_dim_}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; parameter bytes {pbytes}", flush=True)
    rng = np.random.default_rng(seed)
    lens = rng.permutation(np.repeat(PROMPT_LENS, MAX_BATCH // 2))
    prompts = [rng.integers(2, cfg.vocab_size, int(n)) for n in lens]

    results = {}
    for name, kw in (("pallas", dict(alloc_backend="pallas")),
                     ("jnp", {})):
        streams, stats, facts = _serve(model, params, prompts, **kw)
        print(f"serve {name}: {facts}", flush=True)
        assert len(streams) == len(prompts), f"{name}: lost requests"
        assert stats["alloc_failures"] == 0, f"{name}: {stats}"
        assert stats["allocs"] == stats["frees"], f"{name}: {stats}"
        results[name] = (streams, stats, facts)
    assert results["pallas"][2]["launches_per_tick"] == 1
    if results["pallas"][0] != results["jnp"][0]:
        raise AssertionError("token streams differ between the tick on "
                             "the Pallas allocator and on the jnp one")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"serve: token streams identical for {len(prompts)} requests; "
          f"peak_bytes_in_use {peak}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro import compile_cache

    print(f"device {dev.device_kind}, {len(jax.devices())} device(s); "
          f"compile cache {compile_cache.enable()}", flush=True)
    for name, phase in (("allocator", allocator_phase),
                        ("serving", lambda: serving_phase(args.seed))):
        t0 = time.perf_counter()
        phase()
        print(f"phase {name}: {time.perf_counter() - t0:.1f} s wall",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
