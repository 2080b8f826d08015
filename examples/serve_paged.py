"""End-to-end example: serve a small LM with continuous batching over
the Ouroboros paged KV cache — requests of mixed lengths stream through
the allocator (alloc on growth, free on completion).

    PYTHONPATH=src python examples/serve_paged.py
"""
import sys

sys.path.insert(0, "src")

import numpy as np

import jax

from repro.configs import get_arch
from repro.models.model import build_model
from repro.serve.engine import ServingEngine

cfg = get_arch("qwen2-0.5b").smoke()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

eng = ServingEngine(model, params, max_batch=4, max_seq=256)
rng = np.random.default_rng(0)

# 12 requests with wildly mixed prompt/output lengths — the dynamic
# partitioning workload the paper motivates (§1).
for i in range(12):
    plen = int(rng.integers(4, 60))
    eng.submit(rng.integers(2, cfg.vocab_size, plen),
               max_new_tokens=int(rng.integers(4, 24)))

done = eng.run_until_done()
for r in sorted(done, key=lambda r: r.uid):
    print(f"req {r.uid:2d}: prompt {len(r.prompt):2d} tok "
          f"→ generated {len(r.out_tokens):2d} tok")
print(f"\nallocator: {eng.stats['allocs']} pages allocated, "
      f"{eng.stats['frees']} freed, "
      f"{eng.stats['alloc_failures']} failures over "
      f"{eng.stats['steps']} engine steps")
assert eng.stats["allocs"] == eng.stats["frees"], "page leak!"
print("no page leaks — every allocation returned to the heap")

# ---- the fused decode tick (DESIGN.md §11) --------------------------------
# Every decode step above ran as ONE jitted device-resident function —
# page growth, grant scatter, paged attention, greedy sampling,
# sequence advance — and the host synced a (B,) finished/failed flag
# vector per token.  Its kernel-launch count, read off the jaxpr:
print(f"launches per fused tick = {eng.launches_per_tick()} "
      f"(constant in max_batch)")

import jax.numpy as jnp

# the uninterrupted reference streams for the crash-safe run below
ref = ServingEngine(model, params, max_batch=4, max_seq=256,
                    kv_dtype=jnp.float32, compute_dtype=jnp.float32)
rng = np.random.default_rng(1)
prompts = [(rng.integers(2, cfg.vocab_size, int(rng.integers(4, 40))),
            int(rng.integers(4, 12))) for _ in range(6)]
for p, mx in prompts:
    ref.submit(p, max_new_tokens=mx)
want = {r.uid: r.out_tokens for r in ref.run_until_done()}

# ---- crash-safe serving (DESIGN.md §12) -----------------------------------
# Snapshot the COMPLETE serving state mid-stream — arena word image +
# control block, KV page heaps + page tables, request queue — into an
# atomic on-disk checkpoint, "crash", restore into a fresh engine, and
# finish: the streams are token-identical to never having crashed.
# (launch/serve.py wires this to SIGTERM via --snapshot-dir/--resume.)
import tempfile

snapdir = tempfile.mkdtemp(prefix="serve_snap_")
eng = ServingEngine(model, params, max_batch=4, max_seq=256,
                    kv_dtype=jnp.float32, compute_dtype=jnp.float32)
for p, mx in prompts:
    eng.submit(p, max_new_tokens=mx)
early = []
for _ in range(4):                       # a few ticks...
    early.extend(eng.step())
eng.snapshot(directory=snapdir)          # ...snapshot...
del eng                                  # ...and "crash"

resumed = ServingEngine(model, params, max_batch=4, max_seq=256,
                        kv_dtype=jnp.float32, compute_dtype=jnp.float32)
step = resumed.restore(snapdir)          # fingerprint-validated
got2 = {r.uid: r.out_tokens
        for r in early + resumed.run_until_done()}
assert got2 == want, "restored run diverged from the reference!"
print(f"crash-safe serving: snapshot at step {step}, restored engine "
      f"finished all {len(got2)} streams token-identically")
