"""End-to-end behaviour tests: every assigned architecture smokes
(forward + train step on a reduced config, CPU), decode matches the
train-mode forward on one arch per family, and the paged-KV serving
engine round-trips requests through the Ouroboros allocator.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_arch
from repro.models.model import build_model
from repro.paged import kv_cache as KV

B, S = 2, 32


def _batch(cfg, rng, s=S):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, s)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, s)),
                               jnp.int32),
    }
    if cfg.modality == "audio":
        batch["src_embeds"] = jnp.asarray(
            rng.standard_normal((B, s, cfg.d_model)), jnp.float32)
    if cfg.modality == "vision":
        batch["mm_embeds"] = jnp.asarray(
            rng.standard_normal((B, s, cfg.d_model)) * 0.02, jnp.float32)
    return batch


@pytest.mark.slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_forward_and_train_step(arch, rng):
    """One forward + one optimizer step on the reduced config: output
    shapes correct, loss finite, gradients flow (params change)."""
    cfg = get_arch(arch).smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)

    loss, metrics = jax.jit(m.loss)(params, batch)
    assert np.isfinite(float(loss))
    assert abs(float(loss) - np.log(cfg.padded_vocab)) < 1.5

    from repro.train.optimizer import AdamW
    from repro.train.train_step import init_state, make_train_step
    opt = AdamW(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    step = jax.jit(make_train_step(m, opt))
    state = init_state(m, jax.random.PRNGKey(0), opt)
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # at least one leaf moved
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(state2.params)))
    assert moved


FAMILY_REPS = ["qwen2-0.5b", "mixtral-8x7b", "mamba2-780m",
               "recurrentgemma-9b", "seamless-m4t-large-v2"]


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILY_REPS)
def test_decode_matches_forward(arch, rng):
    """Paged-KV/stateful decode reproduces the train-mode forward
    logits token-by-token (f32, MoE no-drop capacity)."""
    cfg = get_arch(arch).smoke()
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=4.0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    T, S0 = 40, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)

    if cfg.is_encdec:
        from repro.models import encdec as ED
        se = jnp.asarray(rng.standard_normal((B, S0, cfg.d_model)),
                         jnp.float32)
        enc = ED.encode(cfg, params, se, "full", jnp.float32)
        logits_full, _ = ED.decode_stack(
            cfg, params, toks, enc, "train",
            ED.EncDecCaches(None, None, None, None), "full", jnp.float32)
    else:
        from repro.models import transformer as TF
        logits_full, _, _ = TF.forward(cfg, params, toks, mode="train",
                                       dtype=jnp.float32)

    caches = m.make_decode_caches(B, max_seq=T, kv_dtype=jnp.float32)
    pps = -(-T // KV.PAGE_SIZE)
    pt = (jnp.arange(B)[:, None] * pps
          + jnp.arange(pps)[None, :]).astype(jnp.int32)
    if cfg.is_encdec:
        caches = caches._replace(self_kv=caches.self_kv._replace(
            page_table=pt))
    elif caches.kv is not None:
        caches = caches._replace(kv=caches.kv._replace(page_table=pt))

    batch_pre = {"tokens": toks[:, :S0]}
    if cfg.is_encdec:
        batch_pre["src_embeds"] = se
    lp, caches = m.prefill(params, batch_pre, caches, dtype=jnp.float32)
    scale = float(np.abs(np.asarray(logits_full)).max())
    errs = [float(np.abs(lp - logits_full[:, S0 - 1]).max())]
    for t in range(S0, T):
        ld, caches = m.decode_step(params, toks[:, t:t + 1], caches,
                                   dtype=jnp.float32)
        errs.append(float(np.abs(ld - logits_full[:, t]).max()))
    assert max(errs) < 0.01 * max(scale, 1.0), errs


def test_engine_roundtrip(rng):
    from repro.serve.engine import ServingEngine
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(m, params, max_batch=3, max_seq=96,
                        kv_dtype=jnp.float32)
    for _ in range(5):
        eng.submit(rng.integers(2, cfg.vocab_size,
                                int(rng.integers(4, 30))),
                   max_new_tokens=6)
    done = eng.run_until_done(200)
    assert len(done) == 5
    assert all(len(r.out_tokens) == 6 for r in done)
    assert eng.stats["alloc_failures"] == 0
    assert eng.stats["frees"] == eng.stats["allocs"]


def test_engine_validates_allocator_knobs():
    """A typo like alloc_backend="palas" must fail at construction
    with the menu of choices — never silently behave like another
    configuration (it previously surfaced, if at all, only from deep
    inside allocator setup)."""
    from repro.serve.engine import ServingEngine
    with pytest.raises(ValueError, match="alloc_backend.*palas"):
        ServingEngine(None, None, alloc_backend="palas")
    with pytest.raises(ValueError, match="alloc_lowering.*bocked"):
        ServingEngine(None, None, alloc_lowering="bocked")


def test_engine_surfaces_active_lowering(rng):
    """engine.stats reports the allocator backend and the RESOLVED
    kernel lowering actually in use (whole|blocked for pallas, none
    for jnp), so operators can tell which compiled story served a
    request stream."""
    from repro.serve.engine import ServingEngine
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32, alloc_backend="pallas",
                        alloc_lowering="blocked")
    assert eng.stats["alloc_backend"] == "pallas"
    assert eng.stats["alloc_lowering"] == "blocked"
    eng.submit(rng.integers(2, cfg.vocab_size, 6), max_new_tokens=3)
    done = eng.run_until_done(50)
    assert len(done) == 1 and len(done[0].out_tokens) == 3
    assert eng.stats["alloc_failures"] == 0
    assert eng.stats["frees"] == eng.stats["allocs"] > 0

    eng2 = ServingEngine(m, params, max_batch=2, max_seq=64,
                         kv_dtype=jnp.float32, alloc_backend="jnp")
    assert eng2.stats["alloc_lowering"] == "none"


def test_engine_roundtrip_pallas_alloc_backend(rng):
    """The engine's bulk page grants/releases through the fused
    single-kernel arena transactions (alloc_backend="pallas") behave
    identically to the jnp oracle path: same grants, no failures, all
    pages returned.  (Bit-level backend parity is test_alloc_txn_parity;
    this pins the serving wiring end to end.)"""
    from repro.serve.engine import ServingEngine
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32, alloc_backend="pallas")
    assert eng.stats["arena_mem_words"] > 0
    for _ in range(3):
        eng.submit(rng.integers(2, cfg.vocab_size,
                                int(rng.integers(4, 20))),
                   max_new_tokens=4)
    done = eng.run_until_done(100)
    assert len(done) == 3
    assert all(len(r.out_tokens) == 4 for r in done)
    assert eng.stats["alloc_failures"] == 0
    assert eng.stats["frees"] == eng.stats["allocs"] > 0


def test_engine_sharded_allocator(rng):
    """num_shards>1: the engine's KV allocator becomes the sharded
    multi-arena (core/shards.py) — each sequence slot homes on
    slot % num_shards — and the per-shard live-page occupancy the
    engine derives (``shard_pages_live``) is right mid-flight and
    returns to zero when every request retires."""
    from repro.serve.engine import ServingEngine
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(m, params, max_batch=3, max_seq=96,
                        kv_dtype=jnp.float32, num_shards=2)
    assert eng.stats["num_shards"] == 2
    assert len(eng.shard_pages_live()) == 2
    for _ in range(4):
        eng.submit(rng.integers(2, cfg.vocab_size,
                                int(rng.integers(4, 24))),
                   max_new_tokens=4)
    eng.step()  # admit: slots 0..2 prefill → shards 0 and 1 populated
    live = eng.shard_pages_live()
    assert sum(live) == eng.stats["allocs"] - eng.stats["frees"]
    assert all(x > 0 for x in live), \
        "slot % num_shards routing left a shard empty mid-flight"
    done = eng.run_until_done(200)
    assert len(done) == 4
    assert eng.stats["alloc_failures"] == 0
    assert eng.stats["frees"] == eng.stats["allocs"] > 0
    assert eng.shard_pages_live() == [0, 0], \
        "per-shard occupancy must drain with the requests"


def test_engine_validates_num_shards():
    from repro.serve.engine import ServingEngine
    with pytest.raises(ValueError, match="num_shards"):
        ServingEngine(None, None, num_shards=0)


def test_engine_greedy_matches_batch_decode(rng):
    """Engine output == straight prefill+decode for the same prompt."""
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    prompt = rng.integers(2, cfg.vocab_size, 12)

    from repro.serve.engine import ServingEngine
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32,
                        compute_dtype=jnp.float32)
    eng.submit(prompt, max_new_tokens=5)
    done = eng.run_until_done(50)
    got = done[0].out_tokens

    # reference with IDENTICAL batch shape (padded row) and dtype so
    # the computation is bit-identical and argmax ties cannot flip
    caches = m.make_decode_caches(2, max_seq=64, kv_dtype=jnp.float32)
    pps = -(-64 // KV.PAGE_SIZE)
    pt = jnp.full((2, pps), -1, jnp.int32).at[0].set(jnp.arange(pps))
    caches = caches._replace(kv=caches.kv._replace(page_table=pt))
    toks = np.zeros((2, len(prompt)), np.int32)
    toks[0] = prompt
    lp, caches = m.prefill(params, {"tokens": jnp.asarray(toks)}, caches,
                           dtype=jnp.float32)
    want = [int(np.argmax(np.asarray(lp[0])))]
    for _ in range(4):
        step_toks = jnp.asarray([[want[-1]], [0]], jnp.int32)
        ld, caches = m.decode_step(params, step_toks, caches,
                                   dtype=jnp.float32)
        want.append(int(np.argmax(np.asarray(ld[0]))))
    assert got == want
