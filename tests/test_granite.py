"""A model served from its layer list (granite-4.0-h), at a small size
on the CPU, against the plain float32 reference ``bench/granite_ref.py``.

- The engine's one-row prefill and its decode through the paged KV and
  the paged recurrent state give the reference's logits, on a layer
  list with both kinds and a held share of the experts.
- The held shares of an expert layer, summed over every share with the
  shared expert counted once, are the uncut layer.
- A serving prefill drops no token, however the router loads one
  expert, and its dispatch buffer holds S rows an expert, not S·K.
- A slot's state pages are granted with its prompt's KV pages in one
  transaction, freed with its KV pages at retirement, eviction and
  cancel, and follow a defragmentation wave with their heap rows.

Weights come from the benchmark's own weight rules (``bench/serving``
with the configuration file's ``init``), so the state and the routing
look as they do on the chip.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import granite_ref, serving
from repro.configs import get_arch
from repro.models import layers as Lyr
from repro.models import moe as Moe
from repro.models.model import build_model
from repro.obs import trace as obs_trace
from repro.paged import kv_cache as KV
from repro.serve.engine import ServingEngine

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Both sides compute in float32 here; the program's chunked SSD scan and
# blockwise attention only reorder sums, which moved logits (|x| < 1)
# by 1.2e-7 at most.  The fp8 control moves them by ~1e-2, so 1e-4
# separates the two by two orders of magnitude each way.
LOGIT_ATOL = 1e-4


def _file():
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-4.0-h-small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """The smoke layer list (mamba, attention, mamba), 4 of 8 experts
    held, a shared expert; weights by the configuration's rules."""
    cfg = get_arch("granite-4.0-h-small").smoke()
    m = build_model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sizes = dict(hidden_size=cfg.d_model, vocab_size=cfg.vocab_size,
                 init=_file()["init"])
    params = serving.make_params(shapes, m.logical_axes(), sizes, 2 ** 33 + 9)
    ref = dict(
        layer_types=list(cfg.layer_types), rms_norm_eps=cfg.norm_eps,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        attention_multiplier=cfg.attn_scale_,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling, mamba_n_heads=cfg.ssm_nheads,
        mamba_d_head=cfg.ssm_headdim, mamba_d_state=cfg.ssm_state,
        mamba_n_groups=cfg.ssm_ngroups, mamba_d_conv=cfg.ssm_conv,
        num_experts_per_tok=cfg.num_experts_per_tok,
        held_expert_offset=cfg.expert_offset)
    return cfg, m, params, ref


def _engine(small, mega=False, **kw):
    cfg, m, params, _ = small
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 96)
    return ServingEngine(m, params, kv_dtype=jnp.float32,
                         compute_dtype=jnp.float32, mega_step=mega, **kw)


def _reference_logits(small, seq, quant=None):
    cfg, _, params, ref = small
    tokens = np.zeros(512, np.int32)
    tokens[:len(seq)] = seq
    h = granite_ref.hidden(params, jnp.asarray(tokens), ref, quant)
    emb = params["embed"].astype(jnp.float32) / cfg.logits_scaling
    return np.asarray(granite_ref.mm("sd,vd->sv", h, emb, quant))


@pytest.mark.parametrize("lp", (37, 16))
def test_prefill_and_paged_decode_match_reference(small, lp):
    """A prompt of several SSD chunks (37 at chunk 16) or exactly one:
    the prefill's last logits, then 12 decode steps through the slot's
    page tables (KV pages grown tick by tick), each against the
    reference's full forward over the same tokens."""
    cfg, m, params, _ = small
    eng = _engine(small)
    prompt = np.random.default_rng(lp).integers(
        2, cfg.vocab_size, lp).astype(np.int32)
    eng.submit(prompt, max_new_tokens=16)
    eng._admit()
    slot = 0
    c, kv = eng.caches, eng.caches.kv
    view = c._replace(
        kv=kv._replace(page_table=kv.page_table[slot:slot + 1],
                       seq_lens=jnp.zeros(1, jnp.int32)),
        state_table=c.state_table[slot:slot + 1])
    got = [np.asarray(m.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                view, remat_policy="none",
                                dtype=jnp.float32)[0][0])]
    decode = jax.jit(lambda p, t, c: m.decode_step(p, t, c,
                                                   dtype=jnp.float32))
    toks = [eng.slot_req[slot].out_tokens[0]]
    for _ in range(12):
        eng._grow_active([slot])
        t = np.zeros((eng.max_batch, 1), np.int32)
        t[slot, 0] = toks[-1]
        lg, eng.caches = decode(params, jnp.asarray(t), eng.caches)
        eng.slot_len[slot] += 1
        got.append(np.asarray(lg[slot]))
        toks.append(int(np.argmax(got[-1])))
    seq = np.concatenate([prompt, toks[:-1]])
    want = _reference_logits(small, seq)[lp - 1:len(seq)]
    assert toks[0] == int(np.argmax(want[0]))
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=LOGIT_ATOL)
    control = _reference_logits(small, seq, "fp8")[lp - 1:len(seq)]
    assert np.abs(control - want).max() > 10 * LOGIT_ATOL


def test_held_shares_sum_to_the_uncut_layer(small):
    """Four chips of two experts each: the parts they compute, with
    the shared expert (which every chip computes alike) counted once,
    add up to the reference's layer holding all eight."""
    cfg, _, params, ref = small
    p = jax.tree.map(lambda a: a[0], params["mamba"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, cfg.d_model))
    E, ranks = cfg.num_experts, 4
    full = dataclasses.replace(cfg, num_held_experts=E, expert_offset=0)
    # the uncut layer: all eight experts, their weights drawn apart
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    whole = dict(p, **{k: jax.random.normal(kk, (E,) + p[k].shape[1:])
                       * p[k].shape[-2] ** -0.5
                       for k, kk in zip(("w_gate", "w_up", "w_down"), keys)})
    shared = Lyr.apply_mlp(cfg, p["shared"], x)
    parts = 0.0
    for r in range(ranks):
        n = E // ranks
        share = dataclasses.replace(cfg, num_held_experts=n,
                                    expert_offset=r * n)
        pr = dict(whole, **{k: whole[k][r * n:(r + 1) * n]
                            for k in ("w_gate", "w_up", "w_down")})
        y, _, _ = Moe.apply_moe(share, pr, x, no_drop=True)
        parts = parts + (y - shared)
    y_full, _, _ = Moe.apply_moe(full, whole, x, no_drop=True)
    # float32 throughout: the shares only regroup one sum of eight
    # weighted expert outputs (|y| ~ 1), which moves it by ~1e-7
    np.testing.assert_allclose(parts + shared, y_full, rtol=1e-5, atol=1e-5)
    want = granite_ref.moe(whole, x[0], dict(ref, held_expert_offset=0),
                           None)
    np.testing.assert_allclose(y_full[0], want, rtol=1e-5, atol=1e-5)


def test_serving_prefill_drops_no_token(small):
    """A router that sends every token to expert 0: it gets S tokens,
    over the training capacity 1.25·S·K/E, and the serving prefill
    still matches the reference; the dispatch buffer is (1, held, S, d),
    never S·K rows an expert."""
    cfg, _, params, ref = small
    p = dict(jax.tree.map(lambda a: a[0], params["mamba"]["ffn"]))
    S, K, E = 32, cfg.num_experts_per_tok, cfg.num_experts
    assert S > 1.25 * S * K / E
    x = jax.random.normal(jax.random.PRNGKey(5), (1, S, cfg.d_model))
    x = x.at[..., 0].set(4.0)                 # a shared direction
    p["router"] = p["router"].at[0, 0].set(8.0)
    logits = x[0] @ p["router"]
    assert (jnp.argmax(logits, -1) == 0).all()
    y, _, load = Moe.apply_moe(cfg, p, x, no_drop=True)
    want = granite_ref.moe(p, x[0], ref, None)
    # float32 on both sides, dispatch against dense weighting: sums in
    # another order (~1e-7); a dropped token misses a whole expert's
    # part, > 1e-3 (asserted below)
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-5)
    assert int(load[0]) == S and int(load.sum()) <= S * K
    dropped, _, _ = Moe.apply_moe(cfg, p, x, no_drop=False)
    assert np.abs(np.asarray(dropped[0]) - want).max() > 1e-3
    text = str(jax.make_jaxpr(lambda x: Moe.apply_moe(cfg, p, x, True))(x))
    d, h = cfg.d_model, cfg.held_experts
    assert f"[1,{h},{S},{d}]" in text
    assert f"[1,{h},{S * K},{d}]" not in text


def _live_pages(eng):
    pt = np.asarray(eng._kv().page_table)
    return np.concatenate([pt[pt >= 0], np.asarray(
        [p for aux in eng.slot_aux for p in aux], np.int64)])


@pytest.mark.parametrize("mega", [False, True], ids=["host", "mega"])
def test_state_pages_granted_with_kv_and_freed_with_them(small, mega):
    """Each admission is one transaction of the slot's state pages and
    its prompt's KV pages; retirement, eviction and cancel each return
    both in one; the page balance is 0 throughout, and a drained engine
    holds no page and no state-table entry."""
    cfg, *_ = small
    tracer = obs_trace.Tracer(enabled=True)
    eng = _engine(small, mega, max_batch=3, tracer=tracer)
    aux = KV.modality_page_quota(cfg)
    assert eng.aux_pages == aux == 2 * KV.state_pages_per_layer(cfg)
    assert eng.caches.ssm_h is None and eng.caches.ssm_conv is None
    rng = np.random.default_rng(1)
    uids = [eng.submit(rng.integers(2, cfg.vocab_size, 20),
                       max_new_tokens=6) for _ in range(5)]
    txns = eng.stats["alloc_txns"]
    eng._admit()
    assert eng.stats["alloc_txns"] - txns == 3          # one per slot
    assert eng.stats["state_pages_granted"] == 3 * aux
    tab = np.asarray(eng.caches.state_table)
    for s in range(3):
        assert sorted(tab[s].ravel()) == sorted(eng.slot_aux[s])
    live = _live_pages(eng)
    assert live.size == np.unique(live).size
    assert eng.stats["allocs"] - eng.stats["frees"] == live.size
    eng.step()
    eng._evict_slot(0)                                   # eviction
    assert eng.cancel(uids[1])                           # cancel
    assert eng.stats["state_pages_freed"] == 2 * aux
    assert (np.asarray(eng.caches.state_table)[:2] < 0).all()
    live = _live_pages(eng)
    assert eng.stats["allocs"] - eng.stats["frees"] == live.size
    done = eng.run_until_done(300)                       # retirement
    assert len(done) == 4
    assert eng.stats["allocs"] == eng.stats["frees"]
    assert eng.stats["state_pages_freed"] == eng.stats["state_pages_granted"]
    assert (np.asarray(eng.caches.state_table) < 0).all()
    assert not any(eng.slot_aux)
    names = [e["name"] for e in tracer.events]
    assert names.count("state_grant") == eng.stats["state_pages_granted"] // aux
    assert names.count("state_free") == eng.stats["state_pages_freed"] // aux
    load = eng.expert_load()
    assert load.shape == (cfg.num_layers, cfg.held_experts) and load.sum() > 0
    reg = eng.publish_metrics()
    assert reg.get("repro_engine_state_pages_freed_total").samples[()] \
        == eng.stats["state_pages_freed"]


def test_defrag_moves_state_pages_with_their_rows(small):
    """Retirements leave holes; a defragmentation wave then moves live
    pages, state pages among them, and every stream goes on exactly as
    in an engine that never defragmented."""
    cfg, *_ = small

    def serve(defrag):
        eng = _engine(small, True, max_batch=3)
        rng = np.random.default_rng(4)
        for n in (2, 9, 3, 9, 4, 9):
            eng.submit(rng.integers(2, cfg.vocab_size, 18),
                       max_new_tokens=n)
        done, moved = [], 0
        for k in range(200):
            done += eng.step()
            if defrag and k == 4:
                aux = [list(a) for a in eng.slot_aux]
                moved = eng.defrag()
                tab = np.asarray(eng.caches.state_table)
                for s, pages in enumerate(eng.slot_aux):
                    assert sorted(tab[s][tab[s] >= 0]) == sorted(pages)
                moved_aux = sum(a != b for a, b in zip(aux, eng.slot_aux))
            if not eng.waiting and all(r is None for r in eng.slot_req):
                break
        assert eng.stats["allocs"] == eng.stats["frees"]
        out = {r.uid: r.out_tokens for r in done}
        return out, (moved, moved_aux) if defrag else None

    plain, _ = serve(False)
    moved_run, (moved, moved_aux) = serve(True)
    assert moved > 0 and moved_aux > 0
    assert moved_run == plain
