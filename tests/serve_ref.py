"""Reference token streams for the serving tests.

A request served alone: an unpadded ``(1, lp)`` prefill on a fresh
one-row cache with the canonical page table (and, for a layer-list
model, the canonical state-page table), then greedy ``decode_step``.
It shares no code with the engine's admission, tick, allocator or
page bookkeeping, so an engine stream that equals it is right whatever
slot, shard count or allocator backend served it.

Everything is float32: the comparison is exact.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.paged import kv_cache as KV
from repro.serve.replay import engine_factory


@functools.lru_cache(maxsize=None)
def factory(arch, max_seq=96):
    """``engine_factory(arch, max_seq=...)``, built once per process."""
    return engine_factory(arch, max_seq=max_seq)


@functools.lru_cache(maxsize=None)
def _programs(arch, max_seq):
    """The factory's model and weights, with a jitted prefill and
    decode."""
    eng = factory(arch, max_seq)[1]()
    m = eng.model
    return (m, eng.params,
            jax.jit(functools.partial(m.prefill, remat_policy="none",
                                      dtype=jnp.float32)),
            jax.jit(functools.partial(m.decode_step, dtype=jnp.float32)))


def _one_row_caches(m, max_seq):
    cfg = m.cfg
    pps = -(-max_seq // KV.PAGE_SIZE)
    aux = KV.modality_page_quota(cfg)
    caches = m.make_decode_caches(1, max_seq=max_seq, kv_dtype=jnp.float32,
                                  num_pages=pps + aux if aux else None)
    kv = caches.self_kv if cfg.is_encdec else caches.kv
    if kv is not None:
        kv = kv._replace(page_table=jnp.arange(
            kv.page_table.shape[1], dtype=jnp.int32)[None])
        caches = (caches._replace(self_kv=kv) if cfg.is_encdec
                  else caches._replace(kv=kv))
    if aux:  # the state pages follow the KV pages in the heap
        tab = caches.state_table
        caches = caches._replace(state_table=(
            pps + jnp.arange(aux, dtype=jnp.int32)).reshape(tab.shape))
    return caches


@functools.lru_cache(maxsize=None)
def reference_stream(arch, prompt, n, max_seq=96):
    """``n`` greedy tokens of ``prompt`` (a tuple) served alone."""
    m, params, prefill, decode = _programs(arch, max_seq)
    cfg = m.cfg
    prompt = np.asarray(prompt, np.int32)
    caches = _one_row_caches(m, max_seq)
    batch = {"tokens": jnp.asarray(prompt[None])}
    if cfg.modality == "audio":
        batch["src_embeds"] = jnp.zeros((1, max_seq, cfg.d_model),
                                        jnp.float32)
        batch["src_valid"] = jnp.asarray([len(prompt)], jnp.int32)
    logits, caches = prefill(params, batch, caches)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < n:
        logits, caches = decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                                caches)
        out.append(int(jnp.argmax(logits[0])))
    return out


def expected_stream(arch, prompt, max_new, eos_id=None, max_seq=96):
    """What the engine emits for one request: the prefill token, then
    decode tokens until the budget (at least one decode, so
    ``max_new_tokens=1`` yields two tokens) or a decoded ``eos_id``."""
    ref = reference_stream(arch, tuple(int(t) for t in prompt),
                           max(max_new, 2), max_seq)
    if eos_id is not None and eos_id in ref[1:]:
        return ref[:ref.index(eos_id, 1) + 1]
    return ref
