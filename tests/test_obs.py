"""Observability layer tests (obs/, DESIGN.md §14).

Three surfaces under test:

1. **In-kernel telemetry** — the ctl-block accumulator region
   (``ArenaLayout.tele_fields()``) is advanced inside the existing
   single transaction ``pallas_call``.  The matrix here replays the
   same randomized trace through the jnp oracle and BOTH Pallas
   lowerings (whole / blocked), single-arena and ``num_shards=4``, and
   requires the drained telemetry words to be **bit-identical** across
   implementations AND to reconcile against host-side bookkeeping of
   the trace (granted/freed/failed lane counts).  The one-kernel fusion
   criterion is re-asserted on the jaxpr with telemetry active — the
   accumulators must not cost a launch.

2. **Metrics registry** (obs/metrics.py) — labelled counters / gauges /
   histograms, Prometheus text exposition (schema-checked by
   ``validate_exposition``) and JSON export, declaration hygiene.

3. **Trace spans** (obs/trace.py) — Chrome ``trace_event`` documents,
   the engine span taxonomy, the compile-vs-steady tick split that
   ``validate_trace(..., require_phases=True)`` enforces, and the NULL
   no-op tracer.  Plus ``StepMonitor`` publishing through a registry
   (ft/runtime.py), so training and serving export through one funnel.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import HeapConfig, Ouroboros
from repro.core import arena
from repro.kernels.ops import count_pallas_calls
from repro.obs import telemetry
from repro.obs.metrics import (MetricsRegistry, validate_exposition)
from repro.obs.trace import (NULL, PHASES, Tracer, validate_trace)

pytestmark = pytest.mark.obs

CFG = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                 min_page_bytes=16)
# menu spans every class plus an over-chunk size that must fail AND an
# over-large size (class == num_classes) that must count as neither an
# attempt nor a failure
SIZES = [16, 24, 100, 256, 1000, 2048, 8192]
N = 16
SHARDS = 4

IMPLS = (("jnp", dict(backend="jnp")),
         ("whole", dict(backend="pallas", lowering="whole")),
         ("blocked", dict(backend="pallas", lowering="blocked")))


def _cls(size_bytes):
    """Host size→class that maps oversized to num_classes instead of
    raising (mirrors ``size_to_class_device``)."""
    import math
    sz = max(int(size_bytes), CFG.min_page_bytes)
    return (math.ceil(math.log2(sz))
            - int(math.log2(CFG.min_page_bytes)))


def _drain(ouro, state):
    """Decoded telemetry dict for a single or sharded allocator."""
    lay = ouro.layout
    shard_lay = getattr(lay, "shard", lay)
    return telemetry.decode(shard_lay, np.asarray(state.ctl))


def _replay_with_books(ouro, seed=0, ops=8):
    """Replay a short trace; return (decoded telemetry, host books).

    The books count what the trace observably did — granted lanes,
    freed lanes, failed *attempts* (masked-in, class < C, offset < 0)
    — from the transaction outputs alone, implementation-blind.
    """
    rng = np.random.default_rng(seed)
    C = CFG.num_classes
    st = ouro.init()
    books = {"granted": np.zeros(C, np.int64),
             "freed": np.zeros(C, np.int64),
             "failed_min": np.zeros(C, np.int64)}
    live = []
    for _ in range(ops):
        kind = rng.choice(["alloc", "free"]) if live else "alloc"
        if kind == "alloc":
            sizes = rng.choice(SIZES, N).astype(np.int32)
            mask = rng.random(N) < 0.85
            st, offs = ouro.alloc(st, jnp.asarray(sizes),
                                  jnp.asarray(mask))
            offs = np.asarray(offs)
            for sz, m, off in zip(sizes, mask, offs):
                c = _cls(sz)
                if not m or c >= C:
                    continue
                if off >= 0:
                    books["granted"][c] += 1
                    live.append((int(off), int(sz)))
                else:
                    # at least one failed attempt; under sharding each
                    # visited shard adds one, so this is a lower bound
                    books["failed_min"][c] += 1
        else:
            k = min(len(live), N)
            picks = [live.pop() for _ in range(k)]
            offs = np.full(N, -1, np.int32)
            sizes = np.full(N, 16, np.int32)
            for i, (off, sz) in enumerate(picks):
                offs[i], sizes[i] = off, sz
            mask = offs >= 0
            st = ouro.free(st, jnp.asarray(offs), jnp.asarray(sizes),
                           jnp.asarray(mask))
            for off, sz in picks:
                books["freed"][_cls(sz)] += 1
    return _drain(ouro, st), books


@pytest.mark.parametrize("num_shards", [1, SHARDS])
@pytest.mark.compiled_lowering
def test_telemetry_bit_identical_across_impls(num_shards):
    """The telemetry region is part of the bit-parity contract: the
    same trace drains to word-identical accumulators from the jnp
    oracle and both Pallas lowerings, single-arena and sharded."""
    kw = {} if num_shards == 1 else {"num_shards": num_shards}
    drained = {}
    for name, impl_kw in IMPLS:
        ouro = Ouroboros(CFG, "page", **impl_kw, **kw)
        drained[name], _ = _replay_with_books(ouro, seed=0)
    ref = drained["jnp"]
    for name in ("whole", "blocked"):
        for field, want in ref.items():
            np.testing.assert_array_equal(
                want, drained[name][field],
                err_msg=f"telemetry {field} diverged on {name} "
                        f"(shards={num_shards})")


@pytest.mark.parametrize("num_shards", [1, SHARDS])
def test_telemetry_reconciles_with_host_books(num_shards):
    """Drained words match implementation-blind host bookkeeping of
    the same trace: t_alloc == granted lanes per class, t_free ==
    freed, t_fail ≥ failed attempts (== for one shard; per-visit under
    sharding), walk bins sum to total grants, and oversized lanes
    (class == num_classes) never count."""
    kw = {} if num_shards == 1 else {"num_shards": num_shards}
    ouro = Ouroboros(CFG, "page", backend="jnp", **kw)
    tele, books = _replay_with_books(ouro, seed=0)
    # sharded decode keeps a leading shard axis; totals sum it away
    t_alloc = np.asarray(tele["t_alloc"]).reshape(-1, CFG.num_classes)
    t_free = np.asarray(tele["t_free"]).reshape(-1, CFG.num_classes)
    t_fail = np.asarray(tele["t_fail"]).reshape(-1, CFG.num_classes)
    np.testing.assert_array_equal(t_alloc.sum(0), books["granted"])
    np.testing.assert_array_equal(t_free.sum(0), books["freed"])
    if num_shards == 1:
        np.testing.assert_array_equal(t_fail.sum(0),
                                      books["failed_min"])
        # single-arena traffic never walks past bin 0
        walk = np.asarray(tele["t_walk"]).reshape(-1)
        assert walk[1:].sum() == 0
    else:
        assert np.all(t_fail.sum(0) >= books["failed_min"])
    assert int(np.asarray(tele["t_walk"]).sum()) == \
        int(books["granted"].sum())
    assert int(np.asarray(tele["t_grow"]).sum()) >= 0


def test_telemetry_segment_churn_counts_grow_shrink():
    """With tiny chunks the virtualized queues grow and reclaim
    segments mid-trace; t_grow/t_shrink mirror the pool counters the
    core already maintains (and pool wraps count full ring turns)."""
    cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=64,
                     min_page_bytes=16)
    ouro = Ouroboros(cfg, "vl_page", backend="jnp")
    lay = ouro.layout
    st = ouro.init()
    ctl0 = np.asarray(st.ctl).copy()  # init pre-claims chunks
    rng = np.random.default_rng(2)
    live = []
    for _ in range(10):
        sizes = rng.choice([16, 32, 64], N).astype(np.int32)
        st, offs = ouro.alloc(st, jnp.asarray(sizes),
                              jnp.ones(N, bool))
        offs = np.asarray(offs)
        live += [(int(o), int(s)) for o, s in zip(offs, sizes)
                 if o >= 0]
        if len(live) > N:
            picks = [live.pop() for _ in range(N)]
            offs_f = np.asarray([o for o, _ in picks], np.int32)
            sizes_f = np.asarray([s for _, s in picks], np.int32)
            st = ouro.free(st, jnp.asarray(offs_f),
                           jnp.asarray(sizes_f), jnp.ones(N, bool))
    ctl = np.asarray(st.ctl)
    tele = telemetry.decode(lay, ctl)
    assert int(tele["t_grow"]) == (int(ctl[lay.off_pool_front])
                                   - int(ctl0[lay.off_pool_front]))
    assert int(tele["t_shrink"]) == (int(ctl[lay.off_pool_back])
                                     - int(ctl0[lay.off_pool_back]))
    assert int(tele["t_grow"]) > 0
    tot = telemetry.totals(lay, ctl)
    assert tot["t_grow"] == int(tele["t_grow"])


@pytest.mark.parametrize("lowering", ["whole", "blocked"])
@pytest.mark.parametrize("num_shards", [1, SHARDS])
@pytest.mark.compiled_lowering
def test_single_pallas_call_with_telemetry(lowering, num_shards):
    """The accumulators ride inside the existing kernel: with
    telemetry active (it always is), alloc and free still lower to
    exactly ONE pallas_call, both lowerings, sharded or not."""
    kw = {} if num_shards == 1 else {"num_shards": num_shards}
    o = Ouroboros(CFG, "page", backend="pallas", lowering=lowering,
                  **kw)
    st = o.init()
    sizes = jnp.full(N, 64, jnp.int32)
    mask = jnp.ones(N, bool)
    offs = jnp.zeros(N, jnp.int32)
    ja = jax.make_jaxpr(lambda s, z, m: o.alloc(s, z, m))(
        st, sizes, mask)
    jf = jax.make_jaxpr(lambda s, x, z, m: o.free(s, x, z, m))(
        st, offs, sizes, mask)
    assert count_pallas_calls(ja) == 1, (
        f"{lowering}/shards={num_shards}: telemetry cost alloc a launch")
    assert count_pallas_calls(jf) == 1, (
        f"{lowering}/shards={num_shards}: telemetry cost free a launch")


def test_tele_fields_cover_region_exactly():
    """The field table tiles [core_ctl_words, ctl_words) with no gaps
    or overlaps — what decode() and DESIGN.md §14 both render."""
    lay = arena.layout(CFG, "page", "ring")
    fields = lay.tele_fields()
    cursor = lay.core_ctl_words
    for name, off, w in fields:
        assert off == cursor, f"{name} leaves a gap at {cursor}"
        cursor = off + w
    assert cursor == lay.ctl_words
    assert lay.tele_words == lay.ctl_words - lay.core_ctl_words


# ---- metrics registry ------------------------------------------------------

def test_metrics_counter_gauge_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "a counter",
                    labelnames=("shard",))
    c.labels(shard=0).inc()
    c.labels(shard=0).inc(2)
    c.labels(shard=1).set(7)  # re-publishing a device total
    reg.gauge("repro_test_waiting", "a gauge").set(3)
    text = reg.to_prometheus()
    assert validate_exposition(text) == 3
    assert 'repro_test_total{shard="0"} 3' in text
    assert 'repro_test_total{shard="1"} 7' in text
    doc = reg.to_json()
    assert doc["repro_test_total"]["type"] == "counter"
    vals = {tuple(s["labels"].items()): s["value"]
            for s in doc["repro_test_total"]["samples"]}
    assert vals[(("shard", "0"),)] == 3


def test_metrics_histogram_exposition():
    reg = MetricsRegistry()
    h = reg.histogram("repro_test_ms", "latency",
                      buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert validate_exposition(text) > 0
    assert 'repro_test_ms_bucket{le="10"} 2' in text
    assert 'repro_test_ms_bucket{le="+Inf"} 4' in text
    assert "repro_test_ms_count 4" in text
    assert "repro_test_ms_sum 555.5" in text


def test_metrics_declaration_hygiene():
    reg = MetricsRegistry()
    reg.counter("repro_ok_total", "x", labelnames=("a",))
    # idempotent re-declaration returns the same family
    assert reg.counter("repro_ok_total", "x", labelnames=("a",)) \
        is reg.get("repro_ok_total")
    with pytest.raises(ValueError, match="re-declared"):
        reg.gauge("repro_ok_total", "x", labelnames=("a",))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name", "x")
    with pytest.raises(ValueError, match="got labels"):
        reg.get("repro_ok_total").labels(b=1)
    with pytest.raises(TypeError):
        reg.histogram("repro_h", "x").inc()


def test_validate_exposition_rejects_malformed():
    with pytest.raises(ValueError, match="no TYPE"):
        validate_exposition("orphan_sample 1\n")
    with pytest.raises(ValueError, match="malformed sample"):
        validate_exposition("# TYPE x counter\nx{bad 1\n")
    with pytest.raises(ValueError, match="no samples"):
        validate_exposition("# TYPE x counter\n")


# ---- trace spans -----------------------------------------------------------

def test_tracer_spans_and_validation():
    tr = Tracer()
    with tr.span("prefill", slot=1):
        pass
    ts = tr.begin()
    tr.complete("tick", ts, cat="compile", step=0)
    ts = tr.begin()
    tr.complete("tick", ts, cat="steady", step=1)
    tr.instant("cancel", uid=3)
    doc = tr.to_json()
    assert validate_trace(doc, require_phases=True) == 4
    names = [ev["name"] for ev in doc["traceEvents"]]
    assert names == ["prefill", "tick", "tick", "cancel"]
    assert all(ev["name"].split("/")[0] in PHASES
               for ev in doc["traceEvents"])


def test_validate_trace_rejections():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({})
    bad = {"traceEvents": [{"name": "not_a_phase", "cat": "engine",
                            "ph": "X", "ts": 0, "dur": 1,
                            "pid": 0, "tid": 0}]}
    with pytest.raises(ValueError, match="taxonomy"):
        validate_trace(bad)
    steady_only = Tracer()
    ts = steady_only.begin()
    steady_only.complete("tick", ts, cat="steady")
    with pytest.raises(ValueError, match="compile"):
        validate_trace(steady_only.to_json(), require_phases=True)
    # but fine without the replay acceptance requirement
    assert validate_trace(steady_only.to_json()) == 1


def test_null_tracer_is_noop():
    before = len(NULL.events)
    with NULL.span("tick"):
        pass
    NULL.complete("tick", NULL.begin())
    NULL.instant("cancel")
    assert len(NULL.events) == before


def test_step_monitor_publishes_through_registry():
    from repro.ft.runtime import StepMonitor
    reg = MetricsRegistry()
    mon = StepMonitor(warmup=1, registry=reg)
    for _ in range(3):
        mon.start()
        mon.stop()
    text = reg.to_prometheus()
    assert validate_exposition(text) > 0
    steps = reg.get("repro_steps_total").samples[()]
    assert steps == 3
    assert reg.get("repro_step_time_ms").samples[()].count == 3
    assert reg.get("repro_step_time_ewma_ms") is not None


# ---- spans on the profiler's clock -----------------------------------------

def _profiled(fn):
    """Run ``fn`` under ``jax.profiler`` (Python tracer off); return the
    host plane's ``serve.*`` spans as ``(name, start_ns, end_ns, stats)``
    in start order, and the names of the jitted programs dispatched
    (``PjitFunction(<name>)`` events)."""
    import glob
    import os
    import tempfile

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        pd = ProfileData.from_file(path)
        spans, programs = [], set()
        for plane in pd.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
                    elif ev.name.startswith("PjitFunction("):
                        programs.add(ev.name[len("PjitFunction("):-1])
    return sorted(spans, key=lambda s: (s[1], -s[2])), programs


def _inside(child, parents):
    return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]


@pytest.fixture(scope="module")
def smoke_model():
    from repro.configs import get_arch
    from repro.models.model import build_model
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("mega", [True, False])
def test_engine_spans_on_the_profiler_clock(smoke_model, mega):
    """Every engine span is a ``serve.<phase>`` profiler annotation,
    nested tick ⊃ admission ⊃ prefill and retire ⊃ bulk_free, one
    prefill per admission and one retire per retirement, each with its
    request's uid; the prefill program is named, no anonymous program
    runs; and the JSON sink records the same spans and validates."""
    from repro.serve.engine import ServingEngine
    cfg, m, params = smoke_model
    tracer = Tracer()
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32, compute_dtype=jnp.float32,
                        mega_step=mega, tracer=tracer)
    rng = np.random.default_rng(0)
    uids = [eng.submit(rng.integers(2, cfg.vocab_size, 8),
                       max_new_tokens=3) for _ in range(3)]
    done = []
    spans, programs = _profiled(
        lambda: done.extend(eng.run_until_done(50)))
    assert sorted(r.uid for r in done) == uids

    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    ticks = by["serve.tick"]
    assert [t[3]["step"] for t in ticks] == list(
        range(1, eng.stats["steps"] + 1))
    for p in by["serve.prefill"]:
        adm = _inside(p, by["serve.admission"])
        assert len(adm) == 1 and len(_inside(adm[0], ticks)) == 1
        assert p[3]["prompt_len"] == 8
    for r in by["serve.retire"]:
        assert len(_inside(r, ticks)) == 1
        assert _inside(r, by["serve.admission"]) == []
        assert any(_inside(f, [r]) for f in by["serve.bulk_free"])
        assert r[3]["tokens"] == 3
    assert sorted(p[3]["uid"] for p in by["serve.prefill"]) == uids
    assert sorted(r[3]["uid"] for r in by["serve.retire"]) == uids
    for d in by["serve.decode"]:
        assert len(_inside(d, ticks)) == 1 and 1 <= d[3]["slots"] <= 2
    assert len(by["serve.flag_sync"]) == len(by["serve.decode"])
    if mega:
        assert sorted(p[3]["uid"] for p in by["serve.slot_push"]) == uids
        assert {"prefill", "mega"} <= programs
    else:
        assert "serve.slot_push" not in by
        assert {"prefill", "decode"} <= programs
    assert "<lambda>" not in programs

    doc = tracer.to_json()
    assert validate_trace(doc) == len(spans)
    json_names = sorted(ev["name"] for ev in doc["traceEvents"])
    assert json_names == sorted(s[0][len("serve."):] for s in spans)


def test_begin_complete_is_one_profiler_span_and_null_records_no_json():
    before = len(NULL.events)
    tr = Tracer()

    def run():
        for t in (tr, NULL):
            ts = t.begin("tick", step=7)
            with t.span("admission"):
                pass
            t.complete("tick", ts, cat="steady", step=7)

    spans, _ = _profiled(run)
    assert [(s[0], s[3]) for s in spans] == [
        ("serve.tick", {"step": 7}), ("serve.admission", {}),
        ("serve.tick", {"step": 7}), ("serve.admission", {})]
    assert len(NULL.events) == before
    assert validate_trace(tr.to_json()) == 2
