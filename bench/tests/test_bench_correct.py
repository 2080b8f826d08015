"""``correct`` through a whole run at a size a CPU holds: the harness's
look for a chip is skipped, the rest of a run is driven, and the check
must pass on the program, and fail on the control and on a planted
fault."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import device, harness  # noqa: E402


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


@pytest.fixture()
def tiny_serving(monkeypatch):
    """The reasoning cell's path (closed loop, fused tick) on the smoke
    widths of qwen2, with the jnp allocator, which a CPU runs fast."""
    import repro.configs as C
    real = C.get_arch("qwen2-0.5b")
    sm = real.smoke()
    monkeypatch.setattr(C, "get_arch", lambda name: sm)
    sizes = _load("configs", "qwen2-0.5b.json")
    sizes.update(num_hidden_layers=sm.num_layers, hidden_size=sm.d_model,
                 num_attention_heads=sm.num_heads,
                 num_key_value_heads=sm.num_kv_heads, head_dim=sm.head_dim_,
                 intermediate_size=sm.d_ff, vocab_size=sm.vocab_size,
                 engine={"alloc_backend": "jnp", "mega_step": True})
    mix = _load("traffic", "reasoning.json")
    # up to sixteen finished requests compared, some 240 tokens: with
    # four, the control's widest gap hangs on which four finished
    mix.update(clients=4, block=4, check_requests=16,
               prompt_tokens={"dist": "choice", "values": [16, 32]},
               output_tokens={"dist": "loguniform", "low": 8, "high": 24},
               engine={"max_batch": 4, "max_seq": 96, "max_new_cap": 32})
    return dict(name="tiny", chips=1, config=sizes, mix=mix)


def _run(cell, seed, control=False, counter=None):
    import jax
    counter = counter or device.CompileCounter()
    rec = harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                           jax.devices(), counter, control=control)
    return rec, harness.correct(rec["checks"])


def test_open_loop_compiles_nothing_in_the_window(tiny_serving):
    """The chat cell's path: requests long enough that freeing their
    pages needs more lanes than ``2 * max_batch``, each count a program
    of its own, all warmed in set-up."""
    mix = dict(_load("traffic", "chat.json"), rate_per_s=4.0, block=8,
               lead_in_s=0.5, drain_s=10.0,
               engine={"max_batch": 4, "max_seq": 192, "max_new_cap": 96})
    mix["prompt_tokens"] = {"dist": "lognormal", "median": 24,
                            "sigma": 1.0, "round_up_to": [16, 32, 64]}
    mix["output_tokens"] = {"dist": "lognormal", "median": 40,
                            "sigma": 0.8, "min": 2, "max": 96}
    cell = dict(tiny_serving, mix=mix)
    counter = device.CompileCounter()
    rec, ok = _run(cell, 2 ** 36 + 1, counter=counter)
    assert ok, rec["checks"]
    assert counter.count == 0
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert max(r["prompt_len"] + r["n_out"] for r in rec["rec"]
               if r["n_out"]) > 16 * 2 * 4     # past 2 * max_batch pages


def test_knee_sweep_runs_the_cells_open_loop(tiny_serving, monkeypatch,
                                             capsys):
    """``knee.py`` drives ``serving._open`` once per order and rate on
    one engine, drains it between rates, and reads the cell's own
    metric readers."""
    import jax
    from bench import knee
    from repro import compile_cache

    mix = dict(_load("traffic", "chat.json"), lead_in_s=0.3, drain_s=10.0,
               engine={"max_batch": 4, "max_seq": 96, "max_new_cap": 32})
    mix["prompt_tokens"] = {"dist": "choice", "values": [16, 32]}
    mix["output_tokens"] = {"dist": "lognormal", "median": 12,
                            "sigma": 0.5, "min": 2, "max": 32}
    cell = dict(tiny_serving, mix=mix)
    monkeypatch.setattr(harness, "cell", lambda bm, name: cell)
    monkeypatch.setattr(device, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    assert knee.main(["--workload", "tiny", "--seed", "3", "--seconds",
                      "0.6", "--rates", "2,4", "--orders", "5,6"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [(x["order"], x["rate"]) for x in lines] == [
        (5, 2.0), (5, 4.0), (6, 2.0), (6, 4.0)]
    for x in lines:
        assert x["compiles_in_window"] == 0 and x["unfinished"] == 0
        assert x["due_in_window"] == 0 or (
            0 < x["ttft_p85_ms"] <= x["ttft_p90_ms"])


def test_serving_program_passes_control_and_fault_fail(tiny_serving,
                                                       monkeypatch):
    rec, ok = _run(tiny_serving, 2 ** 40 + 5)
    assert ok, rec["checks"]
    assert rec["compared_tokens"] > 0

    # the fp8 control in the program's place, on the tokens the program
    # served in the same run, reads far above the program's own gap and
    # is judged not correct (two runs of one seed need not compare the
    # same requests: which ones finish follows the host clock)
    rec, ok = _run(tiny_serving, 2 ** 40 + 5, control=True)
    ctl, limit = rec["checks"]["logit_gap"]
    assert ctl > max(3 * rec["program_gap"], 1e-3)
    assert not ok and ctl > limit

    # a token altered where it is produced: the prefill's argmax
    import repro.serve.engine as E
    real = E._tokens_of

    def altered(out):
        tok, caches = real(out)
        return (tok + 1) % tiny_serving["config"]["vocab_size"], caches

    monkeypatch.setattr(E, "_tokens_of", altered)
    rec, ok = _run(tiny_serving, 2 ** 40 + 5)
    assert not ok
    assert rec["checks"]["logit_gap"][0] > rec["checks"]["logit_gap"][1]


def test_traced_run_traces_the_window_end(tiny_serving, monkeypatch):
    """The trace covers the window's last ``trace_s``; the harness-clock
    layers get the part before it.  (No TPU plane on a CPU: the capture
    is stood in by a trace of nothing.)"""
    from bench import xplane

    def capture(fn):
        fn()
        return xplane.Trace(window_s=1.0, ops=[[]], modules=[[]], host=[])

    monkeypatch.setattr(xplane, "capture", capture)
    cell = dict(tiny_serving, mix=dict(tiny_serving["mix"], trace_s=0.5))
    import jax
    rec = harness.run_cell(cell, 7, 1.5, True, time.perf_counter(),
                           jax.devices(), device.CompileCounter())
    (h0, h1), (k0, kc) = rec["harness"]
    assert rec["window"][0] == h0 < h1 < rec["window"][1]
    assert rec["window_steps"][0] == k0 <= kc == rec["trace_steps"][0]
    assert harness.reader("batch_occupancy.tput")(rec) > 0


@pytest.fixture()
def tiny_alloc():
    cfg = _load("configs", "ouroboros-vl_chunk.json")
    cfg["heap"] = {"total_bytes": 1 << 20, "chunk_bytes": 8192,
                   "min_page_bytes": 16}
    mix = _load("traffic", "paper_iter.json")
    mix.update(lanes=64, oracle_iterations=2)
    return dict(name="tiny", chips=1, config=cfg, mix=mix)


def test_allocator_program_passes(tiny_alloc):
    counter = device.CompileCounter()
    rec, ok = _run(tiny_alloc, 3, counter=counter)
    assert ok, rec["checks"]
    assert counter.count == 0
    assert rec["oracle_iterations"] == 2 and rec["granted"] > 0
    assert len(rec["segments"]) == 5 and sum(rec["segments"]) > 0


def test_allocator_write_altered_fails(tiny_alloc, monkeypatch):
    """Some regions written with a wrong tag where the program writes
    them, and passed by the program's own read-back check: the host's
    read of the heap words catches it."""
    import jax.numpy as jnp
    from repro.core import Ouroboros
    real = Ouroboros.write_pattern

    def off_by_one(self, st, offs, sizes, tags):
        return real(self, st, offs, sizes, tags + (offs % 7 == 0))

    monkeypatch.setattr(Ouroboros, "write_pattern", off_by_one)
    monkeypatch.setattr(Ouroboros, "check_pattern",
                        lambda self, st, offs, sizes, tags:
                        jnp.ones(offs.shape, bool))
    rec, ok = _run(tiny_alloc, 6)
    assert not ok
    assert rec["checks"]["readback_words"][0] > 0
    assert rec["checks"]["readback_fail"][0] == 0


def test_allocator_control_fails(tiny_alloc):
    """The control hands two lanes overlapping regions."""
    rec, ok = _run(tiny_alloc, 4, control=True)
    assert not ok
    assert rec["checks"]["overlaps"][0] > 0


def test_allocator_answer_altered_fails(tiny_alloc, monkeypatch):
    """Every grant one page off where the kernel produces it: no two
    regions overlap, and the oracle replay still catches it."""
    from repro.core import Ouroboros
    real = Ouroboros.alloc

    def shifted(self, st, sizes, mask, shard_hint=None):
        st, offs = real(self, st, sizes, mask)
        if self.backend == "jnp":       # the oracle is left sound
            return st, offs
        return st, np.where(np.asarray(offs) >= 0, np.asarray(offs) + 4,
                            -1).astype(np.int32)

    monkeypatch.setattr(Ouroboros, "alloc", shifted)
    rec, ok = _run(tiny_alloc, 5)
    assert not ok
    assert rec["checks"]["oracle_mismatch"][0] > 0
