"""The arithmetic from clocks and traces to metrics, on synthetic
timelines and on a trace recorded on a TPU v5e."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import device, flops, harness, stats, xplane  # noqa: E402

TRACE = os.path.join(ROOT, "bench", "testdata", "paper_iter.xplane.pb")

QWEN2 = {"num_hidden_layers": 24, "hidden_size": 896,
         "num_attention_heads": 14, "num_key_value_heads": 2,
         "head_dim": 64, "intermediate_size": 4864, "vocab_size": 151936,
         "flops": "flops"}


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2, 4, 5, 6, 7, 8, 9, 10], 90) == 9


def test_union_and_gaps():
    iv = [(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0, 5) == [(2, 3), (4, 5)]


def _trace(ops, window=10.0, host=()):
    return xplane.Trace(window_s=window, ops=[ops],
                        modules=[[("jit_mega", 0.0, 4.0),
                                  ("jit_mega", 5.0, 9.0)]],
                        host=list(host))


def test_idle_share_and_attribution():
    tr = _trace([("fusion", 0.0, 4.0), ("fusion", 5.0, 9.0),
                 ("arena_alloc_txn_blocked", 1.0, 1.5)],
                host=[("bench.step", 0.0, 9.5),
                      ("bench.wait_arrival", 9.5, 10.0)])
    assert tr.busy_s == pytest.approx(8.0)
    assert tr.idle_share == pytest.approx(0.2)
    assert tr.op_seconds("arena_alloc_txn_blocked", inside="jit_mega") \
        == (pytest.approx(0.5), 1)
    assert tr.module_seconds("jit_mega") == (pytest.approx(8.0), 2)
    gaps = dict(tr.breakdown()["idle_gaps"])
    # the gap at 4..5 lies in a step; 9..10 is named by the shorter
    # span around its middle
    assert gaps == {"bench.step": pytest.approx(1.0),
                    "bench.wait_arrival": pytest.approx(1.0)}


def _serving_run(**kw):
    rec = [dict(i=0, due=0.0, admit0=0.1, admit1=0.3, admit_step=0,
                retire=1.3, retire_step=9, n_out=11, prompt_len=8),
           dict(i=1, due=0.5, admit0=0.6, admit1=0.9, admit_step=3,
                retire=None, retire_step=None, n_out=None, prompt_len=8)]
    steps = [dict(active=1, admitted=a) for a in (1, 0, 0, 1) + (0,) * 6]
    run = dict(system="serving", mix={"loop": "open", "drain_s": 2.0},
               window=(0.0, 1.0), window_reqs=[0, 1], rec=rec, steps=steps,
               window_steps=(0, 10), max_batch=2, tokens=100,
               sizes=QWEN2, device={"kind": "TPU v5 lite"})
    run.update(kw)
    return run


def test_serving_readers_on_a_synthetic_timeline():
    run = _serving_run()
    # first tokens at 0.3 - 0.0 and 0.9 - 0.5: the p85 and the p90 of
    # two are the max
    assert harness.reader("ttft_p85_ms")(run) == pytest.approx(400.0)
    assert harness.reader("ttft_ms_p90.ttft")(run) == pytest.approx(400.0)
    assert harness.reader("tpot_p90_ms")(run) == pytest.approx(100.0)
    assert harness.reader("queue_wait_ms_p90.ttft")(run) == \
        pytest.approx(100.0)
    occ = harness.reader("batch_occupancy.tput")(run)
    assert occ == pytest.approx(100.0 * (10 + 2) / 20)
    closed = _serving_run(mix={"loop": "closed"}, window=(1.0, 3.0))
    assert harness.reader("output_tokens_per_s")(closed) == 50.0


def test_traced_runs_read_the_harness_clock_before_the_trace():
    """The profiler stalls the host; harness-clock layers take only the
    part of the window before it."""
    run = _serving_run(harness=((0.0, 0.55), (0, 4)))
    # request 1 is due at 0.5 but admitted by a step that began in the
    # trace, at 0.8: it is left out; a trace from 0.85 on counts it
    run["rec"][1]["admit0"] = 0.8
    assert harness.reader("queue_wait_ms_p90.ttft")(run) == \
        pytest.approx(100.0)
    run["harness"] = ((0.0, 0.85), (0, 4))
    assert harness.reader("queue_wait_ms_p90.ttft")(run) == \
        pytest.approx(300.0)
    assert harness.reader("ttft_ms_p90.ttft")(run) == pytest.approx(400.0)
    run["harness"] = ((0.0, 0.75), (0, 4))
    assert harness.reader("ttft_ms_p90.ttft")(run) == pytest.approx(300.0)
    run["harness"] = ((0.0, 0.55), (0, 4))
    assert harness.reader("batch_occupancy.tput")(run) == \
        pytest.approx(100.0 * (4 + 2) / 8)
    total = flops.prefill_flops(QWEN2, 8) * 2 + sum(
        flops.decode_token_flops(QWEN2, 8 + j) for j in range(1, 5)) + \
        flops.decode_token_flops(QWEN2, 8 + 1)
    assert harness.reader("mfu.tput")(run) == pytest.approx(
        100.0 * total / (0.55 * 197e12))


def test_unadmitted_request_counts_its_wait():
    run = _serving_run()
    run["rec"][1]["admit1"] = None
    # not admitted by the drain limit (1.0 + 2.0): it waited 3.0 - 0.5
    assert harness.reader("ttft_p85_ms")(run) == pytest.approx(2500.0)
    assert harness.reader("ttft_ms_p90.ttft")(run) == pytest.approx(2500.0)


def test_mfu_counts_each_token_at_its_context():
    run = _serving_run()
    total = flops.prefill_flops(QWEN2, 8) * 2
    total += sum(flops.decode_token_flops(QWEN2, 8 + j)
                 for j in range(1, 11))            # request 0: steps 0..9
    total += sum(flops.decode_token_flops(QWEN2, 8 + j)
                 for j in range(1, 8))             # request 1: steps 3..9
    got = harness.reader("mfu.tput")(run)
    assert got == pytest.approx(100.0 * total / 197e12)


def test_qwen2_flops_by_hand():
    per_layer = (896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864)
    assert per_layer == 14909440
    assert flops.matmul_params(QWEN2) == 24 * per_layer == 357826560
    assert flops.logits_flops(QWEN2) == 2 * 896 * 151936
    # one token at context 1000: projections and MLP, attention, logits
    assert flops.decode_token_flops(QWEN2, 1000) == (
        2 * 357826560 + 4 * 1000 * 896 * 24 + 2 * 896 * 151936)
    assert flops.prefill_flops(QWEN2, 3) == (
        3 * 2 * 357826560 + 4 * 896 * 24 * (1 + 2 + 3)
        + 2 * 896 * 151936)


def test_unknown_device_kind_is_an_error():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v4")
    run = _serving_run(device={"kind": "cpu"})
    with pytest.raises(KeyError):
        harness.reader("mfu.tput")(run)


def test_recorded_chip_trace():
    """One §3 iteration traced on a TPU v5e: the alloc and free kernels
    are found by name, and the device was busy most of the window."""
    tr = xplane.load(TRACE, window="iter")
    assert 0.02 < tr.window_s < 0.1
    assert 0.5 < tr.busy_s / tr.window_s <= 1.0
    sec, n = tr.op_seconds("arena_alloc_txn_blocked")
    assert n == 1 and 1e-3 < sec < 1e-2
    assert tr.op_seconds("arena_free_txn_blocked")[1] >= 1
    assert tr.module_seconds("jit__alloc")[1] == 1
    run = {"system": "allocator", "trace": tr}
    assert harness.reader("txn_kernel_us.alloc")(run) > 1000
    assert 0 < harness.reader("idle_share.alloc")(run) < 50
    b = tr.breakdown()
    assert len(b["device_ops"]) <= 10 and b["idle_gaps"]


def test_op_and_module_names():
    assert xplane.op_name("%arena_alloc_txn_blocked.1 = (s32[8]) "
                          "custom-call(s32[8] %a)") == \
        "arena_alloc_txn_blocked"
    assert xplane.op_name("%fusion = s32[4] fusion()") == "fusion"
    assert xplane.module_name("jit_mega(1292769538)") == "jit_mega"


def test_off_a_tpu_the_command_exits_nonzero(capsys):
    import jax
    from bench import run
    assert jax.devices()[0].platform != "tpu"
    rc = run.main(["--workload", "ouroboros-vl_chunk.paper_iter",
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"correct"' not in out


def test_nested_ops_count_once():
    """A ``while`` op holds its body's ops on the same line: the
    breakdown gives it only its own time."""
    ops = [("while", 0.0, 10.0), ("fusion", 1.0, 3.0), ("copy", 4.0, 5.0),
           ("fusion", 11.0, 12.0)]
    tr = xplane.Trace(window_s=12.0, ops=[ops], modules=[[]], host=[])
    got = dict(tr.breakdown()["device_ops"])
    assert got == {"while": pytest.approx(7.0), "fusion": pytest.approx(3.0),
                   "copy": pytest.approx(1.0)}
    assert sum(got.values()) == pytest.approx(tr.busy_s)
