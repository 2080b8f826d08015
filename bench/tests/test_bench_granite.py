"""The granite-4.0-h-small configuration file at smoke widths on the
CPU: its sizes are checked against the program, its weight rules give
hidden states that move with the tokens, and its reference module
judges the program correct and the fp8 control not, on every seed."""
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

from bench import device, granite_ref, harness, serving  # noqa: E402

NAME = "granite-4.0-h-small"


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def smoke_sizes():
    """The configuration file at the program's smoke widths: the layer
    list (mamba, attention, mamba), 4 of 8 experts held, top 3."""
    from repro.configs import get_arch
    sm = get_arch(NAME).smoke()
    sizes = _load("configs", NAME + ".json")
    sizes.update(
        num_hidden_layers=sm.num_layers, layer_types=list(sm.layer_types),
        hidden_size=sm.d_model, intermediate_size=sm.d_ff,
        shared_intermediate_size=sm.shared_expert_d_ff,
        num_attention_heads=sm.num_heads, num_key_value_heads=sm.num_kv_heads,
        num_local_experts=sm.held_experts, router_num_experts=sm.num_experts,
        num_experts_per_tok=sm.num_experts_per_tok,
        mamba_d_state=sm.ssm_state, mamba_d_head=sm.ssm_headdim,
        mamba_n_heads=sm.ssm_nheads, mamba_chunk_size=sm.ssm_chunk,
        vocab_size=sm.vocab_size, attention_multiplier=sm.attn_scale,
        param_dtype=sm.param_dtype)
    return sm, sizes


@pytest.fixture()
def smoke_cell(monkeypatch):
    """The hybrid_reasoning cell's path (closed loop, fused tick) at
    smoke widths with the jnp allocator and the real reference."""
    import repro.configs as C
    sm, sizes = smoke_sizes()
    monkeypatch.setattr(C, "get_arch", lambda name: sm)
    sizes["engine"] = {"alloc_backend": "jnp", "mega_step": True}
    # float32 compute: at d = 128 a bfloat16 rounding flips a top-3 of 8
    # routing choice often enough that which requests finish (the host
    # clock) decides the program's widest gap; in float32 the program
    # is the reference's to ~1e-6
    sizes["compute_dtype"] = "float32"
    mix = _load("traffic", "hybrid_reasoning.json")
    # logits here are ~0.0014 wide (d = 128, the embedding at its
    # full-width scale), so the chip cell's limit does not apply: 1e-4
    # lies far above the program and below the control's least (7e-4)
    mix["limits"] = {"logit_gap": 1e-4}
    mix.update(clients=4, block=4, check_requests=16,
               prompt_tokens={"dist": "choice", "values": [16, 48]},
               output_tokens={"dist": "loguniform", "low": 8, "high": 24},
               engine={"max_batch": 4, "max_seq": 96, "max_new_cap": 32})
    return dict(name="smoke", chips=1, config=sizes, mix=mix)


def test_file_is_the_program_and_a_wrong_size_is_refused():
    from repro.configs import get_arch
    sizes = _load("configs", NAME + ".json")
    cfg = get_arch(sizes["arch"])
    serving.check_arch(cfg, sizes)
    assert cfg.held_experts == 9 and cfg.num_experts == 72
    types = list(sizes["layer_types"])
    swapped = types[:4] + ["attention", "mamba"] + types[6:]
    for key, bad in (("layer_types", swapped),
                     ("layer_types", types[:9]),
                     ("num_local_experts", 72),
                     ("router_num_experts", 9),
                     ("held_expert_offset", 9)):
        with pytest.raises(ValueError, match=key):
            serving.check_arch(cfg, dict(sizes, **{key: bad}))


@pytest.mark.parametrize("seed", (2 ** 34 + 1, 7, 2 ** 31 + 5))
def test_weight_rules_give_hidden_states_that_move(seed):
    """The final hidden state's cosine to its mean over the positions,
    on random tokens: qwen2's first weights read 0.97 on every seed
    (one direction added layer after layer); these rules stay far
    below it."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import build_model
    sm, sizes = smoke_sizes()
    m = build_model(sm)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    params = serving.make_params(shapes, m.logical_axes(), sizes, seed)
    ref = {k: sizes[k] for k in granite_ref.SIZES}
    tokens = np.random.default_rng(seed).integers(2, sm.vocab_size, 512)
    h = np.asarray(granite_ref.hidden(params, jnp.asarray(tokens), ref))
    mu = h.mean(0)
    cos = h @ mu / (np.linalg.norm(h, axis=1) * np.linalg.norm(mu))
    assert cos.mean() < 0.6, cos.mean()
    a_log = np.asarray(params["mamba"]["mixer"]["a_log"], np.float64)
    assert (a_log >= 0).all() and (a_log <= np.log(16) + 1e-5).all()
    dt = np.logaddexp(0, np.asarray(params["mamba"]["mixer"]["dt_bias"],
                                    np.float64))
    assert (dt > 0.00099).all() and (dt < 0.1001).all()


@pytest.mark.parametrize("seed", (2 ** 40 + 5, 11))
def test_program_passes_and_fp8_control_fails(smoke_cell, seed):
    """In one run, the fp8 control in the program's place, on the
    tokens the program served, reads far above the program's own gap
    and over the cell's limit; the program passes it.  The page checks
    count the slots' state pages."""
    import jax
    rec = harness.run_cell(smoke_cell, seed, 1.0, False,
                           time.perf_counter(), jax.devices(),
                           device.CompileCounter(), control=True)
    ctl, limit = rec["checks"]["logit_gap"]
    assert rec["compared_tokens"] > 0
    assert rec["program_gap"] < limit
    assert ctl > 3 * rec["program_gap"] and ctl > limit
    assert not harness.correct(rec["checks"])
    for k in ("page_dups", "page_balance", "alloc_failures"):
        assert rec["checks"][k][0] == 0, k


def test_sizes_and_bytes_by_hand():
    """The stage's weights counted by hand: a Mamba
    mixer 102.2 M, the held experts with router and shared expert
    104.1 M a layer, attention 41.9 M, the tied embedding 411.0 M."""
    from bench import granite_flops as F
    s = _load("configs", NAME + ".json")
    assert F.mamba_params(s) == 4096 * (2 * 8192 + 2 * 128 + 128) \
        + 8192 * 4096 == 102236160
    assert F.attn_params(s) == 41943040
    moe = 4096 * 72 + 9 * 3 * 4096 * 768 + 3 * 4096 * 1536
    assert F.held_weights(s) == 9 * (102236160 + moe) + 41943040 + moe \
        + 100352 * 4096 == 2414149632
    # a slot's state: 9 layers of 128 (64, 128) heads and a (3, 8448)
    # conv tail, float32
    assert F.state_bytes(s) == 9 * 4 * (128 * 64 * 128 + 3 * 8448)
    assert F.kv_bytes(s, 100) == 2 * 100 * 8 * 128 * 4


class _Trace:
    def module_seconds(self, name):
        assert name == "jit_mega"
        return 0.5, 5                      # five ticks of 0.1 s


def test_hbm_share_counts_each_live_slot_at_its_context():
    from bench import granite_flops as F
    s = _load("configs", NAME + ".json")
    rec = [dict(admit_step=0, retire_step=3, prompt_len=256),
           dict(admit_step=2, retire_step=None, prompt_len=512),
           dict(admit_step=None, retire_step=None, prompt_len=1024)]
    run = dict(system="serving", trace=_Trace(), trace_steps=(1, 5),
               rec=rec, sizes=s, device={"kind": "TPU v5 lite"})
    ticks = [F.tick_bytes(s, c) for c in
             ([258], [259, 513], [260, 514], [515])]
    want = 100.0 * (sum(ticks) / 4) * 5 / (0.5 * 819e9)
    assert harness.reader("hbm_share.hybrid")(run) == pytest.approx(want)
    assert harness.reader("hbm_share.hybrid")(dict(run, trace=None)) is None
