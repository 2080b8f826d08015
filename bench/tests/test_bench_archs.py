"""A serving architecture is data: its configuration file names its
reference, weight rules, size map and operation count, and the harness
runs it with no edit.  Checked at smoke widths on the CPU, on an MoE
family with state pages and on a family with no KV at all, each with a
stand-in reference module made here."""
import json
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import device, harness, serving  # noqa: E402


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def first_make_params(shapes, sizes: dict, seed: int):
    """The weight rule the qwen2 cells were first measured with, frozen:
    matrices N(0, 1/shape[-2]), the embedding N(0, 1/hidden) with rows
    past the vocabulary zero, norm scales 1 + N(0, 0.1²), Q/K/V biases
    N(0, 0.5²)."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    d, vocab = sizes["hidden_size"], sizes["vocab_size"]

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, sd) in zip(keys, leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            z = jax.random.normal(k, sd.shape, jnp.float32)
            if name == "embed":
                rows = jnp.arange(sd.shape[0])[:, None] < vocab
                x = jnp.where(rows, z * d ** -0.5, 0.0)
            elif name == "scale":
                x = 1.0 + 0.1 * z
            elif name in ("bq", "bk", "bv"):
                x = 0.5 * z
            else:
                x = z * sd.shape[-2] ** -0.5
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(init)(serving._key(seed))


def _model(name: str):
    import jax
    from repro.configs import get_arch
    from repro.models.model import build_model
    model = build_model(get_arch(name).smoke())
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("seed", (2 ** 33 + 17, 5))
def test_qwen2_weights_are_the_first_rule_bit_for_bit(seed):
    import jax
    model, shapes = _model("qwen2-0.5b")
    sizes = dict(_load("configs", "qwen2-0.5b.json"), hidden_size=128,
                 vocab_size=500)        # rows 500.. of the 512 zeroed
    old = first_make_params(shapes, sizes, seed)
    # the harness's own rules are the first rule bit for bit
    own = {k: v for k, v in sizes.items() if k != "init"}
    base = serving.make_params(shapes, model.logical_axes(), own, seed)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(base),
                            jax.tree.leaves(old)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path
    # the file's init changes the V bias alone: the same draw, std 0.01
    assert sizes["init"] == {"bv": {"normal": [0.0, 0.01]}}
    new = serving.make_params(shapes, model.logical_axes(), sizes, seed)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new),
                            jax.tree.leaves(old)):
        if path[-1].key == "bv":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b) * 0.02,
                                       rtol=1e-6)
        else:
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path
    assert not np.asarray(new["embed"])[500:].any()


# the two families, as a later configuration file would give them: the
# published keys (here at the program's smoke widths), the map of each
# to the program, weight rules for what is not a matrix
SMOKE = {
    "mixtral-8x7b": {
        "num_hidden_layers": 2, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 256, "num_local_experts": 4,
        "num_experts_per_tok": 2, "sliding_window": 64, "vocab_size": 512,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False,
        "program_sizes": {
            "num_hidden_layers": "num_layers", "hidden_size": "d_model",
            "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim_",
            "intermediate_size": "d_ff", "num_local_experts": "num_experts",
            "num_experts_per_tok": "num_experts_per_tok",
            "sliding_window": "sliding_window", "vocab_size": "vocab_size",
            "rope_theta": "rope_theta",
            "tie_word_embeddings": "tie_embeddings"},
        "not_program_sizes": ["rms_norm_eps"],
        "init": {"router": {"normal": [0.0, 0.02]}},
    },
    "mamba2-780m": {
        "num_hidden_layers": 2, "hidden_size": 128, "state_size": 32,
        "head_dim": 16, "expand": 2, "n_groups": 1, "conv_kernel": 4,
        "vocab_size": 512, "layer_norm_epsilon": 1e-5,
        "tie_word_embeddings": True,
        "program_sizes": {
            "num_hidden_layers": "num_layers", "hidden_size": "d_model",
            "state_size": "ssm_state", "head_dim": "ssm_headdim",
            "expand": "ssm_expand", "n_groups": "ssm_ngroups",
            "conv_kernel": "ssm_conv", "vocab_size": "vocab_size",
            "tie_word_embeddings": "tie_embeddings"},
        "not_program_sizes": ["layer_norm_epsilon"],
        "init": {"a_log": {"uniform": [1, 16], "then": "log"},
                 "dt_bias": {"log_uniform": [0.001, 0.1],
                             "then": "inv_softplus"},
                 "d_skip": {"const": 1.0}, "conv_b": {"const": 0.0},
                 "norm_scale": {"normal": [1.0, 0.1]}},
    },
}


def _standin(gap: float, calls: list):
    """A reference module as the harness loads one by name: the sizes
    it reads, its control, and the gaps, here all ``gap``."""
    ref = types.ModuleType("bench.standin_ref")
    ref.SIZES = ("hidden_size", "vocab_size")
    ref.CONTROL = "fp8"

    def served_gaps(params, sizes, prompt, served, pad_to, quant=None):
        calls.append(dict(params=params, sizes=sizes, n=len(prompt)
                          + len(served), pad_to=pad_to, quant=quant))
        return np.full(len(served), gap, np.float32)

    ref.served_gaps = served_gaps
    return ref


@pytest.fixture()
def new_arch(request, monkeypatch):
    """A serving cell of the family ``request.param`` from data alone:
    the closed loop of the reasoning mix, cut to a CPU, the jnp
    allocator, and the stand-in reference under its name."""
    import repro.configs as C
    arch = request.param
    monkeypatch.setattr(C, "get_arch",
                        lambda name: C.ARCH_REGISTRY[name].smoke())
    C.get_arch(arch)
    sizes = dict(SMOKE[arch], system="serving", arch=arch,
                 source="smoke widths of the program's registered arch",
                 reference="standin_ref", flops="flops",
                 param_dtype="float32", kv_dtype="bfloat16",
                 compute_dtype="bfloat16",
                 engine={"alloc_backend": "jnp", "mega_step": True})
    mix = dict(_load("traffic", "reasoning.json"), clients=3, block=3,
               prompt_tokens={"dist": "choice", "values": [16, 32]},
               output_tokens={"dist": "loguniform", "low": 4, "high": 12},
               engine={"max_batch": 3, "max_seq": 64, "max_new_cap": 16})
    calls = []

    def use(gap):
        monkeypatch.setitem(sys.modules, "bench.standin_ref",
                            _standin(gap, calls))
        return calls

    return dict(name=arch, chips=1, config=sizes, mix=mix), use


def _run(cell, seed):
    import jax
    counter = device.CompileCounter()
    rec = harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                           jax.devices(), counter)
    rec["compiles_in_window"] = counter.count
    return rec, harness.correct(rec["checks"])


@pytest.mark.parametrize("new_arch", sorted(SMOKE), indirect=True)
def test_new_arch_runs_from_data_and_its_named_reference(new_arch):
    import jax
    cell, use = new_arch
    calls = use(0.0)
    rec, ok = _run(cell, 2 ** 35 + 3)
    assert ok, rec["checks"]
    assert rec["checks"]["page_balance"][0] == 0
    assert rec["checks"]["page_dups"][0] == 0
    assert rec["compared_tokens"] > 0 and calls
    assert rec["compiles_in_window"] == 0
    assert all(c["sizes"] == {"hidden_size": 128, "vocab_size": 512}
               and c["quant"] is None and c["n"] <= c["pad_to"]
               for c in calls)
    # the configuration's rules made the weights the reference was given
    leaves = {jax.tree_util.keystr(p): np.asarray(x) for p, x in
              jax.tree_util.tree_leaves_with_path(calls[0]["params"])}
    for path, x in leaves.items():
        assert np.isfinite(x).all(), path
    if cell["name"] == "mamba2-780m":
        a_log = leaves["['blocks']['mixer']['a_log']"]
        assert (a_log >= 0).all() and (a_log <= np.log(16) + 1e-6).all()
        assert (leaves["['blocks']['mixer']['d_skip']"] == 1).all()
        scale = leaves["['blocks']['mixer']['norm_scale']"]
        assert abs(scale.mean() - 1) < 0.05
        dt = np.logaddexp(0, leaves["['blocks']['mixer']['dt_bias']"])
        assert (dt > 0.00099).all() and (dt < 0.1001).all()
    else:
        assert leaves["['blocks']['ffn']['router']"].std() < 0.05

    # the named reference is the one judged: a gap above the limit fails
    use(1.0)
    rec, ok = _run(cell, 2 ** 35 + 3)
    assert not ok
    assert rec["checks"]["logit_gap"][0] == 1.0


@pytest.mark.parametrize("new_arch", sorted(SMOKE), indirect=True)
def test_new_arch_size_that_is_not_the_program_raises(new_arch):
    cell, use = new_arch
    use(0.0)
    wrong = dict(cell, config=dict(cell["config"], num_hidden_layers=3))
    with pytest.raises(ValueError, match="num_hidden_layers"):
        _run(wrong, 1)
    loose = dict(cell, config=dict(cell["config"], max_window_layers=2))
    with pytest.raises(ValueError, match="max_window_layers"):
        _run(loose, 1)


@pytest.mark.parametrize("arch", sorted(SMOKE))
def test_a_stacked_vector_needs_a_rule(arch):
    """The fan-in of a leaf stacked over layers is never its layer axis:
    a vector with no rule is refused, not drawn N(0, 1/layers)."""
    model, shapes = _model(arch)
    sizes = dict(SMOKE[arch], init={})
    if arch == "mixtral-8x7b":          # every leaf has a rule or a fan-in
        serving.make_params(shapes, model.logical_axes(), sizes, 1)
        return
    with pytest.raises(ValueError, match="a_log"):
        serving.make_params(shapes, model.logical_axes(), sizes, 1)


@pytest.mark.parametrize("rule", (
    {"normal": [0.0, 1.0], "then": "exp"}, {"gamma": 1.0},
    {"const": 1.0, "normal": [0.0, 1.0]}))
def test_an_unknown_init_rule_is_refused(rule):
    with pytest.raises(ValueError, match="init rule"):
        serving.init_rules({"init": {"w": rule}})
