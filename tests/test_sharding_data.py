"""Sharding-rule mapping + data-pipeline determinism tests."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import SHAPES, ShapeConfig, cells_for
from repro.data.pipeline import DataConfig, batch_at, input_specs
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.parallel.sharding import ShardingRules


class FakeMesh:
    """Shape-only stand-in so rule mapping is testable without devices."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _rules(multi_pod=False):
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16} if multi_pod
                    else {"data": 16, "model": 16})
    return ShardingRules(mesh=mesh, rules=ShardingRules.for_mesh.__func__(
        ShardingRules, mesh).rules)


def test_param_spec_mapping_single_pod():
    r = _rules()
    # embedding: vocab→model, embed→data
    assert r.spec_for(("vocab", "embed"), (151936, 896)) == P("model", "data")
    # merged attention: embed→data, heads→model
    assert r.spec_for(("embed", "heads"), (5120, 5120)) == P("data", "model")
    # non-divisible dim stays unsharded (jit in_shardings are strict)
    assert r.spec_for(("embed", "heads"), (5120, 40)) == P("data")
    # mlp weight
    assert r.spec_for(("embed", "mlp"), (4096, 14336)) == P("data", "model")


def test_param_spec_mapping_multi_pod():
    r = _rules(multi_pod=True)
    got = r.spec_for(("embed", "mlp"), (4096, 14336))
    assert got == P(("pod", "data"), "model")
    # dim not divisible by pod*data=32 → drops fsdp mapping
    assert r.spec_for(("embed",), (5,)) == P()


def test_activation_specs():
    r = _rules(multi_pod=True)
    assert r.spec_for(("batch", "seq", "act_embed"),
                      (256, 4096, 4096)) == P(("pod", "data"), "model")
    # decode: seq=1 → no SP
    assert r.spec_for(("batch", "seq", "act_embed"),
                      (128, 1, 4096)) == P(("pod", "data"))


def test_no_axis_used_twice():
    r = _rules()
    spec = r.spec_for(("vocab", "heads"), (256, 256))  # both want 'model'
    flat = [a for part in spec if part for a in
            (part if isinstance(part, tuple) else (part,))]
    assert len(flat) == len(set(flat))


def test_cells_for_skips():
    """long_500k only for sub-quadratic archs (DESIGN §Arch-applicability)."""
    names = {a: [s.name for s in cells_for(get_arch(a))]
             for a in ("qwen1.5-32b", "mamba2-780m", "mixtral-8x7b",
                       "recurrentgemma-9b", "command-r-35b")}
    assert "long_500k" not in names["qwen1.5-32b"]
    assert "long_500k" not in names["command-r-35b"]
    assert "long_500k" in names["mamba2-780m"]
    assert "long_500k" in names["mixtral-8x7b"]
    assert "long_500k" in names["recurrentgemma-9b"]
    total = sum(len(cells_for(get_arch(a))) for a in
                [a for a in __import__("repro.configs",
                                       fromlist=["ALL_ARCHS"]).ALL_ARCHS])
    assert total == 39  # 12×3 + 3 long_500k


# ---- data pipeline -----------------------------------------------------------

def test_batch_determinism():
    cfg = get_arch("qwen2-0.5b").smoke()
    shape = ShapeConfig("t", 64, 4, "train")
    d = DataConfig(seed=5)
    b1 = batch_at(cfg, shape, d, step=3)
    b2 = batch_at(cfg, shape, d, step=3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_at(cfg, shape, d, step=4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_shard_slices_disjoint():
    cfg = get_arch("qwen2-0.5b").smoke()
    shape = ShapeConfig("t", 32, 8, "train")
    full = batch_at(cfg, shape, DataConfig(seed=1), 0)
    s0 = batch_at(cfg, shape, DataConfig(seed=1, shard_index=0,
                                         num_shards=2), 0)
    s1 = batch_at(cfg, shape, DataConfig(seed=1, shard_index=1,
                                         num_shards=2), 0)
    np.testing.assert_array_equal(full["tokens"][:4], s0["tokens"])
    np.testing.assert_array_equal(full["tokens"][4:], s1["tokens"])


def test_input_specs_match_batches():
    for arch in ("qwen2-vl-2b", "seamless-m4t-large-v2", "qwen2-0.5b"):
        cfg = get_arch(arch)
        spec = input_specs(cfg, SHAPES["train_4k"])
        smoke_shape = ShapeConfig("t", 16, 2, "train")
        batch = batch_at(cfg.smoke(), smoke_shape, DataConfig(), 0)
        # spec keys ⊇ batch keys minus smoke-dependent dims
        for k in batch:
            assert k in spec, (arch, k)
