import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import/init: jax locks device count on first use.

__doc__ = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step /
prefill_step / serve_step), lowers it with ShapeDtypeStruct inputs
(zero allocation), compiles for the production mesh, and records:

    memory_analysis   — proves the cell fits per-chip HBM
    cost_analysis     — HLO FLOPs / bytes for the roofline terms
    collective bytes  — parsed from the partitioned HLO

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json, which
benchmarks/roofline.py and EXPERIMENTS.md consume.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k [--multi-pod] [--all] [--out experiments/dryrun]
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cells_for, get_arch
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model, kv_dtype_for
from repro.models import transformer as TF
from repro.paged import kv_cache as KVC
from repro.parallel.sharding import ShardingRules, use_rules
from repro.train.optimizer import AdamW
from repro.train.train_step import (abstract_state, make_train_step,
                                    state_logical_axes)

# ---------------------------------------------------------------------------
# HLO collective parsing (§Roofline: collective bytes are NOT in
# cost_analysis — sum operand sizes of every collective op)
# ---------------------------------------------------------------------------

_DEF_RE = re.compile(r"%?([\w.\-]+) = ([a-z0-9]+)\[([0-9,]*)\]")
_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
                "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def hlo_collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective kind (operand sizes)."""
    shapes = {}
    for m in _DEF_RE.finditer(hlo_text):
        shapes[m.group(1)] = _shape_bytes(m.group(2), m.group(3))
    out = {k: 0 for k in _COLL}
    counts = {k: 0 for k in _COLL}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _DEF_RE.match(line)
        if not m:
            continue
        for kind in _COLL:
            if re.search(rf"\b{kind}(-start|-done)?\(", line):
                if f"{kind}-done" in line:
                    break  # counted at -start
                args = re.findall(r"\(([^)]*)\)", line)
                total = 0
                if args:
                    for a in args[0].split(","):
                        a = a.strip().lstrip("%")
                        a = a.split(" ")[0]
                        total += shapes.get(a, 0)
                out[kind] += total
                counts[kind] += 1
                break
    out_counts = {f"n_{k}": v for k, v in counts.items()}
    return {**out, **out_counts,
            "total": sum(out[k] for k in _COLL)}


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def shaped_batch(cfg: ModelConfig, shape: ShapeConfig):
    return input_specs(cfg, shape)


def batch_shardings(rules: ShardingRules, batch):
    def spec(path_unused, x):
        if x.ndim >= 2:
            return NamedSharding(rules.mesh,
                                 rules.spec_for(("batch", "seq"), x.shape))
        return NamedSharding(rules.mesh, rules.spec_for(("batch",), x.shape))
    return jax.tree_util.tree_map_with_path(spec, batch)


def decode_inputs(cfg: ModelConfig, shape: ShapeConfig,
                  kv_dtype=None, window_ring: bool = False):
    """Abstract caches + one-token batch for serve_step."""
    b, s = shape.global_batch, shape.seq_len
    m = build_model(cfg)
    kv_dtype = kv_dtype or kv_dtype_for(cfg, s, b)
    caches = m.make_decode_caches(b, max_seq=s, kv_dtype=kv_dtype,
                                  abstract=True, window_ring=window_ring)
    if cfg.is_encdec:
        # decode consumes prefill-built cross-attention KV (source side)
        sds = jax.ShapeDtypeStruct
        hd = cfg.head_dim_
        caches = caches._replace(
            cross_k=sds((cfg.num_layers, b, s, cfg.num_kv_heads, hd),
                        jnp.bfloat16),
            cross_v=sds((cfg.num_layers, b, s, cfg.num_kv_heads, hd),
                        jnp.bfloat16),
            enc_valid=sds((b,), jnp.int32))
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    return tokens, caches


def cache_shardings(rules: ShardingRules, caches):
    """KV page heaps fully sharded (pages over every axis); page tables
    and scalar state replicated; recurrent states: batch × model;
    enc-dec cross-KV: batch over DP, source length over model."""
    mesh = rules.mesh

    def one(x):
        if x is None:
            return None
        if x.ndim >= 4:     # page heap (L, NP, page, *kv_row), its scales
            return NamedSharding(mesh, rules.spec_for(
                (None, "pages") + (None,) * (x.ndim - 2), x.shape))
        if x.ndim == 3:     # ssm conv (Lr, B, ...) / rglru states
            return NamedSharding(mesh, rules.spec_for(
                (None, "batch", "mlp"), x.shape))
        if x.ndim == 2:     # page_table (B, P)
            return NamedSharding(mesh, rules.spec_for(
                ("batch", None), x.shape))
        return NamedSharding(mesh, P())

    def ssm5(x):  # (Lr, B, H, P, N)
        return NamedSharding(mesh, rules.spec_for(
            (None, "batch", "heads", None, None), x.shape))

    out = jax.tree.map(one, caches)
    if getattr(caches, "ssm_h", None) is not None:
        if caches.ssm_h.ndim == 5:
            out = out._replace(ssm_h=ssm5(caches.ssm_h))
    if getattr(caches, "cross_k", None) is not None:
        xsh = NamedSharding(mesh, rules.spec_for(
            (None, "batch", "seq", None, None), caches.cross_k.shape))
        out = out._replace(cross_k=xsh, cross_v=xsh)
    return out


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               remat_policy: str = "full", microbatches: int = 1,
               sequence_parallel: bool = True, kv_dtype=None,
               fsdp_over_pod: bool = True, dp_over_model: bool = False,
               window_ring: bool = False, ssm_chunk: int = 0,
               kv_shard: str = "all"):
    """Returns (fn, args, in_shardings) ready to lower."""
    import dataclasses as _dc
    if ssm_chunk:
        cfg = _dc.replace(cfg, ssm_chunk=ssm_chunk)
    rules = ShardingRules.for_mesh(mesh, sequence_parallel=sequence_parallel,
                                   fsdp_over_pod=fsdp_over_pod,
                                   dp_over_model=dp_over_model)
    if kv_shard != "all":
        # page-heap sharding strategy: 'all' (batch axes + model),
        # 'model' (TP only), 'data' (DP axes only)
        rules.rules["pages"] = (("model",) if kv_shard == "model"
                                else rules.rules["batch"])
    model = build_model(cfg)
    ax = model.logical_axes()
    absp = model.abstract_params()
    psh = rules.param_shardings(ax, absp)

    if shape.kind == "train":
        opt = AdamW(total_steps=1000)
        step = make_train_step(model, opt, remat_policy=remat_policy,
                               microbatches=microbatches, rules=rules)
        state = abstract_state(model, opt)
        st_ax = state_logical_axes(model)
        st_sh = jax.tree.map(
            lambda a, s: NamedSharding(mesh, rules.spec_for(a, s.shape))
            if s is not None else None,
            st_ax, state,
            is_leaf=lambda x: x is None or (isinstance(x, tuple) and
                                            all(isinstance(e, (str, type(None)))
                                                for e in x)))
        batch = shaped_batch(cfg, shape)
        bsh = batch_shardings(rules, batch)
        return step, (state, batch), (st_sh, bsh), rules

    if shape.kind == "prefill":
        def prefill_step(params, batch, caches):
            with use_rules(rules):
                return model.prefill(params, batch, caches,
                                     remat_policy="none")
        b, s = shape.global_batch, shape.seq_len
        kvd = kv_dtype or kv_dtype_for(cfg, s, b)
        caches = model.make_decode_caches(b, max_seq=s, kv_dtype=kvd,
                                          abstract=True,
                                          window_ring=window_ring)
        batch = shaped_batch(cfg, shape)
        batch.pop("targets")
        return (prefill_step, (absp, batch, caches),
                (psh, batch_shardings(rules, batch),
                 cache_shardings(rules, caches)), rules)

    # decode
    def serve_step(params, tokens, caches):
        with use_rules(rules):
            return model.decode_step(params, tokens, caches)
    tokens, caches = decode_inputs(cfg, shape, kv_dtype,
                                   window_ring=window_ring)
    tsh = NamedSharding(mesh, rules.spec_for(("batch", None),
                                             tokens.shape))
    return (serve_step, (absp, tokens, caches),
            (psh, tsh, cache_shardings(rules, caches)), rules)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def _measure(cfg, shape, mesh, *, analysis: bool, kv_dtype=None,
             want_memory: bool = False, **build_kw):
    """Lower+compile one variant; returns cost/collective (+memory) dict.

    ``analysis=True`` unrolls every inner scan (flash blocks, SSD
    chunks) and widens the decode page block to the full table, so HLO
    cost analysis counts every iteration — XLA counts a while body
    exactly once.  Memory numbers always come from analysis=False
    (realistic blocked execution)."""
    from repro.models import layers as Lyr
    Lyr.set_analysis_unroll(analysis)
    KVC.set_page_block_override(10 ** 9 if analysis else None)
    try:
        fn, args, shardings, _rules = build_cell(cfg, shape, mesh,
                                                 kv_dtype=kv_dtype,
                                                 **build_kw)
        t0 = time.time()
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        compiled = lowered.compile()
        dt = time.time() - t0
        cost = compiled.cost_analysis()
        out = {"flops": float(cost.get("flops", 0.0)),
               "bytes": float(cost.get("bytes accessed", 0.0)),
               "coll": hlo_collective_bytes(compiled.as_text()),
               "seconds": round(dt, 1)}
        if want_memory:
            mem = compiled.memory_analysis()
            out["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0),
            }
        return out
    finally:
        Lyr.set_analysis_unroll(False)
        KVC.set_page_block_override(None)


def _probe_plan(cfg: ModelConfig):
    """(probe pairs, unit counts) for trip-count correction of the
    layer scan: corrected = F1 + (units-1)·(F2-F1) [+ tail·rec_unit]."""
    import dataclasses as dc
    if cfg.family == "hybrid":
        ntri, tail = divmod(cfg.num_layers, cfg.attn_period)
        plan = {"main": (dc.replace(cfg, num_layers=cfg.attn_period),
                         dc.replace(cfg, num_layers=2 * cfg.attn_period),
                         ntri)}
        if tail:
            plan["rec"] = (dc.replace(cfg, num_layers=1,
                                      attn_period=10 ** 6),
                           dc.replace(cfg, num_layers=2,
                                      attn_period=10 ** 6),
                           tail)
        return plan
    if cfg.is_encdec:
        import dataclasses as dc
        return {"main": (dc.replace(cfg, num_layers=1, enc_layers=1),
                         dc.replace(cfg, num_layers=2, enc_layers=2),
                         cfg.num_layers)}
    import dataclasses as dc
    return {"main": (dc.replace(cfg, num_layers=1),
                     dc.replace(cfg, num_layers=2), cfg.num_layers)}


_COST_KEYS = ("flops", "bytes")


def _corrected(probes: dict) -> dict:
    """Combine probe measurements into whole-model cost estimates.

    Per-layer units are clamped at 0: XLA occasionally lowers the L=1
    probe with *more* collectives than L=2 (different fusion/CSE
    choices), and a negative per-layer cost would poison the total."""
    main1, main2, units = probes["main"]
    out = {"probe_raw": {"f1": {k: main1[k] for k in _COST_KEYS},
                         "f2": {k: main2[k] for k in _COST_KEYS},
                         "f1_coll": main1["coll"]["total"],
                         "f2_coll": main2["coll"]["total"]}}
    for k in _COST_KEYS:
        unit = max(main2[k] - main1[k], 0.0)
        out[k] = main1[k] + (units - 1) * unit
        out[f"{k}_per_layer"] = unit
    coll = {}
    for k in list(probes["main"][0]["coll"].keys()):
        unit = max(main2["coll"][k] - main1["coll"][k], 0)
        coll[k] = main1["coll"][k] + (units - 1) * unit
    if "rec" in probes:
        r1, r2, tail = probes["rec"]
        for k in _COST_KEYS:
            out[k] += tail * max(r2[k] - r1[k], 0.0)
        for k in coll:
            coll[k] += tail * max(r2["coll"][k] - r1["coll"][k], 0)
    out["coll"] = coll
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = "experiments/dryrun", tag: str = "",
             probes: bool = True, **build_kw):
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
           "devices": int(mesh.devices.size),
           "build_kw": {k: str(v) for k, v in build_kw.items()}}
    kvd = (build_kw.pop("kv_dtype", None)
           or (kv_dtype_for(cfg, shape.seq_len, shape.global_batch)
               if shape.kind in ("prefill", "decode") else None))
    rec["kv_dtype"] = str(kvd) if kvd is not None else None
    try:
        full = _measure(cfg, shape, mesh, analysis=False, kv_dtype=kvd,
                        want_memory=True, **build_kw)
        rec.update(ok=True, memory=full["memory"],
                   compile_s=full["seconds"],
                   raw_cost={"flops": full["flops"],
                             "bytes": full["bytes"],
                             "coll": full["coll"]})
        if probes:
            pl = _probe_plan(cfg)
            meas = {}
            for name, (c1, c2, units) in pl.items():
                f1 = _measure(c1, shape, mesh, analysis=True,
                              kv_dtype=kvd, **build_kw)
                f2 = _measure(c2, shape, mesh, analysis=True,
                              kv_dtype=kvd, **build_kw)
                meas[name] = (f1, f2, units)
            rec["cost"] = _corrected(meas)
            rec["collectives"] = rec["cost"].pop("coll")
        else:
            rec["cost"] = {"flops": full["flops"], "bytes": full["bytes"]}
            rec["collectives"] = full["coll"]
    except Exception as e:  # noqa: BLE001 — recorded, surfaced by caller
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2500:]})
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-sp", action="store_true")
    ap.add_argument("--dp-over-model", action="store_true")
    ap.add_argument("--window-ring", action="store_true")
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--kv-shard", default="all",
                    choices=("all", "model", "data"))
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    kw = dict(remat_policy=args.remat, microbatches=args.microbatches,
              sequence_parallel=not args.no_sp,
              dp_over_model=args.dp_over_model,
              window_ring=args.window_ring, ssm_chunk=args.ssm_chunk,
              kv_shard=args.kv_shard)
    cells = []
    if args.all:
        from repro.configs import ALL_ARCHS
        for a in ALL_ARCHS:
            for sh in cells_for(get_arch(a)):
                cells.append((a, sh.name))
    else:
        cells.append((args.arch, args.shape))

    for arch, shape in cells:
        rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                       out_dir=args.out, probes=not args.multi_pod,
                       tag=args.tag, **kw)
        status = "OK " if rec.get("ok") else "FAIL"
        extra = (f"compile={rec.get('compile_s')}s "
                 f"flops/dev={rec.get('cost', {}).get('flops', 0):.3g} "
                 f"coll/dev={rec.get('collectives', {}).get('total', 0):.3g}B "
                 f"peak/dev={rec.get('memory', {}).get('peak_bytes', 0)/2**30:.2f}GiB"
                 if rec.get("ok") else rec.get("error"))
        print(f"[{status}] {arch} × {shape} "
              f"({'2x16x16' if args.multi_pod else '16x16'}): {extra}",
              flush=True)


if __name__ == "__main__":
    main()
