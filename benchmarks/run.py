"""Benchmark harness entry: one module per paper figure (figs. 1-6).

Prints ``name,us_per_call,derived`` CSV as mandated — ``us_per_call`` is
the paper's headline metric (average subsequent allocation time), and
``derived`` carries the full methodology split (avg-all vs
avg-subsequent, free time, per-alloc ns, data-integrity check).

``--backend`` selects the allocator transaction implementation; with
``both``, every figure cell is reported for the jnp reference path and
the fused Pallas kernel path side by side.  ``--alloc-json PATH``
**appends** a run record — platform, git sha, per-variant jnp-vs-pallas
cells, and the pallas launches-per-transaction counts proving
single-kernel fusion — so ``BENCH_alloc.json`` accumulates a perf
trajectory across PRs instead of overwriting it (records made before
the append format are migrated in place as the first run).

Each run record also carries ``lowering: blocked|whole`` — which Pallas
kernel shape the cells ran (``--lowering``; auto = whole on CPU
interpret, blocked on TPU) — so perf rows stay comparable across the
two compiled stories.

``--num-shards`` runs the figure cells on the sharded multi-arena
allocator (core/shards.py); independently of it, every ``--alloc-json``
record now also appends a ``shard_sweep`` — throughput vs num_shards
(1, 2, 4) per swept variant — so BENCH_alloc.json tracks horizontal
scaling alongside the jnp-vs-pallas trajectory.

``--serve-json PATH`` appends a serving-throughput record (benchmarks/
fig8_serve.py): tokens/sec for the fused decode tick on the jnp and
Pallas allocators plus the launches-per-tick proof, accumulating in
``BENCH_serve.json`` with the same append-only trajectory format.
``fig8_serve`` is not in the default figure list (it builds a model);
run it with ``--fig fig8_serve`` or via ``--serve-json``.

``--fig fig9_replay`` runs the traffic-replay figure (benchmarks/
fig9_replay.py): deterministic Poisson/bursty/abandonment traces
through the serving engine for one config per model family, one and
four allocator shards parity-checked per pair; with ``--serve-json`` the
per-scenario p50/p99 + fragmentation cells append as a ``replay``
record (the ``serve``-kind fig8 record only appends when fig8 actually
ran, i.e. with no ``--fig`` filter or with ``--fig fig8_serve``).
Record schemas are validated on append (benchmarks/common.py,
``validate_serve_record``).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--fig fig1_page]
        [--backend jnp|pallas|both] [--lowering auto|whole|blocked]
        [--num-shards N] [--alloc-json BENCH_alloc.json]
        [--serve-json BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import subprocess
import sys

# make `python benchmarks/run.py` equivalent to
# `PYTHONPATH=src python -m benchmarks.run` — script invocation puts
# benchmarks/ (not the repo root) on sys.path.
_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FIGS = ["fig1_page", "fig2_chunk", "fig3_va_page", "fig4_vl_page",
        "fig5_va_chunk", "fig6_vl_chunk", "fig7_frag"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced grid (CI)")
    ap.add_argument("--fig", action="append", default=None,
                    help="run only the named figure module(s)")
    ap.add_argument("--backend", choices=("jnp", "pallas", "both"),
                    default="jnp",
                    help="allocator transaction backend per cell")
    ap.add_argument("--lowering", choices=("auto", "whole", "blocked"),
                    default="auto",
                    help="Pallas kernel lowering: whole-arena refs vs "
                         "the region-blocked compiled lowering "
                         "(DESIGN.md §8); auto picks per platform")
    ap.add_argument("--num-shards", type=int, default=1, metavar="N",
                    help="run the figure cells on the sharded "
                         "multi-arena allocator (core/shards.py, "
                         "DESIGN.md §9)")
    ap.add_argument("--alloc-json", default=None, metavar="PATH",
                    help="also write per-variant jnp-vs-pallas "
                         "avg_all/avg_subsequent to PATH")
    ap.add_argument("--serve-json", default=None, metavar="PATH",
                    help="append a serving-throughput record "
                         "(fig8_serve: fused-tick tokens/sec + "
                         "launches-per-tick) to PATH")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    figs = args.fig or FIGS
    backends = (("jnp", "pallas") if args.backend == "both"
                else (args.backend,))

    print("name,us_per_call,derived")
    for fig in figs:
        mod = importlib.import_module(f"benchmarks.{fig}")
        for backend in backends:
            for row in mod.run(quick=args.quick, backend=backend,
                               lowering=args.lowering,
                               num_shards=args.num_shards):
                name = (f"{fig}/{row['variant']}/{row['backend']}"
                        f"/{row['lowering']}/sh{row['num_shards']}"
                        f"/n{row['n']}/s{row['size']}")
                if "tick_ms_p99" in row:  # replay rows (fig9_replay)
                    derived = (
                        f"p50_ms={row['tick_ms_p50']:.2f} "
                        f"p99_ms={row['tick_ms_p99']:.2f} "
                        f"wait_p50={row['queue_wait_p50']:.0f} "
                        f"wait_p99={row['queue_wait_p99']:.0f} "
                        f"done={row['completed']}/{row['requests']} "
                        f"cancelled={row['cancelled']} "
                        f"evictions={row['evictions']} "
                        f"frag={row['frag_ratio_final']:.3f}")
                    print(f"{name},{row['tick_ms_p99']:.2f},{derived}",
                          flush=True)
                    continue
                if "tokens_per_s" in row:  # serving rows (fig8_serve)
                    derived = (
                        f"tok_per_s_all={row['tokens_per_s_all']:.1f} "
                        f"tok_per_s_sub={row['tokens_per_s']:.1f} "
                        f"alloc_txns={row['alloc_txns']} "
                        f"launches_per_tick={row['launches_per_tick']}")
                    print(f"{name},{row['tokens_per_s']:.1f},{derived}",
                          flush=True)
                    continue
                derived = (f"alloc_all={row['alloc_us_all']:.0f}us "
                           f"alloc_sub={row['alloc_us_subsequent']:.0f}us "
                           f"free_sub={row['free_us_subsequent']:.0f}us "
                           f"per_alloc={row['per_alloc_ns']:.0f}ns "
                           f"data_ok={row['data_ok']}")
                print(f"{name},{row['alloc_us_subsequent']:.1f},{derived}",
                      flush=True)

    if args.alloc_json:
        import jax
        from benchmarks.common import (SHARD_SWEEP, alloc_comparison_cell,
                                       pallas_calls_per_txn,
                                       shard_scaling_cell)
        from repro.core import VARIANTS

        from repro.kernels.ops import resolve_lowering

        lowering = resolve_lowering(args.lowering)
        launches = {}
        for v in VARIANTS:
            a, f = pallas_calls_per_txn(v, "pallas", args.lowering)
            launches[v] = {"alloc": a, "free": f}
            print(f"launches_per_txn,{v}/pallas/{lowering},"
                  f"alloc={a} free={f}", flush=True)
        # the one-kernel contract holds for the sharded allocator too:
        # the (attempt, shard) schedule rides the grid, not extra
        # launches (DESIGN.md §9)
        for v in ("page", "vl_chunk"):
            a, f = pallas_calls_per_txn(v, "pallas", args.lowering,
                                        num_shards=4)
            launches[f"{v}/shards4"] = {"alloc": a, "free": f}
            print(f"launches_per_txn,{v}/pallas/{lowering}/shards4,"
                  f"alloc={a} free={f}", flush=True)
        # ...and for defragmentation waves: plan + migrate is ONE
        # launch, sharded or not (DESIGN.md §10)
        from benchmarks.common import pallas_calls_per_defrag_wave
        for v, S in (("vl_chunk", 1), ("vl_chunk", 4)):
            w = pallas_calls_per_defrag_wave(v, "pallas", args.lowering,
                                             num_shards=S)
            key = f"{v}/defrag" + (f"/shards{S}" if S > 1 else "")
            launches[key] = {"wave": w}
            print(f"launches_per_txn,{key}/pallas/{lowering},wave={w}",
                  flush=True)

        # throughput vs num_shards: the horizontal-scaling record
        # (jnp column — the CPU perf signal; see README)
        shard_sweep = {v: shard_scaling_cell(v, quick=args.quick)
                       for v in ("page", "vl_chunk")}
        for v, cells in shard_sweep.items():
            for S in SHARD_SWEEP:
                c = cells[str(S)]
                print(f"shard_sweep,{v}/jnp/shards{S},"
                      f"alloc_sub={c['alloc_us_subsequent']:.0f}us "
                      f"allocs_per_s={c['allocs_per_s_subsequent']:.0f}",
                      flush=True)

        # churn-then-defrag reclamation curve (benchmarks/fig7_frag.py,
        # DESIGN.md §10): fragmentation gauges per churn round, then
        # the one-wave reclamation deltas + wave latency
        from benchmarks import fig7_frag
        frag_defrag = fig7_frag.reclamation_record(quick=args.quick)
        print(f"frag_defrag,{frag_defrag['variant']}/jnp,"
              f"migrated={frag_defrag['pages_migrated']} "
              f"wave_ms={frag_defrag['wave_ms_first']} "
              f"frag_after={frag_defrag['after_defrag']['frag_ratio']}",
              flush=True)

        # pallas timings on a non-TPU platform are interpret-mode and
        # only the jnp column is a perf signal there; record which —
        # and which kernel lowering (whole|blocked) the pallas cells
        # actually ran, so the trajectory stays comparable.
        record = {
            "platform": jax.default_backend(),
            "git_sha": _git_sha(),
            "quick": bool(args.quick),
            "lowering": lowering,
            "launches_per_txn": launches,
            "shard_sweep": shard_sweep,
            "frag_defrag": frag_defrag,
            "variants": {v: alloc_comparison_cell(v, quick=args.quick,
                                                  lowering=args.lowering)
                         for v in VARIANTS},
        }
        from benchmarks.common import load_runs
        runs = load_runs(args.alloc_json)
        runs.append(record)
        # atomic replace: a failure mid-dump must not truncate the
        # trajectory file the append format exists to preserve.
        tmp = args.alloc_json + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"runs": runs}, f, indent=2, sort_keys=True)
        os.replace(tmp, args.alloc_json)
        print(f"appended run {len(runs)} to {args.alloc_json}", flush=True)

    if args.serve_json:
        import jax
        from benchmarks.common import append_serve_record

        # which record kinds this invocation actually measured: fig8's
        # serve record unless a --fig filter excluded it, fig9's replay
        # record only when explicitly requested (it builds a model per
        # family).
        envelope = lambda: {"platform": jax.default_backend(),
                            "git_sha": _git_sha(),
                            "quick": bool(args.quick)}
        if args.fig is None or "fig8_serve" in figs:
            from benchmarks import fig8_serve

            cells = fig8_serve.serve_record(quick=args.quick)
            for name, c in cells.items():
                print(f"serve,{name},"
                      f"tok_per_s_sub={c['tokens_per_s']:.1f} "
                      f"launches_per_tick={c['launches_per_tick']}",
                      flush=True)
            n = append_serve_record(args.serve_json, dict(
                envelope(), record="serve", cells=cells))
            print(f"appended serve run {n} to {args.serve_json}",
                  flush=True)
        if "fig9_replay" in figs:
            from benchmarks import fig9_replay

            cells = fig9_replay.replay_record(quick=args.quick)
            for name, c in cells.items():
                print(f"replay,{name},p99_ms={c['tick_ms_p99']:.2f} "
                      f"done={c['completed']}/{c['requests']} "
                      f"frag={c['frag_ratio_final']:.3f}", flush=True)
            n = append_serve_record(args.serve_json, dict(
                envelope(), record="replay", cells=cells))
            print(f"appended replay run {n} to {args.serve_json}",
                  flush=True)


def _git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"


if __name__ == "__main__":
    main()
