"""End-to-end serving driver: continuous batching over the Ouroboros
paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --smoke --requests 12 --max-new 16

Crash-safe mode (DESIGN.md §12): with ``--snapshot-dir`` a
``PreemptionGuard`` arms SIGTERM/SIGINT — the loop finishes the
in-flight tick, snapshots the complete serving state (arena + KV pages
+ queue) and exits with code 3; a restart with ``--resume`` picks the
stream back up token-identically.  Each completed request prints a
stable ``REQ <uid> <tokens...>`` line, so killed-run + resumed-run
output concatenates to exactly the uninterrupted run's output (the CI
crash-restart smoke asserts this).

Observability (DESIGN.md §14): ``--metrics-file`` periodically exports
the metrics registry (engine stats, frag gauges, drained in-kernel
allocator telemetry) as Prometheus text or JSON; ``--trace-file``
emits a Chrome/Perfetto trace of engine phase spans with compile ticks
tagged distinctly from steady-state ticks.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--alloc-backend", choices=("jnp", "pallas"),
                    default="jnp",
                    help="allocator transaction backend (fused Pallas "
                         "kernels or jnp reference path)")
    ap.add_argument("--alloc-lowering",
                    choices=("auto", "whole", "blocked"), default="auto",
                    help="Pallas kernel lowering (whole-arena refs vs "
                         "blocked; DESIGN.md §8) — the active "
                         "one is reported in the engine stats")
    ap.add_argument("--num-shards", type=int, default=1,
                    help="shard the KV page allocator into N "
                         "independent arenas with overflow routing "
                         "(core/shards.py, DESIGN.md §9); per-shard "
                         "occupancy lands in the engine stats")
    ap.add_argument("--defrag-threshold", type=float, default=None,
                    metavar="RATIO",
                    help="fire a proactive defrag wave when frag_ratio "
                         "exceeds RATIO (0-1; default: only the "
                         "allocation-failure retry defrags)")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="arm crash-safe serving: SIGTERM/SIGINT "
                         "finishes the current tick, snapshots the "
                         "complete serving state into DIR "
                         "(ckpt/checkpoint.py atomic layout) and "
                         "exits with code 3")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest committed snapshot under "
                         "--snapshot-dir and resume mid-stream "
                         "(token-identically) instead of submitting "
                         "fresh requests")
    ap.add_argument("--metrics-file", default=None, metavar="PATH",
                    help="write the metrics registry (engine stats, "
                         "frag gauges, drained in-kernel telemetry) to "
                         "PATH as Prometheus text exposition "
                         "(.json suffix → JSON) every --metrics-every "
                         "steps and at drain (obs/metrics.py, "
                         "DESIGN.md §14)")
    ap.add_argument("--metrics-every", type=int, default=50,
                    metavar="STEPS",
                    help="steps between --metrics-file rewrites "
                         "(default 50)")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="emit a Chrome/Perfetto trace_event JSON of "
                         "engine phase spans to PATH at exit — compile "
                         "ticks tagged distinctly from steady ticks "
                         "(obs/trace.py, DESIGN.md §14)")
    args = ap.parse_args(argv)
    if args.metrics_every < 1:
        ap.error("--metrics-every must be >= 1")
    if args.resume and not args.snapshot_dir:
        ap.error("--resume requires --snapshot-dir")

    import jax
    import numpy as np
    from repro import compile_cache
    from repro.configs import get_arch
    from repro.models.model import build_model
    from repro.serve.engine import ServingEngine

    compile_cache.enable()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    tracer = None
    if args.trace_file:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    eng = ServingEngine(model, params, max_batch=args.max_batch,
                        max_seq=args.max_seq,
                        alloc_backend=args.alloc_backend,
                        alloc_lowering=args.alloc_lowering,
                        num_shards=args.num_shards,
                        max_new_cap=max(args.max_new, 16),
                        defrag_threshold=args.defrag_threshold,
                        tracer=tracer)
    eng.launches_per_tick()  # record into stats before serving

    def write_metrics():
        if not args.metrics_file:
            return
        eng.publish_metrics().write(
            args.metrics_file,
            fmt="json" if args.metrics_file.endswith(".json")
            else "prometheus")

    guard = None
    if args.snapshot_dir:
        from repro.ft.runtime import PreemptionGuard
        guard = PreemptionGuard()

    if args.resume:
        step = eng.restore(args.snapshot_dir)
        print(f"resumed from snapshot step {step} "
              f"under {args.snapshot_dir}", flush=True)
    else:
        rng = np.random.default_rng(args.seed)
        for _ in range(args.requests):
            plen = int(rng.integers(4, args.max_seq // 4))
            eng.submit(rng.integers(2, cfg.vocab_size, plen),
                       max_new_tokens=args.max_new)

    t0 = time.time()
    done, preempted = [], False
    for tick in range(100000):
        finished = eng.step()
        if args.metrics_file and tick % args.metrics_every == 0:
            write_metrics()
        for r in finished:
            # one stable line per completed stream: killed-run output +
            # resumed-run output must concatenate to the uninterrupted
            # run's output (the crash-restart smoke diffs these)
            print("REQ", r.uid, *r.out_tokens, flush=True)
        done.extend(finished)
        drained = (not eng.waiting
                   and all(s is None for s in eng.slot_req))
        if drained:
            break
        if guard is not None and guard.should_stop:
            path = eng.snapshot(directory=args.snapshot_dir)
            print(f"preempted: snapshot committed to {path}",
                  flush=True)
            preempted = True
            break
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    write_metrics()
    if tracer is not None:
        tracer.write(args.trace_file)
        print(f"trace written to {args.trace_file} "
              f"({len(tracer.events)} events)", flush=True)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s incl. compile)")
    print(f"allocator stats: {eng.stats}")
    if preempted:
        return 3
    return 0 if drained else 1


if __name__ == "__main__":
    sys.exit(main())
