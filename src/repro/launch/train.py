"""End-to-end training driver with checkpoint/restart, preemption
handling, straggler monitoring and (optional) compressed cross-pod
gradient sync.

Runs on whatever devices exist: real hardware uses the production mesh
shardings; this container runs the same code on a 1-device mesh (or a
forced multi-device host mesh via --fake-devices N for integration
tests).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --smoke --steps 20 --ckpt-dir /tmp/run1 [--resume]
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}")

    import jax
    import numpy as np
    from repro import compile_cache
    from repro.ckpt import checkpoint as CK
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, batch_at
    from repro.ft.runtime import PreemptionGuard, StepMonitor
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build_model
    from repro.parallel.sharding import ShardingRules
    from repro.train.optimizer import AdamW
    from repro.train.train_step import init_state, make_train_step

    compile_cache.enable()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    dcfg = DataConfig(seed=args.seed)

    mesh = make_host_mesh()
    rules = ShardingRules.for_mesh(mesh)
    opt = AdamW(peak_lr=args.lr, warmup_steps=5, total_steps=args.steps)
    step_fn = jax.jit(make_train_step(
        model, opt, remat_policy=args.remat,
        microbatches=args.microbatches, rules=rules))

    state = init_state(model, jax.random.PRNGKey(args.seed), opt)
    start_step = 0
    ckpt = CK.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and args.ckpt_dir and CK.latest_step(args.ckpt_dir) is not None:
        state, start_step = CK.restore(state, args.ckpt_dir)
        print(f"resumed from step {start_step}", flush=True)

    guard = PreemptionGuard()
    mon = StepMonitor()
    for step in range(start_step, args.steps):
        mon.start()
        batch = jax.tree.map(
            lambda x: jax.numpy.asarray(x),
            batch_at(cfg, shape, dcfg, step))
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        m = mon.stop()
        print(f"step {step:5d} loss {loss:8.4f} "
              f"gnorm {float(metrics['grad_norm']):7.3f} "
              f"t {m['step_time']:6.2f}s"
              + (" [straggler]" if m["straggler"] else ""), flush=True)
        if not np.isfinite(loss):
            print("non-finite loss; aborting", file=sys.stderr)
            return 1
        want_ckpt = ckpt and ((step + 1) % args.ckpt_every == 0
                              or guard.should_stop
                              or step + 1 == args.steps)
        if want_ckpt:
            ckpt.save(state, step + 1)
        if guard.should_stop:
            print("preempted: checkpoint flushed, exiting", flush=True)
            break
    if ckpt:
        ckpt.wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
