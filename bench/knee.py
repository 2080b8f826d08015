"""Find the highest request rate an open-loop serving cell sustains.

    python3 bench/knee.py --workload <name> --seed <n> --seconds <s> \\
        --rates 1,1.25,1.5 --orders 20240710,7

One process, one engine.  For each order of sizes and gaps (the mix's
``schedule_seed``) and each rate, the cell's own open loop
(``serving._open``: prompt lengths and free programs warmed, a lead-in,
a window of ``--seconds``, its requests followed to their end) runs on
the engine, which is then drained before the next rate.  A rate is
sustained when the waiting queue does not grow across the window: its
mean over the steps of the last third is within one request of its mean
over the first third.  TTFT and TPOT are the cell's own metric readers.
Prints one JSON line per order and rate.  Run once when a cell is
defined, to fix its rate; the benchmark never searches.
"""
import argparse
import json
import os
import sys
import time

DRAIN_S = 120.0     # past this an engine still busy ends the sweep


def queue_growth(steps, window, k0, k1):
    """Mean waiting queue over the window's first and last thirds, by
    the steps that start in each."""
    import numpy as np
    w0, w1 = window
    third = (w1 - w0) / 3
    first = [s["waiting"] for s in steps[k0:k1] if s["t0"] < w0 + third]
    last = [s["waiting"] for s in steps[k0:k1] if s["t0"] >= w1 - third]
    return (float(np.mean(first)) if first else 0.0,
            float(np.mean(last)) if last else 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--orders", required=True,
                    help="schedule seeds, one sweep each")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import device, harness, serving
    from bench import traffic as T
    from repro import compile_cache

    c = harness.cell(harness.load_benchmark(), args.workload)
    try:
        device.require_tpu(c["chips"])
    except device.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    _, eng = serving._engine(c, args.seed)
    vocab = c["config"]["vocab_size"]
    ttft, tpot = harness.reader("ttft_p85_ms"), harness.reader("tpot_p90_ms")
    tail = harness.reader("ttft_ms_p90.ttft")
    counter = device.CompileCounter()
    for order in (int(o) for o in args.orders.split(",")):
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(c["mix"], rate_per_s=rate, schedule_seed=order)
            reqs = T.requests(mix, args.seed,
                              serving._n_requests(mix, args.seconds), vocab)
            drv = serving.Driver(eng, reqs)
            counter.count = 0
            run = dict(system="serving", steps=drv.steps, rec=drv.rec,
                       mix=mix, sizes=c["config"])
            serving._open(drv, mix, args.seconds, False, counter, run)
            q0, q2 = queue_growth(drv.steps, run["window"],
                                  *run["window_steps"])
            t_end = time.perf_counter()
            while drv.busy() and time.perf_counter() < t_end + DRAIN_S:
                drv.step()
            print(json.dumps({
                "order": order, "rate": rate,
                "due_in_window": run["attempted"],
                "unfinished": run["failed"],
                "queue_first_third": q0, "queue_last_third": q2,
                "sustained": q2 <= q0 + 1,
                "ttft_p85_ms": ttft(run), "ttft_p90_ms": tail(run),
                "tpot_p90_ms": tpot(run),
                "compiles_in_window": counter.count,
                "drain_s": time.perf_counter() - t_end}), flush=True)
            if drv.busy():
                print("knee: engine not drained; sweep ends",
                      file=sys.stderr)
                return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
