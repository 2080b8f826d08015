"""Readings for setting a cell's limits: the program and its control.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed in this one process, as ``run.py`` does,
and also its control: for a served model the reference module the
configuration names, computed at its ``CONTROL`` precision (fp8
operands for ``qwen2_ref``) and put in the program's place (each
position's gap of the token the lower precision puts first); for the
allocator the program with overlapping grants planted.  Prints one JSON
line per seed and mode with every number compared, as the harness judges it
(the control's reading in a control run), the verdict, and for a
served model the program's own gap on the same tokens.  The
benchmark's own runs never run the control.
"""
import argparse
import gc
import json
import os
import sys
import time


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import device, harness
    from repro import compile_cache

    c = harness.cell(harness.load_benchmark(), args.workload)
    try:
        devs = device.require_tpu(c["chips"])
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    counter = device.CompileCounter()
    # a serving run reads the program and its control on the same tokens
    modes = ((True,) if c["config"]["system"] == "serving"
             else (False, True))
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in modes:
            rec = harness.run_cell(c, seed, args.seconds, False, t_start,
                                   devs, counter, control=control)
            line = {"seed": seed, "control": control,
                    "checks": rec["checks"],
                    "reference": c["config"].get("reference"),
                    "program_gap": rec.get("program_gap"),
                    "compared_tokens": rec.get("compared_tokens"),
                    "correct": harness.correct(rec["checks"])}
            print(json.dumps(line), flush=True)
            del rec
            gc.collect()
            t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
