"""One run of one cell, driven by data.

``BENCHMARK.json`` names the cell; its configuration and traffic files
(``configs/``, ``traffic/``) say what to build and what to send; the
configuration's ``system`` names the module that runs it (``serving``,
``allocator``); each metric the cell reports is read from the run's
record by ``metrics/<metric name>.py``.  A later cell is then an entry
and data files, and a later metric a reader of its own.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bm: dict, workload: str, root: str = ROOT) -> dict:
    """The workload's entry with its configuration and mix loaded."""
    w = next((x for x in bm["workloads"] if x["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    c = next(x for x in bm["configs"] if x["name"] == w["config"])
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    return dict(name=workload, chips=w["chips"], config=config, mix=mix)


def metrics_for(bm: dict, workload: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer
    ones: those whose ``workloads`` list it, or that have none."""
    group = bm["per_layer"] if traced else bm["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def reader(name: str):
    """``metrics/<name>.py``'s ``read(run) -> value or None``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(c: dict, seed: int, seconds: float, traced: bool,
             t_start: float, devs, counter, control: bool = False) -> dict:
    system = importlib.import_module("bench." + c["config"]["system"])
    return system.run(c, seed, seconds, traced, t_start, counter, devs,
                      control=control)


def correct(checks: dict) -> bool:
    """Every number compared is within its limit."""
    return all(v <= lim for v, lim in checks.values())


def result(bm: dict, c: dict, rec: dict, traced: bool) -> dict:
    """The result line: every number compared beside its limit comes
    last, under ``checks``."""
    metrics = {}
    for m in metrics_for(bm, c["name"], traced):
        v = reader(m["name"])(rec)
        if v is None:
            if not traced:
                raise RuntimeError(f"end-to-end metric {m['name']!r} "
                                   f"read nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in rec["checks"].items()}
    out = {"correct": correct(rec["checks"]), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics,
           "device": rec["device"]}
    if traced and rec.get("trace") is not None:
        out["breakdown"] = rec["trace"].breakdown()
    out["checks"] = checks
    return out


def main(args, t_start: float) -> int:
    from bench import device

    bm = load_benchmark()
    c = cell(bm, args.workload)
    try:
        devs = device.require_tpu(c["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache

    cache = compile_cache.enable()
    counter = device.CompileCounter()
    print(f"bench: {c['name']} seed {args.seed} on {devs[0].device_kind} "
          f"x{len(devs)}; compile cache {cache}", flush=True)
    rec = run_cell(c, args.seed, args.seconds, bool(args.trace), t_start,
                   devs, counter)
    facts = {k: rec[k] for k in ("setup_s", "reference_s", "lateness_s",
                                 "iterations", "compared_tokens",
                                 "oracle_iterations", "slowest_steps",
                                 "segments")
             if k in rec}
    facts["window_s"] = rec["window"][1] - rec["window"][0]
    facts["compiles_in_window"] = counter.count
    print("bench: " + json.dumps(facts), flush=True)
    out = result(bm, c, rec, bool(args.trace))
    for k, ch in out["checks"].items():
        print(f"check {k} {ch['value']} limit {ch['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
