"""Inspect observability artifacts (obs/, DESIGN.md §14): pretty-print
a Prometheus metrics exposition or a Chrome trace JSON, decode the
telemetry words out of a serving snapshot, or drive a quick live
replay and dump everything from it.

    # validate + summarize artifacts launch/serve.py wrote
    PYTHONPATH=src python scripts/obs_dump.py --metrics metrics.prom
    PYTHONPATH=src python scripts/obs_dump.py --trace trace.json

    # per-class / per-shard occupancy heatmap from a snapshot dir
    # (reads the ctl words + fingerprint sidecar directly — no model,
    # no engine, works on snapshots from any geometry)
    PYTHONPATH=src python scripts/obs_dump.py --snapshot ./snap

    # stand up a tiny engine, replay a scenario, dump everything
    PYTHONPATH=src python scripts/obs_dump.py --live \
        [--arch qwen2-0.5b] [--scenario steady]

Every path validates before printing (obs.metrics.validate_exposition
/ obs.trace.validate_trace), so this doubles as the CI artifact
checker.
"""
import argparse
import json
import re
import sys

sys.path.insert(0, "src")

_BLOCKS = " ▁▂▃▄▅▆▇█"


def dump_metrics(path: str) -> None:
    from repro.obs.metrics import validate_exposition
    text = open(path).read()
    if path.endswith(".json"):
        doc = json.loads(text)
        print(f"{path}: JSON metrics, {len(doc)} families")
        for name, fam in sorted(doc.items()):
            print(f"  {fam['type']:<9} {name} "
                  f"({len(fam['samples'])} samples)")
        return
    n = validate_exposition(text)
    print(f"{path}: valid Prometheus exposition, {n} samples")
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            print(f"  {kind:<9} {name}")


def dump_trace(path: str, require_phases: bool = False) -> None:
    from repro.obs.trace import load, validate_trace
    doc = load(path)
    n = validate_trace(doc, require_phases=require_phases)
    print(f"{path}: valid trace, {n} events")
    by = {}
    for ev in doc["traceEvents"]:
        key = (ev["name"].split("/")[0], ev["cat"])
        tot, cnt = by.get(key, (0.0, 0))
        by[key] = (tot + ev.get("dur", 0.0), cnt + 1)
    print(f"  {'phase':<16} {'cat':<8} {'count':>6} {'total ms':>10}")
    for (name, cat), (tot, cnt) in sorted(by.items()):
        print(f"  {name:<16} {cat:<8} {cnt:>6} {tot / 1e3:>10.2f}")


def dump_snapshot(directory: str, step=None) -> None:
    """Decode a serving snapshot's telemetry words and render the
    per-class / per-shard live-occupancy heatmap (t_alloc − t_free:
    pages currently held, by class, by shard) plus the raw telemetry
    table — straight from the committed files, engine-free."""
    import os

    import numpy as np

    from repro.ckpt import checkpoint as CK

    meta_rec, s = CK.read_meta(directory, step)
    extra = meta_rec.get("extra") or {}
    fp = extra.get("fingerprint")
    if fp is None:
        raise SystemExit(f"{directory}: step {s} has no serving "
                         f"fingerprint sidecar (not an engine snapshot)")
    # the fingerprint's describe() rendering carries the ctl word map;
    # parsing it means this tool needs no layout reconstruction
    fields = [(m.group(3), int(m.group(1)), int(m.group(2)))
              for m in re.finditer(r"ctl\[(\d+):(\d+)\]\s+(\S+)",
                                   fp["arena_layout"])]
    info = meta_rec["leaves"]["arena_ctl"]
    ctl = np.load(os.path.join(directory, f"step_{s:08d}",
                               info["file"]))
    ctl = np.atleast_2d(ctl)  # (S, ctl_words)
    print(f"{directory}: snapshot step {s}, arch {fp.get('arch')}, "
          f"variant {fp.get('variant')}, "
          f"{fp.get('num_shards')} shard(s)")
    tele = {name: ctl[:, a:b] for name, a, b in fields
            if name.startswith("t_")}
    if not tele:
        raise SystemExit("snapshot predates the telemetry region "
                         "(no t_* ctl words in its fingerprint)")
    held = tele["t_alloc"] - tele["t_free"]   # (S, C)
    peak = max(1, int(held.max()))
    print(f"\nlive pages held (t_alloc − t_free), peak {peak}:")
    print("        " + " ".join(f"c{c}" for c in range(held.shape[1])))
    for sh in range(held.shape[0]):
        cells = "  ".join(_BLOCKS[min(len(_BLOCKS) - 1,
                                      (int(v) * (len(_BLOCKS) - 1)
                                       + peak - 1) // peak)]
                          for v in held[sh])
        print(f"  shard{sh} {cells}   {held[sh].tolist()}")
    print("\ntelemetry words:")
    for name, a, b in fields:
        if name.startswith("t_"):
            print(f"  {name:<12} {ctl[:, a:b].squeeze().tolist()}")


def live(arch: str, scenario: str) -> None:
    from repro.obs.metrics import validate_exposition
    from repro.obs.trace import Tracer, validate_trace
    from repro.serve.replay import (SCENARIOS, engine_factory,
                                    generate_trace, replay)

    cfg, make = engine_factory(arch)
    eng = make(tracer=Tracer())
    trace = generate_trace(SCENARIOS[scenario], seed=0,
                           vocab_size=cfg.vocab_size)
    result = replay(eng, trace, scenario=scenario)
    print(f"replay summary ({arch}/{scenario}):")
    print(json.dumps(result.summary(), indent=2, sort_keys=True))
    print("\nin-kernel telemetry (drained ctl words):")
    for k, v in eng.drain_telemetry().items():
        print(f"  {k:<12} {v.tolist()}")
    text = eng.publish_metrics().to_prometheus()
    n = validate_exposition(text)
    print(f"\nmetrics exposition: valid, {n} samples")
    doc = eng.tracer.to_json()
    validate_trace(doc, require_phases=True)
    print(f"trace: valid, {len(doc['traceEvents'])} events "
          f"(compile and steady ticks both present)")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="validate + pretty-print obs/ artifacts")
    ap.add_argument("--metrics", metavar="PATH",
                    help="Prometheus text (or .json) metrics file")
    ap.add_argument("--trace", metavar="PATH",
                    help="Chrome trace_event JSON file")
    ap.add_argument("--require-phases", action="store_true",
                    help="trace must separate compile from steady "
                         "ticks (the replay acceptance check)")
    ap.add_argument("--snapshot", metavar="DIR",
                    help="serving snapshot directory: decode the ctl "
                         "telemetry words and render the per-class/"
                         "per-shard occupancy heatmap (engine-free)")
    ap.add_argument("--step", type=int, default=None,
                    help="snapshot step (default: newest committed)")
    ap.add_argument("--live", action="store_true",
                    help="replay a scenario on a smoke engine and "
                         "dump metrics + telemetry + trace from it")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--scenario", default="steady")
    args = ap.parse_args(argv)
    if not (args.metrics or args.trace or args.snapshot or args.live):
        ap.error("nothing to do: pass --metrics, --trace, "
                 "--snapshot, or --live")
    if args.metrics:
        dump_metrics(args.metrics)
    if args.trace:
        dump_trace(args.trace, require_phases=args.require_phases)
    if args.snapshot:
        dump_snapshot(args.snapshot, step=args.step)
    if args.live:
        live(args.arch, args.scenario)
    return 0


if __name__ == "__main__":
    sys.exit(main())
