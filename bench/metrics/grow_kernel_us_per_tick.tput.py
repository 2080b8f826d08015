"""Allocator kernel (``kernels/alloc_txn_blocked.py``): device time of
the grow transaction's Mosaic kernel inside the fused tick, per tick in
the traced window (device trace).  The kernel is the custom-call op
named after the function that holds its ``pallas_call``."""
KERNEL = "arena_alloc_txn_blocked"
TICK_PROGRAM = "jit_mega"


def read(run):
    tr = run.get("trace")
    if tr is None or run["system"] != "serving":
        return None
    sec, _ = tr.op_seconds(KERNEL, inside=TICK_PROGRAM)
    _, ticks = tr.module_seconds(TICK_PROGRAM)
    return 1e6 * sec / ticks if ticks and sec > 0 else None
