"""Allocator kernels (``kernels/alloc_txn_blocked.py``): mean device
time of one alloc or free transaction kernel in the traced window
(device trace; the custom-call ops named after the functions that hold
their ``pallas_call``)."""
KERNELS = ("arena_alloc_txn_blocked", "arena_free_txn_blocked")


def read(run):
    tr = run.get("trace")
    if tr is None or run["system"] != "allocator":
        return None
    sec = cnt = 0
    for k in KERNELS:
        s, n = tr.op_seconds(k)
        sec, cnt = sec + s, cnt + n
    return 1e6 * sec / cnt if cnt else None
