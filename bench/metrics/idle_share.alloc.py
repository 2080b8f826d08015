"""Device: share of the traced window in which no op ran on the chip,
1 - (union of op intervals) / window (device trace)."""


def read(run):
    tr = run.get("trace")
    if tr is None or run["system"] != "allocator":
        return None
    return 100.0 * tr.idle_share
