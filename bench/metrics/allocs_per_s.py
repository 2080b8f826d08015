"""Allocations granted inside the window per second of it; the window
holds whole §3 iterations, the write and the read-back included."""


def read(run):
    if run["system"] != "allocator":
        return None
    t0, t1 = run["window"]
    return run["granted"] / (t1 - t0)
