"""Output tokens produced inside the window per second of it, counted at
step granularity (host clock; every step ends on the engine's sync)."""


def read(run):
    if run["system"] != "serving" or run["mix"]["loop"] != "closed":
        return None
    t0, t1 = run["window"]
    return run["tokens"] / (t1 - t0)
