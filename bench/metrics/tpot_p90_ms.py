"""90th percentile, over the window's finished requests, of (retirement
time - first-token time) / (tokens - 1): the mean gap between a
request's output tokens as its client sees them (tokens reach the host
only at retirement)."""
from bench import stats


def read(run):
    if run["system"] != "serving" or run["mix"]["loop"] != "open":
        return None
    vals = []
    for i in run["window_reqs"]:
        r = run["rec"][i]
        if r["retire"] is not None and r["n_out"] > 1:
            vals.append((r["retire"] - r["admit1"]) / (r["n_out"] - 1))
    return 1e3 * stats.percentile(vals, 90) if vals else None
