"""Scheduler (``serve/engine.py`` ``step``, ``_admit``): 90th percentile,
over the window's requests, of the time from scheduled arrival to the
end of the step that admitted it; one never admitted by the drain limit
counts its wait by then.  Its top tenth is the requests that arrived
during another's admission step.  In a traced run, only the requests
admitted by steps that began before the trace (the profiler stalls the
host as it stops)."""
from bench import stats


def read(run):
    if run["system"] != "serving" or run["mix"]["loop"] != "open":
        return None
    end = run["window"][1] + run["mix"]["drain_s"]
    cut = run["harness"][0][1] if "harness" in run else None
    vals = []
    for i in run["window_reqs"]:
        r = run["rec"][i]
        if cut is not None and (r["admit0"] is None or r["admit0"] >= cut):
            continue
        t = r["admit1"] if r["admit1"] is not None else end
        vals.append(t - r["due"])
    return 1e3 * stats.percentile(vals, 90) if vals else None
