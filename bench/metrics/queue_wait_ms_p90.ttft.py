"""Scheduler (``serve/engine.py`` ``_admit``): 90th percentile, over the
window's requests that were admitted, of the time from scheduled arrival
to the start of the step that admitted them (harness clock).  In a
traced run, only the requests admitted by steps that began before the
trace: stopping the profiler stalls the host for tens of seconds, and
the requests that wait through it would be read as the scheduler's."""
from bench import stats


def read(run):
    if run["system"] != "serving" or run["mix"]["loop"] != "open":
        return None
    _, end = run.get("harness", (run["window"],))[0]
    vals = [run["rec"][i]["admit0"] - run["rec"][i]["due"]
            for i in run["window_reqs"]
            if run["rec"][i]["admit0"] is not None
            and ("harness" not in run or run["rec"][i]["admit0"] < end)]
    return 1e3 * stats.percentile(vals, 90) if vals else None
