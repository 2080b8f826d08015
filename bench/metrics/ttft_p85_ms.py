"""85th percentile, over every request due inside the window, of the
time from its scheduled arrival to the end of the step that admitted it
(that step's prefill returns its first token).  A request never admitted
by the drain limit counts with the time it had waited by then.  The
85th leaves a dozen of a window's ~83 requests beyond it; the 90th, on
eight, swings by the phase of arrivals against admission steps
(``ttft_ms_p90.ttft`` keeps it as a layer's reading)."""
from bench import stats


def read(run):
    if run["system"] != "serving" or run["mix"]["loop"] != "open":
        return None
    end = run["window"][1] + run["mix"]["drain_s"]
    vals = []
    for i in run["window_reqs"]:
        r = run["rec"][i]
        t = r["admit1"] if r["admit1"] is not None else end
        vals.append(t - r["due"])
    return 1e3 * stats.percentile(vals, 85) if vals else None
