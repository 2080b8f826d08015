"""Scheduler: mean share of the ``max_batch`` slots that hold a request
in each decode tick of the window (host record: slots held before the
step plus those its admission filled; in a traced run, the steps
before the trace)."""


def read(run):
    if run["system"] != "serving":
        return None
    k0, k1 = run.get("harness", (None, run["window_steps"]))[1]
    steps = run["steps"][k0:k1]
    if not steps:
        return None
    b = run["max_batch"]
    return 100.0 * sum(min(s["active"] + s["admitted"], b)
                       for s in steps) / (b * len(steps))
