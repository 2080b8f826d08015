"""Prefill and admission (``models/`` prefill, ``merge_rows``): device
time outside the fused tick program, per request admitted in the traced
window (device trace)."""
TICK_PROGRAM = "jit_mega"


def read(run):
    tr = run.get("trace")
    if tr is None or run["system"] != "serving":
        return None
    k0, k1 = run["trace_steps"]
    n = sum(s["admitted"] for s in run["steps"][k0:k1])
    if n == 0:
        return None
    return 1e3 * tr.op_busy_outside(TICK_PROGRAM) / n
