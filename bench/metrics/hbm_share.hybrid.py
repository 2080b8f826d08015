"""Model decode step against its memory roofline: the bytes the traced
ticks had to move (counted by the configuration's ``flops`` module,
``tick_bytes``: the held weights once a tick, each live slot's state
read and written, its K/V at its context) over the fused tick
program's device time times the chip's HBM bandwidth (``peaks.json``).
A request admitted at step ``a`` is in the tick of each step ``k`` from
``a`` to its retirement, at context ``prompt + k - a + 1``."""
import importlib

from bench import device

TICK_PROGRAM = "jit_mega"


def read(run):
    tr = run.get("trace")
    if tr is None or run["system"] != "serving":
        return None
    sz = run["sizes"]
    flops = importlib.import_module("bench." + sz["flops"])
    if not hasattr(flops, "tick_bytes"):
        return None
    sec, n = tr.module_seconds(TICK_PROGRAM)
    k0, k1 = run["trace_steps"]
    ticks = []
    for k in range(k0, k1):
        ctx = [r["prompt_len"] + k - r["admit_step"] + 1 for r in run["rec"]
               if r["admit_step"] is not None and r["admit_step"] <= k
               and (r["retire_step"] is None or k <= r["retire_step"])]
        if ctx:
            ticks.append(flops.tick_bytes(sz, ctx))
    if not (ticks and n and sec > 0):
        return None
    bw = device.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (sum(ticks) / len(ticks)) * n / (sec * bw)
