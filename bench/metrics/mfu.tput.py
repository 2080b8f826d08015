"""Whole step: model operations the window's work needed (each admitted
prompt once, unpadded; each output token at its context, counted by the
module the configuration names under ``flops``) over the window's
seconds times the chip's bf16 peak (``peaks.json``).  A request
admitted at step ``a`` gets token 0 from that step's prefill and token
``j >= 1`` from the tick of step ``a + j - 1``, attending to
``prompt + j`` positions.  In a traced run, the steps before the
trace."""
import importlib

from bench import device


def read(run):
    if run["system"] != "serving":
        return None
    sz = run["sizes"]
    flops = importlib.import_module("bench." + sz["flops"])
    (t0, t1), (k0, k1) = run.get("harness",
                                 (run["window"], run["window_steps"]))
    total = 0
    for r in run["rec"]:
        a = r["admit_step"]
        if a is None:
            continue
        lp = r["prompt_len"]
        if k0 <= a < k1:
            total += flops.prefill_flops(sz, lp)
        last = r["retire_step"] if r["retire_step"] is not None else k1 - 1
        for j in range(max(1, k0 - a + 1), min(last, k1 - 1) - a + 2):
            total += flops.decode_token_flops(sz, lp + j)
    peak = device.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * total / ((t1 - t0) * peak)
