"""Model decode step (``_build_mega`` -> ``decode_step``,
``paged_attend1``): device time of the fused tick program per run of it
in the traced window (device trace)."""
TICK_PROGRAM = "jit_mega"


def read(run):
    tr = run.get("trace")
    if tr is None or run["system"] != "serving":
        return None
    sec, n = tr.module_seconds(TICK_PROGRAM)
    return 1e3 * sec / n if n else None
