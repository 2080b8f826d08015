"""What the benchmark asks of the device: a chip, its peaks, its memory,
and a count of the programs compiled while a window runs."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The TPU devices a cell runs on; raises :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``);
    a device that is not in the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def describe(devs, trace=None) -> dict:
    """The result line's ``device``: as JAX reports it, the peak memory
    of the fullest chip, and with a trace its busy and window seconds."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace.busy_s
        out["window_s"] = trace.window_s
    return out


class CompileCounter:
    """Counts programs JAX lowers (compiled, or fetched from the
    persistent cache) while ``armed``: a program first met inside a
    measured window shows here."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name == self.EVENT:
            self.count += 1
