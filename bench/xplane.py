"""Profiler traces in, the numbers the per-layer metrics read out.

:func:`capture` runs a callable under ``jax.profiler`` inside one host
span named ``WINDOW`` and reduces the ``.xplane.pb`` it writes with
:func:`load`.  Host and device events of one profile share a clock, so
the window span clips device events directly.

Device events come from each ``/device:TPU:<n>`` plane: line
``XLA Modules`` holds one event per program run (``jit_<name>(<id>)``)
and line ``XLA Ops`` one event per HLO op (``%<op>.<k> = ...``).  A
Pallas kernel is a custom-call op named after the function that holds
its ``pallas_call``.  Busy time is the union of a device's op intervals
inside the window, averaged over the devices.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

from bench import stats

WINDOW = "bench.window"
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?: =|$)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_name(event_name: str) -> str:
    """``'%arena_alloc_txn_blocked.1 = (s32[...]) custom-call(...)'`` →
    ``'arena_alloc_txn_blocked'``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def module_name(event_name: str) -> str:
    """``'jit_mega(12927695389309651671)'`` → ``'jit_mega'``."""
    return _MODULE.match(event_name).group(1)


@dataclasses.dataclass
class Trace:
    """One traced window, reduced.  Times in seconds from the window's
    start; ``ops``/``modules`` are per device."""
    window_s: float
    ops: List[List[Tuple[str, float, float]]]
    modules: List[List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        per = [stats.union_length((s, e) for _, s, e in dev)
               for dev in self.ops]
        return sum(per) / len(per)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, name: str, inside: Optional[str] = None
                   ) -> Tuple[float, int]:
        """Total device seconds and count of ops named ``name``
        (optionally only inside runs of module ``inside``), averaged
        over the devices."""
        tot, cnt = 0.0, 0
        for d, dev in enumerate(self.ops):
            spans = ([(s, e) for m, s, e in self.modules[d] if m == inside]
                     if inside else None)
            for nm, s, e in dev:
                if nm == name and (spans is None or _within(s, spans)):
                    tot += e - s
                    cnt += 1
        n = len(self.ops)
        return tot / n, cnt // n

    def module_seconds(self, name: str) -> Tuple[float, int]:
        """Device seconds and run count of module ``name``, averaged."""
        tot = sum(e - s for dev in self.modules for m, s, e in dev
                  if m == name)
        cnt = sum(1 for dev in self.modules for m, *_ in dev if m == name)
        n = len(self.modules)
        return tot / n, cnt // n

    def op_busy_outside(self, module: str) -> float:
        """Seconds in which an op ran outside every run of ``module``,
        averaged over the devices."""
        per = []
        for d, dev in enumerate(self.ops):
            spans = sorted((s, e) for m, s, e in self.modules[d]
                           if m == module)
            per.append(stats.union_length(
                (s, e) for _, s, e in dev if not _within(s, spans)))
        return sum(per) / len(per)

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the longest idle gaps
        named by the innermost host span around their middle.  An op
        that holds others (a ``while`` around its body) counts only its
        time outside them, so no second is counted twice."""
        dur: Dict[str, float] = collections.Counter()
        for dev in self.ops:
            for nm, t in _self_times(dev):
                dur[nm] += t / len(self.ops)
        ops = sorted(dur.items(), key=lambda kv: -kv[1])[:top]
        idle = []
        for dev in self.ops[:1]:
            for s, e in stats.gaps(((a, b) for _, a, b in dev), 0.0,
                                   self.window_s):
                idle.append((self.host_at((s + e) / 2), e - s))
        by: Dict[str, float] = collections.Counter()
        for nm, d in idle:
            by[nm] += d
        gaps_ = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in gaps_]}

    def host_at(self, t: float) -> str:
        """Name of the shortest host span of the benchmark that covers
        ``t`` (``"none"`` where none does)."""
        best = None
        for nm, s, e in self.host:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (nm, e - s)
        return best[0] if best else "none"


def _self_times(ops):
    """``(name, seconds)`` of each op less the time of the ops nested in
    it (events of one device line nest, they never partly overlap)."""
    out, stack = [], []            # stack: [name, end, self time]
    for nm, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([nm, e, e - s])
    out.extend(tuple(x[::2]) for x in stack)
    return out


def _within(t: float, spans) -> bool:
    for s, e in spans:
        if s <= t < e:
            return True
    return False


def load(path: str, window: str = WINDOW,
         host_prefix: str = "bench.") -> Trace:
    """Reduce one ``.xplane.pb`` to a :class:`Trace` clipped to the
    (last) host span named ``window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, ops, mods = [], [], []
    win = None
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name == window:
                        win = (s, e)
                    elif ev.name.startswith(host_prefix):
                        host.append((ev.name, s, e))
    if win is None:
        raise ValueError(f"{path}: no host span {window!r}")
    lo, hi = win
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev_ops, dev_mods = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo) - lo, min(e, hi) - lo
                if line.name == "XLA Ops":
                    dev_ops.append((op_name(ev.name), s, e))
                else:
                    dev_mods.append((module_name(ev.name), s, e))
        ops.append(dev_ops)
        mods.append(dev_mods)
    if not ops:
        raise ValueError(f"{path}: no TPU device plane")
    host = [(n, max(s, lo) - lo, min(e, hi) - lo) for n, s, e in host
            if e > lo and s < hi]
    return Trace(window_s=hi - lo, ops=ops, modules=mods, host=host)


def capture(fn: Callable[[], None]) -> Trace:
    """Run ``fn`` traced inside the window span; returns the reduction.
    The raw trace goes to a temporary directory (under ``TMPDIR``) that
    is removed."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        return load(path)
    finally:
        shutil.rmtree(d, ignore_errors=True)
