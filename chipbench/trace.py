"""Reduction of a profiler trace (`.xplane.pb`) to device time.

Device planes are those named `/device:TPU:<n>`. On each, the line
`XLA Ops` holds one event per device operation and the line
`XLA Modules` one event per run of a compiled program, named after the
jitted function (`jit_<name>(<id>)`). Host spans are the
`TraceAnnotation` events on the `/host:CPU` plane whose names start with
`chipbench.`; the profiler puts host and device events on one clock.

Everything below works on plain (start_ns, duration_ns) pairs so that
it can be checked on a synthetic event list.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "chipbench."


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Iterable[Interval]) -> float:
    """Length of the union of `intervals`."""
    return sum(e - s for s, e in union(intervals))


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no merged interval covers."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def innermost(spans: Sequence[Tuple[str, float, float]], t: float
              ) -> Optional[str]:
    """Name of the shortest span that contains time t."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def module_name(event_name: str) -> str:
    """`jit_fleet_drift(12)` -> `jit_fleet_drift`."""
    return event_name.split("(")[0].strip()


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[8]{0} fusion(...)` -> `%fusion.12`: the trace
    names a device operation by its whole HLO instruction."""
    return event_name.split(" = ")[0].strip()


class Trace:
    """What the reduction needs from one trace: per device the op and
    module events, and the benchmark's host spans."""

    def __init__(self, ops: Dict[str, List[Tuple[str, float, float]]],
                 modules: Dict[str, List[Tuple[str, float, float]]],
                 spans: List[Tuple[str, float, float]]):
        self.ops = ops          # device -> [(name, start_ns, end_ns)]
        self.modules = modules  # device -> [(name, start_ns, end_ns)]
        self.spans = spans      # [(name, start_ns, end_ns)] host spans

    @classmethod
    def load(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        pd = ProfileData.from_file(files[-1])
        ops, modules, spans = {}, {}, []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        ops[plane.name] = evs
                    elif line.name == "XLA Modules":
                        modules[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                               e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        return cls(ops, modules, spans)

    # -- the window ---------------------------------------------------------
    def window(self, name: str = "loop") -> Interval:
        """The host span `name` (the timed loop), as (start, end)."""
        found = [(s, e) for n, s, e in self.spans if n == name]
        if not found:
            raise ValueError(f"no host span {name!r} in the trace")
        return min(s for s, _ in found), max(e for _, e in found)

    def _busy(self, device: str, lo: float, hi: float) -> List[Interval]:
        evs = self.ops.get(device) or self.modules.get(device) or []
        return union(clip([(s, e) for _, s, e in evs], lo, hi))

    def devices(self) -> List[str]:
        return sorted(set(self.ops) | set(self.modules))

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(covered(self._busy(d, lo, hi)) for d in devs) \
            / len(devs) / 1e9

    def module_time(self, module: str, lo: float, hi: float
                    ) -> Tuple[float, int]:
        """(seconds, runs) of compiled program `module` on all devices."""
        secs, runs = 0.0, 0
        for evs in self.modules.values():
            for name, s, e in evs:
                if module_name(name) == module and e > lo and s < hi:
                    secs += (min(e, hi) - max(s, lo)) / 1e9
                    runs += 1
        return secs, runs

    def top_ops(self, lo: float, hi: float, k: int = 10
                ) -> List[Tuple[str, float]]:
        """The k device operations with the most time, as
        [module/op, seconds], summed over devices."""
        mods = {d: sorted((s, e, module_name(n)) for n, s, e in evs)
                for d, evs in self.modules.items()}
        tot: Dict[str, float] = {}
        for dev, evs in self.ops.items():
            spans = mods.get(dev, [])
            for name, s, e in clip_named(evs, lo, hi):
                mod = _containing(spans, s)
                key = f"{mod}/{op_name(name)}" if mod else op_name(name)
                tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, lo: float, hi: float, k: int = 10
                  ) -> List[Tuple[str, float]]:
        """The k longest stretches with no device operation on the first
        device, named by the innermost host span open at their middle."""
        devs = self.devices()
        if not devs:
            return []
        out = []
        for s, e in gaps(self._busy(devs[0], lo, hi), lo, hi):
            name = innermost(self.spans, 0.5 * (s + e)) or "outside spans"
            out.append((name, (e - s) / 1e9))
        return sorted(out, key=lambda kv: -kv[1])[:k]


def clip_named(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def _containing(spans: Sequence[Tuple[float, float, str]], t: float
                ) -> Optional[str]:
    """Name of the module run (sorted by start) that contains time t."""
    import bisect
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][1]:
        return spans[i][2]
    return None
