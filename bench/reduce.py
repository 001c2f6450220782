"""From a profiler trace (``.xplane.pb``) to the numbers the metrics use.

* busy time: the union of the intervals in which an operation ran on each
  device, inside the harness's ``bench.traced`` span (the traced part of
  the window), averaged over the devices;
* device time per jitted program, found by its module name without the
  ``(<id>)`` suffix the runtime appends (``jit__step_fn``, ``jit_core``);
* the device operations that took most time;
* the longest idle gaps, each named by the innermost harness span
  (``bench.<name>``) that the host was in at the gap's midpoint.

Device planes are ``/device:<platform>:<n>``; operations are the events of
their ``XLA Ops`` line and programs those of their ``XLA Modules`` line.
Host spans are events named ``bench.*`` on any host plane. Both sides are
on the profiler's one clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name.strip())


def op_name(event_name: str) -> str:
    """An operation's HLO text cut to its name and result type
    (``%fusion.4 = bf16[64,1,3072,8,128]``)."""
    return event_name.split("{", 1)[0].strip()[:120]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(busy: Sequence[Interval], spans: Iterable[Interval]) -> float:
    """Time (same unit) in which ``busy`` (merged) covers ``spans``."""
    return sum(total(clip(busy, a, b)) for a, b in spans)


class Trace:
    """The reduced trace. Times are nanoseconds on the profiler's clock."""

    def __init__(self, ops: Dict[str, List[tuple]],
                 modules: Dict[str, List[tuple]], host: List[tuple]):
        # per device: ops (name, start, end) and modules (name, start, end);
        # host spans (name, start, end) without the "bench." prefix
        self.ops, self.modules, self.host = ops, modules, host
        wins = [(a, b) for n, a, b in host if n == "traced"]
        if wins:
            self.window = wins[0]
        else:
            starts = [a for evs in ops.values() for _, a, _ in evs]
            ends = [b for evs in ops.values() for _, _, b in evs]
            self.window = (min(starts, default=0.0), max(ends, default=0.0))
        lo, hi = self.window
        self.busy = {d: merge(clip(((a, b) for _, a, b in evs), lo, hi))
                     for d, evs in ops.items()}

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(total(b) for b in self.busy.values()) \
            / len(self.busy) * 1e-9

    def spans(self, name: str) -> List[Interval]:
        """Host spans of that name inside the traced window."""
        lo, hi = self.window
        return [(a, b) for n, a, b in self.host
                if n == name and a >= lo and b <= hi]

    def busy_within_s(self, spans: Sequence[Interval]) -> float:
        """Seconds of ``spans`` in which the device was busy (mean over
        devices)."""
        if not self.busy:
            return 0.0
        return sum(overlap(b, spans) for b in self.busy.values()) \
            / len(self.busy) * 1e-9

    def module_s(self, name: str) -> Tuple[float, int]:
        """(seconds, executions) of a jitted program inside the window,
        averaged over the devices."""
        lo, hi = self.window
        secs, count = 0.0, 0
        for evs in self.modules.values():
            for n, a, b in evs:
                if n == name and a >= lo and b <= hi:
                    secs += (b - a) * 1e-9
                    count += 1
        n_dev = max(len(self.modules), 1)
        return secs / n_dev, count // n_dev

    def module_names(self) -> List[str]:
        return sorted({n for evs in self.modules.values() for n, _, _ in evs})

    def top_ops(self, k: int = 10) -> List[list]:
        lo, hi = self.window
        acc: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for n, a, b in evs:
                if b > lo and a < hi:
                    acc[n] += (min(b, hi) - max(a, lo)) * 1e-9
        n_dev = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s / n_dev] for n, s in top]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle stretches inside the window, on the first
        device, each named by what the host was doing in it."""
        if not self.busy:
            return []
        busy = self.busy[self.devices[0]]
        lo, hi = self.window
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:k]]

    def host_at(self, t: float) -> str:
        """The innermost harness span (other than the window) around t."""
        best: Optional[tuple] = None
        for n, a, b in self.host:
            if n != "traced" and a <= t <= b and \
                    (best is None or b - a < best[2] - best[1]):
                best = (n, a, b)
        return best[0] if best else "outside spans"

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def load(path) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[tuple]] = {}
    modules: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(op_name(e.name), e.start_ns, e.end_ns)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        (module_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name[len("bench."):], e.start_ns,
                                     e.end_ns))
    return Trace(ops, modules, host)

