"""The program's own host spans in a traced run: the events named
``repro.*`` that ``repro.obs.trace`` writes, read from the same
``.xplane.pb`` as the device's operations and so on the same clock.

Only spans wholly inside the traced window (``bench.traced``) are kept,
each with its attributes. Device-idle time is the window's time not covered
by an operation (``bench/reduce.py``'s busy intervals), averaged over the
devices. On a trace of a program that writes no such spans every reading is
None.

    python3 -m bench.program_spans <trace.xplane.pb>

prints the window's device-idle seconds grouped by the innermost program
span the host was in, with what lies outside every program span, and the
longest idle gaps named the same way.
"""
from __future__ import annotations

import bisect
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence

from bench import reduce

PREFIX = "repro."
OUTSIDE = "outside program spans"


class Span(NamedTuple):
    name: str
    start: float            # ns, on the profiler's clock
    end: float
    attrs: dict


class ProgramSpans:
    """A trace's program spans inside its window, against its busy time."""

    def __init__(self, spans: Sequence[Span], trace: reduce.Trace):
        lo, hi = trace.window
        self.trace = trace
        self.spans = sorted((s for s in spans if lo <= s.start and
                             s.end <= hi), key=lambda s: (s.start, -s.end))
        self._busy = {d: (b, [a for a, _ in b], [e for _, e in b])
                      for d, b in trace.busy.items()}

    def of(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def busy_s(self, a: float, b: float) -> float:
        """Seconds of (a, b) in which an operation ran, mean over devices."""
        if not self._busy:
            return 0.0
        got = 0.0
        for busy, starts, ends in self._busy.values():
            i0 = bisect.bisect_right(ends, a)
            i1 = bisect.bisect_left(starts, b)
            got += reduce.overlap(busy[i0:i1], [(a, b)])
        return got / len(self._busy) * 1e-9

    def idle_s(self, a: float, b: float) -> float:
        return (b - a) * 1e-9 - self.busy_s(a, b)

    def inside(self, name: str, parents: Sequence[Span]) -> List[Span]:
        """Spans of that name each wholly inside one of ``parents`` (spans
        that do not overlap one another, in order)."""
        starts = [p.start for p in parents]
        out = []
        for s in self.of(name):
            i = bisect.bisect_right(starts, s.start) - 1
            if i >= 0 and s.end <= parents[i].end:
                out.append(s)
        return out

    def pieces(self) -> List[tuple]:
        """(start, end, name) pieces of the window, each named by the
        innermost program span open over it, or ``OUTSIDE``."""
        lo, hi = self.trace.window
        out, stack, cur = [], [], lo

        def upto(t):
            nonlocal cur
            if t > cur:
                out.append((cur, t, stack[-1].name if stack else OUTSIDE))
                cur = t
        for s in self.spans:
            while stack and stack[-1].end <= s.start:
                upto(stack[-1].end)
                stack.pop()
            upto(s.start)
            stack.append(s)
        while stack:
            upto(stack[-1].end)
            stack.pop()
        upto(hi)
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """Device-idle seconds of the window by innermost program span."""
        acc: Dict[str, float] = defaultdict(float)
        for a, b, name in self.pieces():
            acc[name] += self.idle_s(a, b)
        return dict(sorted(acc.items(), key=lambda kv: -kv[1]))

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle stretches on the first device, each named
        by the innermost program span at its midpoint."""
        if not self.trace.busy:
            return []
        busy = self.trace.busy[self.trace.devices[0]]
        lo, hi = self.trace.window
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = sorted(((edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
        pieces = self.pieces()
        starts = [p[0] for p in pieces]
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) / 2
            name = pieces[max(bisect.bisect_right(starts, mid) - 1, 0)][2]
            out.append([name, (b - a) * 1e-9])
        return out


def read_spans(path) -> List[Span]:
    """Every ``repro.*`` event on the host planes of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns, e.end_ns,
                                    dict(e.stats)))
    return out


_LOADED: Dict[tuple, List[Span]] = {}


def of_run(run) -> Optional[ProgramSpans]:
    """The program spans of a traced run (the trace is parsed once per
    file), or None without a trace or without program spans."""
    if run.trace is None:
        return None
    path = run.window.xplane()
    if path is None:
        return None
    key = (str(path), Path(path).stat().st_mtime_ns)
    if key not in _LOADED:
        _LOADED[key] = read_spans(path)
    found = ProgramSpans(_LOADED[key], run.trace)
    return found if found.spans else None


# -- what the per-layer readers compute ---------------------------------------

def _traced(run, name: str):
    """The run's program spans, and those of that name (empty without)."""
    ps = of_run(run)
    return ps, (ps.of(name) if ps is not None else [])


def mean_attr(run, name: str, attr: str) -> Optional[float]:
    """Mean of an attribute over the traced spans of that name."""
    _, spans = _traced(run, name)
    vals = [s.attrs[attr] for s in spans if attr in s.attrs]
    return sum(vals) / len(vals) if vals else None


def mean_ms(run, name: str) -> Optional[float]:
    """Mean length of the traced spans of that name, in ms."""
    _, spans = _traced(run, name)
    if not spans:
        return None
    return 1e-6 * sum(s.end - s.start for s in spans) / len(spans)


def mean_idle_ms(run, name: str) -> Optional[float]:
    """Mean device-idle time inside each traced span of that name, in ms."""
    ps, spans = _traced(run, name)
    if not spans:
        return None
    return 1e3 * sum(ps.idle_s(s.start, s.end) for s in spans) / len(spans)


def per_cycle_ms(run, names: Sequence[str],
                 cycle: str = "repro.env.drive") -> Optional[float]:
    """Total length of the spans of ``names`` inside the traced control
    cycles (``cycle`` spans), per cycle, in ms."""
    ps, cycles = _traced(run, cycle)
    if not cycles:
        return None
    total = sum(s.end - s.start for n in names for s in ps.inside(n, cycles))
    return 1e-6 * total / len(cycles)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m bench.program_spans <trace.xplane.pb>",
              file=sys.stderr)
        return 2
    trace = reduce.load(argv[0])
    ps = ProgramSpans(read_spans(argv[0]), trace)
    idle = trace.window_s - trace.busy_s()
    print(f"window {trace.window_s:.6f} s, device idle {idle:.6f} s, "
          f"{len(ps.spans)} program spans")
    print("device-idle seconds by innermost program span:")
    for name, secs in ps.idle_by_span().items():
        print(f"  {name:32s} {secs:.6f}")
    print("longest idle gaps:")
    for name, secs in ps.idle_gaps():
        print(f"  {name:32s} {secs:.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
