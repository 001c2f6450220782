"""What every cell's run shares: loading a cell by name, host spans, the
profiler window, the device record, the per-layer metric readers and the
result line.

A cell is found by its name: ``bench/workloads/<cell>.json`` names its
configuration (``bench/configs/<config>.json``, whose ``kind`` picks the
module ``bench/systems/<kind>.py``) and its traffic
(``bench/traffic/<traffic>.json``, whose ``kind`` picks the generator
``bench/traffic/<kind>.py``). Which metrics a cell reports comes from
``BENCHMARK.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


class Cell:
    """One workload entry with its configuration and traffic, by name."""

    def __init__(self, name: str, benchmark: Optional[dict] = None):
        self.name = name
        self.spec = load_json(BENCH / "workloads" / f"{name}.json")
        self.config = load_json(BENCH / "configs" / f"{self.spec['config']}.json")
        self.traffic = load_json(BENCH / "traffic" / f"{self.spec['traffic']}.json")
        self.chips = int(self.spec["chips"])
        bench = benchmark if benchmark is not None else \
            load_json(ROOT / "BENCHMARK.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in e2e]

    def traffic_source(self, seed: int, seconds: float):
        mod = importlib.import_module(f"bench.traffic.{self.traffic['kind']}")
        return mod.Source(self.traffic, seed, seconds)

    def system(self):
        return importlib.import_module(f"bench.systems.{self.config['kind']}")


def metric_reader(name: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Spans:
    """Host-clock spans of the harness's own calls into the program; when
    the profiler runs they also land in its trace as ``bench.<name>``. A
    span is a ``with`` block, or opens at ``begin`` and closes at ``end``
    where the program's own call returns and its work is done."""

    def __init__(self):
        self.records: List[tuple] = []        # (name, t0, t1, attrs)
        self._open: Dict[str, tuple] = {}

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        import jax
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield attrs
            finally:
                self.records.append((name, t0, time.perf_counter(), attrs))

    def begin(self, name: str, **attrs) -> None:
        import jax
        if name in self._open:
            raise RuntimeError(f"span {name} opened twice")
        mark = jax.profiler.TraceAnnotation("bench." + name)
        mark.__enter__()
        self._open[name] = (time.perf_counter(), attrs, mark)

    def end(self, name: str) -> None:
        t0, attrs, mark = self._open.pop(name)
        t1 = time.perf_counter()
        mark.__exit__(None, None, None)
        self.records.append((name, t0, t1, attrs))

    def of(self, name: str) -> List[tuple]:
        return [r for r in self.records if r[0] == name]


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def device_record(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Window:
    """The measured window: host clock always, the profiler when traced.
    The profiler covers the window's first ``trace_seconds``, marked
    ``bench.traced``; the system calls ``tick``
    between its calls into the program so the trace stops between two of
    them. The trace of one run replaces the last one at a fixed path."""

    def __init__(self, trace: bool, trace_seconds: float):
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.t0 = self.t1 = 0.0
        self.traced = (0.0, 0.0)          # host clock bounds of the trace
        self._tracing = False

    def __enter__(self):
        import jax
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self._mark = jax.profiler.TraceAnnotation("bench.traced")
            self._mark.__enter__()
            self._tracing = True
        self.t0 = time.perf_counter()
        self.traced = (self.t0, self.t0)
        return self

    def tick(self) -> None:
        if self._tracing and \
                time.perf_counter() - self.t0 >= self.trace_seconds:
            self._stop()

    def _stop(self) -> None:
        import jax
        self.traced = (self.t0, time.perf_counter())
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._tracing:
            self._stop()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_trace(self, t0: float, t1: float) -> bool:
        """Whether a host span lies inside the traced part."""
        return self.traced[0] <= t0 and t1 <= self.traced[1]

    def xplane(self) -> Optional[Path]:
        found = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        return found[-1] if found else None


class Run:
    """What a per-layer metric reader sees: the host spans, the window, the
    reduced trace, and the cell's work model and peaks."""

    def __init__(self, cell: Cell, spans: Spans, window: Window, trace,
                 peak: dict, work=None):
        self.cell, self.spans, self.window = cell, spans, window
        self.trace, self.peak, self.work = trace, peak, work


def per_layer(run: Run) -> Dict[str, dict]:
    """Every per-layer metric the cell lists whose reader finds something."""
    out = {}
    for m in run.cell.per_layer:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """Print the numbers compared beside their limits as the last lines of
    standard error, and the result as the last line of standard output,
    with the checks under the key that comes last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line, allow_nan=False), flush=True)


def finite(x: float) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def finish(cell: Cell, out: dict, trace: bool) -> int:
    """Turn a system module's output into the result line; returns the exit
    code."""
    import jax  # noqa: F401  (the profiler reader needs it loaded)
    from bench import reduce, work
    device = dict(out["device"])
    counts = out["counts"]
    print("counts " + json.dumps(counts), file=sys.stderr)
    result = {"correct": all(finite(c["value"]) and c["value"] <= c["limit"]
                             for c in out["checks"].values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        path = out["window"].xplane()
        reduced = reduce.load(path) if path is not None else None
        run = Run(cell, out["spans"], out["window"], reduced,
                  work.peaks(device["kind"]), out.get("work"))
        result["metrics"] = per_layer(run)
        if reduced is not None:
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            result["breakdown"] = reduced.breakdown()
            print("programs " + json.dumps(
                {m: reduced.module_s(m) for m in reduced.module_names()}),
                file=sys.stderr)
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = out["e2e"].get(m["name"])
            if value is None or not finite(value):
                result["correct"] = False
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
    result["device"] = device
    emit(result, out["checks"])
    return 0
