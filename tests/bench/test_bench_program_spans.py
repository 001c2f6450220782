"""The readers of the program's own spans (bench/program_spans.py and the
per-layer metrics built on it): on intervals built by hand, and on short
traces recorded on a TPU v5e (xz-compressed), one of the served model
admitting and decoding, one of the edge deployment's control cycles."""
import lzma
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, program_spans, reduce
from bench.program_spans import OUTSIDE, ProgramSpans, Span

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("recorded")
    paths = {}
    for name in ("serve", "edge9"):
        path = out / f"{name}_spans.xplane.pb"
        path.write_bytes(lzma.decompress(
            (DATA / f"{name}_spans.xplane.pb.xz").read_bytes()))
        paths[name] = path
    return paths


def _hand():
    # window 0..100 ns; device busy 10-30 and 50-60; a step 0-80 holding an
    # admission 5-35 and a decode 40-70
    ops = {DEV: [("fusion.1", 10, 30), ("fusion.2", 50, 60)]}
    trace = reduce.Trace(ops, {DEV: []}, [("traced", 0, 100)])
    spans = [Span("repro.serve.step", 0, 80, {"active": 1}),
             Span("repro.serve.admit", 5, 35, {"wait_us": 1500}),
             Span("repro.serve.decode", 40, 70, {"active": 2}),
             Span("repro.serve.step", 90, 120, {})]      # past the window
    return ProgramSpans(spans, trace)


def test_only_spans_inside_the_window_are_kept():
    ps = _hand()
    assert [s.name for s in ps.spans] == ["repro.serve.step",
                                          "repro.serve.admit",
                                          "repro.serve.decode"]


def test_idle_time_inside_spans():
    ps = _hand()
    assert ps.busy_s(0, 80) == pytest.approx(30e-9)
    assert ps.idle_s(5, 35) == pytest.approx(10e-9)
    assert ps.idle_s(40, 70) == pytest.approx(20e-9)
    assert ps.idle_s(60, 100) == pytest.approx(40e-9)


def test_window_pieces_are_named_by_the_innermost_span():
    assert _hand().pieces() == [
        (0, 5, "repro.serve.step"), (5, 35, "repro.serve.admit"),
        (35, 40, "repro.serve.step"), (40, 70, "repro.serve.decode"),
        (70, 80, "repro.serve.step"), (80, 100, OUTSIDE)]


def test_idle_by_span_adds_up_to_the_window_idle_time():
    idle = _hand().idle_by_span()
    assert idle == pytest.approx({
        OUTSIDE: 20e-9, "repro.serve.decode": 20e-9,
        "repro.serve.step": 20e-9, "repro.serve.admit": 10e-9})
    assert sum(idle.values()) == pytest.approx(100e-9 - 30e-9)


def test_idle_gaps_are_named_by_program_spans():
    # gaps 60-100, 30-50 and 0-10, named at their midpoints 80, 40 and 5
    assert _hand().idle_gaps() == [
        [OUTSIDE, pytest.approx(40e-9)],
        ["repro.serve.decode", pytest.approx(20e-9)],
        ["repro.serve.admit", pytest.approx(10e-9)]]


def test_inside_needs_the_whole_span_in_a_parent():
    ps = _hand()
    steps = ps.of("repro.serve.step")
    assert ps.inside("repro.serve.admit", steps) == ps.of("repro.serve.admit")
    parents = [Span("repro.env.drive", 0, 20, {})]
    assert ps.inside("repro.serve.admit", parents) == []


def _run(path):
    return SimpleNamespace(trace=reduce.load(path),
                           window=SimpleNamespace(xplane=lambda: path))


def _reading(metric, path):
    return harness.metric_reader(metric)(_run(path))


def test_readers_find_nothing_in_a_trace_without_program_spans():
    old = DATA / "small_serve.xplane.pb"
    for metric in ("queue_wait_ms.chat", "step_idle_ms.chat",
                   "admit_idle_ms.docs", "observe_ms.decide"):
        assert _reading(metric, old) is None
    assert program_spans.of_run(SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("metric", ["queue_wait_ms.chat", "step_idle_ms.chat",
                                    "admit_idle_ms.docs"])
def test_serving_readers_on_the_recorded_trace(metric, recorded):
    value = _reading(metric, recorded["serve"])
    assert value is not None and math.isfinite(value) and value >= 0


@pytest.mark.parametrize("metric", ["observe_ms.decide", "pack_ms.decide",
                                    "apply_ms.decide", "sim_tick_ms.decide"])
def test_control_readers_on_the_recorded_trace(metric, recorded):
    value = _reading(metric, recorded["edge9"])
    assert value is not None and math.isfinite(value) and value > 0


# The profiler aligns the device's clock with the host's only to within
# about a millisecond: in the recorded serving trace a decode step starts
# 0.9 ms before the host span around its dispatch opens. Checks on the one
# clock allow this much, against programs of 16-46 ms.
CLOCK_SLACK_NS = 2e6


def _modules(trace, name):
    return sorted((a, b) for evs in trace.modules.values()
                  for n, a, b in evs if n == name)


def _each_inside(execs, spans) -> bool:
    """Each execution lies in one span, which lasts at least as long."""
    return all(sum(s.start - CLOCK_SLACK_NS <= a and b <= s.end +
                   CLOCK_SLACK_NS and s.end - s.start >= b - a
                   for s in spans) == 1 for a, b in execs)


def test_serving_programs_run_inside_their_spans(recorded):
    run = _run(recorded["serve"])
    ps = program_spans.of_run(run)
    steps = _modules(run.trace, "jit__step_fn")
    admits = _modules(run.trace, "jit__admit_fn")
    decodes, admissions = ps.of("repro.serve.decode"), \
        ps.of("repro.serve.admit")
    assert len(steps) == len(decodes) > 0
    assert len(admits) == len(admissions) > 0
    assert _each_inside(steps, decodes)
    assert _each_inside(admits, admissions)
    assert _each_inside(admits + steps, ps.of("repro.serve.step"))


def test_decide_program_lies_between_its_dispatch_and_collect(recorded):
    """The fused decide starts on the device after the host opened its
    dispatch span and ends before the host closed its collect span: host
    spans and device operations are on one clock, up to its alignment."""
    run = _run(recorded["edge9"])
    ps = program_spans.of_run(run)
    cores = _modules(run.trace, "jit_core")
    dispatches = ps.of("repro.rask.dispatch")
    collects = ps.of("repro.rask.collect")
    assert cores and len(cores) == len(dispatches) == len(collects)
    for (a, b), d, c in zip(cores, dispatches, collects):
        assert d.start - CLOCK_SLACK_NS <= a and b <= c.end + CLOCK_SLACK_NS
        assert d.end <= c.start and b - a <= c.end - d.start


def test_program_spans_tool_names_edge9_idle_gaps(recorded, capsys):
    """Between two drives the device idles longest while the cycle's
    fulfillment is measured, not in the simulated ticks."""
    assert program_spans.main([str(recorded["edge9"])]) == 0
    out = capsys.readouterr().out
    assert "repro.env.record" in out and "repro.env.tick" in out
    ps = program_spans.of_run(_run(recorded["edge9"]))
    (name, longest), *rest = ps.idle_gaps()
    assert name == "repro.env.record"
    assert all(secs * 10 < longest for n, secs in rest if n == "repro.env.tick")
    idle = ps.idle_by_span()
    assert max(idle, key=idle.get) == "repro.env.record"
    assert idle["repro.env.tick"] < idle["repro.env.record"] / 5


def test_program_spans_tool_usage():
    assert program_spans.main([]) == 2
