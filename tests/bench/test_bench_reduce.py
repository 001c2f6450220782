"""The reduction from a profiler trace to metrics (bench/reduce.py): on
intervals built by hand, and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import reduce

RECORDED = Path(__file__).resolve().parent / "data" / "small_serve.xplane.pb"
DEV = "/device:TPU:0"


def _trace():
    # window 0..100 ns; ops at 10-30 (two overlapping) and 50-60;
    # the host was in "decode" 5-35 and "prefill" 40-70
    ops = {DEV: [("fusion.1", 10, 25), ("fusion.2", 20, 30),
                 ("copy.3", 50, 60), ("late", 120, 130)]}
    modules = {DEV: [("jit__step_fn", 10, 30), ("jit__admit_fn", 50, 60),
                     ("jit__step_fn", 120, 130)]}
    host = [("traced", 0, 100), ("decode", 5, 35), ("prefill", 40, 70),
            ("step", 0, 100)]
    return reduce.Trace(ops, modules, host)


def test_busy_is_the_union_of_operations_inside_the_window():
    t = _trace()
    assert t.busy[DEV] == [(10, 30), (50, 60)]
    assert t.busy_s() == pytest.approx(30e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_program_time_by_module_name():
    t = _trace()
    assert reduce.module_name("jit__step_fn(17)") == "jit__step_fn"
    assert t.module_s("jit__step_fn") == (pytest.approx(20e-9), 1)
    assert t.module_s("jit__admit_fn") == (pytest.approx(10e-9), 1)
    assert t.module_names() == ["jit__admit_fn", "jit__step_fn"]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = _trace().idle_gaps()
    assert [g[0] for g in gaps] == ["step", "prefill", "decode"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 20e-9, 10e-9])


def test_busy_within_spans_and_breakdown():
    t = _trace()
    assert t.busy_within_s(t.spans("decode")) == pytest.approx(20e-9)
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(15e-9)]
    assert len(b["idle_gaps"]) == 3


def test_merge_and_clip():
    assert reduce.merge([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert reduce.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_recorded_trace():
    t = reduce.load(RECORDED)
    assert t.devices == [DEV]
    assert 0 < t.busy_s() < t.window_s
    step_s, steps = t.module_s("jit__step_fn")
    assert steps > 0 and 0 < step_s < t.window_s
    assert {"decode", "step"} <= {n for n, _, _ in t.host}
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) <= t.window_s - t.busy_s() + 1e-9
