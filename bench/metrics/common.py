"""Arithmetic the per-layer metric readers share. A reader returns None
when its run holds nothing to read, and the metric is then left out.
Every reader reads the traced part of the window only, where the host
spans, the device trace and the work counted all cover the same calls."""
from __future__ import annotations

from typing import Optional

DECODE_PROGRAM = "jit__step_fn"      # ServingEngine's decode step
PREFILL_PROGRAM = "jit__admit_fn"    # ServingEngine's prefill + slot insert
DECIDE_PROGRAM = "jit_core"          # RASKAgent's fused decide


def traced(run, name: str) -> list:
    """The harness's host spans of that name inside the traced part."""
    return [r for r in run.spans.of(name) if run.window.in_trace(r[1], r[2])]


def mean_span_ms(run, name: str) -> Optional[float]:
    spans = traced(run, name)
    if not spans:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in spans) / len(spans)


def call_work(run, span: str) -> list:
    """(operations, bytes) of each traced call of the served model's
    programs (span ``prefill`` or ``decode``)."""
    return [run.work.prefill(attrs["length"]) if span == "prefill"
            else run.work.decode_step(attrs["contexts"])
            for _, _, _, attrs in traced(run, span)]


def roofline(run, span: str, program: str) -> Optional[float]:
    """Sum of the traced calls' least times over the program's device
    time."""
    from bench import work
    if run.trace is None:
        return None
    calls = call_work(run, span)
    device_s, n = run.trace.module_s(program)
    if not calls or device_s <= 0 or n != len(calls):
        return None
    least = sum(work.least_time_s(f, b, run.peak) for f, b in calls)
    return 100.0 * least / device_s


def step_mfu(run) -> Optional[float]:
    steps = traced(run, "step")
    flops = sum(f for span in ("prefill", "decode")
                for f, _ in call_work(run, span))
    wall = sum(t1 - t0 for _, t0, t1, _ in steps)
    if wall <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (wall * run.peak["bf16_flops_per_s"])


def idle(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def idle_within(run, span: str) -> Optional[float]:
    if run.trace is None:
        return None
    spans = run.trace.spans(span)
    length = sum(b - a for a, b in spans) * 1e-9
    if length <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_within_s(spans) / length)


def decide_device_ms(run) -> Optional[float]:
    """Device time of the fused decide per traced control cycle."""
    if run.trace is None:
        return None
    cycles = len(run.trace.spans("drive"))
    device_s, n = run.trace.module_s(DECIDE_PROGRAM)
    if not cycles or n == 0:
        return None
    return 1e3 * device_s / cycles


def drive_ms(run) -> Optional[float]:
    """Mean host time of a traced control cycle's drive."""
    spans = run.trace.spans("drive") if run.trace is not None else []
    if not spans:
        return None
    return 1e-6 * sum(b - a for a, b in spans) / len(spans)
