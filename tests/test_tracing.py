"""The program's own host spans (``repro.obs.trace``) under a profiler
trace on the CPU: a tiny serving engine stepping a few requests and a tiny
edge deployment whose RASK agent is past exploration, in one trace.

Checked: every span name appears; admissions and decode dispatches nest in
engine steps, and a control cycle's observe, pack, dispatch, collect and
apply nest in its drive; one admission span per admitted request and one
decode span per step; the attributes; the same token stream with the
profiler on and off. Also pinned: the compiled module names the benchmark
finds the programs by in a device trace."""
import dataclasses
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.core import RASKAgent, RaskConfig
from repro.env import EdgeEnvironment, paper_knowledge, paper_profiles
from repro.models import build
from repro.obs import trace
from repro.serve.engine import EngineConfig, Request, ServingEngine

N_REQUESTS = 5


def _engine():
    cfg = dataclasses.replace(get("gemma3-1b").smoke(), dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, EngineConfig(
        slots=2, max_seq=64, context=32, chips=4.0))
    return engine, cfg


def _serve(engine, cfg):
    """Seeded requests through to completion; each one's tokens."""
    rng = np.random.default_rng(0)
    reqs = [Request(rid, rng.integers(0, cfg.vocab, (5, 12, 20)[rid % 3],
                                      dtype=np.int32), max_new_tokens=3)
            for rid in range(N_REQUESTS)]
    for r in reqs:
        engine.submit(r)
    while engine.queue or engine.active:
        engine.step()
    return [list(r.generated) for r in reqs]


def _read(path: Path) -> list:
    """(name, start, end, stats) of every ``repro.*`` host event."""
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(trace.PREFIX):
                    out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine, cfg = _engine()
    tokens_off = _serve(engine, cfg)          # compiles; profiler off
    env = EdgeEnvironment(list(paper_profiles().values()), {"cores": 8.0},
                          seed=0)
    agent = RASKAgent(env.platform, paper_knowledge(),
                      RaskConfig(xi=3, stream_resync_every=2), seed=0)
    env.run(agent, 60.0)                      # explore, then first solves
    assert not agent.last_decision.explored
    steps0, done0 = engine.steps, len(engine.completed)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        tokens_on = _serve(engine, cfg)
        env.run(agent, 30.0)
    path = sorted(out.glob("**/*.xplane.pb"))[-1]
    return dict(spans=_read(path), tokens_off=tokens_off,
                tokens_on=tokens_on, steps=engine.steps - steps0,
                admitted=len(engine.completed) - done0, engine=engine,
                agent=agent)


def _of(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_every_span_appears_under_the_prefix(traced):
    assert all(name.startswith(trace.PREFIX) for name in trace.SPANS)
    assert {s[0] for s in traced["spans"]} == set(trace.SPANS)


def test_serving_spans_nest_in_steps_one_per_admission_and_step(traced):
    spans = traced["spans"]
    steps = _of(spans, trace.SERVE_STEP)
    admits = _of(spans, trace.SERVE_ADMIT)
    decodes = _of(spans, trace.SERVE_DECODE)
    assert len(steps) == len(decodes) == traced["steps"]
    assert len(admits) == traced["admitted"] == N_REQUESTS
    assert all(_inside(s, steps) for s in admits + decodes)


def test_control_cycle_spans_nest_in_drives(traced):
    spans = traced["spans"]
    drives = _of(spans, trace.ENV_DRIVE)
    assert len(drives) == 3
    for name in (trace.RASK_OBSERVE, trace.RASK_DECIDE, trace.RASK_PACK,
                 trace.RASK_DISPATCH, trace.RASK_COLLECT, trace.RASK_RESYNC,
                 trace.MUDAP_APPLY):
        assert _of(spans, name)
        assert all(_inside(s, drives) for s in _of(spans, name)), name
    assert len(_of(spans, trace.RASK_OBSERVE)) == 3
    assert len(_of(spans, trace.MUDAP_APPLY)) == 3
    # ticks (one per simulated second) and each cycle's fulfillment record
    # lie outside the drives
    ticks = _of(spans, trace.ENV_TICK)
    records = _of(spans, trace.ENV_RECORD)
    assert len(ticks) == 30 and len(records) == 3
    assert not any(_inside(t, drives) for t in ticks + records)


def test_attributes(traced):
    spans = traced["spans"]
    want = {trace.SERVE_STEP: {"active", "queued"},
            trace.SERVE_ADMIT: {"rid", "length", "bucket", "slot",
                                "wait_us"},
            trace.SERVE_DECODE: {"active"}, trace.ENV_TICK: {"t"},
            trace.ENV_DRIVE: {"round"}, trace.RASK_OBSERVE: {"rows"},
            trace.RASK_DECIDE: {"explored"}, trace.RASK_PACK: {"rows"},
            trace.RASK_DISPATCH: {"cold"}, trace.MUDAP_APPLY: {"changed"}}
    for name, keys in want.items():
        for s in _of(spans, name):
            assert keys <= set(s[3]), (name, s[3])
    admits = _of(spans, trace.SERVE_ADMIT)
    assert sorted(s[3]["rid"] for s in admits) == list(range(N_REQUESTS))
    assert all(s[3]["wait_us"] >= 0 for s in admits)
    assert all(s[3]["bucket"] >= s[3]["length"] > 0 for s in admits)
    assert all(s[3]["rows"] == 3 for s in _of(spans, trace.RASK_OBSERVE))
    assert all(s[3]["explored"] == 0 for s in _of(spans, trace.RASK_DECIDE))
    assert all(s[3]["changed"] > 0 for s in _of(spans, trace.MUDAP_APPLY))


def test_tokens_are_the_same_with_the_profiler_on_and_off(traced):
    assert traced["tokens_on"] == traced["tokens_off"]
    assert all(len(t) == 3 for t in traced["tokens_on"])


def _module(lowered) -> str:
    return re.search(r"module @([\w.]+)", lowered.as_text()).group(1)


def test_compiled_module_names(traced):
    """``bench/metrics/common.py`` finds the programs in a device trace by
    these names."""
    engine, agent = traced["engine"], traced["agent"]
    args = (engine.params, engine._cache, engine._last)
    assert _module(engine._step.lower(*args)) == "jit__step_fn"
    toks = np.zeros((1, 8), np.int32)
    assert _module(engine._admit_one.lower(
        *args, toks, np.int32(5), np.int32(1))) == "jit__admit_fn"
    k_cap = agent._fit_plan.delta_capacity(0)
    fn = agent._fused_fn(agent._fused_key(k_cap), k_cap)
    assert _module(fn._jit.lower(*agent._decide_avals(k_cap))) == "jit_core"
