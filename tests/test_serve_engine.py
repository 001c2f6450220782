"""Continuous-batching engine + elasticity hooks + the served-LM service.

Invariants under test (ISSUE 10):
 * requests complete, chip budget gates admission, context truncates (seed);
 * admission never exceeds the chip-scaled token budget; slots free on
   completion;
 * dict-cache and stacked engines emit identical token streams on seeded
   runs (the stacked path is an optimization, not a semantic change):
   mixed lengths, lanes far apart, a lane writing at max_seq - 1, a freed
   lane decoding while another slot admits, and the hybrid and ssm
   families;
 * bucketed prefill traces once per power-of-two bucket and the decode step
   traces once, total — zero steady-state recompiles;
 * the opt-in Pallas decode-attention path matches the reference stream in
   interpret mode, on the same runs;
 * ``ServedLMService`` telemetry is measured — its profile's analytic
   ``tp_max`` is never called.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.core.platform import MUDAP
from repro.core.regression import TRACE_COUNTS
from repro.models import build
from repro.serve import bucket_length, run_serving_loop
from repro.serve.engine import DictCacheEngine, EngineConfig, Request, \
    ServingEngine
from repro.serve.service import ServedLMService, served_lm_profile


def _model(attn_impl="reference"):
    cfg = dataclasses.replace(get("gemma3-1b").smoke(), dtype="float32",
                              attn_impl=attn_impl)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def make_engine(slots=2, chips=4.0, cls=ServingEngine, attn_impl="reference"):
    model, params, cfg = _model(attn_impl)
    return cls(model, params, EngineConfig(
        slots=slots, max_seq=64, context=32, chips=chips)), cfg


def _requests(cfg, n, lengths, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(0, cfg.vocab,
                                      lengths[rid % len(lengths)],
                                      dtype=np.int64).astype(np.int32),
                    max_new_tokens=max_new) for rid in range(n)]


def test_requests_complete():
    engine, cfg = make_engine()
    for req in _requests(cfg, 5, [16]):
        engine.submit(req)
    for _ in range(40):
        engine.step()
        if len(engine.completed) == 5:
            break
    assert len(engine.completed) == 5
    assert all(len(r.generated) == 4 for r in engine.completed)


def test_chip_budget_gates_admission():
    engine, cfg = make_engine(chips=0.1)    # budget 6 tokens/step
    rng = np.random.default_rng(0)
    engine.submit(Request(0, rng.integers(0, cfg.vocab, 16).astype(np.int32)))
    engine.step()
    assert len(engine.active) == 0          # prompt of 16 > budget
    engine.apply("chips", 4.0)
    engine.step()
    assert len(engine.active) == 1


def test_context_truncation():
    engine, cfg = make_engine()
    engine.apply("context", 8)
    rng = np.random.default_rng(0)
    engine.submit(Request(0, rng.integers(0, cfg.vocab, 30).astype(np.int32),
                          max_new_tokens=6))
    engine.step()
    assert len(engine.active) == 1          # admitted after truncation to 8
    m = engine.metrics()
    assert m["context"] == 8.0


# -- ISSUE 10: continuous-batching invariants ---------------------------------

@pytest.mark.parametrize("cls", [ServingEngine, DictCacheEngine])
def test_admission_never_exceeds_token_budget(cls):
    """Per step, the sum of admitted (post-truncation) prompt lengths must
    stay within ``chips * tokens_per_chip_step``."""
    engine, cfg = make_engine(slots=4, chips=0.5, cls=cls)   # budget 32
    budget = int(engine.cfg.chips * engine.cfg.tokens_per_chip_step)
    for req in _requests(cfg, 12, [10, 20, 30], max_new=3, seed=1):
        engine.submit(req)
    prev = engine.prompt_tokens_in
    for _ in range(60):
        engine.step()
        admitted_this_step = engine.prompt_tokens_in - prev
        assert admitted_this_step <= budget
        prev = engine.prompt_tokens_in
        if len(engine.completed) == 12:
            break
    assert len(engine.completed) == 12


def test_slots_free_on_completion():
    engine, cfg = make_engine(slots=2)
    for req in _requests(cfg, 4, [12], max_new=2):
        engine.submit(req)
    engine.step()                       # admits 2, each produces token #2
    assert len(engine.active) == 0      # max_new=2 reached -> slots freed
    assert len(engine.completed) == 2
    engine.step()                       # freed slots admit the next two
    assert len(engine.completed) == 4
    assert engine.queue == []


# Request plans: (slots, [(step it is submitted at, prompt length, new
# tokens)]). ``mixed``: the seed run. ``spread``: lanes at widely different
# positions. ``full``: a lane writes its last row at max_seq - 1 (prompt 32
# + 32 decode steps, max_seq 64). ``refill``: slot 2 frees and keeps
# decoding while a late request is admitted into slot 0.
PLANS = {
    "mixed": (3, [(0, n, 5) for n in [7, 13, 19, 26] * 2]),
    "spread": (3, [(0, 30, 24), (0, 2, 24), (0, 16, 24)]),
    "full": (2, [(0, 32, 33), (0, 5, 10)]),
    "refill": (3, [(0, 6, 3), (0, 20, 14), (0, 9, 5), (6, 11, 6)]),
}


def _serve(engine, cfg, plan, seed=2):
    """Run ``plan`` on ``engine``, each request submitted at its step; the
    token streams by request id."""
    rng = np.random.default_rng(seed)
    reqs = [(at, Request(rid, rng.integers(0, cfg.vocab, n, dtype=np.int64)
                         .astype(np.int32), max_new_tokens=m))
            for rid, (at, n, m) in enumerate(plan)]
    for step in range(200):
        for at, req in reqs:
            if at == step:
                engine.submit(req)
        engine.step()
        if len(engine.completed) == len(reqs):
            break
    assert len(engine.completed) == len(reqs)
    return {r.rid: list(r.generated) for r in engine.completed}


@pytest.mark.parametrize("plan", list(PLANS))
def test_dict_and_stacked_streams_identical(plan):
    """Seeded run: the stacked engine must reproduce the dict engine's token
    streams bit-for-bit (float32, same params)."""
    slots, reqs = PLANS[plan]
    streams = {}
    for cls in (DictCacheEngine, ServingEngine):
        engine, cfg = make_engine(slots=slots, chips=4.0, cls=cls)
        streams[cls.__name__] = _serve(engine, cfg, reqs)
    assert streams["DictCacheEngine"] == streams["ServingEngine"]


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "mamba2-370m"])
def test_recurrent_family_streams_identical(arch):
    """The hybrid and ssm families through the stacked engine's slot
    stacking: the dict engine's exact streams."""
    cfg = get(arch).smoke()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    slots, reqs = PLANS["refill"]
    streams = {}
    for cls in (DictCacheEngine, ServingEngine):
        engine = cls(model, params, EngineConfig(slots=slots, max_seq=64,
                                                 context=32, chips=4.0))
        streams[cls.__name__] = _serve(engine, cfg, reqs)
    assert streams["DictCacheEngine"] == streams["ServingEngine"]


def test_prefill_traces_once_per_bucket():
    """The seed bug: exact-length prefill retraced per distinct prompt
    length. Bucketed prefill must trace once per power-of-two bucket, and
    the decode step once in total — zero steady-state recompiles."""
    engine, cfg = make_engine(slots=4)
    lengths = [5, 7, 12, 20, 9, 31, 6, 17]      # buckets: 8, 16, 32
    n_buckets = len({bucket_length(n, engine.cfg.max_seq) for n in lengths})
    assert n_buckets == 3
    before_p = TRACE_COUNTS["serve_prefill"]
    before_d = TRACE_COUNTS["serve_decode_step"]
    for req in _requests(cfg, len(lengths), lengths, max_new=3, seed=3):
        engine.submit(req)
    for _ in range(60):
        engine.step()
        if len(engine.completed) == len(lengths):
            break
    assert len(engine.completed) == len(lengths)
    assert TRACE_COUNTS["serve_prefill"] - before_p == n_buckets
    assert TRACE_COUNTS["serve_decode_step"] - before_d == 1


@pytest.mark.parametrize("plan", list(PLANS))
def test_pallas_interpret_stream_parity(plan):
    """The opt-in Pallas decode-attention route under the vmapped stacked
    step must emit the reference engine's exact token stream."""
    slots, reqs = PLANS[plan]
    streams = {}
    for impl in ("reference", "pallas_interpret"):
        engine, cfg = make_engine(slots=slots, attn_impl=impl)
        streams[impl] = _serve(engine, cfg, reqs)
    assert streams["reference"] == streams["pallas_interpret"]


# -- ISSUE 10: measured telemetry, no analytic curve --------------------------

def test_served_service_never_calls_profile_curve(monkeypatch):
    """The served LM's telemetry must be measured: its profile's tp_max is a
    booby trap, and even a spy replacing it must see zero calls through a
    full platform loop (register + pump + scrape + metrics)."""
    prof = served_lm_profile()
    with pytest.raises(RuntimeError):
        prof.tp_max({"chips": 1.0, "context": 32.0, "rung": 3.0})

    calls = []
    spied = dataclasses.replace(
        prof, tp_max=lambda p: calls.append(p) or 1.0)
    base = dataclasses.replace(get("gemma3-1b").smoke(), dtype="float32")
    svc = ServedLMService(build, base, profile=spied, slots=2, max_seq=64,
                          seed=0, rps=2.0, max_new_tokens=3)
    plat = MUDAP({"chips": 4.0})
    plat.register(svc.sid, spied.api, svc, list(spied.slos),
                  dict(spied.defaults))
    hist = run_serving_loop(plat, {str(svc.sid): lambda t: 2.0},
                            duration_s=12.0, cycle_s=10.0)
    assert calls == []
    m = plat.latest_metrics(str(svc.sid))
    assert m["throughput"] > 0.0            # real requests really completed
    assert m["step_latency_ms"] > 0.0       # measured wall-clock latency
    assert hist and hist[0].per_service


def test_served_service_elasticity_mapping():
    """chips/context/rung land on admission budget, truncation and the
    engine rung; a rung switch requeues in-flight work on the new engine."""
    base = dataclasses.replace(get("gemma3-1b").smoke(), dtype="float32")
    svc = ServedLMService(build, base, slots=2, max_seq=64, seed=1,
                          rps=3.0, max_new_tokens=4)
    svc.advance(1.0)
    eng3 = svc._engine()
    assert eng3.cfg.rung == 3
    svc.apply("chips", 2.0)
    svc.apply("context", 12)
    assert eng3.cfg.chips == 2.0 and eng3.cfg.context == 12
    pending = len(eng3.active) + len(eng3.queue)
    svc.apply("rung", 2)
    eng2 = svc._engine()
    assert eng2 is not eng3 and eng2.cfg.rung == 2
    assert eng2.model.cfg.d_model < eng3.model.cfg.d_model
    assert len(eng3.active) == 0            # old rung's work requeued
    assert len(eng2.queue) + len(eng2.active) >= pending
    svc.advance(2.0)
    assert svc.metrics()["rung"] == 2.0
