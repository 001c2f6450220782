"""Streaming device-resident fit engine (ISSUE 8 acceptance gates).

Two layers of parity for the Gram-accumulator fit:

* **Gram-system parity** (gate: 1e-5 relative) — the incrementally
  maintained ``Phi^T Phi`` / ``Phi^T y`` must match the exact recompute
  from the device ring across arbitrary append/evict interleavings.  This
  is where the streaming engine can actually diverge (rank-k add/subtract
  drift, ring slot bookkeeping, eviction masks).
* **Prediction parity** (gate: conditioning-aware) — the ridge solve
  amplifies accumulator-level epsilon by the condition number of the
  normal equations, so the fitted-surface gate runs on well-conditioned
  configurations (ridge >= 1e-4, enough rows per term).  Raw weights are
  deliberately not compared; see test_batched_engine.py for the same
  policy on the batch path.

The seed-parametrized tests are tier-1; the hypothesis property at the
bottom widens the interleaving space where the optional dep is present
(same policy as test_batched_placement.py).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.regression import (BatchedFitPlan, GramFit, TRACE_COUNTS,
                                   pad_capacity)
from repro.core.telemetry import TrainingTable


def _plan(rng, n_rel, cap, ridge=1e-4):
    rels = []
    for _ in range(n_rel):
        f = int(rng.integers(1, 4))
        rels.append(dict(n_features=f, degree=int(rng.integers(1, 3)),
                         x_scale=rng.uniform(0.5, 8.0, f).tolist(),
                         target="tp_max"))
    return BatchedFitPlan(rels, row_capacity=cap, ridge=ridge)


def _rows(rng, plan, i, n):
    f = plan.labels[i][5]              # per-relation feature count
    X = rng.uniform(0.1, 8.0, (n, f)).astype(np.float32)
    coef = rng.uniform(-2, 2, f)
    Y = ((X * coef).sum(axis=1) ** 2 + rng.normal(0, 0.1, n)).astype(
        np.float32)
    return X, Y


def _interleaved_push(rng, plan, n_total):
    """Push ``n_total`` rows per relation in random-size chunks (some empty:
    a relation can sit a cycle out), returning the final state and the full
    per-relation row history."""
    state = plan.stream_init()
    hist = [(_rows(rng, plan, i, n_total)) for i in range(plan.n_relations)]
    done = [0] * plan.n_relations
    while min(done) < n_total:
        deltas = []
        for i in range(plan.n_relations):
            k = int(rng.integers(0, 4))
            k = min(k, n_total - done[i])
            X, Y = hist[i]
            deltas.append((X[done[i]:done[i] + k], Y[done[i]:done[i] + k]))
            done[i] += k
        state = plan.stream_push(state, deltas)
    return state, hist


def _gram_rel_diff(plan, state):
    """Incremental vs exact-recompute Gram system: max relative diff."""
    exact = plan.stream_resync(state)
    dg = float(jnp.max(jnp.abs(state.gram - exact.gram)))
    db = float(jnp.max(jnp.abs(state.xty - exact.xty)))
    span = max(float(jnp.max(jnp.abs(exact.gram))),
               float(jnp.max(jnp.abs(exact.xty))), 1.0)
    return max(dg, db) / span


@pytest.mark.parametrize("seed,n_total", [(s, 5 + (s * 11) % 40)
                                          for s in range(10)])
def test_stream_gram_matches_exact_recompute(seed, n_total):
    """Acceptance: incremental Gram system == exact ring recompute within
    1e-5 relative across random append/evict interleavings (n_total spans
    both under- and over-capacity, so eviction paths are exercised)."""
    rng = np.random.default_rng(seed * 7919)
    plan = _plan(rng, int(rng.integers(1, 5)), cap=16)
    state, _ = _interleaved_push(rng, plan, n_total)
    assert int(state.count.min()) == n_total
    assert _gram_rel_diff(plan, state) <= 1e-5


@pytest.mark.parametrize("seed", range(8))
def test_stream_fit_matches_batch_refit(seed):
    """Acceptance: the streaming fit's predictions match a from-scratch
    batch refit of the same window (the newest ``row_capacity`` rows) on
    well-conditioned data."""
    rng = np.random.default_rng(seed * 104729)
    cap, n_total = 16, int(rng.integers(20, 60))
    plan = _plan(rng, int(rng.integers(1, 4)), cap=cap, ridge=1e-4)
    state, hist = _interleaved_push(rng, plan, n_total)
    window = [(X[-cap:], Y[-cap:]) for X, Y in hist]
    sm_stream = plan.stream_fit(state)
    sm_batch = plan.fit(window)
    for i, (X, Y) in enumerate(window):
        got = np.asarray(sm_stream.model(i).predict(X))
        want = np.asarray(sm_batch.model(i).predict(X))
        span = max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * span)


def test_stream_push_batches_equal_one_shot(rng):
    """Many small pushes == one big push of the same rows (different k_cap
    buckets, same ring contents and Gram system)."""
    plan = _plan(rng, 3, cap=16)
    state, hist = _interleaved_push(rng, plan, 24)
    window = [(X[-16:], Y[-16:]) for X, Y in hist]
    one = plan.stream_rebuild(window)
    exact_a = plan.stream_resync(state)
    exact_b = plan.stream_resync(one)
    np.testing.assert_allclose(np.asarray(exact_a.gram),
                               np.asarray(exact_b.gram), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(exact_a.xty),
                               np.asarray(exact_b.xty), rtol=1e-5, atol=1e-4)


def test_stream_update_is_single_trace_per_bucket(rng):
    """Steady-state pushes (k <= bucket) reuse one compiled update program;
    only a bucket change retraces."""
    plan = _plan(rng, 2, cap=32)
    state = plan.stream_init()
    before = TRACE_COUNTS["stream_update"]
    for _ in range(6):
        deltas = [(_rows(rng, plan, i, 1)) for i in range(2)]
        state = plan.stream_push(state, deltas)
    assert TRACE_COUNTS["stream_update"] == before + 1  # k_cap=1, once
    state = plan.stream_push(state, [(_rows(rng, plan, i, 3))
                                     for i in range(2)])
    assert TRACE_COUNTS["stream_update"] == before + 2  # k_cap=4 variant


def test_gram_fit_accepted_by_solver_stack(rng):
    """A Gram-backed fit handle stands in for StackedModels at the solver
    boundary (SolverProblem.stack unwraps it lazily)."""
    from repro.core.slo import SLO
    from repro.core.solver import ServiceSpec, SolverProblem

    plan = BatchedFitPlan(
        [dict(n_features=2, degree=2, x_scale=[8.0, 1000.0],
              service=f"s{i}", target="tp_max") for i in range(2)],
        row_capacity=64, ridge=1e-4)
    X = np.c_[rng.uniform(0.1, 8, 40), rng.uniform(100, 1000, 40)].astype(
        np.float32)
    Y = (20 * X[:, 0] - X[:, 1] / 100.0).astype(np.float32)
    state = plan.stream_rebuild([(X, Y)] * 2)
    fit = GramFit(plan, state)
    problem = SolverProblem([ServiceSpec(
        name=f"s{i}", param_names=("cores", "quality"),
        lower=(0.1, 100.0), upper=(8.0, 1000.0),
        resource_mask=(True, False), slos=(SLO("completion", 1.0, 1.0),),
        relation_features=(("tp_max", (0, 1)),)) for i in range(2)])
    stacked = problem.stack(fit)
    want = problem.stack(plan.stream_fit(state))
    np.testing.assert_allclose(np.asarray(stacked.w), np.asarray(want.w),
                               rtol=1e-6, atol=1e-6)


# -- TrainingTable retention / compaction -------------------------------------

@pytest.mark.parametrize("seed,retention", [(s, 4 + (s * 3) % 12)
                                            for s in range(8)])
def test_training_table_retention_window(seed, retention):
    """The visible window is exactly the newest ``retention`` rows — stable
    across compactions — and the design matrix matches a brute-force dict
    reference over that window."""
    rng = np.random.default_rng(seed * 65537)
    tab = TrainingTable(initial=4, retention=retention)
    ref = []
    keys = ("cores", "quality", "tp_max")
    n_appends = int(rng.integers(retention + 1, retention * 6))
    for _ in range(n_appends):
        row = {k: float(rng.normal()) for k in keys if rng.random() < 0.9}
        tab.append("s", row)
        ref.append(row)
    kept = ref[-retention:]
    assert tab.count("s") == len(kept)
    assert tab.appended("s") == n_appends
    assert tab.evicted("s") == n_appends - len(kept)
    assert tab.rows("s") == [
        {k: pytest.approx(v) for k, v in r.items()} for r in kept]
    X, Y = tab.design_matrix("s", ("cores", "quality"), "tp_max")
    want = [r for r in kept if all(k in r for k in keys)]
    assert X.shape == (len(want), 2)
    for i, r in enumerate(want):
        assert X[i, 0] == pytest.approx(r["cores"])
        assert Y[i] == pytest.approx(r["tp_max"])


def test_training_table_delta_stream_covers_all_appends(rng):
    """Cursor-driven delta export: concatenating every delta reproduces the
    full (finite-filtered) append stream, across compactions."""
    tab = TrainingTable(initial=4, retention=8)
    cursor, got_x, got_y, want = 0, [], [], []
    for step in range(50):
        row = {"cores": float(rng.normal()), "tp_max": float(rng.normal())}
        tab.append("s", row)
        want.append(row)
        if step % 7 == 0:
            X, Y, cursor = tab.delta_matrix("s", ("cores",), "tp_max", cursor)
            got_x.extend(X[:, 0].tolist())
            got_y.extend(Y.tolist())
    X, Y, cursor = tab.delta_matrix("s", ("cores",), "tp_max", cursor)
    got_x.extend(X[:, 0].tolist())
    got_y.extend(Y.tolist())
    assert cursor == tab.appended("s") == len(want)
    np.testing.assert_allclose(got_x, [r["cores"] for r in want], rtol=1e-6)
    np.testing.assert_allclose(got_y, [r["tp_max"] for r in want], rtol=1e-6)


def test_training_table_memory_is_bounded(rng):
    """Backing arrays never exceed 2x retention no matter how many rows are
    appended (the host-memory bound that motivated retention)."""
    tab = TrainingTable(initial=4, retention=16)
    for _ in range(500):
        tab.append("s", {"a": float(rng.normal())})
    col = tab._cols["s"]["a"]          # internal: backing buffer length
    assert len(col) <= 32
    assert tab.count("s") == 16


# -- agent integration: zero steady-state uploads, churn invalidation ---------

def _run_agent(duration=220, seed=0, **kw):
    from repro.core import RASKAgent, RaskConfig
    from repro.env import EdgeEnvironment, paper_knowledge, paper_profiles

    env = EdgeEnvironment(list(paper_profiles().values()), {"cores": 8.0},
                          seed=seed)
    agent = RASKAgent(env.platform, paper_knowledge(),
                      RaskConfig(xi=6, backend="pgd", **kw), seed=seed)
    hist = env.run(agent, duration_s=duration)
    return env, agent, hist


@pytest.mark.parametrize("pipeline", [False, True])
def test_agent_steady_state_streams_without_uploads(pipeline):
    """Acceptance: after the one rebuild upload, steady-state decide cycles
    move ONLY delta rows host->device — the design window never re-uploads
    and no fused/update program retraces."""
    env, agent, hist = _run_agent(pipeline=pipeline)
    up0 = TRACE_COUNTS["h2d_design_upload"]
    dr0 = TRACE_COUNTS["h2d_delta_rows"]
    traces0 = {k: v for k, v in TRACE_COUNTS.items()
               if k not in ("h2d_design_upload", "h2d_delta_rows")}
    env.run(agent, duration_s=80)
    assert TRACE_COUNTS["h2d_design_upload"] == up0, \
        "steady state re-uploaded the design window"
    assert TRACE_COUNTS["h2d_delta_rows"] > dr0, "no delta rows streamed"
    grew = {k: TRACE_COUNTS[k] - traces0.get(k, 0) for k in TRACE_COUNTS
            if k not in ("h2d_design_upload", "h2d_delta_rows")
            and TRACE_COUNTS[k] - traces0.get(k, 0) > 0}
    assert not grew, f"steady state retraced: {grew}"


def test_agent_churn_invalidates_stream_once():
    """Service-set churn invalidates the device accumulators: the next
    solve does exactly ONE design-window rebuild upload, then returns to
    pure delta streaming."""
    from repro.env import paper_profiles

    env, agent, hist = _run_agent()
    victim = agent.services[0]
    env.platform.deregister(victim)
    env.add_service(paper_profiles()["qr-detector"])
    agent.refresh_topology()
    assert agent._stream is None
    up0 = TRACE_COUNTS["h2d_design_upload"]
    env.run(agent, duration_s=200)          # re-explore + re-solve
    solved = sum(1 for h in env.run(agent, duration_s=60) if not h.explored)
    assert solved > 0
    assert TRACE_COUNTS["h2d_design_upload"] == up0 + 1


def test_agent_streaming_fit_matches_batch_mode():
    """End-to-end parity: the streaming agent and the batch-upload agent
    converge to the same fulfillment on the paper scenario."""
    env_a, agent_a, hist_a = _run_agent(duration=300)
    env_b, agent_b, hist_b = _run_agent(duration=300, streaming_fit=False)
    a = np.mean([h.fulfillment for h in hist_a[-5:]])
    b = np.mean([h.fulfillment for h in hist_b[-5:]])
    assert abs(a - b) <= 0.05, (a, b)


def test_agent_precompile_warms_decide_program():
    """RASKAgent.precompile AOT-compiles the fused decide for the declared
    layout: the production run never traces another decide variant."""
    from repro.core import RASKAgent, RaskConfig
    from repro.env import EdgeEnvironment, paper_knowledge, paper_profiles

    env = EdgeEnvironment(list(paper_profiles().values()), {"cores": 8.0},
                          seed=0)
    agent = RASKAgent(env.platform, paper_knowledge(),
                      RaskConfig(xi=6, backend="pgd"), seed=0)
    warmed = agent.precompile(layouts=(64,))
    assert warmed, "precompile warmed nothing"
    before = TRACE_COUNTS["decide_fused"]
    env.run(agent, duration_s=220)
    assert TRACE_COUNTS["decide_fused"] == before, \
        "decide retraced despite precompile"


def test_aot_export_roundtrip_matches_live_program():
    """The serialized decide program (jax.export) rehydrates to the same
    function — proof the AOT artifact survives a process boundary."""
    from repro.core.rask import _AotFn

    fn = _AotFn(lambda a, b: a @ b + 1.0)
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(4, 4)).astype(np.float32))
    fn.warm(x, x)
    rehydrated = fn.export_roundtrip(x, x)
    np.testing.assert_allclose(np.asarray(rehydrated(x, x)),
                               np.asarray(fn(x, x)), rtol=1e-6)


# -- hypothesis property (optional dep; tier-1 coverage is above) -------------

def test_stream_parity_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n_total=st.integers(1, 80),
           n_rel=st.integers(1, 4))
    def prop(seed, n_total, n_rel):
        rng = np.random.default_rng(seed)
        plan = _plan(rng, n_rel, cap=16)
        state, _ = _interleaved_push(rng, plan, n_total)
        assert _gram_rel_diff(plan, state) <= 1e-5

    prop()
