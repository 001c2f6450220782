"""RASK Algorithm 1 end-to-end on the simulated environment."""
import numpy as np
import pytest

from repro.core import RASKAgent, RaskConfig
from repro.env import EdgeEnvironment, paper_knowledge, paper_profiles


def run_rask(backend="slsqp", xi=15, duration=400, seed=0, **kw):
    env = EdgeEnvironment(list(paper_profiles().values()), {"cores": 8.0},
                          seed=seed)
    agent = RASKAgent(env.platform, paper_knowledge(),
                      RaskConfig(xi=xi, backend=backend, **kw), seed=seed)
    hist = env.run(agent, duration_s=duration)
    return env, agent, hist


def test_exploration_phase_length():
    env, agent, hist = run_rask(duration=200, xi=15)
    explored = [h.explored for h in hist]
    assert all(explored[:15])
    assert not any(explored[15:])


def test_convergence_beats_default():
    env, agent, hist = run_rask(duration=500, xi=15)
    post = [h.fulfillment for h in hist[-10:]]
    assert np.mean(post) > 0.9, post


@pytest.mark.parametrize("backend", ["pgd"])
def test_pgd_backend_converges(backend):
    env, agent, hist = run_rask(backend=backend, duration=500, xi=15)
    post = [h.fulfillment for h in hist[-10:]]
    assert np.mean(post) > 0.9, post


def test_cache_warm_start_used():
    env, agent, hist = run_rask(duration=300, xi=15)
    assert agent._cached_x is not None
    env2, agent2, hist2 = run_rask(duration=300, xi=15, cache=False)
    # both run; caching agent must not be worse at the end
    a = np.mean([h.fulfillment for h in hist[-5:]])
    b = np.mean([h.fulfillment for h in hist2[-5:]])
    assert a >= b - 0.1


def test_noise_applied():
    env, agent, hist = run_rask(duration=300, xi=10, eta=0.1, seed=1)
    # noisy assignments still valid (clipped by platform on apply)
    for sid in env.platform.services():
        a = env.platform.assignment(sid)
        api = env.platform.service(sid).api
        for k, v in a.items():
            lo, hi = api.bounds()[k]
            assert lo <= v <= hi


def test_constraint_never_violated():
    env, agent, hist = run_rask(duration=400, xi=10)
    total = sum(env.platform.assignment(s).get("cores", 0.0)
                for s in env.platform.services())
    assert total <= 8.0 + 1e-6


def test_backend_parity_gate_on_paper_scenario():
    """SLSQP stays as the paper-faithful reference behind a parity gate: on
    the e1/e3 scenario (paper profiles, trained table) the default PGD
    backend's objective score must be within 5% of the SLSQP score."""
    env, agent, hist = run_rask(backend="pgd", duration=350, xi=15)
    obs = agent.observe(env.t)
    rps = agent._rps_vector(obs)
    x0 = agent._cached_x
    _, s_slsqp = agent.problem.solve_slsqp(agent.stacked, rps, x0,
                                           agent.capacity)
    _, s_pgd = agent.problem.solve_pgd(agent.stacked, rps, x0,
                                       agent.capacity)
    assert s_pgd >= s_slsqp - 0.05 * abs(s_slsqp), (s_pgd, s_slsqp)


def test_fused_decide_matches_two_stage_solve():
    """The single-dispatch fused pipeline (fit+solve+project+noise in one
    jitted program) must match running the same fit and solve as separate
    dispatches.  Assignments can differ when multi-start scores are
    near-tied (argmax over float-reassociated scores), so the gate is on
    solve quality and feasibility, not bit-equality."""
    import numpy as np

    env, agent, hist = run_rask(backend="pgd", duration=300, xi=15)
    obs = agent.observe(env.t)
    data = agent._collect_fit_data()
    a, noised, score = agent._decide_fused(data, obs, 123, agent._x0())
    np.testing.assert_allclose(noised, a, rtol=1e-6)   # eta = 0 -> no noise
    p = agent.problem
    assert np.all(a >= p.lower - 1e-4) and np.all(a <= p.upper + 1e-4)
    assert a[p.resource_mask].sum() <= agent.capacity + 1e-3
    sm = agent._fit_plan.fit(data)
    a2, score2 = p.solve_pgd(
        sm, agent._rps_vector(obs), agent._x0(), agent.capacity,
        n_starts=agent.cfg.pgd_starts, iters=agent.cfg.pgd_iters,
        lr=agent.cfg.pgd_lr, seed=123)
    assert score >= score2 - 0.05 * max(abs(score2), 1.0), (score, score2)
    # and the in-pipeline fit equals the standalone batched fit (loose:
    # the normal equations are ill-conditioned, so fusion order shifts
    # raw weights slightly — prediction parity is covered elsewhere)
    np.testing.assert_allclose(np.asarray(agent.stacked.w),
                               np.asarray(sm.w), rtol=2e-3, atol=5e-2)


def test_compile_time_reported_separately():
    """The first solved cycle records jit compile time in compile_s, not in
    runtime_s — steady-state cycles report compile_s == 0."""
    env, agent, hist = run_rask(duration=300, xi=15)
    solved = [h for h in hist if not h.explored]
    assert solved, "scenario never reached the solve phase"
    assert solved[0].compile_s > 0.0          # first solve compiles
    assert all(h.compile_s == 0.0 for h in solved[1:])
    # the compile spike dwarfs the steady-state runtime it was skewing
    assert solved[0].compile_s > solved[0].runtime_s
    obs = agent.observe(env.t)
    agent.decide(obs)
    assert agent.last_decision.runtime_s > 0.0
    assert agent.last_decision.compile_s == 0.0
    # the synchronous fused decide also splits a warm cycle into the
    # dispatch of its program and the collect of its result
    env, agent, hist = run_rask(backend="pgd", duration=200, xi=15)
    solved = [h for h in hist if not h.explored]
    assert solved[0].compile_s > solved[0].runtime_s
    agent.decide(agent.observe(env.t))
    info = agent.last_decision
    assert not info.explored and not info.pipelined
    assert info.compile_s == 0.0
    assert info.dispatch_s > 0.0 and info.collect_s > 0.0
    assert info.dispatch_s + info.collect_s <= info.runtime_s


# -- online solver budget adaptation (ISSUE 5 satellite) ----------------------

def _agent_only(**cfg_kw):
    env = EdgeEnvironment(list(paper_profiles().values()), {"cores": 8.0},
                          seed=0)
    return env, RASKAgent(env.platform, paper_knowledge(),
                          RaskConfig(**cfg_kw), seed=0)


def test_adapt_budget_shrinks_to_floors_and_restores_on_shift():
    env, agent = _agent_only(adapt_budget=True, adapt_patience=2,
                             pgd_iters=32, pgd_starts=6)
    full = (32, 6)

    def budget():
        return (agent._budget_iters, agent._budget_starts)

    agent._adapt_budget(10.0, 10.001)         # calm 1: within patience
    assert budget() == full
    agent._adapt_budget(10.0, 10.002)         # calm 2 -> halve
    assert budget() == (16, 3)
    assert agent._last_score is None          # grace cycle after a change
    for _ in range(4):                        # down to the floors, no lower
        agent._adapt_budget(10.0, 10.0)
    assert budget() == (8, 2)
    agent._adapt_budget(10.0, 10.2)           # 2%: noise band, no restore
    assert budget() == (8, 2) and agent._calm_cycles == 0
    agent._adapt_budget(10.0, 10.5)           # 5% score move -> restore
    assert budget() == full
    agent._adapt_budget(10.0, 10.05)          # sub-tol move counts as calm
    agent._adapt_budget(None, 10.0)           # no score baseline: no-op
    agent._adapt_budget(float("nan"), 10.0)   # degenerate solve: no-op
    assert budget() == full


def test_adapt_budget_off_keeps_configured_budget():
    env, agent = _agent_only(pgd_iters=24, pgd_starts=5)
    for _ in range(6):
        agent._adapt_budget(10.0, 10.0)
    assert (agent._budget_iters, agent._budget_starts) == (24, 5)


def test_decision_info_records_active_budget():
    env, agent, hist = run_rask(backend="pgd", xi=4, duration=200,
                                eta=0.0, adapt_budget=True, adapt_patience=2,
                                adapt_iters_floor=8, adapt_starts_floor=2,
                                pgd_iters=16, pgd_starts=4)
    info = agent.last_decision
    assert not info.explored
    assert info.pgd_iters in (16, 8) and info.pgd_starts in (4, 2)
    # constant-load steady state: the score is stationary, so the budget
    # converges to the floors (and stays there modulo rare noise restores)
    seen = set()
    for _ in range(10):
        agent.decide(agent.observe(env.t))
        seen.add((agent.last_decision.pgd_iters,
                  agent.last_decision.pgd_starts))
    assert (8, 2) in seen


# -- topology refresh after churn (ISSUE 5) -----------------------------------

def test_refresh_topology_is_noop_for_same_services():
    env, agent = _agent_only()
    problem = agent.problem
    agent.refresh_topology()
    assert agent.problem is problem           # same service set: kept


def test_refresh_topology_carries_warm_start_across_service_set_change():
    env, agent = _agent_only()
    agent._cached_x = np.arange(agent.problem.dim, dtype=np.float32)
    old = {s.name: (agent.problem.offsets[i], s.n_params)
           for i, s in enumerate(agent.problem.specs)}
    victim = agent.services[0]
    kept = [s for s in agent.services if s != victim]
    env.platform.deregister(victim)
    newcomer = env.add_service(paper_profiles()["qr-detector"])
    agent.refresh_topology()
    assert agent.services == kept + [newcomer]
    assert agent.problem.dim == agent._cached_x.shape[0]
    mid = 0.5 * (agent.problem.lower + agent.problem.upper)
    for i, s in enumerate(agent.problem.specs):
        o, n = agent.problem.offsets[i], s.n_params
        got = agent._cached_x[o:o + n]
        if s.name in old:                     # survivors keep their slices
            off, _ = old[s.name]
            np.testing.assert_array_equal(
                got, np.arange(off, off + n, dtype=np.float32))
        else:                                 # newcomers start mid-box
            np.testing.assert_allclose(got, mid[o:o + n])
    # models and fit plan are rebuilt lazily against the new relation set
    assert agent.stacked is None and agent._fit_plan is None


# -- reactive blind spots (ISSUE 9 satellite) ---------------------------------

def test_rps_vector_falls_back_to_last_known_not_zero(monkeypatch):
    env, agent = _agent_only()
    env.platform.scrape(1.0)
    obs = agent.observe(5.0)
    live = agent._rps_vector(obs)
    assert (live > 0).all()
    # scrape gap: an empty observation window AND an empty metrics store
    # must reuse the last-known rates — solving against 0 rps scales the
    # fleet to the floor mid-traffic and the next cycle pays the spike
    monkeypatch.setattr(agent.platform, "latest_metrics", lambda sid: {})
    stale = agent._rps_vector({})
    np.testing.assert_array_equal(stale, live)
    # a real reading refreshes its cache entry; the rest keep the fallback
    sid = agent.services[0]
    nxt = agent._rps_vector({sid: {"rps": float(live[0]) * 2.0}})
    assert nxt[0] == pytest.approx(live[0] * 2.0)
    np.testing.assert_array_equal(nxt[1:], live[1:])
    assert agent._last_rps[sid] == pytest.approx(live[0] * 2.0)
    # NaN readings are treated as missing, not cached
    bad = agent._rps_vector({sid: {"rps": float("nan")}})
    assert bad[0] == pytest.approx(live[0] * 2.0)
