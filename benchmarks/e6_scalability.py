"""E6 (Fig. 11): scalability to 3/6/9 services (replicated QR/CV/PC images,
proportional capacity 8/16/24 cores). Also the beyond-paper comparison:
the vmapped multi-start PGD solver vs scipy SLSQP at each |S| — the paper's
Discussion explicitly flags solver parallelization as the fix for E6's
runtime growth.

``--hetero`` (beyond-paper) exercises the *heterogeneous* fleet engine:

* a seeded two-tier scenario (10 services capacity-placed 2/8 over a
  4-core and a 16-core device, mixed diurnal/bursty/constant load) driven
  by RASK end-to-end, with a steady-state recompile guard;
* a solve microbench on a 2-bucket fleet — hosts of 2 and of 8 services —
  comparing the bucketed per-host dispatch against the single padded
  layout (every host padded to the largest) and the sequential per-host
  loop, plus the bucketed-vs-sequential parity gap (acceptance: <= 1e-5).

Bucketing trades one extra compiled scan per layout bucket for not padding
small hosts to the largest host's layout, so it pays off once buckets hold
several hosts each (the XLA-CPU dispatch floor dominates below that) —
``SOLVE_FLEET`` sizes the committed artifact past that crossover.

The ISSUE-7 control-plane scale suite rides the same artifact:

* ``scale`` — the bucketed fleet solve swept to the 1000-service /
  100-host point (``SCALE_FLEETS``), with the least-squares scaling
  exponent of solve time in |S| (acceptance: <= 1.2 — the vmapped
  one-dispatch path must stay near-linear), the wall time of the largest
  point (acceptance: < 10 s, i.e. inside one control interval), and the
  sharded-vs-unsharded byte parity at that point (``shard="auto"`` via
  ``shard_map`` when multiple XLA devices exist; acceptance on XLA-CPU:
  exactly 0.0 — a TPU mesh differs by float32 rounding, see
  ``core.solver.shard_rows``);
* ``pipeline`` — decide latency with ``RaskConfig(pipeline=True)`` vs the
  synchronous path on a seeded 48-service / 16-host fleet driven
  end-to-end: the dispatch-then-collect cycle must hide >= 50% of the
  solve latency behind the apply + telemetry-scrape window.

``benchmarks/run.py --check e6`` re-runs the microbenches against the
committed artifact and fails on a solve-time regression, a parity gap, a
lost speedup, a superlinear scaling exponent, a blown control interval at
the 1000-service point, a pipeline that stops hiding its solve, or any
steady-state recompile.
"""
import numpy as np

from . import common

# the 2-bucket acceptance fleet: (n_hosts, services_per_host, cores_per_host)
SOLVE_FLEET = ((16, 2, 4.0), (8, 8, 16.0))
SOLVE_REPS = 7
SCENARIO_REPS = 2
SCENARIO_DURATION = None     # None -> E3_DURATION / 2 at call time
HETERO_ARTIFACT = "e6_hetero"

# ISSUE-7 scale sweep: same-shape fleets (10 services per host) growing to
# the 1000-service / 100-host acceptance point, so the fitted exponent
# measures |S| growth and not layout-bucket churn
SCALE_FLEETS = ((13, 10, 20.0), (25, 10, 20.0), (50, 10, 20.0),
                (100, 10, 20.0))
SCALE_REPS = 3
SCALE_EXPONENT_LIMIT = 1.2
SCALE_INTERVAL_S = 10.0      # one control interval: ceiling for the 1000-pt
PIPELINE_REPLICAS = 16       # 16 x paper triple = 48 services on 16 hosts
PIPELINE_HOSTS = 16
PIPELINE_DURATION = 500.0
PIPELINE_HIDDEN_MIN = 0.5


def run(reps: int = common.REPS, duration: float = common.E3_DURATION / 2,
        backends=("slsqp", "pgd"), fleet: bool = False):
    """``fleet=True`` spreads the replicated services over one 8-core device
    each (a Fleet of |replicas| hosts) instead of one big device — same |S|
    growth, per-device constraints arbitrated by the plan control plane."""
    results = {}
    for backend in backends:
        for replicas, cores in ((1, 8.0), (2, 16.0), (3, 24.0)):
            runs = []
            for rep in range(reps):
                patterns = common.e3_patterns("diurnal", duration, seed=rep)
                env = common.make_env(seed=rep, patterns=patterns,
                                      replicas=replicas,
                                      capacity=8.0 if fleet else cores,
                                      hosts=replicas if fleet else 1)
                agent = common.make_rask(env, seed=rep, xi=20, eta=0.0,
                                         backend=backend)
                runs.append(common.run_agent(env, agent, duration))
            rts = np.concatenate([r["runtime_ms"] for r in runs])
            fls = np.concatenate([r["fulfillment"] for r in runs])
            results[f"{backend},S={replicas * 3}"] = {
                "median_runtime_ms": float(np.median(rts)),
                "runtime_ms_p95": float(np.percentile(rts, 95)),
                "max_runtime_ms": float(np.max(rts)),
                "median_fulfillment": float(np.median(fls)),
            }
    common.save("e6_scalability_fleet" if fleet else "e6_scalability", results)
    return results


def _solve_fleet(fleet=SOLVE_FLEET):
    """Synthetic fleet problem (``fleet`` tiers of (n_hosts, services_per_
    host, cores_per_host)) with fitted paper-like 3-parameter services —
    returns (problem, host_of, caps, models, rps, x0)."""
    from repro.core.regression import fit_polynomial
    from repro.core.slo import SLO
    from repro.core.solver import ServiceSpec, SolverProblem

    specs, host_of, caps = [], {}, {}
    for tier, (n_hosts, n_svc, cores) in enumerate(fleet):
        for h in range(n_hosts):
            hostname = f"tier{tier}-{h}"
            caps[hostname] = cores
            for i in range(n_svc):
                s = ServiceSpec(
                    name=f"t{tier}h{h}s{i}",
                    param_names=("cores", "data_quality", "model_size"),
                    lower=(0.1, 100.0, 1.0), upper=(8.0, 1000.0, 4.0),
                    resource_mask=(True, False, False),
                    slos=(SLO("data_quality", 800.0, 0.5),
                          SLO("model_size", 3.0, 0.2),
                          SLO("completion", 1.0, 1.0)),
                    relation_features=(("tp_max", (0, 1, 2)),))
                specs.append(s)
                host_of[s.name] = hostname
    problem = SolverProblem(specs)
    rng = np.random.default_rng(0)
    X = np.c_[rng.uniform(0.1, 8, 300), rng.uniform(100, 1000, 300),
              rng.uniform(1, 4, 300)]
    Y = 20 * X[:, 0] - X[:, 1] / 100.0 + 3 * X[:, 2]
    m = fit_polynomial(X.astype(np.float32), Y.astype(np.float32), 2,
                       x_scale=[8.0, 1000.0, 4.0])
    models = {s.name: {"tp_max": m} for s in specs}
    rps = np.full(len(specs), 50.0, np.float32)
    x0 = problem.random_assignment(np.random.default_rng(1),
                                   float(sum(caps.values())))
    return problem, host_of, caps, models, rps, x0


def solve_bench(reps: int = None) -> dict:
    """Bucketed vs single-padded-layout vs sequential per-host solves on
    the 2-bucket SOLVE_FLEET, plus the bucketed/sequential parity gap."""
    from repro.core.solver import FleetSolverProblem

    reps = SOLVE_REPS if reps is None else reps

    problem, host_of, caps, models, rps, x0 = _solve_fleet()
    fb = FleetSolverProblem(problem, host_of, caps)
    fu = FleetSolverProblem(problem, host_of, caps, bucketed=False)
    a_b, _ = fb.solve_many(models, rps, x0)
    a_q, _ = fb.solve_sequential(models, rps, x0)
    row = {
        "hosts": "+".join(f"{n}x{s}svc" for n, s, _ in SOLVE_FLEET),
        "services": len(problem.specs),
        "buckets": [list(bk.key) for bk in fb.buckets],
        "bucketed_us": common.bench(
            lambda: fb.solve_many(models, rps, x0), reps),
        "padded_us": common.bench(
            lambda: fu.solve_many(models, rps, x0), reps),
        "sequential_us": common.bench(
            lambda: fb.solve_sequential(models, rps, x0), max(reps // 2, 2)),
        "parity_max_abs_diff": float(np.max(np.abs(a_b - a_q))),
    }
    row["bucketed_speedup"] = row["padded_us"] / row["bucketed_us"]
    row["sequential_speedup"] = row["sequential_us"] / row["bucketed_us"]
    return row


def scale_bench(reps: int = None, fleets=None) -> dict:
    """The control plane at 1000 services: bucketed solve time swept over
    ``SCALE_FLEETS``, the fitted |S| scaling exponent, the largest point's
    wall time against one control interval, and sharded-vs-unsharded byte
    parity at that point (real multi-device parity when run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    import jax

    from repro.core.solver import FleetSolverProblem

    reps = SCALE_REPS if reps is None else reps
    fleets = SCALE_FLEETS if fleets is None else fleets
    points = []
    fp = None
    for fleet in fleets:
        problem, host_of, caps, models, rps, x0 = _solve_fleet((fleet,))
        fp = FleetSolverProblem(problem, host_of, caps, shard="auto")
        t_us = common.bench(lambda: fp.solve_many(models, rps, x0),
                            reps, warmup=1)
        points.append({"services": len(problem.specs), "hosts": len(caps),
                       "solve_us": t_us})
    xs = np.log([p["services"] for p in points])
    ys = np.log([p["solve_us"] for p in points])
    exponent = float(np.polyfit(xs, ys, 1)[0])
    # byte parity at the largest point: sharding changes WHERE a host's
    # subproblem runs, never what it computes
    a_s, s_s = fp.solve_many(models, rps, x0)
    f0 = FleetSolverProblem(problem, host_of, caps, shard=False)
    a_0, s_0 = f0.solve_many(models, rps, x0)
    parity = float(max(np.max(np.abs(a_s - a_0)), np.max(np.abs(s_s - s_0))))
    return {"points": points,
            "scaling_exponent": exponent,
            "largest_solve_s": points[-1]["solve_us"] / 1e6,
            "n_devices": jax.device_count(),
            "n_shards": fp.n_shards,
            "shard_parity_max_abs_diff": parity}


def pipeline_bench(duration: float = None, seed: int = 0) -> dict:
    """Pipelined vs synchronous decide on a seeded 48-service / 16-host
    fleet driven end-to-end: ``runtime_s`` of a pipelined cycle is only the
    blocked dispatch + collect time — the solve itself runs on device while
    the plan is applied and telemetry scraped.  Reports the hidden fraction
    of the synchronous solve latency (acceptance: >= PIPELINE_HIDDEN_MIN)
    and the fulfillment cost of the one-cycle plan lag."""
    from repro.core import RASKAgent, RaskConfig
    from repro.env import EdgeEnvironment, paper_knowledge, paper_profiles

    duration = PIPELINE_DURATION if duration is None else duration

    def drive(pipeline: bool):
        env = EdgeEnvironment(list(paper_profiles().values()),
                              {"cores": 8.0}, replicas=PIPELINE_REPLICAS,
                              hosts=PIPELINE_HOSTS, seed=seed)
        agent = RASKAgent(env.platform, paper_knowledge(),
                          RaskConfig(xi=14, eta=0.0, pipeline=pipeline),
                          seed=seed)
        hist = env.run(agent, duration_s=duration)
        solved = [h for h in hist if not h.explored and h.runtime_s > 0]
        return {
            "median_runtime_ms": float(np.median(
                [h.runtime_s for h in solved]) * 1e3),
            "median_dispatch_ms": float(np.median(
                [h.dispatch_s for h in solved]) * 1e3),
            "median_collect_ms": float(np.median(
                [h.collect_s for h in solved]) * 1e3),
            "mean_fulfillment": float(np.mean(
                [h.fulfillment for h in hist[agent.cfg.xi:]])),
        }

    sync, piped = drive(False), drive(True)
    hidden = 1.0 - piped["median_runtime_ms"] / sync["median_runtime_ms"]
    return {"services": PIPELINE_REPLICAS * 3, "hosts": PIPELINE_HOSTS,
            "sync": sync, "pipelined": piped,
            "hidden_fraction": float(hidden)}


def scenario_bench(reps: int = None, duration: float = None) -> dict:
    """The seeded two-tier RASK run: fulfillment + decide runtime + a
    steady-state recompile guard over extra post-run decides."""
    from repro.core import RASKAgent, RaskConfig
    from repro.core.regression import TRACE_COUNTS
    from repro.env import two_tier_environment

    reps = SCENARIO_REPS if reps is None else reps
    if duration is None:
        duration = SCENARIO_DURATION if SCENARIO_DURATION is not None \
            else common.E3_DURATION / 2
    runs, recompiles = [], 0
    for rep in range(reps):
        env, knowledge = two_tier_environment(duration_s=duration, seed=rep)
        agent = RASKAgent(env.platform, knowledge,
                          RaskConfig(xi=20, eta=0.0), seed=rep)
        runs.append(common.run_agent(env, agent, duration))
        traces0 = dict(TRACE_COUNTS)
        for _ in range(3):            # steady state: decides must not retrace
            agent.decide(agent.observe(env.t))
        # h2d_delta_rows is a runtime transfer counter that legitimately
        # moves every streaming cycle; traces AND design-window uploads
        # must both stay flat
        recompiles += sum(TRACE_COUNTS[k] - traces0.get(k, 0)
                          for k in TRACE_COUNTS if k != "h2d_delta_rows")
    rts = np.concatenate([r["runtime_ms"] for r in runs])
    fls = np.concatenate([r["fulfillment"] for r in runs])
    return {
        "services": 10, "hosts": "1x4core(2svc)+1x16core(8svc)",
        "median_runtime_ms": float(np.median(rts)),
        "median_fulfillment": float(np.median(fls)),
        "mean_fulfillment": float(np.mean(fls)),
        "steady_state_recompiles": int(recompiles),
    }


def run_hetero(reps: int = None, duration: float = None,
               solve_reps: int = None, stages=None) -> dict:
    """``stages``: subset of ("scenario", "solve", "scale", "pipeline") to
    measure (None = all)."""
    has = (lambda s: True) if stages is None else (lambda s: s in stages)
    results = {}
    if has("scenario"):
        results["scenario"] = scenario_bench(reps, duration)
    if has("solve"):
        results["solve"] = solve_bench(solve_reps)
    if has("scale"):
        results["scale"] = scale_bench()
    if has("pipeline"):
        results["pipeline"] = pipeline_bench()
    common.save(HETERO_ARTIFACT, results)
    return results


def report_hetero(r: dict) -> None:
    s, v = r.get("scenario"), r.get("solve")
    if s:
        print(f"e6[hetero-scenario],{s['median_runtime_ms'] * 1e3:.0f},"
              f"{s['median_fulfillment']:.4f}"
              f" recompiles={s['steady_state_recompiles']}")
    if v:
        print(f"e6[hetero-solve,{v['hosts']}],{v['bucketed_us']:.0f},"
              f"padded={v['padded_us']:.0f}us"
              f" speedup={v['bucketed_speedup']:.2f}x"
              f" seq={v['sequential_us']:.0f}us"
              f" parity={v['parity_max_abs_diff']:.2e}")
    sc = r.get("scale")
    if sc:
        big = sc["points"][-1]
        print(f"e6[scale,S={big['services']}/H={big['hosts']}],"
              f"{big['solve_us']:.0f},exponent={sc['scaling_exponent']:.3f}"
              f" largest={sc['largest_solve_s']:.2f}s"
              f" shards={sc['n_shards']}/{sc['n_devices']}dev"
              f" parity={sc['shard_parity_max_abs_diff']:.2e}")
    p = r.get("pipeline")
    if p:
        print(f"e6[pipeline,S={p['services']}/H={p['hosts']}],"
              f"{p['pipelined']['median_runtime_ms'] * 1e3:.0f},"
              f"sync={p['sync']['median_runtime_ms'] * 1e3:.0f}us"
              f" hidden={p['hidden_fraction']:.1%}"
              f" lag_cost="
              f"{p['sync']['mean_fulfillment'] - p['pipelined']['mean_fulfillment']:+.4f}")


def main_hetero():
    report_hetero(run_hetero())


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--hetero", action="store_true",
                    help="run the heterogeneous-fleet suite instead of the "
                         "paper's homogeneous scalability sweep")
    args = ap.parse_args(argv)
    if args.hetero:
        main_hetero()
        return
    r = run()
    for k, v in r.items():
        print(f"e6[{k}],{v['median_runtime_ms'] * 1e3:.0f},"
              f"{v['median_fulfillment']:.4f}")


if __name__ == "__main__":
    main()
