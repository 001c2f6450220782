"""E8 (beyond-paper): candidate-batched placement scoring + failover churn.

The PR-4 placement scorer looped O(|S| x |H|) per-host subset solves through
per-subset ``SolverProblem``s — one ``pgd_solve`` dispatch each — which kept
rebalancing out of the per-cycle decide path.  This benchmark measures the
candidate-batched replacement (``core.solver.PlacementProblem``: every
(service, host) what-if subset scored in ONE jitted vmapped dispatch) and
the churn machinery built on top of it:

* ``scorer``   — a trained 9-service / 3-host fleet agent's full
  ``placement_scores`` snapshot: the batched dispatch (``batched_us``) vs
  the brute-force per-candidate dispatch loop on identical padded tables
  and PRNG keys (``brute_us`` — the PR-4 cost shape), their parity gap
  (acceptance: <= 1e-5, same argmax move per service) and a zero-recompile
  guard over repeated steady-state snapshots;
* ``failover`` — the seeded ``env.scenarios.failover_scenario``: the tiered
  camera/hub/gateway fleet runs under mixed load with the per-cycle
  rebalance stage on (``RaskConfig(rebalance_every=3)``), the hub drains at
  60% of the run (residents evacuated via the batched scorer, telemetry
  windows carried), and the artifact records SLO fulfillment before the
  event, through it, and after recovery.

``benchmarks/run.py --check e8`` re-runs the scorer microbench against the
committed artifact and fails on a batched-time regression, a parity gap, a
lost batched-vs-brute speedup, or any steady-state scoring recompile.
"""
import numpy as np

from repro.core import RASKAgent, RaskConfig
from repro.core.regression import TRACE_COUNTS

from . import common

REPS = 5             # batched-scorer reps
BRUTE_REPS = 3       # the per-candidate loop runs ~30 dispatches per call
TRAIN_CYCLES = 20    # exploration cycles populating the training table
FAILOVER_DURATION = 1200.0
FAILOVER_REPS = 1
ARTIFACT = "e8_placement"

# ISSUE-7 scale point: 1000 services / 100 hosts with a CAPPED candidate
# set — an exhaustive |S| x |H| what-if sweep is 100k rows, but a real
# rebalance pass only weighs moving services OFF the hottest devices ONTO
# the coolest ones, so candidates are the residents of the SCALE_MOVER_HOSTS
# most-loaded hosts crossed with the SCALE_TARGETS least-loaded targets
# (plus one stay-put row per host)
SCALE_FLEET = (100, 10, 20.0)
SCALE_MOVER_HOSTS = 25
SCALE_TARGETS = 4
SCALE_REPS = 1


def _trained_fleet_agent(replicas: int = 3, hosts: int = 3, seed: int = 0,
                         **cfg_kw):
    """9 services on a 3-host fleet with a populated training table and one
    warm solve cycle (the e7 `_trained_agent` recipe, fleet-shaped)."""
    env = common.make_env(seed=seed, replicas=replicas, capacity=8.0,
                          hosts=hosts)
    agent = common.make_rask(env, seed=seed, xi=TRAIN_CYCLES, eta=0.0,
                             **cfg_kw)
    env.run(agent, duration_s=(TRAIN_CYCLES + 2) * common.CYCLE_S)
    return env, agent


def scorer_bench(reps: int = None, brute_reps: int = None) -> dict:
    """Batched vs brute-force placement scoring on the trained fleet, with
    the parity gap, per-service argmax agreement, and a recompile guard."""
    reps = REPS if reps is None else reps
    brute_reps = BRUTE_REPS if brute_reps is None else brute_reps
    env, agent = _trained_fleet_agent()
    obs = agent.observe(env.t)
    sb = agent.placement_scores(obs)                     # warm both paths
    sq = agent.placement_scores(obs, batched=False)
    hosts = sorted(h.host for h in env.platform.hosts())
    diffs = [abs(sb[s][h] - sq[s][h]) for s in sb for h in hosts]
    argmax = all(
        max(sb[s], key=lambda h: (sb[s][h], h)) ==
        max(sq[s], key=lambda h: (sq[s][h], h)) for s in sb)
    pp = next(iter(agent._placement_cache.values()))
    row = {
        "services": len(agent.services),
        "hosts": len(hosts),
        "candidates": pp.n_candidates,
        "buckets": [list(bk.key) for bk in pp.buckets],
        "batched_us": common.bench(
            lambda: agent.placement_scores(obs), reps),
        "brute_us": common.bench(
            lambda: agent.placement_scores(obs, batched=False),
            brute_reps),
        "parity_max_abs_diff": float(max(diffs)),
        "argmax_match": bool(argmax),
    }
    row["speedup"] = row["brute_us"] / row["batched_us"]
    traces0 = dict(TRACE_COUNTS)
    for _ in range(3):                   # steady-state scoring: no retraces
        agent.placement_scores(obs)
    row["recompiles_during_scoring"] = {
        k: TRACE_COUNTS[k] - traces0.get(k, 0) for k in TRACE_COUNTS
        if TRACE_COUNTS[k] - traces0.get(k, 0)}
    return row


def scale_snapshot():
    """The 1000-service / 100-host placement snapshot: the capped candidate
    set (every host's stay-put row plus hot-host movers x cool-host
    targets) — returns (problem, subsets, capacities, models, rps, x0)."""
    from .e6_scalability import _solve_fleet

    problem, host_of, caps, models, rps, x0 = _solve_fleet((SCALE_FLEET,))
    residents = {h: [] for h in caps}
    for i, s in enumerate(problem.specs):
        residents[host_of[s.name]].append(i)
    load = {h: sum(float(x0[problem.offsets[i]]) for i in residents[h])
            / caps[h] for h in caps}
    by_load = sorted(caps, key=lambda h: (load[h], h))
    targets, movers = by_load[:SCALE_TARGETS], by_load[-SCALE_MOVER_HOSTS:]
    subsets = [residents[h] for h in sorted(caps)]       # stay-put rows
    caps_list = [caps[h] for h in sorted(caps)]
    for h in movers:
        for i in residents[h]:
            for t in targets:
                subsets.append(sorted(residents[t] + [i]))
                caps_list.append(caps[t])
    return problem, subsets, caps_list, models, rps, x0


def scale_bench(reps: int = None) -> dict:
    """Placement scoring at the 1000-service / 100-host point: one batched
    ``PlacementProblem`` dispatch over the capped candidate set (hot-host
    movers x cool-host targets), sharded over available devices, with
    sharded-vs-unsharded byte parity."""
    import jax

    from repro.core.solver import PlacementProblem

    reps = SCALE_REPS if reps is None else reps
    problem, subsets, caps_list, models, rps, x0 = scale_snapshot()
    pp_s = PlacementProblem(problem, subsets, caps_list, shard="auto")
    pp_0 = PlacementProblem(problem, subsets, caps_list, shard=False)
    s_s = pp_s.scores(models, rps, x0)
    s_0 = pp_0.scores(models, rps, x0)
    return {
        "services": len(problem.specs), "hosts": SCALE_FLEET[0],
        "candidates": pp_s.n_candidates,
        "buckets": [list(bk.key) for bk in pp_s.buckets],
        "batched_us": common.bench(
            lambda: pp_s.scores(models, rps, x0), reps, warmup=1),
        "n_devices": jax.device_count(), "n_shards": pp_s.n_shards,
        "shard_parity_max_abs_diff": float(np.max(np.abs(s_s - s_0))),
    }


def failover_bench(reps: int = None, duration: float = None) -> dict:
    """SLO fulfillment through a seeded hub drain: per-cycle rebalance on,
    residents evacuated via the batched scorer at 60% of the run."""
    from repro.env import failover_scenario

    reps = FAILOVER_REPS if reps is None else reps
    duration = FAILOVER_DURATION if duration is None else duration
    runs = []
    for rep in range(reps):
        env, knowledge, events = failover_scenario(duration_s=duration,
                                                   seed=rep)
        agent = RASKAgent(env.platform, knowledge,
                          RaskConfig(xi=20, eta=0.0, rebalance_every=3),
                          seed=rep)
        fail_t = events[0].t
        hist = env.run(agent, duration_s=duration, events=events)
        pre = [h.fulfillment for h in hist
               if h.t <= fail_t and not h.explored]
        post = [h.fulfillment for h in hist if h.t > fail_t]
        settled = [h.fulfillment for h in hist if h.t > fail_t + 100.0]
        runs.append({
            "fail_t": fail_t,
            "hosts_after": len(env.platform.hosts()),
            "mean_pre_failover": float(np.mean(pre)) if pre else 0.0,
            "min_post_failover": float(np.min(post)) if post else 0.0,
            "mean_recovered": float(np.mean(settled)) if settled else 0.0,
            "fulfillment": [h.fulfillment for h in hist],
            "t": [h.t for h in hist],
        })
    agg = {k: float(np.mean([r[k] for r in runs]))
           for k in ("mean_pre_failover", "min_post_failover",
                     "mean_recovered")}
    agg.update(fail_t=runs[0]["fail_t"], hosts_after=runs[0]["hosts_after"],
               runs=runs)
    return agg


def run(stages=None) -> dict:
    """``stages``: subset of ("scorer", "failover", "scale") to measure
    (None = all) — the --check gate passes ("scorer",) and skips the slow
    scenario and the 1000-service scale point."""
    has = (lambda s: True) if stages is None else (lambda s: s in stages)
    results = {}
    if has("scorer"):
        results["scorer"] = scorer_bench()
    if has("failover"):
        results["failover"] = failover_bench()
    if has("scale"):
        results["scale"] = scale_bench()
    common.save(ARTIFACT, results)
    return results


def report(results: dict) -> None:
    s = results.get("scorer")
    if s:
        print(f"e8[scorer,S={s['services']}/H={s['hosts']}],"
              f"{s['batched_us']:.0f},brute={s['brute_us']:.0f}us"
              f" speedup={s['speedup']:.2f}x"
              f" candidates={s['candidates']}")
        print(f"e8[scorer-parity],0,{s['parity_max_abs_diff']:.2e}"
              f" argmax_match={s['argmax_match']}")
        rec = s.get("recompiles_during_scoring") or {}
        print(f"e8[scorer-recompiles],0,{sum(rec.values())}")
    f = results.get("failover")
    if f:
        print(f"e8[failover],0,pre={f['mean_pre_failover']:.4f}"
              f" dip={f['min_post_failover']:.4f}"
              f" recovered={f['mean_recovered']:.4f}"
              f" hosts_after={f['hosts_after']}")
    sc = results.get("scale")
    if sc:
        print(f"e8[scale,S={sc['services']}/H={sc['hosts']}],"
              f"{sc['batched_us']:.0f},candidates={sc['candidates']}"
              f" shards={sc['n_shards']}/{sc['n_devices']}dev"
              f" parity={sc['shard_parity_max_abs_diff']:.2e}")


def main():
    report(run())


if __name__ == "__main__":
    main()
