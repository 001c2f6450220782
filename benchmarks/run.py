"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only e1,e3]

Prints ``name,us_per_call,derived`` CSV rows; artifacts land in
benchmarks/artifacts/.
"""
import argparse
import sys
import time


def _report_from_artifacts(name, common) -> bool:
    """Print the CSV rows for ``name`` from cached artifacts. Returns True
    if the artifact existed (benchmarks are deterministic given seeds, so a
    cached artifact is the experiment's result; --force recomputes)."""
    if name == "e1":
        r = common.load("e1_convergence")
        if not r:
            return False
        for k, v in r.items():
            print(f"e1[{k}],0,{v['final10_mean']:.4f}")
        return True
    if name == "e2":
        r = common.load("e2_poly_degree")
        if not r:
            return False
        for svc, row in r["mse"].items():
            print(f"e2[{svc}],0,best_degree={r['best_degree'][svc]}")
        return True
    if name == "e3":
        r = common.load("e3_sota_comparison")
        if not r:
            return False
        for kind, pa in r.items():
            for agent in ("rask", "rask_pgd", "vpa", "dqn"):
                if agent not in pa:
                    continue
                print(f"e3[{kind},{agent}],0,"
                      f"{pa[agent]['mean_fulfillment']:.4f}"
                      f" peak={pa[agent].get('peak_fulfillment', 0):.4f}")
            print(f"e3[{kind},peak-violation-reduction],0,"
                  f"{pa['violation_reduction_vs_best_baseline']:.4f}")
        return True
    if name == "e4":
        found = False
        for backend in ("slsqp", "pgd"):
            r = common.load(f"e4_dimensions_{backend}_cache1")
            if not r:
                continue
            found = True
            for dims, v in r.items():
                print(f"e4[{backend},dims={dims}],"
                      f"{v['median_runtime_ms'] * 1e3:.0f},"
                      f"{v['median_fulfillment']:.4f}")
        return found
    if name == "e5":
        r = common.load("e5_caching")
        if not r:
            return False
        for mode, table in r.items():
            for dims, v in table.items():
                print(f"e5[{mode},dims={dims}],"
                      f"{v['median_runtime_ms'] * 1e3:.0f},"
                      f"{v['median_fulfillment']:.4f}")
        return True
    if name == "e6":
        r = common.load("e6_scalability")
        if not r:
            return False
        for k, v in r.items():
            print(f"e6[{k}],{v['median_runtime_ms'] * 1e3:.0f},"
                  f"{v['median_fulfillment']:.4f}")
        return True
    if name == "e6h":
        from . import e6_scalability
        r = common.load(e6_scalability.HETERO_ARTIFACT)
        if not r:
            return False
        e6_scalability.report_hetero(r)
        return True
    if name == "e7":
        r = common.load("e7_hot_path")
        if not r:
            return False
        from . import e7_hot_path
        e7_hot_path.report(r)
        return True
    if name == "e8":
        from . import e8_placement
        r = common.load(e8_placement.ARTIFACT)
        if not r:
            return False
        e8_placement.report(r)
        return True
    if name == "e9":
        from . import e9_slo_burn
        r = common.load(e9_slo_burn.ARTIFACT)
        if not r:
            return False
        e9_slo_burn.report(r)
        return True
    if name == "e10":
        from . import e10_forecast
        r = common.load(e10_forecast.ARTIFACT)
        if not r:
            return False
        e10_forecast.report(r)
        return True
    if name == "e11":
        from . import e11_serving
        r = common.load(e11_serving.ARTIFACT)
        if not r:
            return False
        e11_serving.report(r)
        return True
    return False


def check_e6() -> int:
    """Heterogeneous-fleet + control-plane-scale regression gate vs the
    committed e6 artifact: the bucketed solve must stay within 1.5x of the
    committed time (CI machine headroom), still beat the single-padded-
    layout path, match the sequential per-host oracle to 1e-5, and a quick
    two-tier scenario must finish its steady-state decides without a single
    jit recompile.  The ISSUE-7 scale gates ride the same check: the fitted
    |S| scaling exponent of the bucketed solve must stay <= 1.2 with the
    1000-service / 100-host point inside one 10 s control interval, the
    sharded solve must be byte-identical to the unsharded one (exactly
    0.0, which holds on XLA-CPU devices), and the pipelined decide must hide >= 50% of the synchronous
    solve latency behind the apply + scrape window."""
    from . import common, e6_scalability

    committed = common.load(e6_scalability.HETERO_ARTIFACT)
    if not committed or not all(k in committed for k in
                                ("solve", "scale", "pipeline")):
        print("e6-check,1,missing-committed-artifact")
        return 1
    row = e6_scalability.solve_bench(reps=5)
    scen = e6_scalability.scenario_bench(reps=1, duration=260.0)
    # 3 of the 4 sweep points (skip the 250-svc one: one less compile, the
    # fit still spans 130 -> 1000 services), 2 reps each
    sc = e6_scalability.scale_bench(
        reps=2, fleets=e6_scalability.SCALE_FLEETS[:1] +
        e6_scalability.SCALE_FLEETS[2:])
    pipe = e6_scalability.pipeline_bench(duration=400.0)
    common.save("e6_hetero_check", {"scenario": scen, "solve": row,
                                    "scale": sc, "pipeline": pipe})
    ref = committed["solve"]
    limit = 1.5 * ref["bucketed_us"]
    ok = (row["bucketed_us"] <= limit
          and row["bucketed_speedup"] >= 1.0
          and row["parity_max_abs_diff"] <= 1e-5
          and scen["steady_state_recompiles"] == 0
          and sc["scaling_exponent"] <= e6_scalability.SCALE_EXPONENT_LIMIT
          and sc["largest_solve_s"] < e6_scalability.SCALE_INTERVAL_S
          and sc["shard_parity_max_abs_diff"] == 0.0
          and committed["scale"]["shard_parity_max_abs_diff"] == 0.0
          and pipe["hidden_fraction"] >= e6_scalability.PIPELINE_HIDDEN_MIN)
    print(f"e6-check[bucketed],{row['bucketed_us']:.0f},"
          f"limit={limit:.0f}us committed={ref['bucketed_us']:.0f}us")
    print(f"e6-check[speedup],0,{row['bucketed_speedup']:.2f}x "
          f"(committed {ref['bucketed_speedup']:.2f}x)")
    print(f"e6-check[parity],0,{row['parity_max_abs_diff']:.2e}")
    print(f"e6-check[recompiles],0,{scen['steady_state_recompiles']}")
    big = sc["points"][-1]
    print(f"e6-check[scale],{big['solve_us']:.0f},"
          f"exponent={sc['scaling_exponent']:.3f}"
          f" (limit {e6_scalability.SCALE_EXPONENT_LIMIT})"
          f" largest={sc['largest_solve_s']:.2f}s"
          f" (limit {e6_scalability.SCALE_INTERVAL_S:.0f}s)"
          f" S={big['services']}/H={big['hosts']}")
    print(f"e6-check[shard-parity],0,"
          f"{sc['shard_parity_max_abs_diff']:.2e}"
          f" shards={sc['n_shards']}/{sc['n_devices']}dev"
          f" (committed "
          f"{committed['scale']['shard_parity_max_abs_diff']:.2e}"
          f" @ {committed['scale']['n_shards']}shards)")
    print(f"e6-check[pipeline],0,hidden={pipe['hidden_fraction']:.1%}"
          f" (min {e6_scalability.PIPELINE_HIDDEN_MIN:.0%}, committed "
          f"{committed['pipeline']['hidden_fraction']:.1%})")
    print(f"e6-check,{0 if ok else 1},{'ok' if ok else 'REGRESSION'}")
    return 0 if ok else 1


def check_e8() -> int:
    """Placement-scorer regression gate vs the committed e8 artifact: the
    batched snapshot must stay within 1.5x of the committed time (CI
    machine headroom), keep a real batched-vs-brute-force speedup, match
    the per-candidate oracle to 1e-5, and re-score without a single jit
    recompile."""
    from . import common, e8_placement

    committed = common.load("e8_placement")
    if not committed or "scorer" not in committed:
        print("e8-check,1,missing-committed-artifact")
        return 1
    e8_placement.REPS = 3
    e8_placement.BRUTE_REPS = 2
    e8_placement.TRAIN_CYCLES = 12
    e8_placement.ARTIFACT = "e8_placement_check"
    row = e8_placement.run(stages=("scorer",))["scorer"]
    ref = committed["scorer"]
    limit = 1.5 * ref["batched_us"]
    recompiles = sum((row.get("recompiles_during_scoring") or {}).values())
    ok = (row["batched_us"] <= limit
          and row["speedup"] >= 2.0
          and row["parity_max_abs_diff"] <= 1e-5
          and row["argmax_match"]
          and recompiles == 0)
    print(f"e8-check[batched],{row['batched_us']:.0f},"
          f"limit={limit:.0f}us committed={ref['batched_us']:.0f}us")
    print(f"e8-check[speedup],0,{row['speedup']:.2f}x "
          f"(committed {ref['speedup']:.2f}x)")
    print(f"e8-check[parity],0,{row['parity_max_abs_diff']:.2e} "
          f"argmax_match={row['argmax_match']}")
    print(f"e8-check[recompiles],0,{recompiles}")
    print(f"e8-check,{0 if ok else 1},{'ok' if ok else 'REGRESSION'}")
    return 0 if ok else 1


def check_e7() -> int:
    """Regression gate: a quick |S|=9 hot-path run vs the committed
    artifact — fail on a >1.5x ``decide_us`` regression (the gate headroom
    absorbs CI machine variance; a retired fast path blows straight
    through it), on ANY jit recompile during steady-state decides, or
    (ISSUE 8) on ANY steady-state design-window upload — the streaming
    Gram engine must keep moving only delta rows.  The fit-phase gate
    re-runs the synthetic |S|=96 breakdown at full reps (the phase is a
    ~2 ms host-side composite with +-15% run-to-run spread, so the
    committed baseline is a median-of-medians): the streaming fit must
    stay within 1.5x of the committed time and >= 2x faster than the
    batch window-rebuild path — the batch/stream RATIO is the
    load-independent regression signal."""
    from . import common, e7_hot_path

    committed = common.load("e7_hot_path")
    if not committed or "S=9" not in committed:
        print("e7-check,1,missing-committed-artifact")
        return 1
    e7_hot_path.S_LIST = (9,)
    e7_hot_path.REPS = 5
    e7_hot_path.SOLVE_REPS = 3
    e7_hot_path.TRAIN_CYCLES = 12
    e7_hot_path.ARTIFACT = "e7_hot_path_check"
    # only the gated measurements: skip the slow slsqp/seed-loop/fleet
    # baselines whose numbers the gate would discard
    row = e7_hot_path.run(stages=("decide",))["S=9"]
    ref = committed["S=9"]
    limit = 1.5 * ref["decide_us"]
    recompiles = sum((row.get("recompiles_during_decide") or {}).values())
    uploads = row.get("design_uploads_during_decide", 0)
    fit = e7_hot_path.fit_phase_bench(s_list=(96,), reps=20)["S=96"]
    fit_ref = (committed.get("fit_phase") or {}).get("S=96")
    fit_limit = 1.5 * fit_ref["stream_fit_us"] if fit_ref else float("inf")
    ok = (row["decide_us"] <= limit and recompiles == 0 and uploads == 0
          and fit_ref is not None
          and fit["stream_fit_us"] <= fit_limit
          and fit["stream_speedup"] >= 2.0)
    print(f"e7-check[decide],{row['decide_us']:.0f},"
          f"limit={limit:.0f}us committed={ref['decide_us']:.0f}us")
    print(f"e7-check[recompiles],0,{recompiles}")
    print(f"e7-check[steady-uploads],0,{uploads}"
          f" delta_rows={row.get('delta_rows_during_decide', 0)}")
    print(f"e7-check[fit-phase],{fit['stream_fit_us']:.0f},"
          f"limit={fit_limit:.0f}us speedup={fit['stream_speedup']:.2f}x"
          f" (min 2.0x, committed "
          f"{fit_ref['stream_speedup'] if fit_ref else 0:.2f}x)")
    print(f"e7-check,{0 if ok else 1},{'ok' if ok else 'REGRESSION'}")
    return 0 if ok else 1


def check_e9() -> int:
    """SLO error-budget control-plane gate vs the committed e9 artifact:
    a seeded re-run of the committed failover configuration (the
    trajectory is deterministic, so every runbook fact must reproduce)
    has to show the fast-burn alert firing within ``ALERT_FIRE_CYCLES``
    agent cycles of the hub outage with no alert already firing entering
    it, clearing after the evacuated services recover, burn-weighted
    recovery at least as good as the burn-blind e8 baseline, a non-empty
    quiet window with zero recompiles, and a jit-trace-free accounting
    pass.  Shorter durations are NOT used here: the alert policy is tuned
    against the settled pre-failover equilibrium, which a truncated run
    never reaches."""
    from . import common, e9_slo_burn

    committed = common.load("e9_slo_burn")
    if not committed or "burn_failover" not in committed:
        print("e9-check,1,missing-committed-artifact")
        return 1
    ref = committed["burn_failover"]
    e9_slo_burn.REPS = 10
    e9_slo_burn.ARTIFACT = "e9_slo_burn_check"
    acct = e9_slo_burn.accounting_bench()
    row = e9_slo_burn.burn_failover_bench()
    common.save("e9_slo_burn_check",
                {"accounting": acct, "burn_failover": row})
    e8 = common.load("e8_placement") or {}
    baseline = (e8.get("failover") or {}).get("mean_recovered", 0.0)
    recompiles = sum((row.get("steady_state_recompiles") or {}).values())
    ref_recompiles = sum((ref.get("steady_state_recompiles") or {}).values())
    jit_traces = sum((acct.get("jit_traces_during_accounting") or {}).values())
    fired = row["alert_fire_cycles"] is not None \
        and row["alert_fire_cycles"] <= e9_slo_burn.ALERT_FIRE_CYCLES
    ok = (fired
          and row["alert_cleared"]
          and not row["firing_at_failure"]
          and ref["alert_fire_cycles"] is not None
          and ref["alert_fire_cycles"] <= e9_slo_burn.ALERT_FIRE_CYCLES
          and ref["alert_cleared"]
          and not ref["firing_at_failure"]
          and row["mean_recovered"] >= max(baseline, 0.864)
          and ref["mean_recovered"] >= max(baseline, 0.864)
          and recompiles == 0
          and ref_recompiles == 0
          and row.get("quiet_cycles", 0) > 0
          and ref.get("quiet_cycles", 0) > 0
          and jit_traces == 0)
    print(f"e9-check[alert],0,fire_cycles={row['alert_fire_cycles']}"
          f" cleared={row['alert_cleared']}"
          f" firing_at_failure={row['firing_at_failure']}")
    print(f"e9-check[recovery],0,{row['mean_recovered']:.4f}"
          f" committed={ref['mean_recovered']:.4f}"
          f" baseline_e8={baseline:.4f}")
    print(f"e9-check[recompiles],0,{recompiles}"
          f" committed={ref_recompiles}"
          f" (quiet_cycles={row.get('quiet_cycles', 0)})"
          f" jit_traces={jit_traces}")
    print(f"e9-check,{0 if ok else 1},{'ok' if ok else 'REGRESSION'}")
    return 0 if ok else 1


def check_e10() -> int:
    """Proactive-scaling gate vs the committed e10 artifact: a seeded
    re-run of the committed configuration (deterministic trajectory) must
    show the forecast gate cutting the bursty violation rate below the
    reactive run's, never worsening the diurnal rate (small tolerance) or
    the mean fulfillment on either trace, actually gating services in,
    adding zero trailing-cycle recompiles and design-window uploads, and a
    transfer arrival that keeps the fleet solving (zero post-arrival
    exploration with priors, nonzero without — the blind spot the priors
    close).  Full durations are used: the hybrid gate needs ``min_evals``
    scored horizons past exploration before it can open."""
    from . import common, e10_forecast

    committed = common.load("e10_forecast")
    if not committed or "proactive" not in committed:
        print("e10-check,1,missing-committed-artifact")
        return 1
    e10_forecast.ARTIFACT = "e10_forecast_check"
    res = e10_forecast.run()
    ok = True
    for src, tag in ((committed, "committed"), (res, "rerun")):
        p, t = src["proactive"], src["transfer"]
        bursty, diurnal = p["bursty"], p["diurnal"]
        ok = (ok
              and bursty["violation_reduction"] > 0.0
              and diurnal["violation_reduction"] >= -0.02
              and all(k["forecast"]["mean_fulfillment"]
                      >= k["reactive"]["mean_fulfillment"] - 0.01
                      for k in (bursty, diurnal))
              and all(k["forecast"]["proactive_cycles"] > 0
                      and k["forecast"]["tail_recompiles"] == 0
                      and k["forecast"]["tail_uploads"] == 0
                      for k in (bursty, diurnal))
              and t["priors_skip_exploration"])
        print(f"e10-check[{tag}],0,"
              f"bursty_dviol={bursty['violation_reduction']:.3f}"
              f" diurnal_dviol={diurnal['violation_reduction']:.3f}"
              f" gated={bursty['forecast']['proactive_cycles']}"
              f"/{diurnal['forecast']['proactive_cycles']}"
              f" tail_recompiles="
              f"{bursty['forecast']['tail_recompiles']}"
              f"+{diurnal['forecast']['tail_recompiles']}"
              f" transfer_skip={t['priors_skip_exploration']}")
    print(f"e10-check,{0 if ok else 1},{'ok' if ok else 'REGRESSION'}")
    return 0 if ok else 1


def check_e11() -> int:
    """Real-serving gate vs the committed e11 artifact.  Both the committed
    record and a fresh re-run must show: the stacked engine >= 2x the
    dict-cache engine's step throughput at the top slot count, ZERO
    steady-state jit recompiles in the timed decode window (TRACE_COUNTS,
    h2d_* runtime transfer counters excluded), prefill tracing exactly once
    per power-of-two prompt bucket, and the RASK-autoscaled serving run
    sustaining steady-state mean fulfillment >= the fixed-equal-split
    baseline under the identical workload.  All gates are comparative or
    count-based — no absolute wall-clock numbers — so they hold across
    machines; the engine numbers are measured wall-clock, which is the
    point of the whole experiment."""
    from . import common, e11_serving

    committed = common.load("e11_serving")
    if not committed or "engine" not in committed or "loop" not in committed:
        print("e11-check,1,missing-committed-artifact")
        return 1
    e11_serving.ARTIFACT = "e11_serving_check"
    res = e11_serving.run()
    top = f"slots={max(e11_serving.SLOT_SWEEP)}"
    ok = True
    for src, tag in ((committed, "committed"), (res, "rerun")):
        e, lo = src["engine"][top], src["loop"]
        ok = (ok
              and e["speedup"] >= 2.0
              and e["stacked_steady_recompiles"] == 0
              and src["engine"]["prefill_traces"]
              == src["engine"]["distinct_buckets"]
              and lo["auto_mean_fulfillment"]
              >= lo["fixed_mean_fulfillment"])
        print(f"e11-check[{tag}],{e['stacked_step_us']:.0f},"
              f"speedup={e['speedup']:.2f}x (min 2.0x @ {top}) "
              f"recompiles={e['stacked_steady_recompiles']} "
              f"prefill_traces={src['engine']['prefill_traces']}"
              f"/{src['engine']['distinct_buckets']} "
              f"auto={lo['auto_mean_fulfillment']:.4f} "
              f"fixed={lo['fixed_mean_fulfillment']:.4f}")
    print(f"e11-check,{0 if ok else 1},{'ok' if ok else 'REGRESSION'}")
    return 0 if ok else 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced reps/durations (CI-sized)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--force", action="store_true",
                    help="recompute even when an artifact exists")
    ap.add_argument("--check", default=None, metavar="SUITE",
                    help="regression gate: compare a quick run against the "
                         "committed artifact (supported: e6, e7, e8, e9, "
                         "e10, e11); exits nonzero on regression")
    args = ap.parse_args()

    if args.check:
        checks = {"e6": check_e6, "e7": check_e7, "e8": check_e8,
                  "e9": check_e9, "e10": check_e10, "e11": check_e11}
        if args.check not in checks:
            ap.error(f"--check supports {sorted(checks)}, got {args.check!r}")
        sys.exit(checks[args.check]())

    from . import (common, e1_convergence, e2_poly_degree,
                   e3_sota_comparison, e4_dimensions, e5_caching,
                   e6_scalability, e7_hot_path, e8_placement, e9_slo_burn,
                   e10_forecast, e11_serving, roofline)

    if args.quick:
        common.REPS = 2
        common.E1_DURATION = 400.0
        common.E3_DURATION = 900.0
        # CI-sized hot-path smoke: |S|=3, few cycles/reps; separate artifact
        # so the committed full-sweep acceptance record is not overwritten
        e7_hot_path.S_LIST = (3,)
        e7_hot_path.REPS = 5
        e7_hot_path.SOLVE_REPS = 3
        e7_hot_path.TRAIN_CYCLES = 12
        e7_hot_path.ARTIFACT = "e7_hot_path_quick"
        # CI-sized hetero smoke: one short scenario rep (xi=20 needs 200 s
        # of exploration; 300 s reaches steady state), same 2-bucket solve
        # fleet (comparable to the committed record), fewer reps
        e6_scalability.SCENARIO_REPS = 1
        e6_scalability.SCENARIO_DURATION = 300.0
        e6_scalability.SOLVE_REPS = 3
        e6_scalability.HETERO_ARTIFACT = "e6_hetero_quick"
        # CI-sized scale/pipeline smoke: sweep stops at 250 services and the
        # pipelined fleet shrinks to 24 services on 8 hosts — the full
        # 1000-service acceptance points live in --check e6
        e6_scalability.SCALE_FLEETS = ((13, 10, 20.0), (25, 10, 20.0))
        e6_scalability.SCALE_REPS = 2
        e6_scalability.PIPELINE_REPLICAS = 8
        e6_scalability.PIPELINE_HOSTS = 8
        e6_scalability.PIPELINE_DURATION = 300.0
        # CI-sized placement smoke: fewer reps/training cycles, a short
        # failover scenario; separate artifact so the committed acceptance
        # record (scorer speedup + full failover trace) is not clobbered
        e8_placement.REPS = 3
        e8_placement.BRUTE_REPS = 2
        e8_placement.TRAIN_CYCLES = 12
        e8_placement.FAILOVER_DURATION = 500.0
        e8_placement.ARTIFACT = "e8_placement_quick"
        # CI-sized SLO-burn smoke: fewer accounting reps, a short failover;
        # separate artifact so the committed runbook record survives
        e9_slo_burn.REPS = 10
        e9_slo_burn.FAILOVER_DURATION = 500.0
        e9_slo_burn.ARTIFACT = "e9_slo_burn_quick"
        # CI-sized forecast smoke: shorter traces (the gate still opens —
        # min_evals horizons past exploration fit inside 600 s) and an
        # earlier arrival; separate artifact so the committed acceptance
        # record keeps the full-duration violation numbers
        e10_forecast.DURATION = 600.0
        e10_forecast.TRANSFER_DURATION = 450.0
        e10_forecast.ARTIFACT = "e10_forecast_quick"
        # CI-sized serving smoke: fewer timed steps, a shorter closed loop
        # (the comparative auto-vs-fixed acceptance number lives in --check
        # e11); separate artifact so the committed idle-machine record of
        # measured step latencies is not clobbered by a loaded CI box
        e11_serving.BENCH_STEPS = 15
        e11_serving.LOOP_DURATION = 300.0
        e11_serving.ARTIFACT = "e11_serving_quick"

    suites = {
        "e1": e1_convergence.main,
        "e2": e2_poly_degree.main,
        "e3": e3_sota_comparison.main,
        "e4": e4_dimensions.main,
        "e5": e5_caching.main,
        "e6": lambda: e6_scalability.main([]),
        "e6h": e6_scalability.main_hetero,
        "e7": e7_hot_path.main,
        "e8": e8_placement.main,
        "e9": e9_slo_burn.main,
        "e10": e10_forecast.main,
        "e11": e11_serving.main,
        "roofline": roofline.main,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    for name, fn in suites.items():
        if name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        if not args.force and _report_from_artifacts(name, common):
            print(f"# {name} reported from cached artifact "
                  f"(--force recomputes)", flush=True)
            continue
        fn()
        print(f"# {name} done in {time.time() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
