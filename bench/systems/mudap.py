"""The MUDAP control plane with its RASK agent, driven through
``repro.env.EdgeEnvironment``: simulated stream services under the cell's
load, one ``observe -> decide -> apply_plan`` control cycle
(``EdgeEnvironment._drive``) after every ``ticks_per_cycle`` ticks.

Set-up builds the deployment, compiles the fused decide for every training
window the run reaches (``RASKAgent.precompile``), explores for ``xi``
cycles and runs on until the retained training window is full
(``setup_rows``), so that the window measures the steady state a deployment
reaches after its first hours: no design-window rebuild, no compile. The
window then runs cycles back to back for the run's seconds; each cycle's
drive, with the plan on the host, is one ``decide`` span.

``correct`` is judged on cycles drawn from the seed (``bench.reference``):
the fitted models' predictions against a float64 ridge fit of the same
training rows (``fit_err``); each returned plan's objective, under that fit
and the cycle's observed load, against the optimum a float64 search finds
(``objective_gap``); and each plan against the guarantees the configuration
states, every host's resource within capacity and every parameter within
bounds (``capacity_excess``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import harness
from bench.reference import plan as ref_plan
from bench.reference import ridge

SAMPLES = 16                    # control cycles checked per run


def build(cfgd: dict, source, seed: int):
    """The deployment and its agent, through the program's own types."""
    from repro.core import RASKAgent, RaskConfig
    from repro.env import EdgeEnvironment, paper_knowledge, paper_profiles
    profiles = paper_profiles()
    check_profiles(cfgd, profiles)
    types = list(cfgd["services"])
    patterns = source.patterns({t: profiles[t].default_rps for t in types})
    env_seed, agent_seed = (int(x) for x in np.random.SeedSequence(
        [seed, 0xDEC1DE]).generate_state(2) % (2 ** 31))
    hosts = cfgd["hosts"]
    env = EdgeEnvironment([profiles[t] for t in types],
                          dict(cfgd["host_capacity"]), patterns=patterns,
                          replicas=cfgd["replicas"], hosts=hosts,
                          seed=env_seed)
    agent = RASKAgent(env.platform, paper_knowledge(),
                      RaskConfig(**cfgd["agent"]), seed=agent_seed)
    return env, agent


def warm_resync(agent) -> None:
    """Compile the streaming fit's periodic exact resync, which the window's
    cycles reach and ``precompile`` does not warm; the state it returns is
    dropped."""
    jax.block_until_ready(agent._fit_plan.stream_resync(agent._stream["state"]))


def check_profiles(cfgd: dict, profiles) -> None:
    """The program's service profiles state what the configuration does."""
    for t, svc in cfgd["services"].items():
        p = profiles[t]
        params = {e.name: [e.min_value, e.max_value] for e in p.api.parameters}
        slos = [[q.metric, q.target, q.weight] for q in p.slos]
        if params != svc["params"] or slos != svc["slos"] or \
                {k: list(v) for k, v in p.knowledge.items()} != svc["relations"] \
                or p.default_rps != svc["default_rps"]:
            raise ValueError(f"program profile {t} differs from the configuration")


class Driven:
    """The environment with a span around each control cycle's drive, and
    snapshots of the cycles drawn for the check, taken outside the span."""

    def __init__(self, env, agent, spans: harness.Spans, relations: dict):
        self.env, self.agent, self.spans = env, agent, spans
        self.relations = relations
        self.want_snapshot = False
        self.snapshots: List[dict] = []
        self.last_plan = None
        self.rps: Dict[str, float] = {}     # each service's last observed load
        drive, decide = env._drive, agent.decide

        def decide_keep(obs):
            for sid, row in obs.items():
                rps = row.get("rps")
                if rps is not None and np.isfinite(rps):
                    self.rps[str(sid)] = float(rps)
            self.last_plan = decide(obs)
            return self.last_plan

        def drive_spanned(a):
            with spans.span("drive"):
                out = drive(a)
            if self.want_snapshot:
                self.snapshots.append(self.snapshot())
                self.want_snapshot = False
            return out

        agent.decide = decide_keep
        env._drive = drive_spanned

    def cycle(self, ticks: int) -> None:
        self.env.run(self.agent, float(ticks))

    def snapshot(self) -> dict:
        """What the check needs of this cycle: each relation's training
        rows, the fitted models (device arrays, read after the window), the
        load the decide saw and the plan."""
        agent = self.agent
        rels = {}
        for sid in agent.services:
            for target, feats in self.relations[
                    ridge.service_type(sid)].items():
                X, Y = agent.table.design_matrix(sid, feats, target)
                rels[(str(sid), target)] = dict(feats=feats, X=X, Y=Y)
        return {"stacked": agent.stacked, "relations": rels,
                "rps": dict(self.rps),
                "plan": {s: dict(a) for s, a in
                         self.last_plan.assignments.items()}}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float):
    from repro.core.regression import TRACE_COUNTS

    cfgd = cell.config
    source = cell.traffic_source(seed, seconds)
    ticks = source.ticks_per_cycle
    spans = harness.Spans()
    env, agent = build(cfgd, source, seed)
    rows_full = int(cell.spec["setup_rows"])
    layouts = [32]
    while layouts[-1] < rows_full:
        layouts.append(layouts[-1] * 2)
    agent.precompile(layouts=layouts)
    driven = Driven(env, agent, spans, {t: svc["relations"] for t, svc
                                        in cfgd["services"].items()})
    first = agent.services[0]
    while agent.table.count(first) < rows_full or agent.last_decision.explored:
        driven.cycle(ticks)
    warm_resync(agent)
    spans.records.clear()
    draw = np.random.default_rng([seed, 0x5A3])
    marks = sorted(draw.uniform(0.0, seconds, SAMPLES))
    before = dict(TRACE_COUNTS)
    setup_s = time.perf_counter() - t_process

    window = harness.Window(trace, cell.spec["trace_seconds"])
    with window:
        while True:
            window.tick()
            now = time.perf_counter() - window.t0
            if now >= seconds:
                break
            if marks and now >= marks[0]:
                driven.want_snapshot = True
                while marks and now >= marks[0]:
                    marks.pop(0)
            driven.cycle(ticks)
    retraces = {k: TRACE_COUNTS[k] - before.get(k, 0) for k in TRACE_COUNTS
                if TRACE_COUNTS[k] != before.get(k, 0)}
    drives = [t1 - t0 for _, t0, t1, _ in spans.of("drive")]
    e2e = {"setup_s": setup_s,
           "decide_p95_ms": 1e3 * harness.percentile(drives, 95)}
    device = harness.device_record(cell.chips)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    counts = {"cycles": len(drives), "services": len(agent.services),
              "retraces": retraces,
              "decide_p50_ms": 1e3 * harness.percentile(drives, 50),
              "checked_cycles": len(driven.snapshots)}
    checks, gaps = check(cfgd, agent, driven.snapshots, cell.spec["limits"])
    counts["objective_gaps"] = gaps
    return dict(e2e=e2e, device=device, spans=spans, window=window,
                counts=counts, checks=checks, attempted=len(drives),
                failed=0, sample=driven.snapshots)


def program_predictions(agent, snap: dict) -> Dict[tuple, np.ndarray]:
    """The program's fitted model of each relation (its weights, terms and
    feature scales, read once from the device) at its training rows."""
    sm = snap["stacked"]
    w, exps, mask, scale = (np.asarray(a, np.float64) for a in
                            (sm.w, sm.exponents, sm.term_mask, sm.x_scale))
    out = {}
    for r, (_, name, target, _) in enumerate(agent.problem.relations):
        X = snap["relations"][(name, target)]["X"]
        out[(name, target)] = ridge.evaluate(X, w[r], exps[r], mask[r],
                                             scale[r])
    return out


def _rows(snap: dict) -> dict:
    return {k: (r["feats"], r["X"], r["Y"]) for k, r in
            snap["relations"].items()}


def objective_gap(cfgd: dict, snap: dict, plan: dict,
                  precision: Optional[str] = None) -> float:
    """How far ``plan``'s objective, under the float64 reference fit and the
    cycle's load, lies below the optimum a float64 search finds, as a share
    of that optimum. ``precision`` solves for the optimum in bfloat16 (the
    control), and the gap is read of that plan instead."""
    rows = _rows(snap)
    models = ref_plan.fit_models(cfgd, rows)
    best = ref_plan.optimum(cfgd, models, snap["rps"], list(snap["plan"]))
    best_value = ref_plan.objective(cfgd, models, snap["rps"], best)
    if precision is not None:
        plan = ref_plan.optimum(cfgd, ref_plan.fit_models(cfgd, rows, precision),
                                snap["rps"], list(snap["plan"]), precision)
    return (best_value - ref_plan.objective(cfgd, models, snap["rps"], plan)) \
        / abs(best_value)


def check(cfgd: dict, agent, snapshots: List[dict], limits: dict
          ) -> Dict[str, dict]:
    """For each number compared, the worst of the checked cycles."""
    per_cycle = {"fit_err": [], "objective_gap": [], "capacity_excess": []}
    for snap in snapshots:
        per_cycle["fit_err"].append(ridge.fit_error(
            cfgd, _rows(snap), program_predictions(agent, snap)))
        per_cycle["objective_gap"].append(objective_gap(cfgd, snap,
                                                        snap["plan"]))
        per_cycle["capacity_excess"].append(ridge.plan_excess(cfgd,
                                                              snap["plan"]))
    return {k: {"value": max(v) if v else ridge.NO_SAMPLE,
                "limit": limits[k]} for k, v in per_cycle.items()}, \
        per_cycle["objective_gap"]


def control(cfgd: dict, seed: int, snapshots: List[dict]) -> Dict[str, float]:
    """The control's reading on a run's checked cycles: the reference fit
    and solve in the program's place, in bfloat16, the precision below the
    float32 (at the default matmul precision) that they are stated at."""
    gaps = [objective_gap(cfgd, snap, None, "bf16") for snap in snapshots]
    return {"fit_err": max(ridge.fit_error(cfgd, _rows(snap), {}, "bf16")
                           for snap in snapshots),
            "objective_gap": max(gaps), "objective_gaps": gaps}
