"""Plain reference of RASK's solve (the paper's Eq. 4): the objective of a
plan under the reference fit, and the objective's optimum.

The objective sums, over every service and each of its SLOs, the SLO's
weight times ``min(numer / denom, 1)``:

* an SLO on a decision parameter: the parameter over the SLO's target;
* ``completion``: the fitted ``tp_max`` over ``max(rps * target, 1e-9)``;
* an SLO on another fitted target: its prediction over the target.

A plan is feasible when every parameter lies within its bounds and, on each
host, the services' summed resource stays within the host's capacity.

``optimum`` searches the whole feasible set, host by host. For each service
it finds the best value the other parameters reach at each resource value of
a grid (those parameters on a dense grid), then shares the host's capacity
among its services by a max-plus knapsack over that grid (dynamic
programming), which is global up to the grid. A second pass repeats both on
a grid of a sixteenth of the step around the first pass's answer. All of it
is float64 numpy; ``precision="bf16"`` (the control) rounds the models'
predictions and every service value to bfloat16 before they are compared.
"""
from __future__ import annotations

import itertools
from typing import Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

from bench.reference import ridge

COMPLETION = "completion"
TP_MAX = "tp_max"
GRID_RESOURCE = 129      # resource values per service in the first pass
GRID_OTHER = 33          # values of each other parameter in the first pass
ZOOM = 16                # the second pass's step: the first's over ZOOM

Model = Tuple[np.ndarray, np.ndarray, np.ndarray]     # exponents, w, scale


def fit_models(cfgd: Mapping, rows: Mapping[tuple, tuple],
               precision: Optional[str] = None) -> Dict[tuple, Model]:
    """The reference fit of every relation, keyed (service, target)."""
    agent = cfgd["agent"]
    out = {}
    for (sid, target), (feats, X, Y) in rows.items():
        scale = ridge.scales(cfgd, sid, feats)
        w = ridge.fit_weights(X, Y, scale, agent["delta"], agent["ridge"],
                              precision)
        out[(sid, target)] = (ridge.monomials(len(feats), agent["delta"]), w,
                              scale)
    return out


def _round(x: np.ndarray, precision: Optional[str]) -> np.ndarray:
    if precision == "bf16":
        return ridge._bf16(x).astype(np.float64)
    return x


def service_values(svc: Mapping, sid: str, models: Mapping[tuple, Model],
                   rps: float, values: np.ndarray,
                   precision: Optional[str] = None) -> np.ndarray:
    """The service's share of the objective at each row of ``values``
    (columns in the order of ``svc["params"]``)."""
    names = list(svc["params"])
    col = {n: values[:, i] for i, n in enumerate(names)}

    def predict(target):
        exps, w, scale = models[(sid, target)]
        feats = svc["relations"][target]
        xs = np.stack([col[f] for f in feats], axis=1) / scale
        terms = np.prod(xs[:, None, :] ** exps[None], axis=-1)
        return _round(terms @ w, precision)

    total = np.zeros(len(values))
    for metric, target, weight in svc["slos"]:
        if metric in col:
            numer, denom = col[metric], target
        elif metric == COMPLETION:
            numer, denom = predict(TP_MAX), max(rps * target, 1e-9)
        else:
            numer, denom = predict(metric), target
        total += weight * np.minimum(numer / denom, 1.0)
    return _round(total, precision)


def objective(cfgd: Mapping, models: Mapping[tuple, Model],
              rps: Mapping[str, float],
              plan: Mapping[str, Mapping[str, float]]) -> float:
    """The plan's objective in float64."""
    total = 0.0
    for sid, assigned in plan.items():
        svc = cfgd["services"][ridge.service_type(sid)]
        row = np.asarray([[assigned[n] for n in svc["params"]]], np.float64)
        total += float(service_values(svc, sid, models, rps[sid], row)[0])
    return total


def _axes(svc: Mapping, around: Optional[Mapping[str, float]],
          step: Optional[Mapping[str, float]]) -> Dict[str, np.ndarray]:
    """Each parameter's grid: its whole range, or ZOOM steps of a
    sixteenth of ``step`` on each side of ``around`` within the bounds."""
    out = {}
    for name, (lo, hi) in svc["params"].items():
        n = GRID_RESOURCE if name == svc["resource"] else GRID_OTHER
        if around is None:
            out[name] = np.linspace(lo, hi, n)
        else:
            h = step[name] / ZOOM
            k = np.arange(-ZOOM, ZOOM + 1)
            out[name] = np.unique(np.clip(around[name] + h * k, lo, hi))
    return out


def _best_per_resource(svc: Mapping, sid: str, models, rps: float,
                       axes: Dict[str, np.ndarray], precision):
    """For each resource value of the grid: the best service value and the
    other parameters that reach it."""
    names = list(svc["params"])
    res = svc["resource"]
    others = [n for n in names if n != res]
    combos = np.asarray(list(itertools.product(*(axes[n] for n in others))),
                        np.float64).reshape(-1, len(others))
    grid = axes[res]
    values = np.empty((len(grid), len(combos), len(names)))
    values[:, :, names.index(res)] = grid[:, None]
    for j, n in enumerate(others):
        values[:, :, names.index(n)] = combos[None, :, j]
    v = service_values(svc, sid, models, rps,
                       values.reshape(-1, len(names)), precision
                       ).reshape(len(grid), len(combos))
    k = np.argmax(v, axis=1)
    return v[np.arange(len(grid)), k], combos[k]


def _knapsack(tables: Sequence[np.ndarray], units: Sequence[np.ndarray],
              budget: int) -> Optional[Sequence[int]]:
    """Max-plus knapsack: one grid index per service, with summed units
    within ``budget``, maximising the summed values (None if no choice
    fits)."""
    best = np.full(budget + 1, -np.inf)
    best[0] = 0.0
    choice = []
    for values, u in zip(tables, units):
        new = np.full(budget + 1, -np.inf)
        pick = np.full(budget + 1, -1)
        for k, (v, c) in enumerate(zip(values, u)):
            if c > budget:
                continue
            cand = np.full(budget + 1, -np.inf)
            cand[c:] = best[:budget + 1 - c] + v
            better = cand > new
            new[better], pick[better] = cand[better], k
        best = new
        choice.append(pick)
    if not np.isfinite(best).any():
        return None
    b = int(np.argmax(best))
    out = []
    for values, u, pick in zip(reversed(tables), reversed(units),
                               reversed(choice)):
        k = int(pick[b])
        out.append(k)
        b -= int(u[k])
    return out[::-1]


def _host_pass(cfgd, sids, models, rps, capacity, around, step, precision):
    svcs = [cfgd["services"][ridge.service_type(s)] for s in sids]
    res = svcs[0]["resource"]
    axes = [_axes(svc, None if around is None else around[s],
                  None if step is None else step[s])
            for svc, s in zip(svcs, sids)]
    base = [float(ax[res][0]) for ax in axes]
    # the unit of the knapsack: the finest resource step of the pass
    h = min((hi - lo) / (GRID_RESOURCE - 1) if step is None
            else step[s][res] / ZOOM
            for s, (lo, hi) in zip(sids, (svc["params"][res] for svc in svcs)))
    # a grid value's units round its distance from the service's lowest up,
    # so that any choice whose units fit keeps within the capacity
    units = [np.ceil((ax[res] - b) / h - 1e-9).astype(np.int64)
             for ax, b in zip(axes, base)]
    budget = int(np.floor((capacity - sum(base)) / h + 1e-9))
    tables, args = [], []
    for svc, s, ax in zip(svcs, sids, axes):
        best, arg = _best_per_resource(svc, s, models, rps[s], ax, precision)
        tables.append(best)
        args.append(arg)
    picks = _knapsack(tables, units, budget)
    plan, steps = {}, {}
    for svc, s, ax, arg, k in zip(svcs, sids, axes, args, picks):
        others = [n for n in svc["params"] if n != res]
        plan[s] = {res: float(ax[res][k])}
        plan[s].update({n: float(arg[k][j]) for j, n in enumerate(others)})
        steps[s] = {n: (hi - lo) / (GRID_RESOURCE - 1 if n == res
                                    else GRID_OTHER - 1)
                    for n, (lo, hi) in svc["params"].items()}
    return plan, steps


def optimum(cfgd: Mapping, models: Mapping[tuple, Model],
            rps: Mapping[str, float], services: Sequence[str],
            precision: Optional[str] = None
            ) -> Dict[str, Dict[str, float]]:
    """The plan of the highest objective found, host by host."""
    capacity = cfgd["host_capacity"]
    hosts: Dict[str, list] = {}
    for s in services:
        hosts.setdefault(ridge.host_of(s), []).append(s)
    plan = {}
    for sids in hosts.values():
        res = cfgd["services"][ridge.service_type(sids[0])]["resource"]
        first, steps = _host_pass(cfgd, sids, models, rps, capacity[res],
                                  None, None, precision)
        plan.update(_host_pass(cfgd, sids, models, rps, capacity[res],
                               first, steps, precision)[0])
    return plan
