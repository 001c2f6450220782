"""Plain reference of RASK's fit and of the guarantees its plans state.

The fit is the polynomial regression of the paper's Eq. 2: every monomial
of total degree at most ``delta`` over the relation's features, each
feature divided by its parameter's upper bound, solved as ridge least
squares with ``lam = ridge * (1 + mean diagonal of the Gram matrix)``, in
float64 with numpy.

``fit_error`` compares the fitted model's predictions at the training rows;
the fit's solve is stated at float32 at the backend's default matmul
precision, so its control (``precision="bf16"``) forms the design rows, the
system and its solution in bfloat16 (sums in float32).
"""
from __future__ import annotations

import itertools
from typing import Dict, Mapping, Optional, Tuple

import numpy as np

NO_SAMPLE = 1e9          # the reading when no cycle was checked


def monomials(n_features: int, degree: int) -> np.ndarray:
    return np.asarray([e for e in itertools.product(range(degree + 1),
                                                    repeat=n_features)
                       if sum(e) <= degree], np.int64)


def features(X: np.ndarray, scale: np.ndarray, degree: int) -> np.ndarray:
    Xs = np.asarray(X, np.float64) / scale
    exps = monomials(Xs.shape[1], degree)
    return np.prod(Xs[:, None, :] ** exps[None, :, :], axis=-1)


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), kept in float32."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


def fit_weights(X, Y, scale, degree: int, ridge: float,
                precision: Optional[str] = None) -> np.ndarray:
    """One relation's ridge weights over ``monomials`` (float64; with
    ``precision="bf16"`` each stage rounded to bfloat16)."""
    P = features(X, scale, degree)
    Y = np.asarray(Y, np.float64)
    if precision == "bf16":
        P16, Y16 = _bf16(P), _bf16(Y)
        A = P16.T @ P16
        lam = np.float32(ridge) * (1 + np.trace(A) / A.shape[0])
        A = _bf16(A + lam * np.eye(len(A), dtype=np.float32))
        return _bf16(np.linalg.solve(A, _bf16(P16.T @ Y16))).astype(np.float64)
    A = P.T @ P
    lam = ridge * (1 + np.trace(A) / A.shape[0])
    return np.linalg.solve(A + lam * np.eye(len(A)), P.T @ Y)


def fit_predict(X, Y, scale, degree: int, ridge: float,
                precision: Optional[str] = None) -> np.ndarray:
    """Fit one relation and return its predictions at the rows ``X``."""
    w = fit_weights(X, Y, scale, degree, ridge, precision)
    P = features(X, scale, degree)
    if precision == "bf16":
        return (_bf16(P) @ w.astype(np.float32)).astype(np.float64)
    return P @ w


def evaluate(X, w, exponents, term_mask, x_scale) -> np.ndarray:
    """A fitted polynomial given by its weights, per-term exponents (padded
    terms masked out) and feature scales, at the rows ``X``, in float64."""
    X = np.asarray(X, np.float64)
    F = X.shape[1]
    Xs = X / np.asarray(x_scale, np.float64)[:F]
    E = np.asarray(exponents)[:, :F]
    P = np.prod(Xs[:, None, :] ** E[None], axis=-1) * term_mask
    return P @ np.asarray(w, np.float64)


def scales(cfgd: Mapping, sid: str, feats) -> np.ndarray:
    params = cfgd["services"][service_type(sid)]["params"]
    return np.asarray([params[f][1] for f in feats], np.float64)


def service_type(sid) -> str:
    return str(sid).split("/")[1]


def host_of(sid) -> str:
    return str(sid).split("/")[0]


def fit_error(cfgd: Mapping, rows: Mapping[tuple, tuple],
              predictions: Mapping[tuple, np.ndarray],
              precision: Optional[str] = None) -> float:
    """Worst relation's largest gap between ``predictions`` and the
    reference fit at the training rows, over the relation's largest
    observed target."""
    agent = cfgd["agent"]
    worst = 0.0
    for (sid, target), (feats, X, Y) in rows.items():
        if len(Y) == 0:
            continue
        ref = fit_predict(X, Y, scales(cfgd, sid, feats), agent["delta"],
                          agent["ridge"])
        got = predictions[(sid, target)] if precision is None else \
            fit_predict(X, Y, scales(cfgd, sid, feats), agent["delta"],
                        agent["ridge"], precision)
        worst = max(worst, float(np.max(np.abs(got - ref)))
                    / max(float(np.max(np.abs(Y))), 1e-12))
    return worst


def plan_excess(cfgd: Mapping, plan: Mapping[str, Mapping[str, float]]
                ) -> float:
    """How far a plan breaks its guarantees: the largest share by which a
    host's summed resource passes its capacity, or a parameter leaves its
    bounds (as a share of the bounds' range); 0 when it keeps them."""
    excess = 0.0
    used: Dict[Tuple[str, str], float] = {}
    for sid, values in plan.items():
        svc = cfgd["services"][service_type(sid)]
        for name, v in values.items():
            lo, hi = svc["params"][name]
            excess = max(excess, (lo - v) / (hi - lo), (v - hi) / (hi - lo))
            if name == svc["resource"]:
                key = (host_of(sid), name)
                used[key] = used.get(key, 0.0) + float(v)
    for (_, res), total in used.items():
        cap = cfgd["host_capacity"][res]
        excess = max(excess, (total - cap) / cap)
    return max(excess, 0.0)
