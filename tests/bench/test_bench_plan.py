"""The plain reference of RASK's solve (``bench.reference.plan``) on small
problems whose answers follow by hand."""
import numpy as np
import pytest

from bench.reference import plan, ridge

CAPACITY = 5.0


def _config(capacity=CAPACITY):
    svc = {"params": {"cores": [0.1, 8.0], "quality": [1.0, 2.0]},
           "resource": "cores",
           "slos": [["quality", 2.0, 0.5], ["completion", 1.0, 1.0]],
           "relations": {"tp_max": ["cores"]}}
    return {"services": {"svc": svc}, "host_capacity": {"cores": capacity},
            "agent": {"delta": 2, "ridge": 1e-6}}


def _linear(per_core: float):
    """tp_max = per_core * cores, as a degree-2 model over cores / 8."""
    return (ridge.monomials(1, 2), np.array([0.0, 8.0 * per_core, 0.0]),
            np.array([8.0]))


SIDS = ["h0/svc/a", "h0/svc/b"]
RPS = {"h0/svc/a": 10.0, "h0/svc/b": 20.0}
MODELS = {(s, "tp_max"): _linear(5.0) for s in SIDS}


def test_objective_by_hand():
    got = plan.objective(_config(), MODELS, RPS, {
        "h0/svc/a": {"cores": 1.0, "quality": 1.5},
        "h0/svc/b": {"cores": 8.0, "quality": 2.0}})
    # a: 0.5 * 1.5/2 + min(5/10, 1); b: 0.5 * 1 + min(40/20, 1)
    assert got == pytest.approx(0.375 + 0.5 + 0.5 + 1.0, abs=1e-12)


@pytest.mark.parametrize("capacity, want", [
    (5.0, 1.0 + 0.75 + 1.0),   # a saturates at 2 cores, b gets the other 3
    (8.0, 2.0 + 1.0),          # both saturate (2 + 4 cores)
    (1.0, 0.5 * 0.9 + 0.25 * 0.1 + 1.0),   # a takes all it can above 0.1
])
def test_optimum_shares_the_capacity(capacity, want):
    cfgd = _config(capacity)
    best = plan.optimum(cfgd, MODELS, RPS, SIDS)
    assert sum(p["cores"] for p in best.values()) <= capacity + 1e-9
    assert all(p["quality"] == 2.0 for p in best.values())
    assert plan.objective(cfgd, MODELS, RPS, best) == pytest.approx(
        want, abs=2e-3)


def test_optimum_is_global_for_a_bumpy_model():
    # tp_max peaks in the middle of the range (a concave quadratic):
    # 20 * x * (1 - x) for x = cores / 8, largest (5) at 4 cores
    bump = (ridge.monomials(1, 2), np.array([0.0, 20.0, -20.0]),
            np.array([8.0]))
    models = {(s, "tp_max"): bump for s in SIDS}
    cfgd = _config(8.0)
    best = plan.optimum(cfgd, models, {s: 5.0 for s in SIDS}, SIDS)
    # both saturate only at exactly 4 cores each
    for p in best.values():
        assert p["cores"] == pytest.approx(4.0, abs=0.05)
    assert plan.objective(cfgd, models, {s: 5.0 for s in SIDS}, best) == \
        pytest.approx(3.0, abs=2e-3)


def test_bf16_search_rounds_what_it_compares():
    cfgd = _config()
    best = plan.optimum(cfgd, MODELS, RPS, SIDS, precision="bf16")
    assert sum(p["cores"] for p in best.values()) <= CAPACITY + 1e-9
    assert plan.objective(cfgd, MODELS, RPS, best) == pytest.approx(
        2.75, abs=0.02)


def test_fit_models_recovers_a_quadratic():
    rng = np.random.default_rng(3)
    X = rng.uniform(0.1, 8.0, (64, 1))
    Y = 3.0 + 2.0 * X[:, 0] - 0.1 * X[:, 0] ** 2
    cfgd = _config()
    models = plan.fit_models(cfgd, {("h0/svc/a", "tp_max"): (["cores"], X, Y)})
    exps, w, scale = models[("h0/svc/a", "tp_max")]
    assert scale.tolist() == [8.0]
    np.testing.assert_allclose(w, [3.0, 16.0, -6.4], rtol=1e-3)
