"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment requirement c)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_pallas


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,S,D,bq,bk", [
    (1, 2, 1, 128, 32, 64, 64),
    (2, 4, 2, 256, 64, 128, 128),
    (1, 8, 8, 64, 16, 32, 32),     # MHA
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(dtype, B, H, KH, S, D, bq, bk, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, KH, S, D), dtype)
    v = jax.random.normal(ks[2], (B, KH, S, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_reference(q, k, v, causal=causal,
                                         window=window)
    assert out.dtype == dtype
    assert jnp.allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                        **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,S,D,bs", [
    (2, 8, 2, 512, 64, 128),
    (1, 4, 4, 256, 32, 64),
    (4, 16, 2, 128, 16, 128),
])
@pytest.mark.parametrize("length,start", [(100, 0), (512, 0), (200, 60)])
def test_decode_attention_sweep(dtype, B, H, KH, S, D, bs, length, start):
    length = min(length, S)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, KH, S, D), dtype)
    vc = jax.random.normal(ks[2], (B, KH, S, D), dtype)
    out = decode_attention_pallas(q, kc, vc, jnp.int32(length),
                                  jnp.int32(start), block_s=bs,
                                  interpret=True)
    want = ref.decode_attention_reference(q, kc, vc, jnp.int32(length),
                                          jnp.int32(start))
    assert jnp.allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                        **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 256, 8, 64, 128, 128),     # production-like head
])
def test_ssd_sweep(dtype, b, l, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = (jax.random.normal(ks[0], (b, l, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))).astype(dtype)
    A = (-jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)).astype(dtype)
    B = (jax.random.normal(ks[3], (b, l, n)) * 0.5).astype(dtype)
    C = (jax.random.normal(ks[4], (b, l, n)) * 0.5).astype(dtype)
    y, fin = ssd_pallas(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, finr = ref.ssd_reference(x, dt, A, B, C, chunk=chunk)
    tol = dict(atol=1e-1, rtol=1e-1) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-3)
    assert jnp.allclose(y.astype(jnp.float32), yr.astype(jnp.float32), **tol)
    assert jnp.allclose(fin.astype(jnp.float32), finr.astype(jnp.float32),
                        **tol)


def test_ssd_chunked_equals_decode_loop():
    """Property: the chunked SSD equals the step-by-step recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, l, h, p, n = 1, 32, 2, 8, 4
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n)) * 0.5
    C = jax.random.normal(ks[4], (b, l, n)) * 0.5
    y, fin = ref.ssd_reference(x, dt, A, B, C, chunk=8)
    state = jnp.zeros((b, h, p, n))
    outs = []
    for t in range(l):
        yt, state = ref.ssd_decode_reference(
            x[:, t], dt[:, t], A, B[:, t], C[:, t], state)
        outs.append(yt)
    y_loop = jnp.stack(outs, axis=1)
    assert jnp.allclose(y, y_loop, atol=1e-4, rtol=1e-3)
    assert jnp.allclose(fin, state, atol=1e-4, rtol=1e-3)


def test_chunked_attention_grads_match_reference():
    from repro.kernels.ref import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, S, KH, G, D = 1, 128, 2, 2, 16
    q = jax.random.normal(ks[0], (B, S, KH, G, D))
    k = jax.random.normal(ks[1], (B, S, KH, D))
    v = jax.random.normal(ks[2], (B, S, KH, D))

    def loss_chunked(q, k, v):
        return jnp.sum(chunked_attention(q, k, v, True, None, 32, 32) ** 2)

    def loss_ref(q, k, v):
        qf = q.reshape(B, S, KH * G, D).transpose(0, 2, 1, 3)
        o = ref.flash_attention_reference(
            qf, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), causal=True)
        return jnp.sum(o ** 2)

    gc = jax.grad(loss_chunked, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gc, gr):
        assert jnp.allclose(a, b, atol=1e-4, rtol=1e-3)


def test_ops_dispatch_reference_and_interpret():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 16))
    k = jax.random.normal(ks[1], (1, 1, 64, 16))
    v = jax.random.normal(ks[2], (1, 1, 64, 16))
    a = ops.flash_attention(q, k, v, impl="reference")
    b = ops.flash_attention(q, k, v, impl="pallas_interpret")
    assert jnp.allclose(a, b, atol=1e-5, rtol=1e-5)


# -- RASK batched objective (autoscaler solve hot path) ----------------------

def _random_objective_case(seed):
    """Random stacked models + SLO tables + K candidates, via the solver's
    own table builder so the kernel is tested against real layouts."""
    import numpy as np
    from repro.core.regression import fit_polynomial
    from repro.core.slo import SLO
    from repro.core.solver import ServiceSpec, SolverProblem

    rng = np.random.default_rng(seed * 2003)
    n_services = int(rng.integers(1, 6))
    specs = []
    for i in range(n_services):
        slos = [SLO("completion", 1.0, 1.0)]
        if rng.random() < 0.7:
            slos.append(SLO("quality", float(rng.uniform(400, 900)), 0.5))
        if rng.random() < 0.4:
            slos.append(SLO("tp_max", float(rng.uniform(50, 150)), 0.3))
        specs.append(ServiceSpec(
            name=f"s{i}", param_names=("cores", "quality"),
            lower=(0.1, 100.0), upper=(8.0, 1000.0),
            resource_mask=(True, False), slos=tuple(slos),
            relation_features=(("tp_max", (0, 1)),)))
    problem = SolverProblem(specs)
    models = {}
    for s in specs:
        X = np.c_[rng.uniform(0.1, 8, 60), rng.uniform(100, 1000, 60)]
        Y = rng.uniform(10, 30) * X[:, 0] - X[:, 1] / rng.uniform(50, 200)
        models[s.name] = {"tp_max": fit_polynomial(
            X.astype(np.float32), Y.astype(np.float32),
            int(rng.integers(1, 4)), x_scale=[8.0, 1000.0])}
    sm = problem.stack(models)
    K = int(rng.integers(1, 20))     # deliberately not a BLOCK_K multiple
    A = jnp.asarray(np.stack([
        problem.random_assignment(rng, float(rng.uniform(2, 20)))
        for _ in range(K)]))
    rps = jnp.asarray(rng.uniform(1, 100, n_services).astype(np.float32))
    return problem, sm, A, rps, n_services


@pytest.mark.parametrize("seed", range(6))
def test_rask_objective_pallas_matches_reference(seed):
    """ISSUE 3 acceptance: the Pallas objective kernel matches the ref.py
    oracle to 1e-4 in interpret mode, across shapes/degrees/K paddings."""
    problem, sm, A, rps, n_services = _random_objective_case(seed)
    t = problem.tables
    args = (A, t.rel_gather, sm.w, sm.exponents, sm.term_mask, sm.x_scale,
            t.slo_kind, t.slo_service, t.slo_weight, t.slo_target,
            t.slo_pidx, t.slo_ridx, rps)
    kw = dict(n_services=n_services, max_degree=sm.max_degree)
    want = ops.rask_objective(*args, impl="reference", **kw)
    got = ops.rask_objective(*args, impl="pallas_interpret", **kw)
    assert got.shape == (A.shape[0], n_services)
    assert jnp.allclose(got, want, atol=1e-4, rtol=1e-4), \
        float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("seed", range(4))
def test_rask_objective_reference_matches_solver_segments(seed):
    """The ref.py oracle IS the solver's fused per-service fulfillment."""
    problem, sm, A, rps, n_services = _random_objective_case(seed + 100)
    t = problem.tables
    want = jnp.stack([problem.per_service_fulfillment(A[i], sm, rps)
                      for i in range(A.shape[0])])
    got = ops.rask_objective(
        A, t.rel_gather, sm.w, sm.exponents, sm.term_mask, sm.x_scale,
        t.slo_kind, t.slo_service, t.slo_weight, t.slo_target, t.slo_pidx,
        t.slo_ridx, rps, n_services=n_services, max_degree=sm.max_degree,
        impl="reference")
    assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5)
