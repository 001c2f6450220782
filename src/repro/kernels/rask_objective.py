"""Pallas TPU kernel for the batched RASK objective evaluation.

The autoscaling solver's hot inner op scores K candidate assignments
against the stacked polynomial models and SLO tables: a (R, F) feature
gather out of each decision vector, a batched polynomial evaluation, a
branch-free per-SLO phi and a per-service segment-sum (see
core/solver.py::_segments_tables).  Gathers and scatters map poorly onto
the TPU vector unit, so the kernel restructures every indexed access as a
dense matmul with a precomputed one-hot selection matrix (MXU-friendly):

* feature gather   -> A @ G_f^T per feature f, G_f (R, D) one-hot of
  ``rel_gather[:, f]``;
* parameter pick   -> A @ P^T   with P (Q, D)  one-hot of ``slo_pidx``;
* relation pick    -> preds @ Rsel^T (Q, R one-hot of ``slo_ridx``);
* segment-sum      -> (weight * phi) @ Ssel (Q, S one-hot of the SLO's
  service).

The polynomial is unrolled over the static feature and term axes: powers
x^0..x^max_degree of each feature are selected by exponent equality — no
``jnp.power``, bit-compatible with the pure-jnp expansion — and multiplied
into each term, so every in-kernel intermediate is a 2-D (BLOCK_K, R)
tile (the TPU lowering has no product reduction and no in-kernel
transposes or 3-D reshapes). The per-SLO denominator does not depend on
the candidates and is computed outside the kernel. Grid: one program per
block of ``BLOCK_K`` starts; every table rides whole in VMEM (edge problem
sizes — R, T, F, Q, S — are all tens at most, far under the tile budget).

Oracle: kernels/ref.py::rask_objective_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_K = 8

# f32 matmuls run at full precision: on TPU the default f32 dot is one bf16
# pass, which would round the decision vectors the one-hot matmuls gather
_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # contract both minor dims: x @ y^T
_mm = functools.partial(jnp.matmul, precision=_HI)


def _dot_nt(x, y):
    return jax.lax.dot_general(x, y, _NT, precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(a_ref, gsel_ref, psel_ref, rsel_ref, ssel_ref, exp_ref, wm_ref,
            xinv_ref, isp_ref, denom_ref, weight_ref, out_ref, *,
            f_count: int, t_count: int, max_degree: int):
    a = a_ref[...]                                            # (bk, D)
    exps = exp_ref[...]                                       # (T*F, R)
    xinv = xinv_ref[...]                                      # (F, R)
    wm = wm_ref[...]                                          # (T, R)

    # polynomial terms, one feature at a time: the feature gather is one
    # matmul against that feature's (R, D) one-hot, its powers x^0..x^d are
    # unrolled, and each term multiplies in the power its exponent selects
    # — every intermediate is a 2-D (bk, R) tile
    terms = [None] * t_count
    for f in range(f_count):
        x = _dot_nt(a, gsel_ref[f]) * xinv[f:f + 1]           # (bk, R)
        pows = [jnp.ones_like(x)]
        for _ in range(max_degree):
            pows.append(pows[-1] * x)
        for t in range(t_count):
            e = exps[t * f_count + f:t * f_count + f + 1]     # (1, R)
            v = jnp.where(e == 0, pows[0], 0.0)
            for d in range(1, max_degree + 1):
                v = v + jnp.where(e == d, pows[d], 0.0)
            terms[t] = v if f == 0 else terms[t] * v
    preds = terms[0] * wm[0:1]
    for t in range(1, t_count):
        preds = preds + terms[t] * wm[t:t + 1]                # (bk, R)

    # branch-free per-SLO phi (the denominator is candidate-independent)
    is_p = isp_ref[...]                                       # (1, Q)
    numer = is_p * _dot_nt(a, psel_ref[...]) \
        + (1.0 - is_p) * _dot_nt(preds, rsel_ref[...])        # (bk, Q)
    phi = jnp.minimum(numer / denom_ref[...], 1.0)

    # per-service segment-sum as one matmul
    out_ref[...] = jnp.dot(phi * weight_ref[...], ssel_ref[...],
                           precision=_HI,
                           preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("n_services", "max_degree", "interpret"))
def rask_objective_pallas(A, rel_gather, w, exponents, term_mask, x_scale,
                          slo_kind, slo_service, slo_weight, slo_target,
                          slo_pidx, slo_ridx, rps, *, n_services: int,
                          max_degree: int, interpret: bool = False):
    """Shapes/semantics: kernels/ref.py::rask_objective_reference."""
    A = jnp.asarray(A, jnp.float32)
    k_count, dim = A.shape
    r_count, t_count, f_count = exponents.shape
    q_count = slo_kind.shape[0]

    # one-hot selection matrices and candidate-independent SLO terms
    # (cheap at edge sizes, traced on device)
    gsel = jax.nn.one_hot(rel_gather.T, dim, dtype=jnp.float32)   # (F, R, D)
    psel = jax.nn.one_hot(slo_pidx, dim, dtype=jnp.float32)   # (Q, D)
    rsel = jax.nn.one_hot(slo_ridx, r_count,
                          dtype=jnp.float32)                  # (Q, R)
    ssel = jax.nn.one_hot(slo_service, n_services,
                          dtype=jnp.float32)                  # (Q, S)
    wm = (jnp.asarray(w, jnp.float32) * term_mask).T          # (T, R)
    xinv = 1.0 / jnp.asarray(x_scale, jnp.float32).T          # (F, R)
    exps = jnp.asarray(exponents, jnp.int32).transpose(1, 2, 0) \
        .reshape(t_count * f_count, r_count)                  # (T*F, R)
    target = jnp.asarray(slo_target, jnp.float32)
    svc_rps = jnp.asarray(rps, jnp.float32)[slo_service]      # (Q,)
    denom = jnp.where(slo_kind == 1, jnp.maximum(svc_rps * target, 1e-9),
                      target)

    pad = -k_count % BLOCK_K
    Ap = jnp.pad(A, ((0, pad), (0, 0)))
    grid = (Ap.shape[0] // BLOCK_K,)
    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    out = pl.pallas_call(
        functools.partial(_kernel, f_count=f_count, t_count=t_count,
                          max_degree=max_degree),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_K, dim), lambda i: (i, 0)),   # A block
            full(f_count, r_count, dim),                      # gsel
            full(q_count, dim),                               # psel
            full(q_count, r_count),                           # rsel
            full(q_count, n_services),                        # ssel
            full(t_count * f_count, r_count),                 # exponents
            full(t_count, r_count),                           # w * term_mask
            full(f_count, r_count),                           # 1 / x_scale
            full(1, q_count),                                 # kind == param
            full(1, q_count),                                 # phi denominator
            full(1, q_count),                                 # weight
        ],
        out_specs=pl.BlockSpec((BLOCK_K, n_services), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Ap.shape[0], n_services), jnp.float32),
        interpret=interpret,
    )(Ap, gsel, psel, rsel, ssel, exps, wm, xinv,
      (slo_kind == 0).astype(jnp.float32)[None], denom[None],
      jnp.asarray(slo_weight, jnp.float32)[None])
    return out[:k_count]


def rask_objective_grad(A, ct, rel_gather, w, exponents, term_mask, x_scale,
                        slo_kind, slo_service, slo_weight, slo_target,
                        slo_pidx, slo_ridx, rps, *, n_services: int,
                        max_degree: int):
    """Analytic VJP of the objective w.r.t. the candidates: cotangent
    ``ct`` (K, S) -> dJ/dA (K, D).

    The backward of the Pallas forward's custom VJP (kernels/ops.py): the
    transposed one-hot matmuls retrace the forward's selection structure
    (``ssel``/``rsel``/``psel`` scatter the per-SLO cotangent back onto
    predictions and parameters, ``gsel`` scatters the per-feature cotangent
    back onto the decision vector), and the polynomial product rule runs a
    static O(F^2) loop over "product of the OTHER features" — exact at
    zeros, no division by ``vals``.  Matches ``jax.grad`` of the reference
    objective everywhere off the measure-zero ``ratio == 1`` clip boundary
    (where both use the half-subgradient).  jnp only — it composes into the
    PGD scan on any backend; a Pallas backward kernel would mirror the
    forward's matmul structure if profiles ever demand it."""
    A = jnp.asarray(A, jnp.float32)
    ct = jnp.asarray(ct, jnp.float32)
    k_count, dim = A.shape
    r_count, t_count, f_count = exponents.shape
    gsel = jax.nn.one_hot(rel_gather.reshape(-1), dim,
                          dtype=jnp.float32)                  # (R*F, D)
    psel = jax.nn.one_hot(slo_pidx, dim, dtype=jnp.float32)   # (Q, D)
    rsel = jax.nn.one_hot(slo_ridx, r_count, dtype=jnp.float32)
    ssel = jax.nn.one_hot(slo_service, n_services, dtype=jnp.float32)
    wm = jnp.asarray(w, jnp.float32) * term_mask              # (R, T)
    xinv = 1.0 / jnp.asarray(x_scale, jnp.float32)            # (R, F)
    exps = jnp.asarray(exponents, jnp.int32)
    weight = jnp.asarray(slo_weight, jnp.float32)
    target = jnp.asarray(slo_target, jnp.float32)

    # forward recompute (cheap at edge sizes; no residual plumbing): same
    # powers-by-exponent-equality accumulation as the kernel, plus the
    # power-rule derivative e * x^(e-1) selected from the same table
    x = _mm(A, gsel.T).reshape(k_count, r_count, f_count) * xinv[None]
    p = jnp.ones_like(x)
    powers = [p]                                              # x^0..x^d
    for _ in range(max_degree):
        p = p * x
        powers.append(p)
    vals = jnp.zeros((k_count, r_count, t_count, f_count), jnp.float32)
    dvals = jnp.zeros_like(vals)
    for e in range(max_degree + 1):
        sel = exps[None] == e
        vals = jnp.where(sel, powers[e][:, :, None, :], vals)
        if e:
            dvals = jnp.where(sel, e * powers[e - 1][:, :, None, :], dvals)
    terms = jnp.prod(vals, axis=-1)                           # (K, R, T)
    preds = jnp.sum(terms * wm[None], axis=-1)                # (K, R)

    is_p = (slo_kind == 0).astype(jnp.float32)                # (Q,)
    is_c = (slo_kind == 1).astype(jnp.float32)
    numer = is_p[None] * _mm(A, psel.T) + (1 - is_p)[None] * _mm(preds,
                                                                 rsel.T)
    svc_rps = _mm(jnp.asarray(rps, jnp.float32), ssel.T)      # (Q,)
    denom = is_c * jnp.maximum(svc_rps * target, 1e-9) \
        + (1 - is_c) * target                                 # (Q,)
    ratio = numer / denom[None]                               # (K, Q)

    # backward: out = (min(ratio, 1) * weight) @ ssel
    dphi = _mm(ct, ssel.T) * weight[None]                     # (K, Q)
    clip = jnp.where(ratio < 1.0, 1.0,
                     jnp.where(ratio == 1.0, 0.5, 0.0))       # min() subgrad
    dnumer = dphi * clip / denom[None]                        # (K, Q)
    dA = _mm(dnumer * is_p[None], psel)                       # (K, D)
    dpreds = _mm(dnumer * (1 - is_p)[None], rsel)             # (K, R)
    dterms = dpreds[:, :, None] * wm[None]                    # (K, R, T)
    dx = jnp.zeros_like(x)
    for f in range(f_count):
        other = jnp.ones_like(terms)
        for f2 in range(f_count):
            if f2 != f:
                other = other * vals[..., f2]
        dx = dx.at[..., f].add(
            jnp.sum(dterms * dvals[..., f] * other, axis=-1))
    dx = dx * xinv[None]                                      # xs = x / scale
    return dA + _mm(dx.reshape(k_count, -1), gsel)
