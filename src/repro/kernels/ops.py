"""Public jit'd wrappers around the Pallas kernels with reference fallback.

Call sites pick the implementation:
  * ``impl="reference"``         — pure-jnp oracle (XLA; used by the dry-run)
  * ``impl="pallas"``            — compiled Pallas TPU kernel (target hardware)
  * ``impl="pallas_interpret"``  — Pallas interpret mode (CPU validation)

The ``interpret`` boolean shorthand maps True -> pallas_interpret.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ref


# pallas_call has no autodiff rule, so the PGD solver's grad would fail on
# the kernel path.  Wrap the forward in a custom VJP whose backward is the
# analytic jnp gradient (kernels/rask_objective.py::rask_objective_grad).
# Every table rides as an explicit primal (a closure over jit tracers is not
# lowerable); only the candidates get a real cotangent — the solver
# differentiates w.r.t. ``A`` alone, so the tables' zero cotangents are
# never consumed.
@partial(jax.custom_vjp, nondiff_argnums=(13, 14, 15))
def _rask_objective_kernel(A, rel_gather, w, exponents, term_mask, x_scale,
                           slo_kind, slo_service, slo_weight, slo_target,
                           slo_pidx, slo_ridx, rps, n_services, max_degree,
                           interpret):
    from .rask_objective import rask_objective_pallas
    return rask_objective_pallas(
        A, rel_gather, w, exponents, term_mask, x_scale, slo_kind,
        slo_service, slo_weight, slo_target, slo_pidx, slo_ridx, rps,
        n_services=n_services, max_degree=max_degree, interpret=interpret)


def _rask_objective_fwd(A, rel_gather, w, exponents, term_mask, x_scale,
                        slo_kind, slo_service, slo_weight, slo_target,
                        slo_pidx, slo_ridx, rps, n_services, max_degree,
                        interpret):
    res = (A, rel_gather, w, exponents, term_mask, x_scale, slo_kind,
           slo_service, slo_weight, slo_target, slo_pidx, slo_ridx, rps)
    return _rask_objective_kernel(*res, n_services, max_degree, interpret), res


def _zero_cotangent(x):
    if jnp.issubdtype(jnp.result_type(x), jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


def _rask_objective_bwd(n_services, max_degree, interpret, res, ct):
    from .rask_objective import rask_objective_grad
    dA = rask_objective_grad(*res[:1], ct, *res[1:], n_services=n_services,
                             max_degree=max_degree)
    return (dA,) + tuple(_zero_cotangent(x) for x in res[1:])


_rask_objective_kernel.defvjp(_rask_objective_fwd, _rask_objective_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "impl", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "pallas", interpret: bool = False):
    """q: (B,H,S,D); k,v: (B,KH,T,D). Tiled online-softmax attention."""
    if impl == "reference":
        return ref.flash_attention_reference(q, k, v, causal=causal,
                                             window=window)
    from .flash_attention import flash_attention_pallas
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        interpret=interpret or impl == "pallas_interpret")


@partial(jax.jit, static_argnames=("impl", "interpret"))
def decode_attention(q, k_cache, v_cache, length, start=0, *,
                     impl: str = "pallas", interpret: bool = False):
    """q: (B,H,D) one new token; caches: (B,KH,S,D); attend to [start, length)."""
    if impl == "reference":
        return ref.decode_attention_reference(q, k_cache, v_cache, length,
                                              start=start)
    from .decode_attention import decode_attention_pallas
    return decode_attention_pallas(
        q, k_cache, v_cache, length, start,
        interpret=interpret or impl == "pallas_interpret")


@partial(jax.jit, static_argnames=("n_services", "max_degree", "impl",
                                   "interpret"))
def rask_objective(A, rel_gather, w, exponents, term_mask, x_scale, slo_kind,
                   slo_service, slo_weight, slo_target, slo_pidx, slo_ridx,
                   rps, *, n_services: int, max_degree: int,
                   impl: str = "reference", interpret: bool = False):
    """A: (K, D) candidate assignments -> (K, |S|) per-service weighted SLO
    fulfillment (autoscaler Eq. (4) inner evaluation; see ref.py for shapes)."""
    if impl == "reference":
        return ref.rask_objective_reference(
            A, rel_gather, w, exponents, term_mask, x_scale, slo_kind,
            slo_service, slo_weight, slo_target, slo_pidx, slo_ridx, rps,
            n_services=n_services, max_degree=max_degree)
    return _rask_objective_kernel(
        A, rel_gather, w, exponents, term_mask, x_scale, slo_kind,
        slo_service, slo_weight, slo_target, slo_pidx, slo_ridx, rps,
        n_services, max_degree, interpret or impl == "pallas_interpret")


@partial(jax.jit, static_argnames=("chunk", "impl", "interpret"))
def ssd(x, dt, A, B, C, *, chunk: int = 128, initial_state=None,
        impl: str = "pallas", interpret: bool = False):
    """Mamba2 chunked SSD scan. See ref.ssd_reference for shapes."""
    if impl == "reference":
        return ref.ssd_reference(x, dt, A, B, C, chunk=chunk,
                                 initial_state=initial_state)
    from .ssd_scan import ssd_pallas
    return ssd_pallas(x, dt, A, B, C, chunk=chunk,
                      initial_state=initial_state,
                      interpret=interpret or impl == "pallas_interpret")
