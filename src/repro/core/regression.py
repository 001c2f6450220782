"""Polynomial regression of structural knowledge — paper Eq. (2).

``w* (X, Y, delta) = argmin_w sum_i (y_i - w^T delta(x_i))^2``

sklearn is deliberately not used: the feature expansion and the (ridge-
regularized) least-squares solve are implemented on jnp so that

* ``fit`` is jit-able, and
* ``PolynomialModel.predict`` is *differentiable in x* — the numerical solver
  (core/solver.py) backpropagates through the learned surfaces to find optimal
  parameter assignments (Eq. 4).

Terms are enumerated statically (all exponent tuples with total degree
<= delta, like sklearn's PolynomialFeatures with bias) and the per-term
product is unrolled in Python, which sidesteps the 0**0 autodiff singularity
of ``jnp.power`` with array exponents.

Batched (stacked) representation
--------------------------------
``StackedModels`` holds *all* |S|x|K| structural relations of a problem as one
padded pytree so the whole fit+predict hot path is a single XLA dispatch:

* ``w``         (R, T_max)        — per-relation weights, zero on padded terms;
* ``exponents`` (R, T_max, F_max) — int32 term exponents, zero on padding;
* ``term_mask`` (R, T_max)        — 1.0 on real terms, 0.0 on padding;
* ``x_scale``   (R, F_max)        — feature conditioning, 1.0 on padding.

Padding invariants: a padded *feature* column has exponent 0 everywhere, so
its (arbitrary) value contributes a factor of 1; a padded *term* has
``term_mask == 0`` so its feature column in the design matrix is zeroed and
the ridge term pins its weight to exactly 0.  All arrays are jit *leaves*
(traced), so refits with new data — or even new exponent values at the same
(R, T_max, F_max) shape — never recompile.

``fit_batched`` solves every relation's ridge system in one ``vmap``ped jitted
call over fixed-capacity padded design matrices (``row_mask`` marks the real
rows), so training-table growth within a capacity bucket never recompiles and
fitting |S|x|K| relations is one dispatch instead of a Python loop.  Powers
are computed by cumulative products + gather (no ``jnp.power``), keeping the
expansion differentiable everywhere and bit-compatible with ``_expand``.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
from functools import partial
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# jit trace counters (incremented at *trace* time, i.e. on compilation of a
# new shape/static combination) — the no-recompile regression tests assert
# these stay flat across cycles once the padded shapes stabilize.
#
# Two RUNTIME counters live in the same Counter (incremented per call, not
# per trace), because they gate *transfers* rather than compiles:
#   * ``h2d_design_upload`` — every host->device upload of a full padded
#     design-matrix window (``BatchedFitPlan.fill``/``fill_packed`` and the
#     streaming engine's rebuild push).  The streaming fit's zero-upload
#     guarantee is "this counter stays flat across steady-state cycles".
#   * ``h2d_delta_rows``    — telemetry rows pushed through the streaming
#     delta path (the O(new rows) uploads that REPLACE the full windows).
TRACE_COUNTS: collections.Counter = collections.Counter()

# float32 products at full precision: on TPU the default f32 dot is one bf16
# pass, which would round the Gram systems the ridge solves consume
_hdot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def polynomial_exponents(n_features: int, degree: int) -> np.ndarray:
    """All exponent tuples with 0 <= sum(e) <= degree, bias term first.

    Shape (T, n_features); T = C(n_features + degree, degree).
    """
    terms = [e for e in itertools.product(range(degree + 1), repeat=n_features)
             if sum(e) <= degree]
    terms.sort(key=lambda e: (sum(e), tuple(-x for x in e)))
    return np.asarray(terms, np.int32)


def _expand(x, exponents: np.ndarray):
    """delta(x): map (..., F) -> (..., T) polynomial features. Unrolled/static."""
    cols = []
    for term in exponents:
        col = jnp.ones(x.shape[:-1], x.dtype)
        for f, e in enumerate(term):
            for _ in range(int(e)):
                col = col * x[..., f]
        cols.append(col)
    return jnp.stack(cols, axis=-1)


@partial(jax.jit, static_argnames=("degree", "n_features"))
def _fit(Xs, Y, degree: int, n_features: int, ridge):
    exps = polynomial_exponents(n_features, degree)
    Phi = _expand(Xs, exps)                                   # (N, T)
    A = _hdot(Phi.T, Phi)
    # scale-aware ridge: constant feature columns (frozen elasticity dims)
    # make A singular; regularize relative to its trace
    lam = ridge * (1.0 + jnp.trace(A) / A.shape[0])
    A = A + lam * jnp.eye(Phi.shape[1], dtype=Phi.dtype)
    b = _hdot(Phi.T, Y)
    return jnp.linalg.solve(A, b)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PolynomialModel:
    """A fitted w*(X, Y, delta) — one structural relation k in K."""

    w: jnp.ndarray            # (T,)
    exponents: np.ndarray     # (T, F) static
    x_scale: np.ndarray       # (F,) static feature scaling for conditioning
    degree: int
    features: Tuple[str, ...] = ()
    target: str = ""

    def predict(self, x):
        """Estimate the target for raw (unscaled) feature vector(s) x (..., F)."""
        xs = jnp.asarray(x, jnp.float32) / jnp.asarray(self.x_scale, jnp.float32)
        return _hdot(_expand(xs, self.exponents), self.w)

    # pytree protocol: only w is a leaf so models can ride through jit/vmap.
    def tree_flatten(self):
        return (self.w,), (self.exponents.tobytes(), self.exponents.shape,
                           self.x_scale.tobytes(), self.x_scale.shape,
                           self.degree, self.features, self.target)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        eb, es, xb, xs_shape, degree, features, target = aux
        return cls(leaves[0],
                   np.frombuffer(eb, np.int32).reshape(es).copy(),
                   np.frombuffer(xb, np.float32).reshape(xs_shape).copy(),
                   degree, features, target)


def fit_polynomial(X, Y, degree: int, x_scale: Optional[Sequence[float]] = None,
                   ridge: float = 1e-6, features: Sequence[str] = (),
                   target: str = "") -> PolynomialModel:
    """Fit Eq. (2). ``x_scale`` (default: column max) conditions the expansion —
    raw features like data_quality in [100, 1000] raised to delta=6 would
    otherwise overflow float32."""
    X = np.atleast_2d(np.asarray(X, np.float32))
    Y = np.asarray(Y, np.float32).reshape(-1)
    n = X.shape[1]
    if x_scale is None:
        x_scale = np.maximum(np.abs(X).max(axis=0), 1e-9)
    x_scale = np.asarray(x_scale, np.float32)
    w = _fit(jnp.asarray(X / x_scale), jnp.asarray(Y), degree, n,
             jnp.float32(ridge))
    return PolynomialModel(w, polynomial_exponents(n, degree), x_scale,
                           degree, tuple(features), target)


def mse(model: PolynomialModel, X, Y) -> float:
    pred = model.predict(jnp.asarray(X, jnp.float32))
    return float(jnp.mean((pred - jnp.asarray(Y, jnp.float32)) ** 2))


def train_test_split(X, Y, test_frac: float = 0.2, seed: int = 0):
    """Deterministic 80/20 split used by E2 (Table IV)."""
    n = len(Y)
    idx = np.random.default_rng(seed).permutation(n)
    cut = max(1, int(round(n * test_frac)))
    te, tr = idx[:cut], idx[cut:]
    X = np.asarray(X)
    Y = np.asarray(Y)
    return X[tr], Y[tr], X[te], Y[te]


def select_degree(X, Y, degrees: Sequence[int] = (1, 2, 3, 4, 5, 6),
                  x_scale=None, seed: int = 0) -> Tuple[int, dict]:
    """E2 / §VI-C2: pick the service-specific degree by test-split MSE."""
    Xtr, Ytr, Xte, Yte = train_test_split(X, Y, seed=seed)
    errs = {}
    for d in degrees:
        m = fit_polynomial(Xtr, Ytr, d, x_scale=x_scale)
        errs[d] = mse(m, Xte, Yte)
    best = min(errs, key=errs.get)
    return best, errs


# --------------------------------------------------------------------------
# Stacked (batched) representation: all |S|x|K| relations as one pytree
# --------------------------------------------------------------------------

def _expand_gather(x, exponents, max_degree: int):
    """delta(x) for a traced exponent table — map (N, F) -> (N, T).

    Powers x^0..x^max_degree are built by cumulative products (same
    multiplication order as ``_expand``), then gathered per (term, feature)
    and multiplied out.  Fully differentiable: no ``jnp.power``, no 0**0.
    """
    n, f = x.shape
    t = exponents.shape[0]
    pows = jnp.cumprod(jnp.broadcast_to(x[:, None, :], (n, max_degree, f)),
                       axis=1) if max_degree else jnp.zeros((n, 0, f), x.dtype)
    pows = jnp.concatenate([jnp.ones((n, 1, f), x.dtype), pows], axis=1)
    idx = jnp.broadcast_to(exponents[None, :, None, :], (n, t, 1, f))
    vals = jnp.take_along_axis(
        jnp.broadcast_to(pows[:, None, :, :], (n, t, max_degree + 1, f)),
        idx, axis=2)[:, :, 0, :]
    return jnp.prod(vals, axis=-1)                            # (N, T)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackedModels:
    """All R = |S|x|K| structural relations as one padded pytree.

    See the module docstring for the padding invariants.  ``labels`` keeps
    the static bookkeeping ((service, target, features, degree, n_terms,
    n_features) per relation) needed to slice per-relation views back out.
    """

    w: jnp.ndarray             # (R, T_max)   zero on padded terms
    exponents: jnp.ndarray     # (R, T_max, F_max) int32, zero on padding
    term_mask: jnp.ndarray     # (R, T_max)   1.0 real / 0.0 padded
    x_scale: jnp.ndarray       # (R, F_max)   1.0 on padded features
    max_degree: int            # static: largest per-relation degree
    labels: Tuple[Tuple[str, str, Tuple[str, ...], int, int, int], ...] = ()

    @property
    def n_relations(self) -> int:
        return self.w.shape[0]

    def predict_all(self, x):
        """One prediction per relation: x (R, F_max) raw features -> (R,)."""
        xs = jnp.asarray(x, jnp.float32) / self.x_scale
        d = self.max_degree
        phi = jax.vmap(lambda xr, er: _expand_gather(xr[None], er, d)[0])(
            xs, self.exponents) * self.term_mask              # (R, T_max)
        return jnp.sum(phi * self.w, axis=-1)                 # (R,)

    def model(self, r: int) -> PolynomialModel:
        """Per-relation ``PolynomialModel`` view (unpadded) — for
        introspection, parity tests and seed-era consumers."""
        _, target, features, degree, n_terms, n_feat = self.labels[r]
        return PolynomialModel(
            jnp.asarray(self.w[r, :n_terms]),
            np.asarray(self.exponents[r, :n_terms, :n_feat], np.int32),
            np.asarray(self.x_scale[r, :n_feat], np.float32),
            degree, tuple(features), target)

    # pytree protocol: arrays are leaves (traced — refits never recompile).
    def tree_flatten(self):
        return ((self.w, self.exponents, self.term_mask, self.x_scale),
                (self.max_degree, self.labels))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, max_degree=aux[0], labels=aux[1])


def fit_batched_arrays(Xp, Yp, row_mask, exponents, term_mask, n_terms,
                       x_scale, ridge, max_degree: int,
                       w_prior=None, prior_lam=None):
    """Unjitted vmapped ridge core — composable into larger jitted pipelines
    (the fused decide dispatches fit+solve as ONE program through this).

    ``w_prior`` (R, T_max) / ``prior_lam`` (R,) add an optional prior-mean
    ridge per relation — ``(A + (lam + prior_lam) I) w = b + prior_lam
    w_prior`` — the transfer-learning path: a relation with few (or zero)
    real rows is pulled toward fleet-mean weights, and ``prior_lam == 0``
    reproduces the unprior'd solve exactly (both are traced data, so
    engaging or decaying a prior never recompiles).  Priors on padded terms
    are masked out, preserving the w == 0 padding invariant."""
    TRACE_COUNTS["fit_batched"] += 1      # executed at trace time only
    if w_prior is None:
        w_prior = jnp.zeros(term_mask.shape, jnp.float32)
    if prior_lam is None:
        prior_lam = jnp.zeros((term_mask.shape[0],), jnp.float32)

    def one(X, Y, rm, e, tm, nt, xs, wp, pl):
        Phi = _expand_gather(X / xs, e, max_degree) * tm[None, :]
        Phi = Phi * rm[:, None]
        A = _hdot(Phi.T, Phi)
        # same scale-aware ridge as ``_fit``; the divisor is the relation's
        # *active* term count so padded shapes reproduce the unpadded lambda
        lam = ridge * (1.0 + jnp.trace(A) / nt)
        A = A + (lam + pl) * jnp.eye(Phi.shape[1], dtype=Phi.dtype)
        return jnp.linalg.solve(A, _hdot(Phi.T, Y * rm) + pl * (wp * tm))

    return jax.vmap(one)(Xp, Yp, row_mask, exponents, term_mask,
                         n_terms.astype(jnp.float32), x_scale,
                         w_prior, prior_lam)


_fit_batched = jax.jit(fit_batched_arrays, static_argnames=("max_degree",))


class StreamState(NamedTuple):
    """Device-resident streaming-fit accumulators for one ``BatchedFitPlan``.

    The expanded design rows live in a per-relation ring (newest
    ``row_capacity`` rows win, same window as ``BatchedFitPlan.fill``), and
    the Gram system (``gram`` = Phi^T Phi, ``xty`` = Phi^T y) is maintained
    incrementally by rank-k pushes of only the NEW telemetry rows — the
    ridge solve (``stream_fit_arrays``) consumes the accumulators directly,
    so a steady-state refit costs O(new rows) host work and uploads no
    design-matrix window.  A NamedTuple, hence a pytree: the whole state
    threads through (and is donated to) the fused decide program.
    """

    phi: jnp.ndarray     # (R, C, T_max) expanded rows (term-masked), ring
    y: jnp.ndarray       # (R, C)        targets, same ring order
    gram: jnp.ndarray    # (R, T_max, T_max) running Phi^T Phi
    xty: jnp.ndarray     # (R, T_max)        running Phi^T y
    count: jnp.ndarray   # (R,) int32        rows ever pushed per relation


@dataclasses.dataclass
class GramFit:
    """A Gram-backed fit handle: (plan, streaming state) standing in for
    fitted ``StackedModels``.  ``SolverProblem.stack``/``FleetSolverProblem``
    accept it anywhere models are expected — the ridge solve happens lazily
    on device from the accumulators (no design-matrix rebuild)."""

    plan: "BatchedFitPlan"
    state: StreamState

    def stacked_models(self) -> StackedModels:
        return self.plan.stream_stacked(self.state)


def pad_capacity(n: int, minimum: int = 64) -> int:
    """Fixed-capacity bucketing for padded design matrices: the next power of
    two >= n (>= ``minimum``), so row growth recompiles only O(log N) times."""
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


class BatchedFitPlan:
    """Precomputed padding tables for *repeated* batched fits.

    A cycle loop refits the same relations every 10 s with one more row of
    data; everything but the data — exponent tables, term masks, feature
    scales, labels — is static given (degrees, features, row capacity).  The
    plan builds those once (device-resident, so they are not re-uploaded per
    call) and reuses preallocated host buffers for the padded design
    matrices, making the per-cycle fit one buffer fill + one jit dispatch.

    ``relations``: one dict per relation with ``n_features``, ``degree``,
    ``x_scale`` and optional ``service`` / ``target`` / ``features`` labels.
    """

    def __init__(self, relations: Sequence[dict], row_capacity: int,
                 ridge: float = 1e-6):
        self.row_capacity = row_capacity
        self.ridge = jnp.float32(ridge)
        r_count = len(relations)
        exps = [polynomial_exponents(int(r["n_features"]), int(r["degree"]))
                for r in relations]
        self.f_max = max(max(int(r["n_features"]), 1) for r in relations)
        self.t_max = max(e.shape[0] for e in exps)
        self.max_degree = max(int(r["degree"]) for r in relations)
        E = np.zeros((r_count, self.t_max, self.f_max), np.int32)
        tmask = np.zeros((r_count, self.t_max), np.float32)
        nterms = np.zeros((r_count,), np.int32)
        scale = np.ones((r_count, self.f_max), np.float32)
        labels = []
        for i, (rel, e) in enumerate(zip(relations, exps)):
            t, f = e.shape
            E[i, :t, :f] = e
            tmask[i, :t] = 1.0
            nterms[i] = t
            scale[i, :f] = np.asarray(rel["x_scale"], np.float32)
            labels.append((rel.get("service", ""), rel.get("target", ""),
                           tuple(rel.get("features", ())),
                           int(rel["degree"]), t, f))
        self.labels = tuple(labels)
        self._E = jnp.asarray(E)
        self._tmask = jnp.asarray(tmask)
        self._nterms = jnp.asarray(nterms)
        self._scale = jnp.asarray(scale)
        # reusable host-side padded buffers: views into ONE contiguous f32
        # block, so the fused decide uploads a single array per cycle (three
        # separate device_puts measurably dominate the host overhead at
        # edge problem sizes)
        self.n_relations = r_count
        self._buf = np.zeros(r_count * row_capacity * (self.f_max + 2),
                             np.float32)
        nx = r_count * row_capacity * self.f_max
        ny = r_count * row_capacity
        self._Xp = self._buf[:nx].reshape(r_count, row_capacity, self.f_max)
        self._Yp = self._buf[nx:nx + ny].reshape(r_count, row_capacity)
        self._rmask = self._buf[nx + ny:].reshape(r_count, row_capacity)
        # streaming-fit scratch: per-k_cap delta buffers + per-plan jits
        self._stream_fns: Dict[object, object] = {}

    def fill(self, data: Sequence[Tuple[np.ndarray, np.ndarray]]
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Overwrite the reusable padded host buffers with ``data`` (one
        (X (N_r, F_r), Y (N_r,)) pair per relation, in plan order; the
        newest ``row_capacity`` rows win if N_r exceeds it) and return
        (Xp, Yp, row_mask) views — the fused decide uploads these once per
        cycle."""
        TRACE_COUNTS["h2d_design_upload"] += 1    # runtime transfer counter
        self._Xp[:] = 0.0
        self._Yp[:] = 0.0
        self._rmask[:] = 0.0
        for i, (X, Y) in enumerate(data):
            X = np.atleast_2d(np.asarray(X, np.float32))
            Y = np.asarray(Y, np.float32).reshape(-1)
            n = min(len(Y), self.row_capacity)
            self._Xp[i, :n, :X.shape[1]] = X[-n:]
            self._Yp[i, :n] = Y[-n:]
            self._rmask[i, :n] = 1.0
        return self._Xp, self._Yp, self._rmask

    def fill_packed(self, data: Sequence[Tuple[np.ndarray, np.ndarray]]
                    ) -> np.ndarray:
        """``fill`` returning the single flat backing buffer — upload once,
        ``unpack`` inside the compiled pipeline (a free reshape at trace)."""
        self.fill(data)
        return self._buf

    def unpack(self, buf):
        """Flat (traced) buffer -> (Xp, Yp, row_mask) with this plan's
        static shapes."""
        r, c, f = self.n_relations, self.row_capacity, self.f_max
        nx, ny = r * c * f, r * c
        return (buf[:nx].reshape(r, c, f), buf[nx:nx + ny].reshape(r, c),
                buf[nx + ny:].reshape(r, c))

    def fit(self, data: Sequence[Tuple[np.ndarray, np.ndarray]]
            ) -> StackedModels:
        """One standalone batched fit over ``data`` (see ``fill``)."""
        Xp, Yp, rmask = self.fill(data)
        w = _fit_batched(jnp.asarray(Xp), jnp.asarray(Yp),
                         jnp.asarray(rmask), self._E, self._tmask,
                         self._nterms, self._scale, self.ridge,
                         self.max_degree)
        return StackedModels(w, self._E, self._tmask, self._scale,
                             self.max_degree, self.labels)

    def stacked(self, w: jnp.ndarray) -> StackedModels:
        """Wrap already-computed weights (e.g. from a fused pipeline that
        ran ``fit_batched_arrays`` on-device) in this plan's static
        metadata — no host transfer."""
        return StackedModels(w, self._E, self._tmask, self._scale,
                             self.max_degree, self.labels)

    # -- streaming fit engine (device-resident Gram accumulators) -------------
    #
    # The batch path above rebuilds and uploads the full padded window every
    # call; the streaming path keeps the window ON DEVICE (``StreamState``)
    # and per cycle packs/uploads only the rows appended since the caller's
    # cursor.  ``fit_batched_arrays`` stays the parity oracle: a stream state
    # holding the same window rows solves the same ridge system (same
    # scale-aware lambda) to float32 accumulation order.

    def stream_init(self) -> StreamState:
        """Fresh all-zero accumulators (created on device — no upload)."""
        r, c, t = self.n_relations, self.row_capacity, self.t_max
        return StreamState(
            phi=jnp.zeros((r, c, t), jnp.float32),
            y=jnp.zeros((r, c), jnp.float32),
            gram=jnp.zeros((r, t, t), jnp.float32),
            xty=jnp.zeros((r, t), jnp.float32),
            count=jnp.zeros((r,), jnp.int32))

    def delta_capacity(self, k: int) -> int:
        """Power-of-two bucket for a delta push of up to ``k`` rows (>= 1,
        <= row_capacity) — steady-state cycles append one row per relation,
        so the bucket pins to 1 and the update program never retraces."""
        return min(pad_capacity(max(int(k), 1), minimum=1), self.row_capacity)

    def fill_delta(self, deltas: Sequence[Tuple[np.ndarray, np.ndarray]],
                   k_cap: int) -> np.ndarray:
        """Pack only the NEW rows (one (X (k_r, F_r), Y (k_r,)) pair per
        relation, in plan order; newest ``row_capacity`` win) into a flat
        delta buffer for ``k_cap`` — the streaming analogue of
        ``fill_packed``, O(new rows) instead of O(window).

        A FRESH buffer per call, never a reused one: jax on CPU may alias
        numpy inputs zero-copy and executes asynchronously, so repacking a
        shared buffer races the previous push's device reads (observed as
        corrupted delta masks under forced multi-device CPU).  The buffer
        is tiny (k_cap is 1 in steady state) and ``np.zeros`` is calloc —
        cheaper than re-zeroing a cached one."""
        r, f = self.n_relations, self.f_max
        nx, ny = r * k_cap * f, r * k_cap
        buf = np.zeros(nx + 2 * ny, np.float32)
        Xd = buf[:nx].reshape(r, k_cap, f)
        Yd = buf[nx:nx + ny].reshape(r, k_cap)
        dmask = buf[nx + ny:].reshape(r, k_cap)
        total = 0
        for i, (X, Y) in enumerate(deltas):
            if not (isinstance(X, np.ndarray) and X.ndim == 2
                    and X.dtype == np.float32):
                X = np.atleast_2d(np.asarray(X, np.float32))
            if not (isinstance(Y, np.ndarray) and Y.ndim == 1
                    and Y.dtype == np.float32):
                Y = np.asarray(Y, np.float32).reshape(-1)
            n = min(len(Y), k_cap)
            if n:
                Xd[i, :n, :X.shape[1]] = X[-n:]
                Yd[i, :n] = Y[-n:]
                dmask[i, :n] = 1.0
            total += n
        TRACE_COUNTS["h2d_delta_rows"] += total   # runtime transfer counter
        return buf

    def unpack_delta(self, dbuf, k_cap: int):
        """Flat (traced) delta buffer -> (Xd, Yd, dmask)."""
        r, f = self.n_relations, self.f_max
        nx, ny = r * k_cap * f, r * k_cap
        return (dbuf[:nx].reshape(r, k_cap, f),
                dbuf[nx:nx + ny].reshape(r, k_cap),
                dbuf[nx + ny:].reshape(r, k_cap))

    def stream_update_arrays(self, state: StreamState, Xd, Yd, dmask
                             ) -> StreamState:
        """Rank-k accumulator push (traced, composable into fused pipelines).

        Per relation: expand the (masked) new rows, subtract the ring rows
        they overwrite from the Gram system (eviction — the training window
        is the newest ``row_capacity`` rows, exactly ``fill``'s), add the
        new contributions, and scatter the rows into the ring.  Rows beyond
        ``dmask`` scatter out of bounds and are dropped.  Requires
        k_cap <= row_capacity (``fill_delta`` enforces it)."""
        TRACE_COUNTS["stream_update"] += 1        # trace-time only
        cap, d = self.row_capacity, self.max_degree

        def one(phi_r, y_r, G, b, count, X, Y, dm, e, tm, xs):
            phi_new = _expand_gather(X / xs, e, d) * tm[None, :]
            phi_new = phi_new * dm[:, None]                   # (k, T)
            y_new = Y * dm
            pos = count + jnp.arange(X.shape[0], dtype=jnp.int32)
            slot = jnp.where(dm > 0, pos % cap, cap)          # OOB -> dropped
            evict = ((dm > 0) & (pos >= cap)).astype(phi_new.dtype)
            take = jnp.clip(slot, 0, cap - 1)
            phi_old = phi_r[take] * evict[:, None]
            y_old = y_r[take] * evict
            G = G + _hdot(phi_new.T, phi_new) - _hdot(phi_old.T, phi_old)
            b = b + _hdot(phi_new.T, y_new) - _hdot(phi_old.T, y_old)
            phi_r = phi_r.at[slot].set(phi_new, mode="drop")
            y_r = y_r.at[slot].set(y_new, mode="drop")
            return phi_r, y_r, G, b, count + jnp.sum(dm).astype(jnp.int32)

        phi, y, gram, xty, count = jax.vmap(one)(
            state.phi, state.y, state.gram, state.xty, state.count,
            Xd, Yd, dmask, self._E, self._tmask, self._scale)
        return StreamState(phi, y, gram, xty, count)

    def stream_resync_arrays(self, state: StreamState) -> StreamState:
        """Recompute the Gram system exactly from the device ring (traced).

        The incremental add/subtract drifts at float32 epsilon per push;
        a periodic resync (still zero host->device transfers — the ring IS
        the window) keeps the accumulated error bounded regardless of run
        length."""
        TRACE_COUNTS["stream_resync"] += 1        # trace-time only
        cap = self.row_capacity

        def one(phi_r, y_r, count):
            valid = (jnp.arange(cap) < jnp.minimum(count, cap)
                     ).astype(phi_r.dtype)
            pm = phi_r * valid[:, None]
            return _hdot(pm.T, pm), _hdot(pm.T, y_r * valid)

        gram, xty = jax.vmap(one)(state.phi, state.y, state.count)
        return StreamState(state.phi, state.y, gram, xty, state.count)

    def stream_fit_arrays(self, state: StreamState, w_prior=None,
                          prior_lam=None) -> jnp.ndarray:
        """Ridge solve straight from the accumulators (traced) — the same
        scale-aware lambda as ``fit_batched_arrays`` (trace(G) IS trace(A)),
        with zero design-matrix work.  ``w_prior``/``prior_lam`` add the
        same optional prior-mean ridge as ``fit_batched_arrays`` (transfer
        learning); ``prior_lam == 0`` solves the exact unprior'd system."""
        TRACE_COUNTS["fit_gram"] += 1             # trace-time only
        ridge = self.ridge
        if w_prior is None:
            w_prior = jnp.zeros((self.n_relations, self.t_max), jnp.float32)
        if prior_lam is None:
            prior_lam = jnp.zeros((self.n_relations,), jnp.float32)

        def one(G, b, nt, tm, wp, pl):
            lam = ridge * (1.0 + jnp.trace(G) / nt)
            A = G + (lam + pl) * jnp.eye(G.shape[0], dtype=G.dtype)
            return jnp.linalg.solve(A, b + pl * (wp * tm))

        return jax.vmap(one)(state.gram, state.xty,
                             self._nterms.astype(jnp.float32), self._tmask,
                             w_prior, prior_lam)

    # host-side conveniences (each jitted once per plan) --------------------
    def _stream_jit(self, name: str, build):
        fn = self._stream_fns.get(name)
        if fn is None:
            fn = self._stream_fns[name] = build()
        return fn

    def stream_push(self, state: StreamState,
                    deltas: Sequence[Tuple[np.ndarray, np.ndarray]]
                    ) -> StreamState:
        """Standalone rank-k push: pack ``deltas`` and update on device."""
        k_cap = self.delta_capacity(max((len(np.atleast_1d(Y)) for _, Y
                                         in deltas), default=1))
        dbuf = self.fill_delta(deltas, k_cap)
        fn = self._stream_jit(("push", k_cap), lambda: jax.jit(
            lambda st, b: self.stream_update_arrays(
                st, *self.unpack_delta(b, k_cap))))
        return fn(state, jnp.asarray(dbuf))

    def stream_rebuild(self, data: Sequence[Tuple[np.ndarray, np.ndarray]]
                       ) -> StreamState:
        """Fresh state holding the newest ``row_capacity`` rows of ``data``
        — the recovery path after churn/migration invalidates the state.
        This IS a full design-window upload and counts as one."""
        TRACE_COUNTS["h2d_design_upload"] += 1    # runtime transfer counter
        return self.stream_push(self.stream_init(), data)

    def stream_resync(self, state: StreamState) -> StreamState:
        fn = self._stream_jit("resync",
                              lambda: jax.jit(self.stream_resync_arrays))
        return fn(state)

    def stream_fit(self, state: StreamState) -> StackedModels:
        """Solve the accumulators into ``StackedModels`` (device-resident)."""
        fn = self._stream_jit("fit", lambda: jax.jit(self.stream_fit_arrays))
        return self.stacked(fn(state))

    def stream_stacked(self, state: StreamState) -> StackedModels:
        return self.stream_fit(state)


def fit_batched(relations: Sequence[dict], ridge: float = 1e-6,
                row_capacity: Optional[int] = None) -> StackedModels:
    """Fit all relations' Eq. (2) ridge systems in one vmapped jitted call.

    Each relation is a dict with keys ``X`` (N_r, F_r), ``Y`` (N_r,),
    ``degree``, ``x_scale`` (F_r,), and optional ``service`` / ``target`` /
    ``features`` labels.  One-shot convenience wrapper over
    ``BatchedFitPlan`` (which is what a cycle loop should hold on to);
    per-relation results match ``fit_polynomial`` on the unpadded data.
    """
    if not relations:
        raise ValueError("fit_batched needs at least one relation")
    data = []
    metas = []
    n_max = 0
    for r in relations:
        X = np.atleast_2d(np.asarray(r["X"], np.float32))
        Y = np.asarray(r["Y"], np.float32).reshape(-1)
        n_max = max(n_max, len(Y))
        data.append((X, Y))
        metas.append(dict(r, n_features=X.shape[1]))
    cap = row_capacity if row_capacity is not None else pad_capacity(n_max)
    if cap < n_max:
        raise ValueError(f"row_capacity {cap} < largest relation ({n_max} rows)")
    return BatchedFitPlan(metas, row_capacity=cap, ridge=ridge).fit(data)


def stack_models(models: Sequence[PolynomialModel],
                 services: Sequence[str] = ()) -> StackedModels:
    """Pad already-fitted per-relation models into one ``StackedModels``."""
    if not models:
        raise ValueError("stack_models needs at least one model")
    r_count = len(models)
    t_max = max(m.w.shape[0] for m in models)
    f_max = max(m.exponents.shape[1] for m in models)
    d_max = max(m.degree for m in models)
    w = np.zeros((r_count, t_max), np.float32)
    E = np.zeros((r_count, t_max, f_max), np.int32)
    tmask = np.zeros((r_count, t_max), np.float32)
    scale = np.ones((r_count, f_max), np.float32)
    labels = []
    svc = list(services) if services else [""] * r_count
    for i, m in enumerate(models):
        t, f = m.exponents.shape
        w[i, :t] = np.asarray(m.w, np.float32)
        E[i, :t, :f] = m.exponents
        tmask[i, :t] = 1.0
        scale[i, :f] = m.x_scale
        labels.append((svc[i], m.target, tuple(m.features), m.degree, t, f))
    return StackedModels(jnp.asarray(w), jnp.asarray(E), jnp.asarray(tmask),
                         jnp.asarray(scale), d_max, tuple(labels))
