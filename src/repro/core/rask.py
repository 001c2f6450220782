"""RASK — Regression Analysis of Structural Knowledge (paper §IV, Algorithm 1).

Per 10 s cycle the agent:
  1. observes stabilized service states (windowed mean of the last 5 s, §IV-A)
     and appends them to its training table D;
  2. while rounds < xi: returns RAND_PARAM (Eq. 3) — uniform exploration
     within bounds subject to the resource constraint (per host on a Fleet);
  3. otherwise fits one polynomial regression per structural relation k in K
     (Eq. 2, degree delta), hands the model W + SLOs Q + bounds P + constraint
     C to the numerical solver (Eq. 4), warm-starting from the cached previous
     assignment (§IV-B3), and
  4. perturbs the solution with Gaussian action noise NOISE(a, eta) (Eq. 5)
     and emits the result as a declarative ``ScalingPlan`` that MUDAP (or a
     multi-host ``Fleet``) applies transactionally.

Single-dispatch fused decide (the default: ``fused=True, backend="pgd"``)
--------------------------------------------------------------------------
The whole post-exploration cycle — the batched ridge fit over padded design
matrices, the multi-start projected-gradient solve, the exact capacity
projection and the Gaussian NOISE — is composed into ONE jitted on-device
pipeline: the stacked models never leave the device, the streaming fit's
device-resident state is donated to the compiled program, and a single
host transfer at the end extracts [cached optimum | noised plan | scores].
On a multi-host ``Fleet`` the same pipeline solves every host's subproblem
against its OWN capacity in one vmapped dispatch (``FleetSolverProblem``),
replacing the aggregate-capacity relaxation — the produced plans are
per-host feasible, so apply-time arbitration no longer clips them.  (The
SLSQP and ``fused=False`` reference paths still solve the aggregate and
rely on apply-time water-filling, like the seed did.)

``backend="slsqp"`` keeps the paper-faithful scipy reference (one dispatch
plus one device->host sync per line-search iteration); the parity gate in
tests/test_solver.py holds the two backends to the same objective scores on
the paper scenarios.  The seed's per-relation Python loop survives behind
``fused=False`` as the e7 benchmark baseline.  ``self.models`` keeps the
seed's {service: {target: PolynomialModel}} *view* (sliced out of the
stack) for introspection and downstream consumers (e3, DQN pretraining).

Beyond-paper extensions (used in EXPERIMENTS.md §Perf):
  * ``eta_decay`` — E1 observes "the noise should decay as the performance
    converges"; eta_t = eta * decay**(rounds - xi);
  * ``auto_degree`` — per-service polynomial degree selected by test-split MSE
    (the E2/§VI-C2 recommendation);
  * ``objective_impl`` — scoring kernel for the PGD candidates
    ("reference" | "pallas" | "pallas_interpret", kernels/rask_objective.py);
  * ``rebalance_every`` — per-cycle placement stage: every N cycles one
    candidate-batched ``placement_scores`` snapshot (ONE jitted dispatch for
    all (service, host) what-ifs — ``PlacementProblem``) and at most one
    migration toward higher predicted marginal fulfillment;
  * ``adapt_budget`` — online solver budget adaptation: pgd_iters/pgd_starts
    halve toward floors while the warm-start optimum is stationary (E5
    steady state) and restore on load shifts; the active budget is recorded
    in ``DecisionInfo``;
  * ``refresh_topology`` — re-binds the agent after churn (host failure or
    drain, capacity degradation, service arrival/departure) without
    discarding surviving services' models, training rows, or warm starts;
  * ``attach_accountant`` — binds the SLO error-budget control plane
    (``repro.obs``): a firing fast-burn alert overrides the rebalance
    cadence and the budget adaptation, and burn weights order the
    placement moves (``RaskConfig.burn_control``).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace
# CycleResult is re-exported here for seed-era callers (it moved to api.py)
from .api import CycleResult, DecisionInfo, PlanningAgent, ScalingPlan
from .forecast import LoadForecaster
from .platform import MUDAP
from .regression import BatchedFitPlan, PolynomialModel, StackedModels, \
    TRACE_COUNTS, fit_batched_arrays, fit_polynomial, pad_capacity, \
    select_degree
from .regression import GramFit, StreamState  # noqa: F401 (re-export)
from .solver import FleetSolverProblem, PlacementProblem, ServiceSpec, \
    SolverProblem, cached_fn, pgd_solve
from .telemetry import TrainingTable

# Structural knowledge K: per service, target -> feature parameter names.
# E.g. {"tp_max": ("cores", "data_quality")} — Eq. (7).
Knowledge = Mapping[str, Mapping[str, Sequence[str]]]


@dataclasses.dataclass
class RaskConfig:
    xi: int = 20                # initial exploration rounds
    eta: float = 0.0            # Gaussian action-noise ratio
    delta: int = 2              # default polynomial degree
    delta_per_service: Optional[Dict[str, int]] = None
    backend: str = "pgd"        # "pgd" (default) | "slsqp" (paper reference)
    cache: bool = True          # §IV-B3 warm-start from last assignment
    ridge: float = 1e-6
    eta_decay: float = 1.0      # beyond-paper: <1.0 decays noise after xi
    auto_degree: bool = False   # beyond-paper: per-service degree by CV
    auto_degree_every: int = 10
    pgd_starts: int = 6
    pgd_iters: int = 32
    pgd_lr: float = 0.18
    resource: str = "cores"     # the shared-capacity resource name
    fused: bool = True          # batched fit + fused objective (False: seed loop)
    # PGD candidate scoring kernel: "reference" (fused jnp, the default) |
    # "pallas" | "pallas_interpret".  NOTE: on CPU both Pallas modes run
    # through the interpreter and are SLOWER than the fused jnp path (e7
    # measures ~1.5-2x on the steady decide); select "pallas" only when
    # lowering to a real TPU/GPU backend.
    objective_impl: str = "reference"
    # streaming device-resident fit engine: the padded design window lives
    # ON DEVICE as per-relation rings + Gram accumulators (regression.py
    # ``StreamState``); each cycle packs and uploads only the telemetry rows
    # appended since the last cycle's cursor (steady state: ONE row per
    # relation), and the ridge solve consumes the accumulators directly —
    # the rebuild-and-upload of the full window (``fill_packed``) happens
    # only on invalidation (churn/migration ``_topo_gen`` bumps, degree or
    # row-bucket changes, training-table compaction overruns).  Zero
    # steady-state design-matrix uploads, gated on
    # ``TRACE_COUNTS["h2d_design_upload"]``.
    streaming_fit: bool = True
    # exact Gram recompute (from the device ring — still no upload) every N
    # delta pushes, bounding float32 accumulate/evict drift; 0 disables
    stream_resync_every: int = 64
    # per-service TrainingTable retention (rows); rounded up to a power of
    # two so the host window and the device ring evict in lockstep.  None
    # keeps the seed's unbounded table.
    table_retention: Optional[int] = 1024
    # AOT-compile the fused decide (jax.jit(...).lower(...).compile()):
    # compiled executables are called directly, skipping per-call jit
    # dispatch resolution; ``RASKAgent.precompile`` warms layout buckets
    # from ShapeDtypeStruct avals before the control loop starts
    aot: bool = True
    # device sharding of the bucketed fleet/placement solves
    # (solver.shard_rows): "auto" (default) spreads each bucket's vmapped
    # solve over every available device and degrades to the plain
    # single-device vmap when jax.device_count() == 1 — results are
    # byte-identical either way.  False disables; an int caps the count.
    shard: Union[bool, int, str, None] = "auto"
    # pipelined decide (dispatch-then-collect): each decide ASYNC-dispatches
    # this cycle's fit+solve and returns the plan collected from the
    # PREVIOUS cycle's dispatch, so the solve runs on device while the
    # environment applies the plan and scrapes telemetry — the 10 s control
    # interval hides the solve latency entirely.  Plans lag observations by
    # one cycle; the first post-exploration cycle is a pipeline-fill round
    # (no solved plan yet).  Per-phase timings land in DecisionInfo.
    pipeline: bool = False
    # per-cycle placement stage: every N post-exploration cycles take one
    # batched placement-score snapshot and apply at most one migration
    # (0 = off; rebalancing then only happens via explicit ``rebalance()``)
    rebalance_every: int = 0
    # placement scoring budget: candidate subsets are warm-started from the
    # cached optimum's slices and only their marginal ORDERING matters (the
    # hysteresis gate absorbs score polish), so the scorer runs a lighter
    # deterministic budget than the decide solve — this is what makes the
    # one-dispatch snapshot cheap enough for the per-cycle stage
    score_starts: int = 4
    score_iters: int = 16
    # online solver budget adaptation (beyond-paper, opt-in): shrink
    # pgd_iters/pgd_starts toward the floors while the warm-started optimum
    # value stays within adapt_tol for adapt_patience consecutive solve
    # cycles (E5 steady state); restore the full budget on any larger move
    # (a load shift)
    adapt_budget: bool = False
    adapt_tol: float = 0.01         # relative solver-score movement = calm
    # restore threshold (None -> 5 * adapt_tol): a shrunk budget solves
    # noisier, so the gap between "not calm" and "load shift" is hysteresis
    # — without it the floor budget's own solution noise would restore the
    # full budget and the adaptation would flap
    adapt_restore_tol: Optional[float] = None
    adapt_patience: int = 3         # calm cycles before each halving
    adapt_iters_floor: int = 8
    adapt_starts_floor: int = 2
    # the placement scorer follows the same shrink/restore hysteresis (its
    # own floors: the scorer already runs a lighter budget than the solve)
    adapt_score_iters_floor: int = 8
    adapt_score_starts_floor: int = 2
    # SLO error-budget control (repro.obs, active once an accountant is
    # attached): a firing fast-burn alert overrides the rebalance cadence
    # (snapshot every cycle until it clears) and the budget adaptation
    # (full solver budget restored, no shrinking while burning), and
    # placement-score rows are scaled by the burn weights so the per-
    # snapshot migration budget goes to the services burning fastest
    burn_control: bool = True
    burn_weight_cap: float = 4.0    # max extra weight (see burn_weights)
    # proactive scaling (core/forecast.py): per-service AR(forecast_lags)
    # load forecasters ride INSIDE the fused decide (their ridge fit and
    # prediction are composed into the same single dispatch — zero extra
    # programs, zero steady-state recompiles), and ``_rps_vector`` solves
    # against predicted-horizon load wherever the hybrid gate trusts the
    # forecaster: a service goes proactive only after forecast_min_evals
    # scored predictions with rolling relative error <= forecast_gate_tol,
    # and falls back to reactive rps the moment its error spikes.  Off the
    # fused PGD path (classic/slsqp/fused=False) the flag is inert.
    forecast: bool = False
    horizon_s: float = 10.0         # how far ahead the solve looks
    forecast_cycle_s: float = 10.0  # control interval (horizon_s -> steps)
    forecast_lags: int = 8          # AR window length (rps history rows)
    forecast_gate_tol: float = 0.35     # rolling rel. error gate threshold
    forecast_min_evals: int = 3     # scored predictions before going proactive
    forecast_err_window: int = 8    # rolling-error window (predictions)
    # transfer learning across churn: at a service-set change the agent
    # captures fleet-mean regression weights per service TYPE (and the
    # forecaster's AR weights) and warm-starts every newly arrived
    # service's relations from them through the prior-mean ridge — so an
    # arrival no longer drops the whole fleet back into exploration while
    # the new relations accumulate >= 3 rows.  The prior decays linearly
    # to zero as transfer_min_rows real rows arrive.
    transfer_priors: bool = True
    transfer_strength: float = 1.0
    transfer_min_rows: int = 3


# host-side stand-in for "no new rows this cycle" (rebuild cycles push the
# window via ``stream_rebuild`` and then run the delta program empty)
_EMPTY_X = np.zeros((0, 1), np.float32)
_EMPTY_Y = np.zeros((0,), np.float32)


class _AotFn:
    """Ahead-of-time-compiled jit wrapper for the fused decide.

    ``jax.jit`` re-resolves its dispatch on every call (signature hashing,
    cache lookup, guard logic); at edge problem sizes that per-call overhead
    is a visible slice of the ~ms decide (benchmarks/roofline.py measures
    it).  This wrapper lowers and compiles ONCE per concrete signature —
    ``jax.jit(f).lower(*args).compile()`` — and then invokes the compiled
    executable directly.  ``warm`` also accepts ``jax.ShapeDtypeStruct``
    avals, so ``RASKAgent.precompile`` can move the whole trace+compile out
    of the control loop without touching data.  A signature change falls
    back to a fresh lower+compile; the fused-fn cache keys on everything
    that changes shapes, so that is cold-path only."""

    def __init__(self, fn, donate: Tuple[int, ...] = ()):
        self._jit = jax.jit(fn, donate_argnums=donate)
        self._compiled = None
        self._sig = None

    @staticmethod
    def _sig_of(args) -> tuple:
        return tuple((tuple(l.shape), np.dtype(l.dtype))
                     for l in jax.tree_util.tree_leaves(args))

    def warm(self, *args) -> None:
        """Lower+compile for ``args`` (arrays OR ShapeDtypeStruct avals)."""
        self._compiled = self._jit.lower(*args).compile()
        self._sig = self._sig_of(args)

    def export_roundtrip(self, *args):
        """``jax.export`` round-trip of the underlying program: serialize,
        deserialize, return the rehydrated callable — proof the compiled
        decide survives a process boundary (AOT artifact caching)."""
        from jax import export as jax_export
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), np.dtype(a.dtype)),
            args)
        exp = jax_export.export(jax.jit(self._jit.__wrapped__))(*avals)
        return jax_export.deserialize(exp.serialize()).call

    def __call__(self, *args):
        if self._compiled is None or self._sig != self._sig_of(args):
            self.warm(*args)
        return self._compiled(*args)


class RASKAgent(PlanningAgent):
    """The action-perception loop of Fig. 3 bound to one MUDAP platform
    (or a multi-host ``Fleet`` — anything with the plan/telemetry surface)."""

    name = "rask"

    def __init__(self, platform: MUDAP, knowledge: Knowledge,
                 config: Optional[RaskConfig] = None, seed: int = 0):
        super().__init__()
        self.platform = platform
        self.knowledge = knowledge
        self.cfg = config if config is not None else RaskConfig()
        self.rng = np.random.default_rng(seed)
        # bounded training table: retention is rounded to a power of two so
        # the host window and the streaming device ring evict in lockstep
        ret = self.cfg.table_retention
        self.table = TrainingTable(
            retention=None if ret is None else pad_capacity(int(ret),
                                                            minimum=1))
        self.rounds = -1            # Algo 1 line 2: first cycle -> 0
        self.services = platform.services()
        self.capacity = platform.capacity[self.cfg.resource]
        self._degrees: Dict[str, int] = {}
        self._cached_x: Optional[np.ndarray] = None
        self.problem = self._build_problem()
        # pipelined decide state: the in-flight dispatched solve (collected
        # by the NEXT decide) and a topology generation counter — a pending
        # result whose generation is stale (rebalance move, churn) is
        # dropped instead of being applied to the wrong layout
        self._pending: Optional[dict] = None
        self._topo_gen = 0
        # on a Fleet, decide against each host's OWN capacity (one vmapped
        # solve per layout bucket) instead of the aggregate relaxation
        self.fleet_problem: Optional[FleetSolverProblem] = None
        self._build_fleet_problem()
        # candidate-batched placement scorers, keyed on residency topology
        self._placement_cache: Dict[tuple, PlacementProblem] = {}
        self._models_loop: Dict[str, Dict[str, PolynomialModel]] = {}
        self._models_view: Optional[Dict[str, Dict[str, PolynomialModel]]] = None
        self.stacked: Optional[StackedModels] = None   # fused-path models
        self._row_capacity = 0      # padded-fit bucket (power-of-two growth)
        self._fit_plan: Optional[BatchedFitPlan] = None
        self._fit_plan_key = None
        # streaming-fit state (``_prepare_fit``): the device-resident
        # StreamState plus per-relation total-index cursors into the
        # training table, the topology generation and plan key it was built
        # against, per-relation window row counts, and the push counter
        # driving the periodic exact resync
        self._stream: Optional[dict] = None
        self._fused_fns: Dict[tuple, callable] = {}
        self._warm_keys: set = set()     # fused pipeline keys already compiled
        self._timed_first_solve = False  # classic-path compile accounting
        self._cycle_draws = None         # per-decide randomness (reused on re-run)
        self._last_solve_cold = False    # last _solve_cycle compiled a variant
        # online budget adaptation state (active PGD budget; equals the
        # configured budget unless adapt_budget has shrunk it)
        self._budget_iters = self.cfg.pgd_iters
        self._budget_starts = self.cfg.pgd_starts
        self._score_iters = self.cfg.score_iters
        self._score_starts = self.cfg.score_starts
        self._calm_cycles = 0
        self._last_score: Optional[float] = None
        # SLO error-budget control plane (attach_accountant): burn states
        # refreshed by observe(), consumed by decide()'s rebalance/budget
        # stages
        self.accountant = None
        self.burn_states: Dict[str, object] = {}
        # last-known per-service rps (fed by observe/_rps_vector): the
        # fallback when a cycle's observe window is empty — a paused scrape
        # mid-traffic must not be solved as zero load
        self._last_rps: Dict[str, float] = {}
        self._rps_scale: Dict[str, float] = {}   # running max (fc x_scale)
        # proactive scaling state (RaskConfig(forecast=True)): the
        # LoadForecaster bound to the current plan/topology and the fit
        # input it prepared for this cycle's dispatch
        self._forecast: Optional[LoadForecaster] = None
        self._fc_prep = None
        # transfer-learning priors captured at churn: fleet-mean regression
        # weights keyed (service type, target, degree, n_features), the
        # forecaster's per-type AR means, and the cached zero-prior arrays
        # dispatched while no prior is live
        self._transfer_priors: Dict[tuple, np.ndarray] = {}
        self._fc_priors: Dict[str, np.ndarray] = {}
        self._prior_zero: Optional[tuple] = None
        # cumulative counters for the metric registry (repro.obs.registry)
        self.moves_total = 0
        self.compile_s_total = 0.0
        self._build_rel_static()

    def _build_rel_static(self) -> None:
        """Static per-relation fit metadata (feature names + scales), in the
        problem's global relation order."""
        self._rel_static: List[Tuple[str, str, Tuple[str, ...], np.ndarray]] = []
        self._sid_types: Dict[str, str] = {}
        for _, sid, target, _ in self.problem.relations:
            svc = self.platform.service(sid)
            self._sid_types[sid] = svc.sid.type
            feats = tuple(self.knowledge[svc.sid.type][target])
            scale = np.asarray(
                [svc.api.parameter(f).max_value for f in feats], np.float32)
            self._rel_static.append((sid, target, feats, scale))

    @property
    def models(self) -> Dict[str, Dict[str, PolynomialModel]]:
        """Seed-style {service: {target: PolynomialModel}} view.

        In fused mode the per-relation models are sliced lazily out of the
        stacked pytree (building them eagerly would add a host sync to every
        cycle); in loop mode this is the dict the fit writes into.
        """
        if not self.cfg.fused:
            return self._models_loop
        if self._models_view is None and self.stacked is not None:
            self._models_view = self.problem.models_dict(self.stacked)
        return self._models_view if self._models_view is not None else {}

    def _build_fleet_problem(self) -> None:
        """(Re)bind the per-host fleet solve to the platform's CURRENT
        placement — called at construction and again after ``rebalance``
        migrates services (the bucket layouts follow the topology).  Any
        in-flight pipelined solve targets the OLD topology and is dropped."""
        self._topo_gen += 1
        self._pending = None
        platform = self.platform
        if hasattr(platform, "hosts") and hasattr(platform, "host_of"):
            self.fleet_problem = FleetSolverProblem(
                self.problem,
                {sid: platform.host_of(sid).host for sid in self.services},
                {h.host: h.capacity[self.cfg.resource]
                 for h in platform.hosts()},
                shard=self.cfg.shard)

    # -- problem construction -------------------------------------------------
    def _build_problem(self) -> SolverProblem:
        specs = []
        for sid in self.services:
            svc = self.platform.service(sid)
            api = svc.api
            names = tuple(api.names)
            rels = []
            for target, feats in self.knowledge[svc.sid.type].items():
                rels.append((target, tuple(names.index(f) for f in feats)))
            specs.append(ServiceSpec(
                name=sid,
                param_names=names,
                lower=tuple(p.min_value for p in api.parameters),
                upper=tuple(p.max_value for p in api.parameters),
                resource_mask=tuple(p.is_resource and p.name == self.cfg.resource
                                    for p in api.parameters),
                slos=tuple(svc.slos),
                relation_features=tuple(rels)))
        return SolverProblem(specs, fused=self.cfg.fused)

    # -- SLO error-budget control plane (repro.obs) -----------------------------
    def attach_accountant(self, accountant) -> None:
        """Bind an ``obs.SLOAccountant``: every ``observe`` refreshes its
        rolling SLI rings (one bulk columnar pass, plain numpy — no jit
        traces), and ``decide`` consumes the burn state as a first-class
        control signal (see ``RaskConfig.burn_control``)."""
        self.accountant = accountant

    def _fast_alerts(self) -> List[str]:
        """Services whose fastest burn policy is firing (empty without an
        attached accountant or with ``burn_control`` off)."""
        if self.accountant is None or not self.cfg.burn_control:
            return []
        return self.accountant.fast_alerts()

    def _max_burn(self) -> float:
        """Worst long-window burn rate across services (0.0 when idle)."""
        return max((st.burn_rate() for st in self.burn_states.values()),
                   default=0.0)

    # -- observation (§IV-A) ---------------------------------------------------
    def observe(self, t: float, window: float = 5.0) -> Dict[str, Dict[str, float]]:
        """Append the stabilized state of each service to D; returns the states.

        All services are read with one bulk telemetry query (one lock/scan
        instead of |S|)."""
        with trace.span(trace.RASK_OBSERVE) as span:
            states = {}
            windowed = self.platform.window_states(since=t - window, until=t)
            for sid in self.services:
                state = windowed.get(sid)
                if not state:
                    continue
                row = dict(state)
                # features = applied params
                row.update(self.platform.assignment(sid))
                self.table.append(sid, row)
                states[sid] = row
                rps = row.get("rps")
                if rps is not None and np.isfinite(rps):
                    self._last_rps[sid] = float(rps)
                    self._rps_scale[sid] = max(self._rps_scale.get(sid, 0.0),
                                               float(rps))
            if self.accountant is not None:
                self.burn_states = self.accountant.update(t)
            span.set_metadata(rows=len(states))
        return states

    # -- Algorithm 1 ------------------------------------------------------------
    def decide(self, obs: Mapping[str, Mapping[str, float]]) -> ScalingPlan:
        """One RASK round: explore or fit+solve; returns the proposed plan
        (the caller — environment or ``cycle`` — applies it)."""
        with trace.span(trace.RASK_DECIDE) as span:
            plan = self._decide(obs)
            span.set_metadata(explored=int(self.last_decision.explored))
        return plan

    def _decide(self, obs) -> ScalingPlan:
        self.rounds += 1
        if self.rounds < self.cfg.xi:                       # lines 3-5
            self.last_decision = DecisionInfo(explored=True)
            return self._plan(self._explore())

        alerts = self._fast_alerts()
        if alerts:
            # a firing fast-burn alert is a regime change by definition:
            # restore the full solver budget at once (the shrunk steady-
            # state budget solves noisier exactly when precision matters
            # most) and hold off further shrinking until the alert clears
            self._budget_iters = self.cfg.pgd_iters
            self._budget_starts = self.cfg.pgd_starts
            self._score_iters = self.cfg.score_iters
            self._score_starts = self.cfg.score_starts
            self._calm_cycles = 0
        moves, scored = self._maybe_rebalance(obs, alerts)
        if self.cfg.pipeline and self.cfg.fused and self.cfg.backend == "pgd":
            return self._decide_pipelined(obs, moves, scored, alerts)
        t0 = time.perf_counter()
        self._cycle_draws = None      # per-cycle randomness, drawn once
        out = self._solve_cycle(obs)                        # lines 6-11
        if out is None:
            self.last_decision = DecisionInfo(
                explored=True, moves=len(moves),
                score_starts=self._score_starts if scored else 0,
                score_iters=self._score_iters if scored else 0,
                burn_alerts=len(alerts), max_burn=self._max_burn())
            return self._plan(self._explore())
        if self._last_solve_cold:
            # that run paid jit trace+compile time: re-run the whole cycle
            # — byte-identical (the drawn seed/warm-start/noise are reused)
            # and covering the same fit+solve window warm cycles measure —
            # so runtime_s reports the steady-state cost and compile_s the
            # rest.  Covers the first solve AND later retraces (row-bucket
            # growth, auto_degree changes): E4-E6 plots carry no compile
            # spikes.
            t1 = time.perf_counter()
            out = self._solve_cycle(obs)
            t2 = time.perf_counter()
            runtime, compile_s = t2 - t1, max((t1 - t0) - (t2 - t1), 0.0)
        else:
            runtime, compile_s = time.perf_counter() - t0, 0.0
        a, noised, score = out
        dispatch_s, collect_s = self._phase_s
        used_starts, used_iters = self._budget_starts, self._budget_iters
        self._cached_x = np.asarray(a, np.float32)          # §IV-B3 cache
        prev_score, self._last_score = self._last_score, float(score)
        if not alerts:      # no shrinking while the error budget is burning
            self._adapt_budget(prev_score, float(score))
        self.moves_total += len(moves)
        self.compile_s_total += compile_s
        self.last_decision = DecisionInfo(
            explored=False, runtime_s=runtime, compile_s=compile_s,
            score=score, pgd_starts=used_starts, pgd_iters=used_iters,
            moves=len(moves),
            score_starts=self._score_starts if scored else 0,
            score_iters=self._score_iters if scored else 0,
            burn_alerts=len(alerts), max_burn=self._max_burn(),
            dispatch_s=dispatch_s, collect_s=collect_s, **self._fc_stats())
        return self._plan(noised)

    def _decide_pipelined(self, obs, moves, scored: bool,
                          alerts: Sequence[str]) -> ScalingPlan:
        """Dispatch-then-collect decide (``RaskConfig(pipeline=True)``).

        Phase 1 COLLECTS the solve dispatched by the *previous* decide —
        ``jax.block_until_ready`` plus the cycle's one device->host
        transfer; having had the whole control interval to run, the solve
        is normally already done and the block is near-free.  Phase 2
        fits this cycle's data and ASYNC-dispatches the next solve (the
        fused jit call returns device futures; the computation overlaps
        the environment's apply + settle + scrape until the next decide).
        The emitted plan is the collected (previous) cycle's — a one-cycle
        plan lag in exchange for hiding the whole solve latency.  Warm
        starts stay as fresh as the synchronous path: the collect happens
        before the dispatch, so the new solve warm-starts from the optimum
        just collected.  A pending result whose topology generation is
        stale (rebalance move, churn) is dropped, and the cycle degrades
        to a pipeline-fill round."""
        # -- phase 1: collect the in-flight solve -----------------------------
        t0 = time.perf_counter()
        pend, self._pending = self._pending, None
        collected = None
        if pend is not None and pend["gen"] == self._topo_gen:
            with trace.span(trace.RASK_COLLECT):
                jax.block_until_ready((pend["out"], pend["w"]))
                out = np.asarray(pend["out"])   # the cycle's ONE transfer
                self.stacked = pend["plan"].stacked(pend["w"])
            self._models_view = None
            a, noised, score, pred = self._split_out(
                out, pend["dim"], pend.get("n_fc", 0))
            collected = (a, noised, score)
            if pred is not None and self._forecast is not None:
                # the prediction dispatched last cycle targets fc_target;
                # settle() in this cycle's dispatch scores it when due
                self._forecast.note(pend["fc_target"], pred)
        collect_s = time.perf_counter() - t0
        if collected is not None:
            a, noised, score = collected
            self._cached_x = np.asarray(a, np.float32)      # §IV-B3 cache
            prev_score, self._last_score = self._last_score, float(score)
            if not alerts:  # no shrinking while the error budget is burning
                self._adapt_budget(prev_score, float(score))

        # -- phase 2: fit + async-dispatch the next solve ---------------------
        dispatch_s = compile_s = 0.0
        used_starts = used_iters = 0
        with trace.span(trace.RASK_PACK) as span:
            prep = self._prepare_fit()
            if prep is not None:
                span.set_metadata(rows=self._prep_rows(prep))
                seed = int(self.rng.integers(2 ** 31))
                x0 = self._x0()
        if prep is None:
            if collected is None:
                self.stacked = None       # models incomplete: keep exploring
        else:
            fkey = self._fused_key(self._prep_k_cap(prep), self._fc_k_cap())
            cold = self._prep_cold(prep) or \
                not (fkey in self._warm_keys and fkey in self._fused_fns)
            plan = self._fit_plan
            td = time.perf_counter()
            out_dev, w_dev, _, n_fc = self._dispatch_fused(prep, obs, seed, x0)
            dispatch_s = time.perf_counter() - td
            fc = self._forecast
            self._pending = dict(out=out_dev, w=w_dev, plan=plan,
                                 dim=self.problem.dim, gen=self._topo_gen,
                                 n_fc=n_fc,
                                 fc_target=self.rounds +
                                 (fc.horizon if fc is not None else 0))
            used_starts, used_iters = self._budget_starts, self._budget_iters
            if cold:
                # a cold dispatch blocks for trace+compile: book it as
                # compile time so runtime_s keeps its steady-state meaning
                compile_s, dispatch_s = dispatch_s, 0.0

        # -- emit: the collected (previous) cycle's plan ----------------------
        self.moves_total += len(moves)
        self.compile_s_total += compile_s
        common = dict(moves=len(moves), compile_s=compile_s,
                      score_starts=self._score_starts if scored else 0,
                      score_iters=self._score_iters if scored else 0,
                      burn_alerts=len(alerts), max_burn=self._max_burn(),
                      pipelined=True, dispatch_s=dispatch_s,
                      collect_s=collect_s, **self._fc_stats())
        if collected is None:
            # pipeline fill: no solved plan to emit yet — hold the cached
            # operating point if one exists, otherwise explore one round
            hold = self._cached_x
            self.last_decision = DecisionInfo(explored=hold is None, **common)
            return self._plan(hold if hold is not None else self._explore())
        self.last_decision = DecisionInfo(
            explored=False, runtime_s=dispatch_s + collect_s, score=score,
            pgd_starts=used_starts, pgd_iters=used_iters, **common)
        return self._plan(noised)

    def _maybe_rebalance(self, obs, alerts: Sequence[str] = ()
                         ) -> Tuple[List[Tuple[str, str, str]], bool]:
        """The optional per-cycle placement stage (``rebalance_every=N``):
        every N post-exploration cycles take ONE fresh batched score
        snapshot and apply at most one migration — the monotone one-move-
        per-snapshot ascent of ``rebalance``, amortized over cycles.  A
        topology change rebuilds the fleet solve (one recompile per applied
        move; none at the rebalance fixed point).

        A firing fast-burn alert (``alerts``) overrides the cadence — a
        snapshot is taken EVERY cycle until the alert clears — and the
        snapshot's rows are scaled by the accountant's burn weights, so the
        one-move budget is spent on the service burning error budget
        fastest first.  Returns (applied moves, whether a snapshot ran)."""
        n = self.cfg.rebalance_every
        if (n <= 0 or self.fleet_problem is None
                or self.rounds < self.cfg.xi
                or ((self.rounds - self.cfg.xi) % n != 0 and not alerts)):
            return [], False
        scores = self.placement_scores(obs)
        if not scores:
            return [], False
        if alerts and self.accountant is not None:
            # scale whole rows: within-row argmax (the best host) is
            # unchanged, but a burning service's gain grows relative to
            # calm services', so it wins the descending-gain ordering and
            # clears the hysteresis gate sooner
            weights = self.accountant.burn_weights(self.cfg.burn_weight_cap)
            scores = {sid: {h: s * weights.get(sid, 1.0)
                            for h, s in row.items()}
                      for sid, row in scores.items()}
        moves = self.platform.rebalance(scores, limit=1)
        if moves:
            self._build_fleet_problem()
            # the migration changes the solve's score baseline by design
            # (that is why the move was chosen): grace the budget
            # adaptation so the jump is not misread as a load shift
            self._last_score = None
        return moves, True

    def _adapt_budget(self, prev_score: Optional[float],
                      score: float) -> None:
        """Online solver budget adaptation (opt-in ``adapt_budget``): E5
        shows the warm-started optimum barely moves at steady state — in
        VALUE; the argmax itself wanders the flat basin with the per-cycle
        multi-start draws — so convergence is measured on the solver score.
        A relative score move below ``adapt_tol`` for ``adapt_patience``
        consecutive solve cycles halves the PGD budget toward the floors; a
        move past ``adapt_restore_tol`` (a load shift — well above the
        noise floor of a shrunk budget's own solves) restores the
        configured budget at once, and the band between the two thresholds
        just resets the calm counter (hysteresis, so the floor budget's
        solution noise cannot flap the budget back up).  Each budget level
        is its own compiled pipeline variant
        (O(log) many), so a settled budget pays no recompiles; the cycle
        right after a budget change is a grace cycle (its score jump is the
        budget's doing, not the load's)."""
        cfg = self.cfg
        if not cfg.adapt_budget or prev_score is None \
                or not np.isfinite(prev_score) or not np.isfinite(score):
            return
        restore_tol = cfg.adapt_restore_tol \
            if cfg.adapt_restore_tol is not None else 5.0 * cfg.adapt_tol
        move = abs(score - prev_score) / max(abs(prev_score), 1.0)
        if move >= cfg.adapt_tol:
            self._calm_cycles = 0
            if move >= restore_tol and \
                    (self._budget_iters, self._budget_starts,
                     self._score_iters, self._score_starts) != \
                    (cfg.pgd_iters, cfg.pgd_starts,
                     cfg.score_iters, cfg.score_starts):
                self._budget_iters = cfg.pgd_iters
                self._budget_starts = cfg.pgd_starts
                self._score_iters = cfg.score_iters
                self._score_starts = cfg.score_starts
                self._last_score = None     # grace cycle after the change
            return
        self._calm_cycles += 1
        if self._calm_cycles >= cfg.adapt_patience:
            iters = max(self._budget_iters // 2, cfg.adapt_iters_floor)
            starts = max(self._budget_starts // 2, cfg.adapt_starts_floor)
            # the scorer shrinks in lockstep (its own floors): at steady
            # state the candidate ordering is as stationary as the optimum,
            # so the per-cycle snapshot does not need the full budget either
            s_iters = max(self._score_iters // 2, cfg.adapt_score_iters_floor)
            s_starts = max(self._score_starts // 2,
                           cfg.adapt_score_starts_floor)
            if (iters, starts, s_iters, s_starts) != \
                    (self._budget_iters, self._budget_starts,
                     self._score_iters, self._score_starts):
                self._budget_iters, self._budget_starts = iters, starts
                self._score_iters, self._score_starts = s_iters, s_starts
                self._last_score = None     # grace cycle after the change
            self._calm_cycles = 0

    def _solve_cycle(self, obs):
        """One full fit+solve+NOISE pass; returns (optimum, noised plan
        vector, score), or None while models are incomplete.  Sets
        ``_last_solve_cold`` when the pass compiled a new jitted variant;
        re-invoking within the same ``decide`` reuses ``_cycle_draws`` so
        the re-run is byte-identical and the rng stream advances once."""
        self._phase_s = (0.0, 0.0)
        if self.cfg.fused and self.cfg.backend == "pgd":
            with trace.span(trace.RASK_PACK) as span:
                prep = self._prepare_fit()                  # lines 6-9
                if prep is not None:
                    span.set_metadata(rows=self._prep_rows(prep))
                    if self._cycle_draws is None:
                        self._cycle_draws = (int(self.rng.integers(2 ** 31)),
                                             self._x0())
            if prep is None:
                self.stacked = None
                self._last_solve_cold = False
                return None
            seed, x0 = self._cycle_draws
            # cold = this pipeline variant will compile (never called, OR
            # called before but since evicted from the bounded fn cache) —
            # or a streaming rebuild cycle (structural OR forecaster),
            # which repacks and re-uploads a full design window (the
            # re-run then measures the steady-state delta path)
            fkey = self._fused_key(self._prep_k_cap(prep), self._fc_k_cap())
            self._last_solve_cold = self._prep_cold(prep) or \
                not (fkey in self._warm_keys and fkey in self._fused_fns)
            return self._decide_fused(prep, obs, seed, x0)
        return self._classic_cycle(obs)

    # -- Eq. (3) --------------------------------------------------------------
    def _explore(self) -> np.ndarray:
        if self.fleet_problem is not None:
            return self.fleet_problem.random_assignment(self.rng)
        return self.problem.random_assignment(self.rng, self.capacity)

    def _rps_vector(self, obs) -> np.ndarray:
        # rps comes from the observe() states already in hand — no extra
        # per-service latest_metrics round-trips through the DB lock.  A
        # service with no sample in the window OR in the metrics store
        # (paused scrapes, a registry gap right after churn) falls back to
        # its LAST-KNOWN rps, not 0.0: solving against zero load mid-
        # traffic scales the service to the floor and the next real cycle
        # pays the violation spike.  The last-known cache is refreshed from
        # every real finite reading (observe() and here).
        obs = obs or {}
        out = np.zeros(len(self.services), np.float32)
        for i, sid in enumerate(self.services):
            v = obs.get(sid, {}).get("rps")
            if v is None or not np.isfinite(v):
                v = self.platform.latest_metrics(sid).get("rps")
            if v is None or not np.isfinite(v):
                v = self._last_rps.get(sid, 0.0)
            else:
                self._last_rps[sid] = float(v)
            out[i] = v
        return out

    def _x0(self) -> np.ndarray:
        if self.cfg.cache and self._cached_x is not None:
            return self._cached_x
        return self._explore()

    # -- the fused single-dispatch cycle --------------------------------------
    def _streaming(self) -> bool:
        """Whether the device-resident streaming fit engine is active (it
        rides inside the fused PGD pipeline)."""
        return (self.cfg.streaming_fit and self.cfg.fused
                and self.cfg.backend == "pgd")

    def _prepare_fit(self):
        """Fit inputs for the fused decide, structural AND (with
        ``forecast=True``) forecaster: the structural prep is returned, the
        forecaster's lands in ``self._fc_prep`` for ``_dispatch_fused`` —
        both advance their cursors here, exactly once per decide (a cold
        re-run's second call yields empty deltas, keeping re-runs
        byte-identical)."""
        prep = self._prepare_fit_structural()
        if prep is not None and self._forecast_on():
            fc = self._ensure_forecaster()
            self._fc_prep = fc.prep(self.table, self._streaming())
        else:
            self._fc_prep = None
        return prep

    def _prepare_fit_structural(self):
        """Structural fit inputs: ``("delta", deltas)`` with only the rows
        appended since each relation's cursor (the streaming steady state —
        O(new rows) host work, zero design-window uploads), or
        ``("batch", data)`` with the full design window (non-streaming
        mode, or a streaming rebuild after invalidation).  None while some
        relation still lacks >= 3 usable rows AND has no transfer prior
        (the agent keeps exploring).
        """
        streaming = self._streaming()
        auto_due = self.cfg.auto_degree and \
            self.rounds % self.cfg.auto_degree_every == 0
        if streaming and not auto_due:
            deltas = self._stream_deltas()
            if deltas is not None:
                return ("delta", deltas)
        data = self._collect_fit_data()   # (re)builds plan, checks degrees
        if data is None:
            self._stream = None
            return None
        if streaming:
            # an auto-degree pass that did NOT change the plan key leaves
            # the stream state valid: keep pushing deltas
            deltas = self._stream_deltas()
            if deltas is not None:
                return ("delta", deltas)
        return ("batch", data)

    def _stream_deltas(self):
        """Pull the unseen training rows of every relation (cursor-driven
        columnar delta export).  Returns the per-relation delta list, or
        None when the stream state is missing/invalid — built against a
        different topology generation or fit plan, a cursor lost rows to
        table compaction, or the training window outgrew the device ring's
        row bucket — in which case the caller rebuilds via the full
        ``_collect_fit_data`` path (ONE counted design upload)."""
        st = self._stream
        if (st is None or st["gen"] != self._topo_gen
                or st["plan_key"] != self._fit_plan_key
                or self._fit_plan is None):
            return None
        ret = self.table.retention
        deltas = []
        max_rows = 0
        for i, (sid, target, feats, scale) in enumerate(self._rel_static):
            if st["cursors"][i] < self.table.evicted(sid):
                return None               # compaction outran the cursor
            Xd, Yd, cur = self.table.delta_matrix(sid, feats, target,
                                                  st["cursors"][i])
            st["cursors"][i] = cur
            # window row estimate: usable rows only ever grow by the delta
            # and never exceed the visible window; an overcount (NaN rows
            # pushing usable rows out of the window) at worst forces one
            # exact rebuild, which resets the estimate
            n = st["rows"][i] + len(Yd)
            n = min(n, self.table.count(sid) if ret is not None else n)
            st["rows"][i] = n
            max_rows = max(max_rows, n)
            deltas.append((Xd, Yd))
        if pad_capacity(max_rows) > self._row_capacity:
            return None                   # window outgrew the device ring
        return deltas

    def _stream_rebuild(self, data) -> dict:
        """Fresh device-resident stream state holding the current design
        window (counts as ONE ``h2d_design_upload``), with cursors at each
        relation's current append total."""
        plan = self._fit_plan
        return dict(
            state=plan.stream_rebuild(data),
            cursors=[self.table.appended(sid)
                     for sid, *_ in self._rel_static],
            rows=[len(Y) for _, Y in data],
            gen=self._topo_gen, plan_key=self._fit_plan_key, pushes=0)

    def _prep_k_cap(self, prep) -> Optional[int]:
        """The delta-row bucket this prep will dispatch with (None = the
        non-streaming full-window program)."""
        if not self._streaming():
            return None
        kind, payload = prep
        if kind == "batch":               # rebuild, then an empty push
            return self._fit_plan.delta_capacity(0)
        return self._fit_plan.delta_capacity(
            max((len(Y) for _, Y in payload), default=1))

    # -- proactive scaling (core/forecast.py) ---------------------------------
    def _forecast_on(self) -> bool:
        """Whether the forecaster rides this agent's decide (it is composed
        into the fused PGD pipeline; the classic/slsqp paths stay purely
        reactive and ignore the flag)."""
        return (self.cfg.forecast and self.cfg.fused
                and self.cfg.backend == "pgd")

    def _ensure_forecaster(self) -> LoadForecaster:
        """The LoadForecaster bound to the CURRENT topology and fit plan —
        rebuilt (carrying the hybrid gate's error history over when the
        service set is unchanged) whenever either moves, so its row ring
        grows in lockstep with the structural plan's bucket."""
        cfg = self.cfg
        key = (self._topo_gen, self._fit_plan_key, cfg.forecast_lags)
        fc = self._forecast
        if fc is not None and fc.bind_key == key:
            return fc
        horizon = max(1, int(round(cfg.horizon_s /
                                   max(cfg.forecast_cycle_s, 1e-9))))
        new = LoadForecaster(
            self.services,
            [self._sid_types.get(s, "") for s in self.services],
            [max(self._rps_scale.get(s, 0.0), 1.0) for s in self.services],
            cfg.forecast_lags, horizon,
            row_capacity=self._fit_plan.row_capacity, ridge=cfg.ridge,
            err_window=cfg.forecast_err_window,
            gate_tol=cfg.forecast_gate_tol, min_evals=cfg.forecast_min_evals,
            priors=self._fc_priors if cfg.transfer_priors else None,
            prior_strength=cfg.transfer_strength,
            min_prior_rows=cfg.transfer_min_rows)
        if fc is not None and fc.services == new.services:
            new.inherit_gate(fc)
        new.bind_key = key
        self._forecast = new
        return new

    def _fc_k_cap(self) -> Optional[int]:
        """The forecaster's delta-row bucket for this cycle's dispatch
        (None = no forecaster in the program, or the non-streaming batch
        path — mirrors ``_prep_k_cap``)."""
        if not (self._forecast_on() and self._fc_prep is not None
                and self._streaming()):
            return None
        return self._forecast.delta_capacity(self._fc_prep)

    def _prep_cold(self, prep) -> bool:
        """Whether this cycle's dispatch includes a full design-window
        rebuild+upload (structural or forecaster) — decide() then re-runs
        so runtime_s keeps its steady-state meaning."""
        if not self._streaming():
            return False
        if prep[0] == "batch":
            return True
        fp = self._fc_prep
        return self._forecast_on() and fp is not None and fp[0] == "batch"

    def _fc_stats(self) -> dict:
        """DecisionInfo's forecast fields (empty off the forecast path, so
        the dataclass defaults apply)."""
        fc = self._forecast
        if not self._forecast_on() or fc is None:
            return {}
        return dict(forecast_used=fc.last_used, forecast_err=fc.last_err)

    @staticmethod
    def _split_out(out, d: int, n_fc: int):
        """Slice one fused-decide output vector — layout
        [optimum (d) | noised plan (d) | predictions (n_fc) | scores] —
        into (a, noised, score, pred-or-None)."""
        a, noised = out[:d], out[d:2 * d]
        pred = np.asarray(out[2 * d:2 * d + n_fc]) if n_fc else None
        return a, noised, float(out[2 * d + n_fc:].sum()), pred

    # -- transfer-learning priors (churn warm start) --------------------------
    def _default_degree(self, sid: str) -> int:
        """The degree relation ``sid`` will fit with absent new data (the
        configured/per-service default or the last auto-selected value) —
        what the prior key must match."""
        if self.cfg.delta_per_service and sid in self.cfg.delta_per_service:
            return self.cfg.delta_per_service[sid]
        return self._degrees.get(sid, self.cfg.delta)

    def _has_prior(self, sid: str, target: str,
                   feats: Tuple[str, ...]) -> bool:
        if not (self.cfg.transfer_priors and self._transfer_priors):
            return False
        return (self._sid_types.get(sid), target, self._default_degree(sid),
                len(feats)) in self._transfer_priors

    def _prior_args(self) -> Tuple[np.ndarray, np.ndarray]:
        """(w_prior (R, T_max), prior_lam (R,)) for this cycle's fit — the
        prior-mean ridge inputs.  A relation whose service is still short
        of ``transfer_min_rows`` table rows is pulled toward its captured
        fleet-mean weights with linearly decaying strength; everything
        else gets prior_lam = 0, which solves the EXACT unprior'd system
        (regression.fit_batched_arrays) — and since both arrays are traced
        data, prior decay never recompiles.  Once every prior has fully
        decayed the capture dict is dropped and a cached zero pair is
        dispatched (no per-cycle allocation on the steady path)."""
        plan = self._fit_plan
        R, T = plan.n_relations, plan.t_max
        if self.cfg.transfer_priors and self._transfer_priors:
            wp = np.zeros((R, T), np.float32)
            pl = np.zeros((R,), np.float32)
            minr = max(self.cfg.transfer_min_rows, 1)
            live = False
            for i, (sid, target, feats, _) in enumerate(self._rel_static):
                w = self._transfer_priors.get(
                    (self._sid_types.get(sid), target,
                     self._default_degree(sid), len(feats)))
                if w is None or w.shape[0] > T:
                    continue
                need = minr - min(self.table.count(sid), minr)
                if need <= 0:
                    continue
                wp[i, :w.shape[0]] = w
                pl[i] = self.cfg.transfer_strength * need / minr
                live = True
            if live:
                return wp, pl
            self._transfer_priors = {}    # fully decayed: back to zeros
        z = self._prior_zero
        if z is None or z[0] != (R, T):
            z = self._prior_zero = ((R, T), np.zeros((R, T), np.float32),
                                    np.zeros((R,), np.float32))
        return z[1], z[2]

    def _fleet_priors(self) -> Dict[tuple, np.ndarray]:
        """Fleet-mean regression weights grouped by (service type, target,
        degree, n_features) from the current stacked models — captured at
        churn time (the one host sync is on the cold path) so arriving
        services of a known type warm-start instead of re-triggering
        fleet-wide exploration.  Falls back to the previously captured
        priors when no fit has happened yet."""
        if self.stacked is None or not self.stacked.labels:
            return dict(self._transfer_priors)
        W = np.asarray(self.stacked.w, np.float32)
        groups: Dict[tuple, list] = {}
        for i, (sid, target, _, degree, t, f) in enumerate(
                self.stacked.labels):
            key = (self._sid_types.get(sid), target, degree, f)
            groups.setdefault(key, []).append(W[i, :t])
        out = dict(self._transfer_priors)
        for key, rows in groups.items():
            out[key] = np.mean(np.stack(rows), axis=0)
        return out

    def _dispatch_fused(self, prep, obs, seed: int, x0: np.ndarray):
        """Dispatch one fused decide (async — device futures out): returns
        (out, w, seconds the dispatch took, n_fc) where n_fc is the number
        of per-service predictions in ``out`` (0 without the forecaster).
        Streaming preps rebuild or rank-k push the device-resident
        accumulators — structural AND forecaster — as a side effect; the
        state pytrees are donated to (and returned by) the compiled
        program."""
        if not (isinstance(prep, tuple) and len(prep) == 2
                and prep[0] in ("batch", "delta")):
            prep = ("batch", prep)        # raw fit data (legacy call sites)
        plan = self._fit_plan
        kind, payload = prep
        streaming = self._streaming()
        fc = self._forecast \
            if (self._forecast_on() and self._fc_prep is not None) else None
        with trace.span(trace.RASK_PACK, rows=self._prep_rows(prep)):
            k_cap = self._prep_k_cap(prep)
            fk_cap = self._fc_k_cap()
            fkey = self._fused_key(k_cap, fk_cap)
            cold = not (fkey in self._warm_keys and fkey in self._fused_fns)
            rps_np = self._rps_vector(obs)
            fc_args: tuple = ()
            n_fc = 0
            if fc is not None:
                # score the prediction that targeted THIS round, then build
                # the cycle's traced gate inputs: lag windows, use mask, AR
                # priors
                fc.settle(self.rounds, rps_np)
                lagm = fc.lag_matrix(self.table)
                fwp, fpl = fc.prior_arrays()
                fc_args = (jnp.asarray(fwp), jnp.asarray(fpl),
                           jnp.asarray(lagm), jnp.asarray(fc.use_mask()))
                n_fc = len(fc.services)
            wp, pl = self._prior_args()
            priors = (jnp.asarray(wp), jnp.asarray(pl))
            tail = (jnp.asarray(x0, jnp.float32), jax.random.PRNGKey(seed),
                    jnp.asarray(rps_np), jnp.float32(self._eta_t()))
            if streaming:
                if kind == "batch":
                    # invalidated (first fit, churn, plan change): rebuild
                    # the device window, then run the steady-state program
                    # empty
                    self._stream = self._stream_rebuild(payload)
                    payload = [(_EMPTY_X, _EMPTY_Y)] * plan.n_relations
                st = self._stream
                fn = self._fused_fn(fkey, k_cap, fk_cap)
                args = (st["state"], jnp.asarray(plan.fill_delta(payload,
                                                                 k_cap)),
                        *priors)
                if fc is not None:
                    fkind, fpairs = self._fc_prep
                    if fkind == "batch" or fc.state is None:
                        # forecaster ring invalidated too: rebuild it on
                        # device, then run the same steady-state program
                        # empty
                        fc.state = fc.plan.stream_rebuild(fpairs)
                        fpairs = [(_EMPTY_X, _EMPTY_Y)] * fc.plan.n_relations
                    args += (fc.state,
                             jnp.asarray(fc.plan.fill_delta(fpairs, fk_cap)),
                             *fc_args)
            else:
                fn = self._fused_fn(fkey, None, None)
                args = (jnp.asarray(plan.fill_packed(payload)), *priors)
                if fc is not None:
                    args += (jnp.asarray(fc.plan.fill_packed(
                        self._fc_prep[1])), *fc_args)
        with trace.span(trace.RASK_DISPATCH, cold=int(cold)):
            t0 = time.perf_counter()
            res = fn(*args, *tail)
            dispatch_s = time.perf_counter() - t0
        if streaming:
            if fc is None:
                out, w, state = res
            else:
                out, w, state, fc.last_w, fc.state = res
            st["state"] = state
            st["pushes"] += 1
            every = self.cfg.stream_resync_every
            if every and st["pushes"] % every == 0:
                # exact Gram recompute from the device ring (no upload):
                # bounds incremental float32 drift on arbitrarily long runs
                with trace.span(trace.RASK_RESYNC):
                    st["state"] = plan.stream_resync(st["state"])
                    if fc is not None and fc.state is not None:
                        fc.state = fc.plan.stream_resync(fc.state)
        elif fc is None:
            out, w = res
        else:
            out, w, fc.last_w = res
        self._warm_keys.add(fkey)  # compiled now — future decides are warm
        self._warm_keys &= set(self._fused_fns)   # evicted keys re-cool
        return out, w, dispatch_s, n_fc

    @staticmethod
    def _prep_rows(prep) -> int:
        """Training rows a ``(kind, pairs)`` fit prep carries (a delta's new
        rows, or a batch's whole window)."""
        return sum(len(y) for _, y in prep[1])

    def _decide_fused(self, prep, obs, seed: int, x0: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, float]:
        """Fit (+ forecast) + solve + project + NOISE as ONE compiled
        dispatch; returns (optimum for the warm-start cache, noised plan
        vector, score). Sets ``_phase_s``: the seconds of the dispatch and
        of the collect (host blocked on the device, then the transfer)."""
        out, w, dispatch_s, n_fc = self._dispatch_fused(prep, obs, seed, x0)
        with trace.span(trace.RASK_COLLECT):
            t0 = time.perf_counter()
            out = np.asarray(out)  # the cycle's ONE device->host transfer
            self.stacked = self._fit_plan.stacked(w)  # weights stay on device
            self._phase_s = (dispatch_s, time.perf_counter() - t0)
        self._models_view = None
        a, noised, score, pred = self._split_out(out, self.problem.dim, n_fc)
        if pred is not None:
            # round-keyed, so a cold re-run's second note overwrites the
            # identical prediction instead of double-counting it
            self._forecast.note(self.rounds + self._forecast.horizon, pred)
        return a, noised, score

    def _fused_key(self, k_cap: Optional[int] = None,
                   fk_cap: Optional[int] = None) -> tuple:
        fp = self.fleet_problem
        # fc_part != None exactly when the forecaster is composed into the
        # dispatched program (same condition as _dispatch_fused's)
        fc_part = (fk_cap, self.cfg.forecast_lags) \
            if self._forecast_on() and self._fc_prep is not None else None
        return (self._fit_plan_key, k_cap, self._budget_starts,
                self._budget_iters, self.cfg.pgd_lr, self.cfg.objective_impl,
                None if fp is None else fp.layout_key, fc_part)

    def _fused_fn(self, key: tuple, k_cap: Optional[int] = None,
                  fk_cap: Optional[int] = None):
        return cached_fn(self._fused_fns, key,
                         lambda: self._build_fused_fn(k_cap, fk_cap))

    def _build_fused_fn(self, k_cap: Optional[int] = None,
                        fk_cap: Optional[int] = None):
        plan = self._fit_plan
        problem = self.problem
        fp = self.fleet_problem
        cfg = self.cfg
        # forecaster composed into THIS program? (same condition as the key
        # and the dispatch — cached_fn builds lazily inside the dispatch)
        fc = self._forecast \
            if (self._forecast_on() and self._fc_prep is not None) else None
        fplan = None if fc is None else fc.plan
        solve = partial(pgd_solve, n_starts=self._budget_starts,
                        iters=self._budget_iters, lr=cfg.pgd_lr,
                        objective_impl=cfg.objective_impl)
        capacity = jnp.float32(self.capacity)

        def tail(sm, x0, key, rps, eta, extra=()):
            k_solve, k_noise = jax.random.split(key)
            if fp is None:
                a, score = solve(x0, k_solve, problem.tables, sm, rps,
                                 capacity, n_services=len(problem.specs))
                scores = jnp.reshape(score, (1,))
            else:
                # one vmapped solve per layout bucket, packed scatter back
                a, scores = fp.solve_tracer(solve, x0, k_solve, sm, rps)
            # NOISE (Eq. 5): sigma = |a| * eta (the paper's worked example;
            # see _noise for why not the printed (a*eta)^2)
            noised = a + jax.random.normal(k_noise, a.shape) * jnp.abs(a) * eta
            return jnp.concatenate([a, noised, *extra, scores])

        def stacked(w):
            return StackedModels(w, plan._E, plan._tmask, plan._scale,
                                 plan.max_degree, ())

        if k_cap is None and fc is None:
            def core(buf, wp, pl, x0, key, rps, eta):
                TRACE_COUNTS["decide_fused"] += 1      # trace-time only
                Xp, Yp, rmask = plan.unpack(buf)
                w = fit_batched_arrays(Xp, Yp, rmask, plan._E, plan._tmask,
                                       plan._nterms, plan._scale, plan.ridge,
                                       plan.max_degree, wp, pl)
                return tail(stacked(w), x0, key, rps, eta), w
        elif k_cap is None:
            def core(buf, wp, pl, fbuf, fwp, fpl, lagm, use,
                     x0, key, rps, eta):
                TRACE_COUNTS["decide_fused"] += 1      # trace-time only
                Xp, Yp, rmask = plan.unpack(buf)
                w = fit_batched_arrays(Xp, Yp, rmask, plan._E, plan._tmask,
                                       plan._nterms, plan._scale, plan.ridge,
                                       plan.max_degree, wp, pl)
                fXp, fYp, frm = fplan.unpack(fbuf)
                fw = fit_batched_arrays(fXp, fYp, frm, fplan._E,
                                        fplan._tmask, fplan._nterms,
                                        fplan._scale, fplan.ridge,
                                        fplan.max_degree, fwp, fpl)
                pred, rps_eff = fc.predict_tracer(fw, lagm, use, rps)
                return (tail(stacked(w), x0, key, rps_eff, eta, (pred,)),
                        w, fw)
        elif fc is None:
            def core(state, dbuf, wp, pl, x0, key, rps, eta):
                TRACE_COUNTS["decide_fused"] += 1      # trace-time only
                state = plan.stream_update_arrays(
                    state, *plan.unpack_delta(dbuf, k_cap))
                w = plan.stream_fit_arrays(state, wp, pl)  # solve from Gram
                return tail(stacked(w), x0, key, rps, eta), w, state
        else:
            def core(state, dbuf, wp, pl, fstate, fdbuf, fwp, fpl, lagm, use,
                     x0, key, rps, eta):
                TRACE_COUNTS["decide_fused"] += 1      # trace-time only
                state = plan.stream_update_arrays(
                    state, *plan.unpack_delta(dbuf, k_cap))
                w = plan.stream_fit_arrays(state, wp, pl)
                fstate = fplan.stream_update_arrays(
                    fstate, *fplan.unpack_delta(fdbuf, fk_cap))
                fw = fplan.stream_fit_arrays(fstate, fwp, fpl)
                pred, rps_eff = fc.predict_tracer(fw, lagm, use, rps)
                return (tail(stacked(w), x0, key, rps_eff, eta, (pred,)),
                        w, state, fw, fstate)

        # donate the streaming accumulator states, which the program
        # updates in place and returns; nothing else has an output of its
        # shape to reuse its buffer (the packed design/delta buffers, the
        # priors and gate arrays), so donating them would only warn
        if k_cap is None:
            donate: Tuple[int, ...] = ()
        else:
            donate = (0,) if fc is None else (0, 4)
        if cfg.aot:
            return _AotFn(core, donate)
        return jax.jit(core, donate_argnums=donate)

    # -- the two-stage (reference / baseline) cycle ---------------------------
    def _classic_cycle(self, obs):
        """Fit then solve as separate dispatches — SLSQP reference or the
        seed's loop path (``fused=False``); None while models are
        incomplete."""
        self._fit_models()
        if not self._models_complete():
            # not enough samples to fit every relation (e.g. xi=0 at cycle
            # 1): keep exploring — there is no model to solve against yet
            self._last_solve_cold = False
            return None
        rps = self._rps_vector(obs)
        models = self.stacked if (self.cfg.fused and self.stacked is not None) \
            else self.models
        if self._cycle_draws is None:
            seed = int(self.rng.integers(2 ** 31)) \
                if self.cfg.backend == "pgd" else 0
            eps = self.rng.normal(
                0.0, 1.0, self.problem.dim).astype(np.float32) \
                if self._eta_t() > 0 else None
            self._cycle_draws = (seed, self._x0(), eps)
        seed, x0, eps = self._cycle_draws
        self._last_solve_cold = not self._timed_first_solve
        self._timed_first_solve = True
        if self.cfg.backend == "pgd":
            a, score = self.problem.solve_pgd(
                models, rps, x0, self.capacity,
                n_starts=self._budget_starts, iters=self._budget_iters,
                lr=self.cfg.pgd_lr, seed=seed,
                objective_impl=self.cfg.objective_impl)
        else:                                                # line 10
            a, score = self.problem.solve_slsqp(models, rps, x0,
                                                self.capacity)
        return a, self._noise(a, eps), score

    def _models_complete(self) -> bool:
        if self.cfg.fused:
            return self.stacked is not None
        for sid in self.services:
            svc = self.platform.service(sid)
            for target in self.knowledge[svc.sid.type]:
                if target not in self.models.get(sid, {}):
                    return False
        return True

    # -- regression fitting (lines 6-9) -----------------------------------------
    def _fit_models(self) -> None:
        if self.cfg.fused:
            data = self._collect_fit_data()
            if data is None:
                self.stacked = None
                return
            self.stacked = self._fit_plan.fit(data)
            self._models_view = None      # seed-style view rebuilt lazily
            return
        for sid in self.services:
            svc = self.platform.service(sid)
            k = self.knowledge[svc.sid.type]
            self._models_loop.setdefault(sid, {})
            for target, feats in k.items():
                X, Y = self.table.design_matrix(sid, feats, target)
                if len(Y) < 3:
                    continue
                scale = np.asarray(
                    [svc.api.parameter(f).max_value for f in feats], np.float32)
                degree = self._degree(sid, X, Y, scale)
                self._models_loop[sid][target] = fit_polynomial(
                    X, Y, degree, x_scale=scale, ridge=self.cfg.ridge,
                    features=feats, target=target)

    def _collect_fit_data(self):
        """Design matrices for all |S|x|K| relations, plus plan upkeep.

        Matrices are padded to a shared power-of-two row capacity (monotone
        per agent), so the compiled fit is reused across cycles — the
        training table growing by one row per cycle never retraces; the
        padding tables themselves are cached in a ``BatchedFitPlan`` and
        only rebuilt when the capacity bucket or a per-relation degree
        changes.  Returns None until every relation has >= 3 usable rows
        OR a transfer prior (the agent keeps exploring until then).
        """
        data = []
        degrees = []
        max_rows = 0
        for sid, target, feats, scale in self._rel_static:
            X, Y = self.table.design_matrix(sid, feats, target)
            if len(Y) < 3 and not self._has_prior(sid, target, feats):
                # a relation with a captured transfer prior fits anyway:
                # the prior-mean ridge supplies what the missing rows would
                # have, so one arrival no longer re-enters fleet-wide
                # exploration (the prior decays as real rows land)
                return None
            max_rows = max(max_rows, len(Y))
            degrees.append(self._degree(sid, X, Y, scale))
            data.append((X, Y))
        self._row_capacity = max(self._row_capacity, pad_capacity(max_rows))
        key = (self._row_capacity, tuple(degrees))
        if self._fit_plan_key != key:
            self._fit_plan = self._make_plan(self._row_capacity, degrees)
            self._fit_plan_key = key
        return data

    def _make_plan(self, cap: int, degrees: Sequence[int]) -> BatchedFitPlan:
        return BatchedFitPlan(
            [dict(n_features=len(feats), degree=d, x_scale=scale,
                  service=sid, target=target, features=feats)
             for (sid, target, feats, scale), d
             in zip(self._rel_static, degrees)],
            row_capacity=cap, ridge=self.cfg.ridge)

    def _static_degrees(self) -> Tuple[int, ...]:
        """Per-relation degrees as they stand WITHOUT new data: the
        configured/per-service defaults, or the last auto-selected value —
        what ``precompile`` keys its warmed layout buckets on."""
        cfg = self.cfg
        out = []
        for sid, *_ in self._rel_static:
            if cfg.delta_per_service and sid in cfg.delta_per_service:
                out.append(cfg.delta_per_service[sid])
            else:
                out.append(self._degrees.get(sid, cfg.delta))
        return tuple(out)

    def _decide_avals(self, k_cap: Optional[int],
                      fk_cap: Optional[int] = None) -> tuple:
        """ShapeDtypeStruct avals of one fused decide dispatch — what
        ``precompile`` lowers against (no data touched)."""
        plan = self._fit_plan
        f32 = np.dtype(np.float32)
        sds = jax.ShapeDtypeStruct
        priors = (sds((plan.n_relations, plan.t_max), f32),
                  sds((plan.n_relations,), f32))
        fc_part: tuple = ()
        if self._forecast_on() and self._fc_prep is not None \
                and self._forecast is not None:
            fplan = self._forecast.plan
            S = len(self.services)
            gate = (sds((fplan.n_relations, fplan.t_max), f32),
                    sds((fplan.n_relations,), f32),
                    sds((S, self._forecast.lags), f32), sds((S,), f32))
            if fk_cap is None:
                nf = fplan.n_relations * fplan.row_capacity * (fplan.f_max + 2)
                fc_part = (sds((nf,), f32),) + gate
            else:
                nfd = fplan.n_relations * fk_cap * (fplan.f_max + 2)
                fc_part = (jax.eval_shape(fplan.stream_init),
                           sds((nfd,), f32)) + gate
        tail = (sds((self.problem.dim,), f32),
                jax.eval_shape(lambda: jax.random.PRNGKey(0)),
                sds((len(self.services),), f32), sds((), f32))
        if k_cap is None:
            n = plan.n_relations * plan.row_capacity * (plan.f_max + 2)
            return (sds((n,), f32),) + priors + fc_part + tail
        state = jax.eval_shape(plan.stream_init)
        nd = plan.n_relations * k_cap * (plan.f_max + 2)
        return (state, sds((nd,), f32)) + priors + fc_part + tail

    def precompile(self, layouts: Sequence[int] = (64,)) -> List[tuple]:
        """AOT-warm the fused decide for the given layout buckets BEFORE
        the control loop runs, so cold-start trace+compile leaves the loop
        entirely.

        Each layout is a training-window row count; it is bucketed by
        ``pad_capacity`` and compiled against the CURRENT topology, solver
        budgets and (static) per-service degrees — exactly the pipeline
        variants the loop will dispatch.  With ``RaskConfig.aot`` the
        warmup lowers pure ``ShapeDtypeStruct`` avals
        (``jax.jit(...).lower(...).compile()`` — no data, no uploads);
        without it, throwaway zero buffers execute the jitted pipeline
        once.  Returns the warmed fused-fn keys; no-op off the fused PGD
        path."""
        if not (self.cfg.fused and self.cfg.backend == "pgd"):
            return []
        saved = (self._fit_plan, self._fit_plan_key, self._row_capacity,
                 self._forecast, self._fc_prep)
        warmed: List[tuple] = []
        try:
            for rows in layouts:
                cap = pad_capacity(int(rows))
                key = (cap, self._static_degrees())
                if self._fit_plan_key != key:
                    self._fit_plan = self._make_plan(cap, key[1])
                    self._fit_plan_key = key
                k_cap = self._fit_plan.delta_capacity(0) \
                    if self._streaming() else None
                fk_cap = None
                if self._forecast_on():
                    # a throwaway forecaster bound to this layout: its plan
                    # shapes (not its data) are what the lowering needs
                    self._forecast = None
                    fc = self._ensure_forecaster()
                    self._fc_prep = ("batch", [])
                    fk_cap = fc.plan.delta_capacity(0) \
                        if self._streaming() else None
                fkey = self._fused_key(k_cap, fk_cap)
                fn = self._fused_fn(fkey, k_cap, fk_cap)
                avals = self._decide_avals(k_cap, fk_cap)
                if isinstance(fn, _AotFn):
                    fn.warm(*avals)
                else:
                    zeros = jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), avals)
                    jax.block_until_ready(fn(*zeros))
                self._warm_keys.add(fkey)
                warmed.append(fkey)
        finally:
            (self._fit_plan, self._fit_plan_key, self._row_capacity,
             self._forecast, self._fc_prep) = saved
        return warmed

    def _degree(self, sid: str, X, Y, scale) -> int:
        if self.cfg.delta_per_service and sid in self.cfg.delta_per_service:
            return self.cfg.delta_per_service[sid]
        if self.cfg.auto_degree and len(Y) >= 10:
            if (sid not in self._degrees
                    or self.rounds % self.cfg.auto_degree_every == 0):
                best, _ = select_degree(X, Y, x_scale=scale)
                self._degrees[sid] = best
            return self._degrees[sid]
        return self.cfg.delta

    # -- marginal-fulfillment placement (candidate-batched scorer) --------------
    def _placement_problem(self, residents: Dict[str, Tuple[int, ...]],
                           caps: Dict[str, float]
                           ) -> Tuple[PlacementProblem,
                                      Dict[Tuple[str, str], Tuple[int, int]]]:
        """The candidate batch for the CURRENT residency: per host its
        resident subset, plus per (service, host) the with/without what-if
        variant — deduplicated (all of a host's 'without' variants share its
        base subset) and compiled once per topology (bounded cache).
        Returns the (cached) ``PlacementProblem`` and the candidate-index
        plan {(sid, host): (with_id, without_id)}."""
        hosts = sorted(residents)
        sidx = {s.name: i for i, s in enumerate(self.problem.specs)}
        cand: Dict[Tuple[str, Tuple[int, ...]], int] = {}
        subsets: List[Tuple[int, ...]] = []
        capacities: List[float] = []

        def cid(host: str, subset: Tuple[int, ...]) -> int:
            k = cand.get((host, subset))
            if k is None:
                k = cand[(host, subset)] = len(subsets)
                subsets.append(subset)
                capacities.append(float(caps[host]))
            return k

        plan: Dict[Tuple[str, str], Tuple[int, int]] = {}
        base = {h: cid(h, residents[h]) for h in hosts}
        for sid in self.services:
            i = sidx[sid]
            cur = self.platform.host_of(sid).host
            for h in hosts:
                if h == cur:
                    plan[(sid, h)] = (
                        base[h],
                        cid(h, tuple(j for j in residents[h] if j != i)))
                else:
                    plan[(sid, h)] = (
                        cid(h, tuple(sorted(residents[h] + (i,)))), base[h])
        key = tuple((h, residents[h], float(caps[h])) for h in hosts)
        pp = cached_fn(self._placement_cache, key,
                       lambda: PlacementProblem(self.problem, subsets,
                                                capacities,
                                                shard=self.cfg.shard), size=4)
        return pp, plan

    def placement_scores(self, obs: Optional[Mapping] = None,
                         batched: bool = True) -> Dict[str, Dict[str, float]]:
        """Predicted marginal SLO fulfillment of every (service, host) pair.

        For service s and host h: solve h's residents WITH s under h's own
        budget, minus the solve WITHOUT s — the fulfillment the fleet gains
        (or loses, when s squeezes the residents' shares) by hosting s on h.
        All O(|S| x |H|) candidate subsets are scored in ONE jitted vmapped
        dispatch (``PlacementProblem``), cheap enough to run every cycle;
        ``batched=False`` routes the same padded candidates through the
        per-candidate brute-force dispatch loop — the parity oracle and the
        PR-4 cost shape the e8 benchmark times against.  Deterministic
        (fixed solver seed), so ``Fleet.rebalance`` fed these scores is
        idempotent.  Returns {} off a Fleet or until every relation has a
        fitted model (exploration phase).
        """
        if self.fleet_problem is None:
            return {}
        if not self._models_complete():
            self._fit_models()
        if not self._models_complete():
            return {}
        problem = self.problem
        rps = self._rps_vector(obs)
        x0 = self._cached_x if self._cached_x is not None else \
            (0.5 * (problem.lower + problem.upper)).astype(np.float32)
        sidx = {s.name: i for i, s in enumerate(problem.specs)}
        hosts = {h.host: h for h in self.platform.hosts()}
        caps = {name: h.capacity[self.cfg.resource]
                for name, h in hosts.items()}
        residents = {name: tuple(sorted(sidx[s] for s in h.services()
                                        if s in sidx))
                     for name, h in hosts.items()}
        pp, plan = self._placement_problem(residents, caps)
        models = self.stacked \
            if (self.cfg.fused and self.stacked is not None) else self.models
        # the ADAPTIVE scoring budget (seed stays fixed): per budget level
        # scores are deterministic, and the hysteresis gate plus the
        # restore-on-shift adaptation absorb the level changes — at the
        # rebalance fixed point the budget is settled, so the fixed point
        # cannot flap with it; the active level is recorded in
        # ``DecisionInfo.score_starts``/``score_iters``
        score_fn = pp.scores if batched else pp.scores_sequential
        vec = score_fn(models, rps, x0, n_starts=self._score_starts,
                       iters=self._score_iters, lr=self.cfg.pgd_lr, seed=0,
                       objective_impl=self.cfg.objective_impl)
        out: Dict[str, Dict[str, float]] = {}
        for sid in self.services:
            row = {}
            for name in hosts:
                w, wo = plan[(sid, name)]
                row[name] = float(vec[w] - vec[wo])
            out[sid] = row
        return out

    def rebalance(self, obs: Optional[Mapping] = None,
                  hysteresis: Optional[float] = None
                  ) -> List[Tuple[str, str, str]]:
        """Migrate services toward higher predicted marginal fulfillment,
        one move per fresh score snapshot.

        A move's gain (best host's score minus the current host's) is
        exactly the predicted fleet-fulfillment delta of applying it, so
        applying the single best move and re-scoring walks total
        fulfillment strictly upward by more than the hysteresis gate per
        move — the loop terminates, never ping-pongs a service, and a
        second ``rebalance`` right after convergence is a no-op.  Rebinds
        the bucketed fleet solve to the final topology.  Returns the
        applied moves as (sid, from, to)."""
        all_moves: List[Tuple[str, str, str]] = []
        for _ in range(2 * max(len(self.services), 1)):   # safety cap
            scores = self.placement_scores(obs)
            if not scores:
                break
            moves = self.platform.rebalance(scores, hysteresis, limit=1)
            if not moves:
                break
            all_moves.extend(moves)
        if all_moves:
            self._build_fleet_problem()   # bucket layouts follow placement
        return all_moves

    def refresh_topology(self) -> None:
        """Re-bind the agent to the platform's CURRENT topology after churn
        (host failure/drain, capacity degradation, service arrival or
        departure — ``env.simulator`` churn events call this).

        Placement-only changes (same service set) keep the fitted models,
        the training table and the warm start — only the per-host fleet
        solve and the aggregate capacity rebuild.  Service-set changes
        rebuild the optimization problem, carrying each surviving service's
        warm-start slice over by name; models refit from the (persistent)
        training table on the next cycle.  With ``transfer_priors`` the
        fleet-mean weights per service type (regression AND forecaster) are
        captured here and warm-start every NEW relation through the
        prior-mean ridge, so an arrival keeps the fleet solving instead of
        re-entering exploration; without priors (first ever fit, transfer
        disabled) the agent explores until every new relation has >= 3
        observed rows, like the initial xi phase."""
        current = self.platform.services()
        cur_set = set(current)
        kept = [s for s in self.services if s in cur_set]
        new = [s for s in current if s not in set(self.services)]
        self.capacity = self.platform.capacity[self.cfg.resource]
        # prune departed services from the control-plane state FIRST — on
        # every refresh, including placement-only ones: stale burn states
        # and accountant rings would otherwise keep a departed service's
        # last (often terrible, mid-drain) SLI firing fast-burn alerts
        # forever, pinning the per-cycle rebalance + full solver budget on
        # a ghost
        self.burn_states = {s: st for s, st in self.burn_states.items()
                            if s in cur_set}
        if self.accountant is not None:
            self.accountant.prune(current)
        for sid in [s for s in self._last_rps if s not in cur_set]:
            self._last_rps.pop(sid, None)
        for sid in [s for s in self._rps_scale if s not in cur_set]:
            self._rps_scale.pop(sid, None)
        # churn is a regime change: restore the full solver AND scorer
        # budgets and let the score baseline re-establish before adapting
        self._budget_iters = self.cfg.pgd_iters
        self._budget_starts = self.cfg.pgd_starts
        self._score_iters = self.cfg.score_iters
        self._score_starts = self.cfg.score_starts
        self._calm_cycles = 0
        self._last_score = None
        if kept == self.services and not new:
            self._build_fleet_problem()   # placement/capacity change only
            return
        # the service set changed: capture transfer priors from the OLD
        # fitted models/forecaster BEFORE the rebuild discards them —
        # ``_sid_types`` still describes the old topology here, which is
        # exactly what the stacked labels refer to
        if self.cfg.transfer_priors:
            self._transfer_priors = self._fleet_priors()
        if self._forecast is not None:
            self._fc_priors.update(self._forecast.type_means())
        self._forecast = None             # rebuilt against the new set
        self._fc_prep = None
        old_slice = {s.name: (self.problem.offsets[i], s.n_params)
                     for i, s in enumerate(self.problem.specs)}
        prev_x = self._cached_x
        self.services = kept + new
        self.problem = self._build_problem()
        self._build_fleet_problem()
        self._build_rel_static()
        self._placement_cache.clear()
        # warm start: surviving services keep their cached slices, new ones
        # start at the box midpoint (projected feasible at first use)
        if prev_x is not None:
            x = (0.5 * (self.problem.lower + self.problem.upper)
                 ).astype(np.float32)
            for i, s in enumerate(self.problem.specs):
                if s.name in old_slice:
                    off, n = old_slice[s.name]
                    o = self.problem.offsets[i]
                    x[o:o + n] = prev_x[off:off + n]
            self._cached_x = x
        self.stacked = None               # refit against the new relation set
        self._models_view = None
        self._fit_plan = None
        self._fit_plan_key = None
        self._stream = None               # device window follows the plan
        for sid in list(self._models_loop):
            if sid not in set(self.services):
                self._models_loop.pop(sid)

    # -- NOISE (Eq. 5) ------------------------------------------------------------
    def _eta_t(self) -> float:
        """Current noise ratio: eta decayed past the exploration phase."""
        return self.cfg.eta * (
            self.cfg.eta_decay ** max(self.rounds - self.cfg.xi, 0))

    def _noise(self, a: np.ndarray,
               eps: Optional[np.ndarray] = None) -> np.ndarray:
        """``eps`` (standard-normal, pre-drawn) lets a cycle re-run apply
        the SAME perturbation instead of consuming the rng stream again."""
        eta = self._eta_t()
        if eta <= 0:
            return a
        if eps is None:
            eps = self.rng.normal(0.0, 1.0, a.shape).astype(np.float32)
        # NOTE: Eq. (5) prints sigma=(a*eta)^2, but the paper's own worked
        # example (a=4, eta=0.1 -> sigma=0.4) and the "relative noise" wording
        # imply sigma = a*eta; we follow the example.
        return a + eps * np.abs(a) * eta

    # -- decision vector -> declarative plan (§IV-C, redesigned) ----------------
    def _plan(self, a: np.ndarray) -> ScalingPlan:
        plan = ScalingPlan(agent=self.name, cycle=self.rounds)
        for i, spec in enumerate(self.problem.specs):
            off = self.problem.offsets[i]
            for j, name in enumerate(spec.param_names):
                plan.set(spec.name, name, float(a[off + j]))
        return plan
