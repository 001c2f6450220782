"""Smoke run of the system's two device paths on one TPU chip.

    python chip_smoke.py                # one chip: control plane + serving
    python chip_smoke.py --four-chips   # four chips: sharded solves only

One process, in phases; each phase prints one line of numbers and the run
stops with a non-zero exit at the first failed check. The last line of
standard output is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip):

* ``control_plane`` — ``repro.launch.autoscale.main`` at the paper's largest
  service count (three LM types x ``--replicas 3`` = 9 services on one host)
  for 10+ post-exploration decides, then a ``RASKAgent`` with
  ``RaskConfig(objective_impl="pallas")`` on the same seeded environment:
  zero steady-state retraces, no steady-state design-window upload, and
  the Pallas candidate objective within 1e-4 of the pure-jnp reference on
  that agent's own problem tables.
* ``serving/<attn>`` — ``repro.launch.serve.main`` on qwen3-32b at its
  published widths (bfloat16, depth cut to 4 layers), once with the
  reference attention and once with the Pallas kernels: every request
  completes with one prefill trace per prompt bucket and one decode trace,
  and prefill-then-cached-decode logits agree with the full forward pass.

``--four-chips`` runs only the sharded control-plane solves and what they
are compared with: the 1000-service / 100-host bucketed fleet solve and one
placement-score snapshot, sharded over all four devices vs ``shard=False``,
within float32 rounding (``SHARD_TOL``).

Exits non-zero without a result when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import RASKAgent, RaskConfig  # noqa: E402
from repro.core.regression import TRACE_COUNTS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# the interpret-mode kernel tests' tolerance for the candidate objective
OBJECTIVE_TOL = 1e-4
# cached-decode vs full-forward logits, as max |diff| over max |logit|. Both
# paths run the same bfloat16 weights but round at different points (a
# (1, S) prompt matmul vs (1, 1) decode steps, flash/decode kernels vs one
# masked softmax), and the logits leave the model in bfloat16, whose
# spacing near the largest logit is 0.4-0.8% of it. A few such ulps is the
# expected gap (1-1.5% at d_model 1024, 4 layers, on XLA-CPU); 5% allows
# about ten. A wrong cache cursor, mask or kernel block gives errors of the
# logits' own size.
LOGITS_TOL = 5e-2

# sharded vs unsharded solves, as max |diff| / max(|unsharded|, 1) per
# element. Sharding only moves rows between devices, and on XLA-CPU the
# results are bit-identical; on a TPU mesh each device compiles the row
# program for its own share of the rows, and float32 rounding differs by a
# few ulps (up to 3.8e-6 absolute on a v5e 2x2 mesh). A row sent to the
# wrong device, or padding read back as a result, is off by its own size.
SHARD_TOL = 1e-4

SERVE_REQUESTS = 16
SERVE_ARGV = ["--arch", "qwen3-32b", "--published", "--layers", "4",
              "--slots", "8", "--max-seq", "2048",
              "--requests", str(SERVE_REQUESTS), "--prompt-len", "384",
              "--max-new", "24", "--chips", "48"]


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **numbers) -> None:
    print(f"[{phase}] {json.dumps(numbers, sort_keys=True)}", flush=True)


def trace_delta(before: collections.Counter) -> dict:
    return {k: v - before.get(k, 0) for k, v in TRACE_COUNTS.items()
            if v != before.get(k, 0)}


# -- control plane -------------------------------------------------------------

def _decide_checks(hist, xi: int) -> dict:
    decides = [h for h in hist if not h.explored]
    check(len(decides) >= 10, f"only {len(decides)} post-exploration decides")
    check(all(math.isfinite(h.fulfillment) for h in hist[xi:]),
          "non-finite fulfillment")
    return dict(decides=len(decides),
                cold_decides=sum(1 for h in decides if h.compile_s > 0),
                compile_s=sum(h.compile_s for h in decides),
                median_runtime_ms=1e3 * float(np.median(
                    [h.runtime_s for h in decides])),
                mean_fulfillment=float(np.mean(
                    [h.fulfillment for h in hist[xi:]])))


def control_plane() -> None:
    from repro.kernels import ops
    from repro.launch import autoscale

    minutes, seed, replicas, xi = 6.0, 0, 3, 20
    # the launcher as a user runs it (reference objective)
    before = collections.Counter(TRACE_COUNTS)
    hist = autoscale.main(["--minutes", str(minutes), "--replicas",
                           str(replicas), "--seed", str(seed)])
    traces = trace_delta(before)
    n_services = len(hist[-1].per_service)
    check(n_services == 3 * replicas, f"{n_services} services")
    stats = _decide_checks(hist, xi)
    # every compiled decide variant is traced once, on a cold decide
    check(traces.get("decide_fused", 0) <= stats["cold_decides"],
          f"decide retraced: {traces}")
    report("control_plane/launcher", services=n_services, traces=traces,
           **stats)

    # the same seeded environment under the Pallas candidate objective,
    # with per-cycle trace snapshots for the steady-state window
    env, knowledge, _ = autoscale.lm_environment(
        minutes * 60.0, seed=seed, replicas=replicas)
    agent = RASKAgent(env.platform, knowledge,
                      RaskConfig(xi=xi, eta=0.0, resource="chips",
                                 objective_impl="pallas"), seed=seed)
    snaps = []
    hist = env.run(agent, duration_s=minutes * 60.0,
                   on_cycle=lambda rec: snaps.append(
                       (rec, collections.Counter(TRACE_COUNTS))))
    stats = _decide_checks(hist, xi)
    # steady state: every decide after the first post-exploration one
    first = next(i for i, (rec, _) in enumerate(snaps) if not rec.explored)
    steady = {k: snaps[-1][1].get(k, 0) - snaps[first][1].get(k, 0)
              for k in ("decide_fused", "h2d_design_upload")}
    check(steady == {"decide_fused": 0, "h2d_design_upload": 0},
          f"steady-state retrace or upload: {steady}")
    check(all(rec.compile_s == 0 for rec, _ in snaps[first + 1:]),
          "a steady-state decide compiled")

    # Pallas vs reference candidate objective on the agent's own tables
    problem, sm = agent.problem, agent.stacked
    tb = problem.tables
    rng = np.random.default_rng(seed)
    A = np.stack([problem.random_assignment(rng, agent.capacity)
                  for _ in range(64)]).astype(np.float32)
    rps = np.asarray([env.platform.latest_metrics(sid)["rps"]
                      for sid in agent.services], np.float32)
    args = (jnp.asarray(A), tb.rel_gather, sm.w, sm.exponents, sm.term_mask,
            sm.x_scale, tb.slo_kind, tb.slo_service, tb.slo_weight,
            tb.slo_target, tb.slo_pidx, tb.slo_ridx, jnp.asarray(rps))
    kw = dict(n_services=len(problem.specs), max_degree=sm.max_degree)
    got = np.asarray(ops.rask_objective(*args, impl="pallas", **kw))
    want = np.asarray(ops.rask_objective(*args, impl="reference", **kw))
    check(got.shape == want.shape == (64, len(problem.specs)),
          f"objective shape {got.shape}")
    check(bool(np.all(np.isfinite(got))), "non-finite Pallas objective")
    diff = float(np.max(np.abs(got - want)))
    check(diff <= OBJECTIVE_TOL, f"objective diff {diff} > {OBJECTIVE_TOL}")
    report("control_plane/pallas", services=len(problem.specs),
           steady_traces=steady, steady_decides=len(snaps) - first - 1,
           objective_max_abs_diff=diff, objective_tol=OBJECTIVE_TOL,
           candidates=int(A.shape[0]), **stats)


# -- serving -------------------------------------------------------------------

def _logits_parity(engine, n_decode: int = 8) -> dict:
    """The engine's path (bucket-padded prefill, then cached decode steps
    over the tokens it generated) against the full forward pass of the same
    model over the same tokens, with the reference attention."""
    from repro.models import transformer
    from repro.serve.engine import bucket_length

    model, params, max_seq = engine.model, engine.params, engine.cfg.max_seq
    req = engine.completed[0]
    prompt = np.asarray(req.prompt, np.int32)
    fed = [int(t) for t in req.generated[:n_decode]]
    n = len(prompt)
    toks = np.zeros((1, bucket_length(n, max_seq)), np.int32)
    toks[0, :n] = prompt
    prefill = jax.jit(lambda p, t, length: model.prefill(
        p, {"tokens": t}, max_seq=max_seq, length=length))
    decode = jax.jit(model.decode, donate_argnums=(2,))
    last, cache = prefill(params, jnp.asarray(toks), jnp.int32(n))
    rows = [last[0]]
    for t in fed:
        logits, cache = decode(params, jnp.full((1, 1), t, jnp.int32), cache)
        rows.append(logits.reshape(-1))
    cached = np.asarray(jnp.stack(rows).astype(jnp.float32))

    ref_cfg = dataclasses.replace(model.cfg, attn_impl="reference")
    seq = jnp.asarray(np.concatenate([prompt, fed]).astype(np.int32))[None]
    full, _, _ = jax.jit(lambda p, t: transformer.decoder_forward(
        p, ref_cfg, t))(params, seq)
    full = np.asarray(full[0, n - 1:].astype(jnp.float32))
    check(cached.shape == full.shape, f"logits {cached.shape} {full.shape}")
    check(bool(np.all(np.isfinite(cached))), "non-finite decode logits")
    scale = float(np.max(np.abs(full)))
    rel = float(np.max(np.abs(cached - full))) / scale
    check(rel <= LOGITS_TOL, f"logits rel diff {rel} > {LOGITS_TOL}")
    return dict(logits_positions=int(full.shape[0]),
                logits_max_abs=scale, logits_rel_diff=rel,
                logits_tol=LOGITS_TOL,
                argmax_agree=float(np.mean(
                    cached.argmax(-1) == full.argmax(-1))))


def serving(attn: str) -> None:
    from repro.launch import serve

    before = collections.Counter(TRACE_COUNTS)
    t0 = time.perf_counter()
    engine = serve.main(SERVE_ARGV + ["--attn", attn])
    wall = time.perf_counter() - t0
    traces = {k: TRACE_COUNTS[k] - before.get(k, 0)
              for k in ("serve_prefill", "serve_decode_step")}
    check(len(engine.completed) == SERVE_REQUESTS,
          f"{len(engine.completed)}/{SERVE_REQUESTS} requests completed")
    check(engine.tokens_out > 0, "no tokens out")
    # every prompt has the same length here, so exactly one prefill bucket
    check(traces == {"serve_prefill": 1, "serve_decode_step": 1},
          f"serving traces {traces}")
    cfg = engine.model.cfg
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
    stats = _logits_parity(engine)
    report(f"serving/{attn}", model=cfg.name, layers=cfg.n_layers,
           d_model=cfg.d_model, n_heads=cfg.n_heads,
           n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff, vocab=cfg.vocab,
           dtype=cfg.dtype, params=int(n_params),
           requests_completed=len(engine.completed),
           tokens_out=engine.tokens_out, engine_steps=engine.steps,
           traces=traces, wall_s_with_compiles=wall, **stats)


# -- four chips ----------------------------------------------------------------

def four_chips() -> None:
    from benchmarks.e6_scalability import SCALE_FLEETS, _solve_fleet
    from benchmarks.e8_placement import scale_snapshot
    from repro.core.solver import FleetSolverProblem, PlacementProblem

    ndev = jax.device_count()
    check(ndev == 4, f"{ndev} devices, --four-chips needs 4")
    problem, host_of, caps, models, rps, x0 = _solve_fleet(
        (SCALE_FLEETS[-1],))
    fs = FleetSolverProblem(problem, host_of, caps, shard="auto")
    f0 = FleetSolverProblem(problem, host_of, caps, shard=False)
    check(fs.n_shards == 4, f"fleet solve n_shards={fs.n_shards}")
    a_s, s_s = fs.solve_many(models, rps, x0)
    a_0, s_0 = f0.solve_many(models, rps, x0)
    assign, score = _shard_parity(a_s, a_0), _shard_parity(s_s, s_0)
    report("four_chips/fleet_solve", services=len(problem.specs),
           hosts=len(caps), buckets=len(fs.buckets), n_shards=fs.n_shards,
           max_abs_diff=max(assign["max_abs_diff"], score["max_abs_diff"]),
           assignment=assign, score=score, tol=SHARD_TOL)

    problem, subsets, caps_list, models, rps, x0 = scale_snapshot()
    ps = PlacementProblem(problem, subsets, caps_list, shard="auto")
    p0 = PlacementProblem(problem, subsets, caps_list, shard=False)
    check(ps.n_shards == 4, f"placement n_shards={ps.n_shards}")
    place = _shard_parity(ps.scores(models, rps, x0),
                          p0.scores(models, rps, x0))
    report("four_chips/placement", services=len(problem.specs),
           candidates=ps.n_candidates, n_shards=ps.n_shards,
           max_abs_diff=place["max_abs_diff"], scores=place, tol=SHARD_TOL)
    for what, p in (("fleet assignment", assign), ("fleet score", score),
                    ("placement score", place)):
        check(p["max_rel_diff"] <= SHARD_TOL,
              f"sharded {what} off by {p['max_rel_diff']} > {SHARD_TOL}")


def _shard_parity(sharded, unsharded) -> dict:
    got, want = np.asarray(sharded), np.asarray(unsharded)
    check(got.shape == want.shape, f"sharded shape {got.shape} {want.shape}")
    check(bool(np.all(np.isfinite(got))), "non-finite sharded result")
    diff = np.abs(got - want)
    return dict(max_abs_diff=float(diff.max()),
                max_rel_diff=float(np.max(diff / np.maximum(np.abs(want),
                                                            1.0))),
                n_differ=int(np.count_nonzero(diff)), n=int(diff.size))


# -- main ----------------------------------------------------------------------

def device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet/placement solves, "
                         "over four devices, against shard=False")
    args = ap.parse_args(argv)

    dev = device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    report("device", **dev)
    try:
        if args.four_chips:
            four_chips()
        else:
            control_plane()
            serving("reference")
            serving("pallas")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
