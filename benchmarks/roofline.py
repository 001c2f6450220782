"""Roofline table (EXPERIMENTS.md §Roofline) from the dry-run artifacts.

Reads benchmarks/artifacts/dryrun/*.json (produced by repro.launch.dryrun),
prints the per-(arch x shape x mesh) three-term roofline and writes the
markdown table.
"""
import json
from pathlib import Path

ART = Path(__file__).resolve().parent / "artifacts"
DRY = ART / "dryrun"

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def rows():
    out = []
    for p in sorted(DRY.glob("*.json")):
        out.append(json.loads(p.read_text()))
    return out


def kernel_floor_s(r) -> float:
    """Decode cells: the Pallas decode kernel streams weights + KV cache
    exactly once in bf16 (by construction of its BlockSpec grid), so its
    memory floor is arg_bytes / HBM_BW. The XLA reference path measured in
    memory_s round-trips the cache ~3x (f32-emulated dots + layout
    transposes on the CPU lowering)."""
    return r["arg_bytes_per_device"] / HBM_BW


def markdown_table(data):
    lines = [
        "| arch | shape | mesh | compute_s | memory_s | kernel_s | "
        "collective_s | bottleneck | MODEL_FLOPS | useful | roofline_frac | "
        "kernel_frac |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in data:
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        n_dev = 512 if "pods" in r["mesh"] else 256
        ideal = r["model_flops"] / (n_dev * PEAK_FLOPS)
        frac = ideal / dom if dom > 0 else 0.0
        is_serve = r["shape"] in ("decode_32k", "long_500k")
        kf = kernel_floor_s(r) if is_serve else float("nan")
        kdom = max(r["compute_s"], kf, r["collective_s"]) if is_serve else dom
        kfrac = ideal / kdom if kdom > 0 else 0.0
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {kf:.3e} | {r['collective_s']:.3e} | {r['bottleneck']} "
            f"| {r['model_flops']:.2e} | {r['useful_flops_frac']:.3f} "
            f"| {frac:.4f} | {kfrac:.4f} |")
    return "\n".join(lines)


def rask_objective_rows(s_list=(3, 9, 27), k_starts=8):
    """Three-term roofline for the RASK batched-objective kernel
    (kernels/rask_objective.py) at the e7 problem shapes.

    Paper layout per 3 services: 7 decision params, 3 relations (F_max = 3,
    degree 2 -> T = 10 terms), 7 SLOs.  Counts assume the kernel's one-hot
    matmul formulation: feature gather, parameter/relation picks and the
    per-service segment-sum are all dense matmuls; term products come from
    statically-unrolled powers.  The kernel is microscopically small for a
    TPU — both floors land in the tens of nanoseconds, i.e. the op is
    dispatch-bound, which is exactly why the solver batches K starts (and a
    Fleet batches hosts) into ONE launch rather than looping.
    """
    out = []
    for s in s_list:
        units = s // 3
        D, R, Q, T, F, deg = 7 * units, 3 * units, 7 * units, 10, 3, 2
        flops = k_starts * (2 * R * F * D            # one-hot gather matmul
                            + R * T * F * (deg + 2)  # power select + product
                            + 2 * R * T              # weighted term sum
                            + 2 * Q * (D + R + 4)    # picks + phi
                            + 2 * Q * s)             # segment-sum matmul
        floats = (k_starts * D + R * F * D + Q * D + Q * R + Q * s
                  + R * T * F + 2 * R * T + R * F + 4 * Q + s
                  + k_starts * s)
        bytes_ = 4 * floats
        compute_s = flops / PEAK_FLOPS
        memory_s = bytes_ / HBM_BW
        out.append(dict(S=s, K=k_starts, flops=flops, bytes=bytes_,
                        compute_s=compute_s, memory_s=memory_s,
                        bound="memory" if memory_s > compute_s else "compute",
                        intensity=flops / bytes_))
    return out


def dispatch_floor_rows(s_list=(3, 9), reps=100):
    """Empirical host dispatch floor of the fused decide program (ISSUE 8).

    The decide op is dispatch-bound (see ``rask_objective_rows``): its
    device floors are tens of nanoseconds, so per-cycle latency is set by
    how fast the host can launch it.  This measures the SAME compiled
    program invoked two ways, at real agent shapes:

    * jit — through the ``jax.jit`` python dispatcher (argument flatten,
      signature hash, cache lookup, guard logic on every call);
    * aot — ``jax.jit(f).lower(...).compile()`` once, then the compiled
      executable called directly (what ``RaskConfig.aot`` ships and
      ``RASKAgent.precompile`` warms).

    Measured result (recorded in roofline_dispatch.json): on CPU jax the
    WARM dispatch floor slightly favors the jit C++ fastpath (~10us) over
    the direct ``Compiled.call`` python entry (~18us) — the AOT win is the
    COLD start: ``warm_ms`` of trace+compile leaves the control loop
    entirely (``precompile`` pays it from ShapeDtypeStructs before the
    first cycle), so no decide ever stalls on a compile.  Zero-filled
    inputs: the ridge term keeps the zero-Gram solve well-posed, and
    dispatch cost is shape-dependent only."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks import common
    from repro.core.rask import _AotFn

    out = []
    for s_count in s_list:
        env = common.make_env(seed=0, replicas=max(s_count // 3, 1),
                              capacity=8.0 * max(s_count // 3, 1))
        agent = common.make_rask(env, 0)
        cap = 64
        key = (cap, agent._static_degrees())
        agent._fit_plan = agent._make_plan(cap, key[1])
        agent._fit_plan_key = key
        k_cap = (agent._fit_plan.delta_capacity(0)
                 if agent._streaming() else None)
        fn = agent._build_fused_fn(k_cap)
        if not isinstance(fn, _AotFn):      # aot disabled in this config
            continue
        avals = agent._decide_avals(k_cap)
        zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), avals)
        jit_us = common.bench(
            lambda: jax.block_until_ready(fn._jit(*zeros)), reps)
        t0 = time.perf_counter()
        fn.warm(*avals)
        warm_ms = (time.perf_counter() - t0) * 1e3
        aot_us = common.bench(
            lambda: jax.block_until_ready(fn(*zeros)), reps)
        # pure dispatch floor: a no-op program over the SAME argument tree
        # (decide compute hides the delta in noise at small S; this isolates
        # the host-side flatten/hash/lookup cost itself)
        floor = _AotFn(lambda *a: jax.tree_util.tree_leaves(a)[-1])
        floor_jit_us = common.bench(
            lambda: jax.block_until_ready(floor._jit(*zeros)), reps)
        floor.warm(*avals)
        floor_aot_us = common.bench(
            lambda: jax.block_until_ready(floor(*zeros)), reps)
        out.append(dict(S=s_count, jit_us=jit_us, aot_us=aot_us,
                        saved_us=jit_us - aot_us,
                        saved_frac=(jit_us - aot_us) / jit_us,
                        warm_ms=warm_ms,
                        floor_jit_us=floor_jit_us,
                        floor_aot_us=floor_aot_us))
    return out


def measured_serving_row():
    """The e11 MEASURED stacked-engine point (tokens/s on the smoke model),
    printed next to the analytic floors: the only row in this table that
    comes from wall-clock decode steps rather than a cost model."""
    p = ART / "e11_serving.json"
    if not p.exists():
        return None
    return json.loads(p.read_text()).get("roofline_point")


def main():
    measured = measured_serving_row()
    if measured:
        print(f"roofline[measured,{measured['arch']}-smoke,"
              f"slots={measured['slots']}],{measured['step_us']:.0f},"
              f"{measured['tokens_per_s']:.0f}tok/s MEASURED"
              f" (e11 stacked engine)")
    dispatch = dispatch_floor_rows()
    for r in dispatch:
        print(f"roofline[dispatch,S={r['S']}],{r['aot_us']:.0f},"
              f"jit={r['jit_us']:.0f}us saved={r['saved_us']:.0f}us"
              f" ({100 * r['saved_frac']:.0f}%)"
              f" cold-compile={r['warm_ms']:.0f}ms"
              f" floor jit={r['floor_jit_us']:.0f}us"
              f" aot={r['floor_aot_us']:.0f}us")
    if dispatch:
        (ART / "roofline_dispatch.json").write_text(
            json.dumps(dispatch, indent=1))
    for r in rask_objective_rows():
        dom = max(r["compute_s"], r["memory_s"])
        print(f"roofline[rask_objective,S={r['S']},K={r['K']}],"
              f"{dom * 1e6:.3f},{r['bound']}-bound"
              f" intensity={r['intensity']:.2f}flop/B")
    data = rows()
    if not data:
        print("roofline,0,no-dryrun-artifacts")
        return
    table = markdown_table(data)
    (ART / "roofline_table.md").write_text(table)
    for r in data:
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        print(f"roofline[{r['arch']},{r['shape']},{r['mesh']}],"
              f"{dom * 1e6:.1f},{r['bottleneck']}")


if __name__ == "__main__":
    main()
