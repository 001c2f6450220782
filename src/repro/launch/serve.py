"""Serving launcher: a model behind the continuous-batching engine, fed
batched synthetic requests.

By default it serves the reduced same-family ``.smoke()`` config (the CPU
tests' size, in float32). ``--published`` serves the architecture at its
published widths and dtype (bfloat16) instead, with ``--layers`` cutting
the depth and ``--max-seq`` / ``--slots`` sizing it for the device:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --requests 24
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --published \\
        --layers 4 --max-seq 2048 --slots 8 --prompt-len 384 --chips 48 \\
        --attn pallas

``--engine dict`` selects the seed-era per-slot-cache baseline (one decode
dispatch per active slot); the default stacked engine decodes every slot in
one dispatch over a device-resident donated cache. ``--attn pallas`` routes
prefill and the batched decode step through the Pallas kernels
(``pallas_interpret`` runs them in interpret mode, for the CPU).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from ..configs import get
from ..core.regression import TRACE_COUNTS
from ..models import build
from ..serve.engine import (DictCacheEngine, EngineConfig, Request,
                            ServingEngine)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--published", action="store_true",
                    help="serve the published widths (default: the reduced "
                         ".smoke() config)")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut (default: the config's own depth)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=None,
                    help="KV cache length per slot (default: prompt + "
                         "max-new + 8)")
    ap.add_argument("--chips", type=float, default=4.0,
                    help="chip share: the admission budget is chips x "
                         "tokens_per_chip_step prompt tokens per step")
    ap.add_argument("--engine", choices=("stacked", "dict"),
                    default="stacked")
    ap.add_argument("--attn", choices=("reference", "pallas",
                                       "pallas_interpret"),
                    default="reference")
    args = ap.parse_args(argv)

    cfg = get(args.arch)
    if not args.published:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, attn_impl=args.attn,
                              n_layers=args.layers or cfg.n_layers)
    model = build(cfg)
    # one jitted init: each random f32 draw fuses with its cast, so a
    # published-width model never holds its weights in f32 on the device
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    max_seq = args.max_seq or args.prompt_len + args.max_new + 8
    cls = ServingEngine if args.engine == "stacked" else DictCacheEngine
    engine = cls(model, params, EngineConfig(
        slots=args.slots, max_seq=max_seq, context=args.prompt_len,
        chips=args.chips))

    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        engine.submit(Request(
            rid, rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new))

    traces0 = dict(TRACE_COUNTS)
    t0 = time.perf_counter()
    ticks = 0
    while len(engine.completed) < args.requests and ticks < 10_000:
        engine.step()
        ticks += 1
    dt = time.perf_counter() - t0
    traces = {k: TRACE_COUNTS[k] - traces0.get(k, 0)
              for k in ("serve_prefill", "serve_decode_step")}
    print(f"[{args.engine}] {cfg.name} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} {cfg.dtype} attn={cfg.attn_impl}: "
          f"completed {len(engine.completed)}/{args.requests} requests in "
          f"{ticks} engine steps, {dt:.1f}s with compiles; "
          f"tokens_out={engine.tokens_out} traces={traces} "
          f"step_ewma={1e3 * (engine.step_ewma_s or 0.0):.2f}ms")
    return engine


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
