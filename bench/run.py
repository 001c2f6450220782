"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the chips the cell asks for
(a TPU): without them it exits non-zero and prints no result. JAX's
persistent compilation cache lives at ``<checkout>/.jax_cache``, whatever
``JAX_COMPILATION_CACHE_DIR`` says, so two checkouts share no cache and
only a cell's first run in a checkout compiles. With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` a profiler trace
of the window gives its per-layer metrics, the device's busy time and a
breakdown. The last line of standard output is the result, in JSON; the
numbers that decide ``correct`` close standard error, each beside its
limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def enable_cache() -> None:
    import jax
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache    # for the program too
    jax.config.update("jax_compilation_cache_dir", cache)
    # every program, however quick to compile, is read back next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    cell = harness.Cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    enable_cache()
    out = cell.system().run(cell, args.seed, args.seconds, bool(args.trace),
                            T_PROCESS)
    return harness.finish(cell, out, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
