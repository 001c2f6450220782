"""Readings for the limits of ``correct``: the program's number and its
control's on the same runs, one seed after another in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--controls <n>]

For each seed it runs the cell as ``bench/run.py`` does (untraced) and
prints one JSON line with the program's checked numbers and, on the first
``n`` seeds (all by default), the control's reading on the same sample: the
reference in the program's place at the precision below the
configuration's (``control`` of the cell's system module).
Needs the chips the cell asks for; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=None)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import enable_cache
    cell = harness.Cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 2
    enable_cache()
    system = cell.system()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = len(seeds) if args.controls is None else args.controls
    for i, seed in enumerate(seeds):
        out = system.run(cell, seed, args.seconds, False, time.perf_counter())
        reading = {"seed": seed,
                   "program": {k: c["value"] for k, c in out["checks"].items()},
                   "control": system.control(cell.config, seed, out["sample"])
                   if i < controls else None,
                   "counts": out["counts"],
                   "metrics": out["e2e"]}
        print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
