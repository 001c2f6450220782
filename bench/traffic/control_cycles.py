"""Control cycles back to back: each cycle is ``ticks_per_cycle`` simulated
one-second ticks of request load, then one decide.

The load of each service type follows the paper's bursty pattern (Fig. 7a:
a low baseline with recurring steep bursts to full load, regenerated
procedurally) at ``rps_scale`` times the type's default rate. The seed
places the bursts; every seed has the same number of bursts, baseline and
height range, so the control plane does the same work.
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np

from . import strata

Pattern = Callable[[float], float]


def bursty(max_rps: float, duration_s: float, rng: np.random.Generator,
           floor: float, n_bursts: int) -> Pattern:
    """Low baseline with recurring steep bursts to full load; repeats with
    period ``duration_s + 1``."""
    n = int(duration_s) + 1
    curve = np.full(n, floor)
    starts = np.sort(rng.uniform(0.03, 0.85, n_bursts)) * duration_s
    for s in starts:
        width = rng.uniform(90.0, 260.0)          # 1.5-4.5 min bursts
        height = rng.uniform(0.7, 1.0)
        i0, i1 = int(s), min(int(s + width), n - 1)
        ramp = int(min(30, (i1 - i0) / 3))        # steep edges
        for i in range(i0, i1):
            edge = min((i - i0) / max(ramp, 1), (i1 - i) / max(ramp, 1), 1.0)
            curve[i] = max(curve[i], floor + (height - floor) * edge)
    kern = np.ones(11) / 11
    jitter = np.convolve(rng.normal(0.0, 0.03, n), kern, mode="same")
    curve = np.clip(curve + jitter, 0.0, 1.0)

    def pattern(t: float) -> float:
        return float(curve[max(int(t), 0) % n] * max_rps)

    return pattern


class Source:
    def __init__(self, params: dict, seed: int, seconds: float):
        self.params = params
        self.ticks_per_cycle = int(params["ticks_per_cycle"])
        self._seed = seed

    def patterns(self, default_rps: Dict[str, float]) -> Dict[str, Pattern]:
        """One load pattern per service type (in the given order)."""
        p = self.params
        rng = strata.generator(self._seed)
        return {t: bursty(rps * p["rps_scale"], p["pattern_seconds"], rng,
                          p["floor"], p["n_bursts"])
                for t, rps in default_rps.items()}
