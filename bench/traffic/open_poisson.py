"""Open-loop arrivals: requests come on a schedule whether or not earlier
ones have finished (independent users).

Parameters (a traffic file with ``"kind": "open_poisson"``):
``rate_per_s``, and ``prompt`` / ``output`` lognormal length specs
(``median``, ``sigma``, ``min``, ``max``). The schedule holds
``round(rate * seconds)`` requests whose gaps are the exponential
distribution's stratified quantiles: every seed's last request is due at
the same time, just inside the window.
"""
from __future__ import annotations

from typing import List, Tuple

from . import strata

Arrival = Tuple[float, int, int]      # (due second, prompt tokens, output tokens)


class Source:
    def __init__(self, params: dict, seed: int, seconds: float):
        n = max(1, round(params["rate_per_s"] * seconds))
        rng = strata.generator(seed)
        gaps = rng.permutation(strata.exponential_gaps(params["rate_per_s"], n))
        prompts = rng.permutation(strata.lognormal_lengths(params["prompt"], n))
        outputs = rng.permutation(strata.lognormal_lengths(params["output"], n))
        t = gaps.cumsum()                 # every seed's last is due alike
        self.schedule: List[Arrival] = [
            (float(t[i]), int(prompts[i]), int(outputs[i])) for i in range(n)]
        self._next = 0

    def due(self, now: float, queued: int) -> List[Arrival]:
        """Requests due by ``now`` (seconds into the window) not yet handed
        out; ``queued`` is ignored, the loop is open."""
        out = []
        while self._next < len(self.schedule) and \
                self.schedule[self._next][0] <= now:
            out.append(self.schedule[self._next])
            self._next += 1
        return out

    def next_due(self) -> float:
        """When the next request is due (inf once the schedule is spent)."""
        if self._next < len(self.schedule):
            return self.schedule[self._next][0]
        return float("inf")
