"""Closed-loop offline batch: the queue is topped up so that it never holds
fewer than ``queue_min`` waiting requests (a batch job feeding a server).

Parameters (a traffic file with ``"kind": "closed_batch"``): ``queue_min``,
``pool`` (how many stratified sizes are drawn before the order repeats),
and uniform ``prompt`` / ``output`` length specs (``min``, ``max``).
"""
from __future__ import annotations

from typing import List, Tuple

from . import strata

Arrival = Tuple[float, int, int]


class Source:
    def __init__(self, params: dict, seed: int, seconds: float):
        n = params["pool"]
        rng = strata.generator(seed)
        self._prompts = rng.permutation(strata.uniform_lengths(params["prompt"], n))
        self._outputs = rng.permutation(strata.uniform_lengths(params["output"], n))
        self.queue_min = params["queue_min"]
        self._i = 0

    def due(self, now: float, queued: int) -> List[Arrival]:
        """New requests, due ``now``, that bring the backlog of ``queued``
        waiting requests up to ``queue_min``."""
        out = []
        for _ in range(max(0, self.queue_min - queued)):
            j = self._i % len(self._prompts)
            out.append((now, int(self._prompts[j]), int(self._outputs[j])))
            self._i += 1
        return out

    def next_due(self) -> float:
        return 0.0
