"""Continuous-batching serving engines — the real service MUDAP autoscales.

A fixed pool of decode slots; requests are admitted when a slot frees and
the *token budget* allows. Both engines expose the elasticity parameters the
LM profiles advertise (see ``repro/env/profiles.py::lm_profile``):

  * ``chips``   -> admission token budget scales with granted chip share
  * ``context`` -> prompts are truncated to the current budget (data quality)
  * ``rung``    -> model-variant rung (a logical switch at engine level;
                   ``serve.service.ServedLMService`` maps it onto a ladder of
                   down-sized model variants)

Two implementations share one public API:

``ServingEngine`` (the production path) is device-resident: every slot's KV
cache lives in ONE stacked pytree that stays on device and is donated
through each step, and a decode step for ALL slots is ONE jitted dispatch
(a vmap of the batch-1 decode over the slot axis — per-slot ``pos`` cursors
ride as a ``(slots,)`` leaf). Each leaf is slot-major: ``(slots, L, 1, KH,
max_seq, D)`` for a head-major K or V. The step reads each layer's slice
where it lies and never modifies it; its one write to the cache puts each
slot's new K/V row at that slot's ``pos``, in place in the donated buffer,
so the step copies no layer slice and no whole cache. Finished slots
free-run (their lane keeps decoding; the host simply stops reading the
lane, and an admission overwrites the whole slot). Prompts are right-padded
to power-of-two buckets and prefilled with a traced true-length, so prefill
compiles once per bucket instead of once per distinct prompt length;
prefill + slot insertion is one fused donated dispatch. Steady state performs ZERO recompiles — gated via
``TRACE_COUNTS['serve_decode_step'/'serve_prefill']``.

``DictCacheEngine`` is the seed-era engine (per-slot ``Dict[int, cache]``,
one decode dispatch + one host sync per active slot, exact-length prefill
that retraces per distinct prompt length). It is kept as the benchmark
baseline (``benchmarks/e11_serving.py``) and as the parity oracle: on a
seeded run both engines must produce identical token streams.

Everything is synchronous and deterministic so tests can drive it tick by
tick, mirroring the 1 s cycle of the stream-processing services in the
paper. The stacked step's wall-clock (``last_step_s`` / ``step_ewma_s``) is
the *measured* latency that feeds the autoscaler's telemetry. Under a
profiler trace, each step, admission and decode dispatch is a host span
(``repro.obs.trace``); an admission's span carries its queue wait, from
``submit`` to its prefill's dispatch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.regression import TRACE_COUNTS
from ..models import Model
from ..obs import trace

MIN_BUCKET = 8          # smallest prefill compile bucket (tokens)
EWMA_ALPHA = 0.25       # step-latency smoothing for telemetry


def bucket_length(n: int, max_seq: int, minimum: int = MIN_BUCKET) -> int:
    """Next power-of-two prompt bucket >= n, clamped to the cache length."""
    b = minimum
    while b < n:
        b *= 2
    return min(b, max_seq)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int = 16
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    submitted_at: float = 0.0             # engine clock at submit


@dataclasses.dataclass
class EngineConfig:
    slots: int = 4                 # decode batch size (fixed pool)
    max_seq: int = 256
    chips: float = 1.0             # elasticity: resource share
    context: int = 256             # elasticity: prompt budget (data quality)
    rung: int = 4                  # elasticity: model-size rung
    tokens_per_chip_step: int = 64 # admission budget per step per chip


class _EngineBase:
    """Shared host-side bookkeeping: queue, elasticity API, counters."""

    def __init__(self, model: Model, params, cfg: EngineConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.queue: List[Request] = []
        self.active: Dict[int, Request] = {}     # slot -> request
        self.completed: List[Request] = []
        self.steps = 0
        self.tokens_out = 0
        self.prompt_tokens_in = 0                # admitted (post-truncation)
        self.last_step_s = 0.0                   # measured decode wall-clock
        self.step_ewma_s: Optional[float] = None
        self.last_prefill_s = 0.0

    # -- elasticity API (what MUDAP's ScalingAPI calls) -----------------------
    def apply(self, param: str, value: float) -> None:
        if param == "chips":
            self.cfg.chips = float(value)
        elif param == "context":
            self.cfg.context = int(value)
        elif param == "rung":
            self.cfg.rung = int(value)
        else:
            raise KeyError(param)

    def metrics(self) -> Dict[str, float]:
        return {"queue": float(len(self.queue)),
                "active": float(len(self.active)),
                "steps": float(self.steps),
                "tokens_out": float(self.tokens_out),
                "step_latency_ms": 1e3 * (self.step_ewma_s or
                                          self.last_step_s),
                "chips": self.cfg.chips, "context": float(self.cfg.context),
                "rung": float(self.cfg.rung)}

    # -- request flow ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _kept(self, req: Request) -> int:
        """How many prompt tokens an admission keeps."""
        return min(len(req.prompt), self.cfg.context, self.cfg.max_seq)

    def _truncate(self, req: Request) -> np.ndarray:
        """Keep the newest ``context`` prompt tokens (and never more than the
        cache can hold)."""
        return req.prompt[-self._kept(req):]

    def _observe_step(self, dt: float) -> None:
        self.last_step_s = dt
        self.step_ewma_s = dt if self.step_ewma_s is None else \
            (1.0 - EWMA_ALPHA) * self.step_ewma_s + EWMA_ALPHA * dt

    def _observe_prefill(self, dt: float) -> None:
        self.last_prefill_s = dt


class ServingEngine(_EngineBase):
    """Stacked-KV continuous batching: one donated cache pytree, one decode
    dispatch per step for all slots, bucketed single-trace prefill."""

    def __init__(self, model: Model, params, cfg: EngineConfig):
        super().__init__(model, params, cfg)
        # (slots, ...) stacked cache: each leaf of the batch-1 cache gains a
        # leading slot axis; per-slot write cursors live in the ``pos`` leaf.
        # The vmap keeps that axis leading in and out, so nothing transposes
        # the cache around the decode. Built in one program, so the device
        # holds it once (stacking per-slot caches held it twice)
        self._cache = jax.jit(lambda: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.slots,) + x.shape),
            model.init_cache(1, cfg.max_seq)))()
        self._last = jnp.zeros((cfg.slots,), jnp.int32)
        self._buckets = model.supports_padded_prefill
        slots = cfg.slots

        def _step_fn(params, cache, last):
            TRACE_COUNTS["serve_decode_step"] += 1   # trace-time only
            toks = last[:, None, None]               # (slots, 1, 1)
            logits, cache = jax.vmap(
                lambda t, c: model.decode(params, t, c))(toks, cache)
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            return nxt, cache

        # the cache is donated: it never round-trips to the host and the
        # buffers are reused across steps (device-resident serving state)
        self._step = jax.jit(_step_fn, donate_argnums=(1,))

        use_length = self._buckets

        def _admit_fn(params, cache, last, toks, length, slot):
            TRACE_COUNTS["serve_prefill"] += 1       # once per prompt bucket
            logits, one = model.prefill(
                params, {"tokens": toks}, max_seq=cfg.max_seq,
                length=length if use_length else None)
            first = jnp.argmax(logits[0]).astype(jnp.int32)
            cache = jax.tree.map(
                lambda big, x: jax.lax.dynamic_update_index_in_dim(
                    big, x, slot, 0), cache, one)
            last = jax.lax.dynamic_update_index_in_dim(last, first, slot, 0)
            return first, cache, last

        # slot + length are traced scalars: ONE compile per prompt bucket
        # covers every slot and every true length inside the bucket
        self._admit_one = jax.jit(_admit_fn, donate_argnums=(1, 2))
        del slots

    def _admit(self) -> None:
        budget = int(self.cfg.chips * self.cfg.tokens_per_chip_step)
        for slot in range(self.cfg.slots):
            if slot in self.active or not self.queue:
                continue
            req = self.queue[0]
            if self._kept(req) > budget:
                continue                  # not enough budget this step
            with trace.span(trace.SERVE_ADMIT, rid=req.rid,
                            slot=slot) as span:
                self.queue.pop(0)
                prompt = self._truncate(req)
                n = len(prompt)
                budget -= n
                width = bucket_length(n, self.cfg.max_seq) \
                    if self._buckets else n
                toks = np.zeros((1, width), np.int32)
                toks[0, :n] = prompt
                t0 = time.perf_counter()
                span.set_metadata(length=n, bucket=width, wait_us=int(
                    1e6 * (t0 - req.submitted_at)))
                first, self._cache, self._last = self._admit_one(
                    self.params, self._cache, self._last, jnp.asarray(toks),
                    jnp.int32(n), jnp.int32(slot))
                first = int(first)        # host sync: end of the dispatch
                self._observe_prefill(time.perf_counter() - t0)
            req.generated.append(first)
            self.active[slot] = req
            self.prompt_tokens_in += n

    def step(self) -> int:
        """One engine tick: admit, then ONE decode dispatch for the whole
        slot pool. Returns tokens produced (for *active* slots — idle lanes
        free-run and their output is discarded)."""
        with trace.span(trace.SERVE_STEP, active=len(self.active),
                        queued=len(self.queue)):
            self._admit()
            with trace.span(trace.SERVE_DECODE, active=len(self.active)):
                t0 = time.perf_counter()
                nxt, self._cache = self._step(self.params, self._cache,
                                              self._last)
                self._last = nxt
                toks = np.asarray(nxt)    # the step's one device->host sync
                self._observe_step(time.perf_counter() - t0)
            produced = 0
            finished = []
            for slot, req in list(self.active.items()):
                req.generated.append(int(toks[slot]))
                produced += 1
                if len(req.generated) >= req.max_new_tokens:
                    req.done = True
                    finished.append(slot)
                    self.completed.append(req)
            for slot in finished:
                del self.active[slot]
            self.steps += 1
            self.tokens_out += produced
        return produced


class DictCacheEngine(_EngineBase):
    """Seed-era engine: per-slot cache dict, one dispatch + host sync per
    active slot, exact-length prefill (retraces per distinct prompt length).
    Kept as the e11 benchmark baseline and seeded-parity oracle."""

    def __init__(self, model: Model, params, cfg: EngineConfig):
        super().__init__(model, params, cfg)
        self.caches: Dict[int, object] = {}
        self._prefill = jax.jit(
            lambda p, t: model.prefill(p, {"tokens": t},
                                       max_seq=cfg.max_seq))
        self._decode = jax.jit(model.decode)

    def _admit(self) -> None:
        budget = int(self.cfg.chips * self.cfg.tokens_per_chip_step)
        for slot in range(self.cfg.slots):
            if slot in self.active or not self.queue:
                continue
            req = self.queue[0]
            prompt = self._truncate(req)
            if len(prompt) > budget:
                continue                  # not enough budget this step
            self.queue.pop(0)
            budget -= len(prompt)
            toks = jnp.asarray(prompt, jnp.int32)[None, :]
            t0 = time.perf_counter()
            logits, cache = self._prefill(self.params, toks)
            first = int(jnp.argmax(logits[0]))
            self._observe_prefill(time.perf_counter() - t0)
            req.generated.append(first)
            self.active[slot] = req
            self.caches[slot] = (cache, first)
            self.prompt_tokens_in += len(prompt)

    def step(self) -> int:
        """One engine tick: admit + one decode dispatch per active slot."""
        self._admit()
        produced = 0
        finished = []
        t0 = time.perf_counter()
        for slot, req in list(self.active.items()):
            cache, last = self.caches[slot]
            tok = jnp.full((1, 1), last, jnp.int32)
            logits, cache = self._decode(self.params, tok, cache)
            nxt = int(jnp.argmax(logits[0]))
            req.generated.append(nxt)
            produced += 1
            if len(req.generated) >= req.max_new_tokens:
                req.done = True
                finished.append(slot)
                self.completed.append(req)
            else:
                self.caches[slot] = (cache, nxt)
        self._observe_step(time.perf_counter() - t0)
        for slot in finished:
            del self.active[slot], self.caches[slot]
        self.steps += 1
        self.tokens_out += produced
        return produced
