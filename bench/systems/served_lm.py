"""A language model behind ``repro.serve.engine.ServingEngine``, driven by
the cell's traffic through ``submit`` and ``step``.

Set-up builds the program's model from its registry entry cut to the
configuration's depth, hands it weights drawn from the seed, and warms the
prefill buckets the traffic's prompt lengths fall in and the decode step.
The window then serves the traffic for the run's seconds. After it the
device's peak memory is read, the program's state is freed, and the
reference runs over a sample of the finished requests (``correct``).

Times are taken when ``step`` returns, which is when the engine hands its
tokens out: a request's first token arrives with the step that admitted it.
A ``prefill`` span runs from the dispatch of an admission to the engine's
own timing of it (``_observe_prefill``, once its first token is on the
host), a ``decode`` span likewise around each decode step; the harness adds
no synchronisation of its own. A run whose spans do not account for every
admission and step of its window fails.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import harness, work
from bench.reference import qwen3 as reference

# the program's config fields that the configuration file sets
_FIELDS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "d_head": "head_dim",
           "d_ff": "intermediate_size", "vocab": "vocab_size",
           "n_layers": "num_hidden_layers", "dtype": "torch_dtype",
           "rope_theta": "rope_theta", "tie_embeddings": "tie_word_embeddings"}
SAMPLE_MAX_REQUESTS = 8
SAMPLE_TOKENS = 384
NO_SAMPLE = 1e9          # the gap read when no request finished


def build_model(cfgd: dict):
    """The program's registry entry for the configuration's name, at the
    configuration file's sizes."""
    from repro.configs import get
    from repro.models import build
    pc = dataclasses.replace(get(cfgd["name"]), **{
        field: cfgd[key] for field, key in _FIELDS.items()})
    if pc.qk_norm != (cfgd["model_type"] == "qwen3") or pc.family != "dense":
        raise ValueError("the program's block differs from the configuration")
    return build(pc)


def check_layout(model, params) -> None:
    """The seeded weights have the program's own tree, shapes and dtypes."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError("seeded weights do not match the program's layout")


def buckets(lo: int, hi: int, max_seq: int) -> List[int]:
    from repro.serve.engine import bucket_length
    out = [bucket_length(lo, max_seq)]
    while out[-1] < bucket_length(hi, max_seq):
        out.append(min(out[-1] * 2, max_seq))
    return out


class Served:
    """The engine with spans around its two programs and the token times
    of every request handed to it."""

    def __init__(self, engine, spans: harness.Spans):
        self.engine, self.spans = engine, spans
        self.reqs: Dict[int, dict] = {}
        self._done = 0
        admit, step = engine._admit_one, engine._step
        prefilled, stepped = engine._observe_prefill, engine._observe_step

        def admit_one(params, cache, last, toks, length, slot):
            spans.begin("prefill", bucket=int(toks.shape[1]),
                        length=int(length))
            return admit(params, cache, last, toks, length, slot)

        def observe_prefill(dt):
            spans.end("prefill")
            prefilled(dt)

        def decode(params, cache, last):
            spans.begin("decode", contexts=[
                len(r.prompt) + len(r.generated)
                for r in engine.active.values()])
            return step(params, cache, last)

        def observe_step(dt):
            spans.end("decode")
            stepped(dt)

        engine._admit_one, engine._step = admit_one, decode
        engine._observe_prefill, engine._observe_step = \
            observe_prefill, observe_step

    def submit(self, req, due: float) -> None:
        self.reqs[req.rid] = {"due": due, "req": req, "times": []}
        self.engine.submit(req)

    def step(self) -> None:
        e = self.engine
        with self.spans.span("step"):
            e.step()
        t = time.perf_counter()
        fresh = list(e.active.values()) + e.completed[self._done:]
        self._done = len(e.completed)
        for r in fresh:
            rec = self.reqs.get(r.rid)
            if rec is not None:
                rec["times"].extend([t] * (len(r.generated) - len(rec["times"])))

    def idle(self) -> bool:
        return not self.engine.active and not self.engine.queue


def warm(served: Served, lengths: List[int]) -> None:
    """Compile and run each prefill bucket and the decode step once."""
    from repro.serve.engine import Request
    for rid, n in enumerate(lengths):
        served.engine.submit(Request(-1 - rid, np.ones(n, np.int32), 2))
    while not served.idle():
        served.engine.step()


def serve(served: Served, source, seconds: float, rng, vocab: int,
          window: harness.Window) -> None:
    """Hand the traffic to the engine and step it until ``seconds`` into
    the window; a request is due at the window's start plus its
    schedule."""
    from repro.serve.engine import Request
    engine = served.engine
    rid = len(served.reqs)
    t0 = window.t0
    while True:
        window.tick()
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        for due, n, m in source.due(now, len(engine.queue)):
            toks = rng.integers(0, vocab, n, dtype=np.int32)
            served.submit(Request(rid, toks, max_new_tokens=m), t0 + due)
            rid += 1
        if served.idle():
            time.sleep(max(0.0, min(source.next_due(), seconds) - now))
            continue
        served.step()


def account(spans: harness.Spans, tokens_in: int, steps: int) -> None:
    """The window's spans cover every prompt token the engine admitted and
    every decode step it ran, or the per-layer readings would be of part of
    the work."""
    prefilled = sum(a["length"] for _, _, _, a in spans.of("prefill"))
    if prefilled != tokens_in or len(spans.of("decode")) != steps:
        raise RuntimeError(
            f"spans cover {prefilled} of {tokens_in} admitted prompt tokens "
            f"and {len(spans.of('decode'))} of {steps} decode steps")


def end_to_end(served: Served, window, tokens_in: int):
    """The window's end-to-end metrics from the host clock, and the output
    tokens handed out in it."""
    t_end = window.t1
    ttft, gaps, out_tokens = [], [], 0
    for rec in served.reqs.values():
        times = [t for t in rec["times"] if t <= t_end]
        ttft.append((times[0] if times else t_end) - rec["due"])
        gaps.extend(np.diff(times))
        out_tokens += len(times)
    return {"ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * harness.percentile(gaps, 95) if gaps else None,
            "tokens_per_s": (tokens_in + out_tokens) / window.seconds,
            }, out_tokens


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_process: float):
    from repro.core.regression import TRACE_COUNTS
    from repro.serve.engine import EngineConfig, ServingEngine

    cfgd = cell.config
    spans = harness.Spans()
    model = build_model(cfgd)
    params = reference.make_params(cfgd, seed)
    check_layout(model, params)
    engine = ServingEngine(model, params, EngineConfig(**cfgd["engine"]))
    served = Served(engine, spans)
    prompt = cell.traffic["prompt"]
    warm(served, buckets(prompt["min"], prompt["max"],
                         cfgd["engine"]["max_seq"]))
    spans.records.clear()
    source = cell.traffic_source(seed, seconds)
    tokens_rng = np.random.default_rng([seed, 0x70C])
    before = dict(TRACE_COUNTS)
    tokens_in0, steps0 = engine.prompt_tokens_in, engine.steps
    setup_s = time.perf_counter() - t_process

    window = harness.Window(trace, cell.spec["trace_seconds"])
    with window:
        serve(served, source, seconds, tokens_rng, cfgd["vocab_size"], window)
    retraces = {k: TRACE_COUNTS[k] - before.get(k, 0) for k in TRACE_COUNTS
                if TRACE_COUNTS[k] != before.get(k, 0)}
    tokens_in = engine.prompt_tokens_in - tokens_in0
    account(spans, tokens_in, engine.steps - steps0)
    e2e, out_tokens = end_to_end(served, window, tokens_in)
    e2e["setup_s"] = setup_s
    device = harness.device_record(cell.chips)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)

    counts = {"requests": len(served.reqs), "prompt_tokens": tokens_in,
              "output_tokens": out_tokens, "steps": len(spans.of("step")),
              "prefills": len(spans.of("prefill")),
              "decodes": len(spans.of("decode")), "retraces": retraces}

    # -- correct: the reference over a sample of the finished requests ---------
    finished = [rec["req"] for rec in served.reqs.values() if rec["req"].done
                and rec["times"] and rec["times"][-1] <= window.t1]
    sample = sample_requests(finished, seed)
    attempted = len(served.reqs)
    del engine, params, served, model
    gc.collect()
    counts["served_tokens_checked"] = sum(len(r.generated) for r in sample)
    checks = check(cfgd, seed, sample, cell.spec["limits"])
    return dict(e2e=e2e, device=device, spans=spans, window=window,
                counts=counts, work=work.DenseDecoder(cfgd), checks=checks,
                attempted=attempted, failed=0, sample=sample)


def sample_requests(finished, seed: int):
    """The longest finished request, then others drawn from the seed, until
    the sample holds some hundreds of served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r.prompt) + len(r.generated)))
    rest = order[1:]
    rng = np.random.default_rng([seed, 0x5A3])
    picked = [order[0]] + [rest[i] for i in rng.permutation(len(rest))]
    sample, served = [], 0
    for r in picked:
        if len(sample) >= SAMPLE_MAX_REQUESTS or served >= SAMPLE_TOKENS:
            break
        sample.append(r)
        served += len(r.generated)
    return sample


def sequences(sample):
    """Each request's prompt and served tokens as the reference's input, and
    the tokens to score at its last positions."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
            for r in sample]
    return seqs, [np.asarray(r.generated, np.int32) for r in sample]


def check(cfgd: dict, seed: int, sample, limits: dict) -> Dict[str, dict]:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the sample (no sample reads as failed)."""
    gap = NO_SAMPLE
    if sample:
        seqs, toks = sequences(sample)
        gap = reference.widest_gap(
            reference.reference_pick(cfgd, seed, seqs, toks))
    return {"logit_gap": {"value": gap, "limit": limits["logit_gap"]}}


def control(cfgd: dict, seed: int, sample) -> Dict[str, float]:
    """The control's reading on a run's sample: the fp8 reference in the
    program's place, scored by the widest gap of the token it puts first."""
    seqs, toks = sequences(sample)
    tops = [top for _, _, top in
            reference.reference_pick(cfgd, seed, seqs, toks, quant="fp8")]
    return {"logit_gap": reference.widest_gap(
        reference.reference_pick(cfgd, seed, seqs, tops))}
