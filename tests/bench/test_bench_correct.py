"""``correct`` comes out false when the timed path is broken underneath.

Each test skips the harness's look for a chip and drives the rest of a run
on the CPU at a toy size (the cell's own code paths, smaller shapes and a
short window), with one fault planted in the program, and reads the result
line. The controls (the reference at the precision below the
configuration's, in the program's place) are checked at the same size.
"""
import json
import time

import jax.numpy as jnp
import pytest

from bench import harness

SEED = 2 ** 32 + 7


def _serving_cell():
    cell = harness.Cell("serve.qwen3-32b.chat")
    cell.config = dict(cell.config, hidden_size=64, intermediate_size=128,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, vocab_size=512, num_hidden_layers=2,
                       engine=dict(cell.config["engine"], slots=4,
                                   max_seq=128, context=64))
    cell.traffic = dict(cell.traffic, rate_per_s=8.0,
                        prompt={"median": 16, "sigma": 1.0, "min": 8, "max": 64},
                        output={"median": 8, "sigma": 0.5, "min": 4, "max": 16})
    return cell


def _decide_cell():
    cell = harness.Cell("decide.edge9")
    cell.spec = dict(cell.spec, setup_rows=65)
    return cell


def _result(cell, capsys, seconds=1.5, seed=SEED):
    out = cell.system().run(cell, seed, seconds, False, time.perf_counter())
    capsys.readouterr()
    harness.finish(cell, out, False)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1]), out


def _wrap_engine(monkeypatch, fault):
    from repro.serve import engine as eng
    init = eng.ServingEngine.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        step = self._step
        self._step = lambda p, c, last: fault(step, p, c, last)

    monkeypatch.setattr(eng.ServingEngine, "__init__", broken_init)


def test_sound_serving_run_is_correct(capsys):
    line, out = _result(_serving_cell(), capsys)
    assert line["correct"] and out["counts"]["served_tokens_checked"] > 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["logit_gap"]["value"] <= \
        line["checks"]["logit_gap"]["limit"]


def test_token_altered_where_produced(monkeypatch, capsys):
    def altered(step, p, c, last):
        nxt, cache = step(p, c, last)
        return (nxt + 1) % 512, cache
    _wrap_engine(monkeypatch, altered)
    line, _ = _result(_serving_cell(), capsys)
    assert not line["correct"]


def test_decode_returns_its_state_unchanged(monkeypatch, capsys):
    def stuck(step, p, c, last):
        pos = jnp.array(c["pos"], copy=True)
        nxt, cache = step(p, c, last)
        return nxt, dict(cache, pos=pos)
    _wrap_engine(monkeypatch, stuck)
    line, _ = _result(_serving_cell(), capsys)
    assert not line["correct"]


def test_spans_must_account_for_the_engines_work():
    from bench.systems import served_lm
    spans = harness.Spans()
    spans.begin("prefill", bucket=8, length=5)
    spans.end("prefill")
    spans.begin("decode", contexts=[6])
    spans.end("decode")
    served_lm.account(spans, 5, 1)
    with pytest.raises(RuntimeError):
        served_lm.account(spans, 9, 1)
    with pytest.raises(RuntimeError):
        served_lm.account(spans, 5, 2)
    spans.begin("decode", contexts=[7])
    with pytest.raises(RuntimeError):
        spans.begin("decode", contexts=[7])


def test_admissions_the_harness_cannot_see_fail_the_run(monkeypatch):
    from bench.systems import served_lm
    init = served_lm.Served.__init__

    def unspanned(self, engine, spans):
        admit, observe = engine._admit_one, engine._observe_prefill
        init(self, engine, spans)
        engine._admit_one, engine._observe_prefill = admit, observe
    monkeypatch.setattr(served_lm.Served, "__init__", unspanned)
    cell = _serving_cell()
    with pytest.raises(RuntimeError, match="admitted prompt tokens"):
        cell.system().run(cell, SEED, 1.5, False, time.perf_counter())


def test_fp8_control_is_not_correct(capsys):
    cell = _serving_cell()
    _, out = _result(cell, capsys)
    limit = cell.spec["limits"]["logit_gap"]
    assert cell.system().control(cell.config, SEED, out["sample"])[
        "logit_gap"] > limit


def test_sound_decide_run_is_correct(capsys):
    line, out = _result(_decide_cell(), capsys)
    assert line["correct"] and out["counts"]["checked_cycles"] > 0
    assert out["checks"]["capacity_excess"]["value"] == 0.0


def test_fit_state_left_unchanged(monkeypatch, capsys):
    from repro.core import rask
    deltas = rask.RASKAgent._stream_deltas

    def no_rows(self):
        out = deltas(self)
        return None if out is None else [(X[:0], Y[:0]) for X, Y in out]
    monkeypatch.setattr(rask.RASKAgent, "_stream_deltas", no_rows)
    line, _ = _result(_decide_cell(), capsys)
    assert not line["correct"]
    assert line["checks"]["fit_err"]["value"] > line["checks"]["fit_err"]["limit"]


def test_plan_altered_where_produced(monkeypatch, capsys):
    from repro.core import rask
    decide = rask.RASKAgent.decide

    def greedy(self, obs):
        plan = decide(self, obs)
        for values in plan.assignments.values():
            values["cores"] *= 1.5
        return plan
    monkeypatch.setattr(rask.RASKAgent, "decide", greedy)
    line, _ = _result(_decide_cell(), capsys)
    assert not line["correct"]
    assert line["checks"]["capacity_excess"]["value"] > 0


def test_solve_that_returns_its_warm_start(monkeypatch, capsys):
    # the plan then stays at the seed's first exploration point; on most
    # seeds it lies 0.27-0.29 below the optimum (the sound solve: 0.03-0.09)
    from repro.core import rask, solver

    def warm_start(x0, key, tables, sm, rps, capacity, **kw):
        a = solver.project_capacity(x0, tables.lower, tables.upper,
                                    tables.resource_mask,
                                    capacity * (1.0 - 1e-6))
        return a, solver.objective_from_tables(a, tables, sm, rps,
                                               kw["n_services"])
    monkeypatch.setattr(rask, "pgd_solve", warm_start)
    line, _ = _result(_decide_cell(), capsys, seed=77)
    assert not line["correct"]
    assert line["checks"]["objective_gap"]["value"] > \
        line["checks"]["objective_gap"]["limit"]


def test_bf16_fit_control_is_not_correct(capsys):
    cell = _decide_cell()
    _, out = _result(cell, capsys)
    readings = cell.system().control(cell.config, SEED, out["sample"])
    limits = cell.spec["limits"]
    assert readings["fit_err"] > limits["fit_err"]
    assert readings["objective_gap"] > limits["objective_gap"]


def test_run_refuses_without_a_tpu():
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "decide.edge9",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
