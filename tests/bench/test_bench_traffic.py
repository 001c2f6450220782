"""Traffic generators (bench/traffic) and the files the harness finds by
name (bench/workloads, bench/configs, bench/metrics, BENCHMARK.json)."""
import json
import re
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.traffic import closed_batch, control_cycles, open_poisson

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CHAT = {"kind": "open_poisson", "rate_per_s": 6.0,
        "prompt": {"median": 512, "sigma": 1.0, "min": 64, "max": 2048},
        "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512}}
DOCS = {"kind": "closed_batch", "queue_min": 64, "pool": 256,
        "prompt": {"min": 1024, "max": 2048}, "output": {"min": 16, "max": 64}}
SEED = 2 ** 31 + 12345          # seeds go past 32 signed bits


def test_open_schedule_repeats_for_a_seed():
    a = open_poisson.Source(CHAT, SEED, 30.0).schedule
    b = open_poisson.Source(CHAT, SEED, 30.0).schedule
    assert a == b and len(a) == 180


def test_open_schedule_same_sizes_another_order():
    a = open_poisson.Source(CHAT, SEED, 30.0).schedule
    b = open_poisson.Source(CHAT, SEED + 1, 30.0).schedule
    assert [x[1:] for x in a] != [x[1:] for x in b]
    for i in (1, 2):
        assert sorted(x[i] for x in a) == sorted(x[i] for x in b)
    # the same gaps, so the last request is due at the same time, inside
    assert a[-1][0] == pytest.approx(b[-1][0]) and a[-1][0] < 30.0


@pytest.mark.parametrize("spec,key", [(CHAT, "prompt"), (CHAT, "output"),
                                      (DOCS, "prompt"), (DOCS, "output")])
def test_lengths_stay_within_their_clips(spec, key):
    mod = open_poisson if spec["kind"] == "open_poisson" else closed_batch
    src = mod.Source(spec, SEED, 30.0)
    got = [a[1 if key == "prompt" else 2] for a in src.due(1e9, 0)]
    assert got and min(got) >= spec[key]["min"] and max(got) <= spec[key]["max"]


def test_open_due_hands_each_request_out_once():
    src = open_poisson.Source(CHAT, SEED, 10.0)
    first = src.due(5.0, 0)
    assert all(t <= 5.0 for t, _, _ in first)
    rest = src.due(1e9, 0)
    assert len(first) + len(rest) == len(src.schedule)
    assert src.due(1e9, 0) == [] and src.next_due() == float("inf")


def test_closed_loop_keeps_the_queue_at_least_full():
    src = closed_batch.Source(DOCS, SEED, 30.0)
    rng = np.random.default_rng(0)
    queue = 0
    for step in range(200):
        queue += len(src.due(float(step), queue))
        assert queue >= DOCS["queue_min"]
        queue -= int(rng.integers(0, 9))     # the server takes some


def test_closed_loop_sizes_repeat_for_a_seed():
    a = closed_batch.Source(DOCS, SEED, 30.0).due(0.0, 0)
    b = closed_batch.Source(DOCS, SEED, 30.0).due(0.0, 0)
    assert a == b


def test_control_cycle_load_repeats_for_a_seed():
    params = json.loads((ROOT / "bench/traffic/bursty.json").read_text())
    rates = {"qr-detector": 80.0, "cv-analyzer": 5.0}
    p1 = control_cycles.Source(params, SEED, 30.0).patterns(rates)
    p2 = control_cycles.Source(params, SEED, 30.0).patterns(rates)
    ts = np.arange(0.0, 4000.0, 7.0)
    for t in rates:
        v = [p1[t](x) for x in ts]
        assert v == [p2[t](x) for x in ts]
        assert 0.0 <= min(v) and max(v) <= rates[t] * params["rps_scale"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_loads_by_name(cell):
    c = harness.Cell(cell, BENCH)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert (c.spec["config"], c.spec["traffic"], c.chips, c.spec["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert c.system().run and c.traffic_source(SEED, 5.0) is not None
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert set(c.spec["limits"]) and all(
        v >= 0 for v in c.spec["limits"].values())


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_each_config_file_matches_its_entry(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["reduced"] == cfg["reduced"]
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_has_a_reader(metric):
    assert callable(harness.metric_reader(metric["name"]))
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    assert set(metric["workloads"]) <= set(moved.get("workloads",
                                                     metric["workloads"]))


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names)) and all(map(name.match, names))
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in BENCH["end_to_end"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_stratified_lengths_follow_the_quantiles():
    from bench.traffic import strata
    x = strata.lognormal_lengths({"median": 100, "sigma": 0.5, "min": 1,
                                  "max": 10 ** 6}, 1001)
    assert x[500] == 100 and np.all(np.diff(x) >= 0)
    assert Counter(strata.uniform_lengths({"min": 0, "max": 10}, 10)) == \
        Counter([0, 2, 2, 4, 4, 6, 6, 8, 8, 10])
