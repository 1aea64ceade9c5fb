"""Every file the harness finds by name loads, and BENCHMARK.json keeps to
the benchmark's contract."""

import json
import re

import pytest

import check
import devtrace
import run

BENCH = run.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]


def one_line(text, limit=200):
    return isinstance(text, str) and 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len((run.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    used = {c["config"] for c in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        body = run.load_named("configs", c["name"])
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["name"] in used


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=CELLS)
def test_cell_files_load(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and one_line(cell["why"])
    assert cell["chips"] in (1, 4)
    config = run.load_named("configs", cell["config"])
    traffic = run.load_named("traffic", cell["traffic"])
    assert config["chips"] == cell["chips"]
    assert traffic["order"] in (1, 2, 3)
    assert 1 <= traffic["check_steps"] <= traffic["window"]
    assert traffic["trace_windows"] >= 2
    limits = run.load_named("limits", cell["name"])
    assert {"field_gap", "charge_gap"} <= set(limits)
    assert limits["charge_gap"] == 0.0
    spec = run.build_spec(config, traffic, seed=2**40 + 17)
    assert spec.deposition.order == traffic["order"] and spec.run.window == traffic["window"]


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs)) and len(CELLS) == len(set(CELLS))
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(1, len(CELLS) // 2)


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_have_readers_and_layers():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layer_names = {l["layer"] for l in run.load_layers()} | {"window loop"}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and one_line(m["layer"]) and m["layer"] in layer_names
        assert set(m.get("workloads", [])) <= set(CELLS)
        assert callable(run.load_reader(m["name"]))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in run.cell_metrics(BENCH, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(BENCH, cell, "per_layer")


def test_layer_files_compile():
    layers = run.load_layers()
    assert {l["key"] for l in layers} >= {"sort", "gather", "push", "deposition", "maxwell"}
    for key, patterns in devtrace.compile_layers(layers):
        assert patterns


def test_unknown_names_are_refused():
    with pytest.raises(FileNotFoundError):
        run.load_named("configs", "no_such_config")
    with pytest.raises(ValueError):
        run.load_named("traffic", "../configs/uniform")
    with pytest.raises(KeyError):
        run.find_cell(BENCH, "no.such.cell")


def test_large_seeds_fold_into_31_bits():
    seeds = {run.plasma_seed(s) for s in (5, 2**33 + 5, 2**62 + 5, 2**31 + 5)}
    assert len(seeds) == 4 and all(0 <= s < 2**31 for s in seeds)
    assert run.plasma_seed(12345) == run.plasma_seed(12345)


def test_check_numbers_match_the_limit_files():
    state = {"fields": [[0.0, 1.0]] * 6, "u": [[0.0, 0.0, 1.0]], "w": [1.0], "alive": [True],
             "pos": [[0.5, 0.5, 0.5]]}
    ref = dict(state, vacuum=[[0.0, 0.0]] * 6)
    numbers = check.compare(state, ref)
    assert numbers == {"field_gap": 0.0, "momentum_gap": 0.0, "charge_gap": 0.0, "position_gap": 0.0}
    for cell in CELLS:
        assert set(run.load_named("limits", cell)) <= set(numbers)
