"""BENCHMARK.json against the contract's static rules."""

import json
import math
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_limits(bm):
    assert set(bm) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= bm["run_seconds"] <= 51 and isinstance(bm["run_seconds"], int)
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert all((ROOT / p).is_dir() for p in bm["paths"])
    assert len(bm["command"]) <= 32


def test_names_and_units(bm):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bm[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in bm["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bm["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_entries_have_exactly_the_contract_keys(bm):
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")


def _cells_of(metric, bm):
    return metric.get("workloads", [w["name"] for w in bm["workloads"]])


def test_every_cell_reports_what_the_contract_asks(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bm["workloads"]:
        mine = [m for m in bm["end_to_end"] if w["name"] in _cells_of(m, bm)]
        assert len(mine) >= 2, w["name"]
        assert any(w["name"] in _cells_of(m, bm) for m in bm["per_layer"])
    for m in bm["per_layer"]:
        for cell in _cells_of(m, bm):
            assert cell in _cells_of(e2e[m["moves"]], bm), (m["name"], cell)


def _check_correct_block(correct):
    """``correct`` in a configuration file: the tolerance with its
    readings; how many served logprobs a sampled request is compared
    over, where the file says; where the reference vouches position by
    position, the least share it must vouch for: never under 12
    positions, what half of the engine's own 3 x 8 is (a share under
    half only where the file compares more); the controls its
    reference knows."""
    assert correct["logprob_tol"] > 0 and correct["why"]
    steps = correct.get("sample_steps", 8)
    assert isinstance(steps, int) and 8 <= steps <= 256
    if "min_vouched" in correct:
        assert 0 < correct["min_vouched"] <= 1
        assert math.ceil(correct["min_vouched"] * 3 * steps) >= 12
    controls = correct.get("controls", ["drop_block"])
    assert isinstance(controls, list) and controls
    assert all(isinstance(c, str) and NAME.match(c) for c in controls)
    assert len(set(controls)) == len(controls)


@pytest.mark.parametrize(
    "file", sorted((ROOT / "tests/chipbench/another_arch/configs").glob("*.json"))
    + sorted((ROOT / "chipbench/configs").glob("*.json")),
    ids=lambda f: f.stem,
)
def test_correct_block_of_every_configuration_file(file):
    """The benchmark's files and the added architecture's fixture."""
    _check_correct_block(json.loads(file.read_text())["correct"])


@pytest.mark.parametrize("bad", [
    {"min_vouched": 0.4}, {"min_vouched": 1.5}, {"controls": []},
    {"min_vouched": 0.1, "sample_steps": 32}, {"sample_steps": 4},
    {"sample_steps": 8.5},
    {"controls": "drop_block"}, {"controls": ["drop block"]},
])
def test_correct_block_refuses(bad):
    with pytest.raises(AssertionError):
        _check_correct_block({"logprob_tol": 0.05, "why": "readings", **bad})


def test_configs_cells_chips_and_files(bm):
    cells = bm["workloads"]
    assert {c["name"] for c in bm["configs"]} == {w["config"] for w in cells}
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in cells)
    assert len(four) <= max(1, len(cells) // 4)
    paths = [ROOT / p for p in bm["paths"]]
    for c in bm["configs"]:
        f = ROOT / c["file"]
        assert f.is_file() and any(p in f.parents for p in paths)
        body = json.loads(f.read_text())
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        for key in ("model", "serving", "memory", "assumed"):
            assert key in body, (c["name"], key)
        # What belongs to the architecture is named, as a reader is.
        for key in ("engine", "builder", "reference"):
            assert ":" in body[key], (c["name"], key)
        _check_correct_block(body["correct"])
    for w in cells:
        assert any((p / "traffic" / f"{w['traffic']}.json").is_file() for p in paths)
    for m in bm["per_layer"]:
        files = [p / "metrics" / f"{m['name']}.json" for p in paths]
        found = [f for f in files if f.is_file()]
        assert found, m["name"]
        body = json.loads(found[0].read_text())
        for key in ("layer", "unit", "moves"):
            assert body[key] == m[key], (m["name"], key)
        # BENCHMARK.json alone says which cells report a metric: a copy
        # here would make every new cell edit a file that is there.
        assert "workloads" not in body, m["name"]
        assert ":" in body["reader"]


def test_pool_never_makes_a_request_wait(bm):
    for c in bm["configs"]:
        s = json.loads((ROOT / c["file"]).read_text())["serving"]
        longest_pages = -(-s["prompt_buckets"][-1] // s["page_size"])
        assert "pool_pages" not in s  # the engine sizes the pool by its rule
