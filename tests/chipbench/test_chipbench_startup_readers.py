"""The ``startup.*`` readers: their manifest entries and files, and
what each returns from a run's gauges (the program's compile account
and its constructor stamp) or from a program that has none."""

import json
from pathlib import Path

import pytest

from chipbench import manifest as mf
from chipbench import startup_readers as sr

ROOT = Path(__file__).parents[2]
#: metric -> (unit, source, reader, the gauge it reads)
METRICS = {
    "startup.programs": (
        "programs", "program_counter", sr.programs,
        "engine.compile.programs",
    ),
    "startup.trace_s": (
        "s", "host_clock", sr.trace_s, "engine.compile.trace_s",
    ),
    "startup.lower_s": (
        "s", "host_clock", sr.lower_s, "engine.compile.lower_s",
    ),
    "startup.backend_s": (
        "s", "host_clock", sr.backend_s, "engine.compile.backend_s",
    ),
    "startup.cache_misses": (
        "programs", "program_counter", sr.cache_misses,
        "engine.compile.cache_misses",
    ),
    "startup.step_program_s": (
        "s", "host_clock", sr.step_program_s,
        "engine.compile.seconds.continuous.step_chunk",
    ),
    "startup.construct_s": (
        "s", "host_clock", sr.construct_s, "engine.construct_s",
    ),
}
CELLS = [
    "gpt2xl_chat", "cgpt1b3_batchgen", "gpt2xl_doc", "kexaone_longgen",
    "falconh1_longgen",
]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_and_its_file(name):
    unit, source, reader, _ = METRICS[name]
    bm = mf.load(ROOT)
    (m,) = [m for m in bm["per_layer"] if m["name"] == name]
    assert m == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "start-up", "moves": "setup_s", "workloads": CELLS,
    }
    assert mf.reader_of(bm, name, ROOT) is reader
    body = json.loads(
        (ROOT / "chipbench/metrics" / f"{name}.json").read_text()
    )
    assert body == {
        "name": name, "layer": "start-up", "unit": unit,
        "moves": "setup_s",
        "reader": f"chipbench.startup_readers:{reader.__name__}",
    }


def test_the_seven_are_set_ups_first_per_layer_metrics():
    bm = mf.load(ROOT)
    moved = [m["name"] for m in bm["per_layer"] if m["moves"] == "setup_s"]
    assert moved == list(METRICS)  # appended, in the issue's order
    assert [m["name"] for m in bm["per_layer"]][-7:] == moved
    # Every cell reports setup_s, so every cell lists them.
    assert CELLS == [w["name"] for w in bm["workloads"]]
    for cell in CELLS:
        assert set(moved) <= {
            m["name"] for m in mf.metrics_of(bm, cell, "per_layer")
        }


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_reader_returns_its_gauge_or_none(name):
    _, _, reader, gauge = METRICS[name]
    others = {g: 7.0 for _, _, _, g in METRICS.values() if g != gauge}
    assert reader(None, {"gauges": {**others, gauge: 3.25}}, "cpu") == 3.25
    # 0 is a reading (a warm run's misses), not an absence.
    got = reader(None, {"gauges": {gauge: 0}}, "TPU v5 lite")
    assert got == 0.0 and isinstance(got, float)
    # The parent's run of a cell (no account), a cache that is off, a
    # harness older than the gauges' place in the records.
    assert reader(None, {"gauges": others}, "TPU v5 lite") is None
    assert reader(None, {"gauges": {}}, "TPU v5 lite") is None
    assert reader(None, {}, "TPU v5 lite") is None
