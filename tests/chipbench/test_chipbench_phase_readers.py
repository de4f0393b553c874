"""The ``tick.idle_*_ms`` readers on hand-made intervals, their
manifest entries, and their walk through the CPU rehearsal."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import manifest as mf
from chipbench import phase_readers as pr
from chipbench import run as bench_run
from chipbench import xtrace

ROOT = Path(__file__).parents[2]
US = 1_000  # the intervals below are written in microseconds
READERS = {
    "admit": pr.idle_admit_ms, "first_token": pr.idle_first_token_ms,
    "launch": pr.idle_launch_ms, "fetch": pr.idle_fetch_ms,
    "commit": pr.idle_commit_ms, "outside": pr.idle_outside_ms,
}


def _ns(intervals):
    return [(s * US, e * US, *name) for s, e, *name in intervals]


def _trace(busy, host):
    ops = [(s, e, "fusion") for s, e in _ns(busy)]
    return xtrace.Trace([xtrace.DeviceTrace(ops, [])], _ns(host))


#: Two ticks. The first holds every phase; the second is all busy.
SPANS = [
    (1000, 2000, "engine.tick"),
    (1010, 1200, "engine.admit"), (1100, 1180, "engine.first_token"),
    (1210, 1400, "engine.prefill"), (1220, 1390, "engine.prefill_chunk"),
    (1300, 1380, "engine.first_token"),
    (1410, 1500, "engine.launch"), (1430, 1480, "engine.draft"),
    (1510, 1800, "engine.fetch"), (1810, 1900, "engine.commit"),
    (1910, 1950, "engine.update"),
    (2100, 3000, "engine.tick"),
    # What the profiler and the benchmark put around and inside them:
    # shorter than the engine spans, and named by no bucket.
    (990, 2005, "chipbench.tick"), (1590, 1710, "np.asarray(jax.Array)"),
    (2020, 2080, "chipbench.submit"),
]
#: Idle: 600-900 before the first tick (not counted); then, each gap
#: inside one span, 40 admit, 60 first_token, 30 prefill_chunk, 50
#: first_token, 70 launch (part of it under engine.draft, which goes
#: to its parent), 100 fetch, 10 commit (under 20 us: no bucket), 25
#: update, 40 the tick itself, 80 between the ticks, and 3100-3500
#: after the last tick (not counted).
BUSY = [
    (500, 600), (900, 1020), (1060, 1110), (1170, 1230), (1260, 1310),
    (1360, 1420), (1490, 1600), (1700, 1820), (1830, 1920), (1945, 1955),
    (1995, 2010), (2090, 3100), (3500, 3600),
]
WANT_US = {  # summed by hand, over the two ticks
    "admit": (40 + 30) / 2, "first_token": (60 + 50) / 2, "launch": 70 / 2,
    "fetch": 100 / 2, "commit": (25 + 40) / 2, "outside": 80 / 2,
}


@pytest.mark.parametrize("bucket", sorted(WANT_US))
def test_idle_goes_to_the_bucket_of_the_innermost_engine_span(bucket):
    got = READERS[bucket](_trace(BUSY, SPANS), {}, "TPU v5 lite")
    assert got == pytest.approx(WANT_US[bucket] / 1e3)


def test_a_gap_across_phases_is_cut_at_their_edges():
    """What a synchronous tick leaves: one gap from the end of a decode
    step, through the rest of fetch, commit, update and the caller, to
    the next tick's launch. Its middle (950) is in none of them."""
    spans = [
        (0, 1000, "engine.tick"), (100, 700, "engine.fetch"),
        (710, 800, "engine.commit"), (810, 900, "engine.update"),
        (1100, 2000, "engine.tick"), (1110, 1150, "engine.admit"),
        (1160, 1170, "engine.prefill"), (1200, 1400, "engine.launch"),
        (1410, 1990, "engine.fetch"),
    ]
    trace = _trace([(0, 600), (1300, 2000)], spans)
    tick_itself = 10 + 10 + 100 + 10 + 10 + 30
    want_us = {
        "fetch": 100 / 2, "commit": (90 + 90 + tick_itself) / 2,
        "outside": 100 / 2, "admit": (40 + 10) / 2, "launch": 100 / 2,
        "first_token": 0.0,
    }
    assert sum(want_us.values()) == 700 / 2
    for bucket, read in READERS.items():
        got = read(trace, {}, "TPU v5 lite")
        assert got == pytest.approx(want_us[bucket] / 1e3), bucket


def test_nested_spans_flatten_to_the_innermost():
    pieces = pr.innermost_pieces(_ns([
        (20, 30, "c"), (0, 100, "a"), (10, 50, "b"), (50, 60, "d"),
        (200, 300, "a"),
    ]))
    assert pieces == _ns([
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 50, "b"),
        (50, 60, "d"), (60, 100, "a"), (200, 300, "a"),
    ])


def test_short_gaps_and_gaps_beyond_the_ticks_are_in_no_bucket():
    gaps, n_ticks = pr.idle_by_span(_trace(BUSY, SPANS))
    assert n_ticks == 2
    assert gaps["gaps_under_20us"] == pytest.approx(10e-6)
    # 2000 us of ticks and between them, of which 1495 are busy.
    assert sum(gaps.values()) == pytest.approx(505e-6)
    assert set(gaps) - {"gaps_under_20us"} <= {
        n for names in pr.BUCKETS.values() for n in names
    }


@pytest.mark.parametrize("bucket", sorted(WANT_US))
def test_spans_without_a_gap_read_zero_and_no_spans_read_none(bucket):
    read = READERS[bucket]
    busy_throughout = _trace([(900, 3100)], SPANS)
    assert read(busy_throughout, {}, "TPU v5 lite") == 0.0
    # A program from before the spans; a run with no device plane (the
    # CPU rehearsal); an untraced run; a device idle throughout.
    no_spans = _trace(BUSY, [s for s in SPANS if "engine." not in s[2]])
    assert read(no_spans, {}, "TPU v5 lite") is None
    assert read(xtrace.Trace([], _ns(SPANS)), {}, "cpu") is None
    assert read(None, {}, "cpu") is None
    assert read(_trace([(10, 20)], SPANS), {}, "TPU v5 lite") is None


def test_the_twelve_entries_and_their_files():
    """The twelve entries this file's readers serve, as PR 24 appended
    them: in one block, each with its file and its reader. How many
    entries stand beside them, and which cells later PRs appended to a
    ``.batch`` entry's ``workloads``, is theirs to say."""
    bm = mf.load(ROOT)
    mine = [m for m in bm["per_layer"] if m["name"].startswith("tick.idle_")]
    first = bm["per_layer"].index(mine[0])
    assert bm["per_layer"][first: first + 12] == mine  # one block of twelve
    cells = {
        ".serve": ("itl_p95_ms", ["gpt2xl_chat"]),
        ".batch": ("out_tok_per_s", ["cgpt1b3_batchgen", "gpt2xl_doc"]),
    }
    reports = {
        e["name"]: e.get("workloads", [w["name"] for w in bm["workloads"]])
        for e in bm["end_to_end"]
    }
    for bucket, read in READERS.items():
        for suffix, (moves, workloads) in cells.items():
            name = f"tick.idle_{bucket}_ms{suffix}"
            (m,) = [m for m in mine if m["name"] == name]
            assert m == {
                "name": name, "unit": "ms", "better": "lower",
                "source": "device_trace", "layer": "tick loop",
                "moves": moves, "workloads": m["workloads"],
            }
            # The cells it was defined for, first; every cell reports
            # the judged metric the entry moves.
            assert m["workloads"][: len(workloads)] == workloads
            assert set(m["workloads"]) <= set(reports[moves])
            assert mf.reader_of(bm, name, ROOT) is read
            body = json.loads(
                (ROOT / "chipbench/metrics" / f"{name}.json").read_text()
            )
            assert "workloads" not in body  # BENCHMARK.json alone says
    # Every entry, whoever added it: its file is there and names a
    # reader that resolves.
    for m in bm["per_layer"]:
        assert callable(mf.reader_of(bm, m["name"], ROOT)), m["name"]


def test_rehearsal_walks_the_readers_and_prints_no_device_number(
    tmp_path, capsys
):
    """On the CPU the traced pass has the program's spans and no device
    plane: every reader is called, answers None, and the line names no
    ``tick.idle`` metric. Run from a copy, so that the trace it leaves
    is this test's alone."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    assert bench_run.main([
        "--rehearse", "--seconds", "1.5", "--workload", "gpt2xl_doc",
        "--root", str(tmp_path),
    ]) == 0
    lines = [
        ln for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("rehearsal ")
    ]
    assert len(lines) == 2 and "correct=True" in lines[1]
    assert "tick." not in lines[0] and "tick." not in lines[1]
    path = xtrace.find_xplane(str(tmp_path / ".chipbench_trace/gpt2xl_doc"))
    trace = xtrace.load(path)
    assert not trace.devices
    names = {name for _, _, name in trace.host}
    assert {
        "engine.tick", "engine.admit", "engine.prefill",
        "engine.prefill_chunk", "engine.first_token", "engine.launch",
        "engine.fetch", "engine.commit", "engine.update",
    } <= names
    assert pr.idle_by_span(trace) is None
    # Each event's attributes ride beside the tuples, index for index.
    assert len(trace.host_attrs) == len(trace.host)
    passes = [
        attrs for (_, _, name), attrs in zip(trace.host, trace.host_attrs)
        if name == "engine.prefill_chunk"
    ]
    assert passes and all(
        {"request", "pos0", "chunk_len", "final"} <= set(a) for a in passes
    )
