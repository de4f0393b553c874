"""The trace reducer on a trace recorded on a TPU
(benchmarks/results/r03) and on hand-made intervals; the traced run's
device block (``run.traced_device``) and the two marks
``lm_engine.measure`` cuts it at."""

import contextlib
import types
from pathlib import Path

import pytest

from chipbench import lm_engine
from chipbench import run as bench_run
from chipbench import xtrace

TRACE_DIR = Path(__file__).parents[2] / "benchmarks/results/r03/trace"


@pytest.fixture(scope="module")
def trace():
    path = xtrace.find_xplane(str(TRACE_DIR))
    assert path, "the recorded trace is part of the repository"
    return xtrace.load(path)


def test_names():
    assert xtrace.op_name("%copy-start.31 = (f32[7,7]{1,0}) copy-start(...)") == "copy-start"
    assert xtrace.op_name("%_paged_impl.5 = bf16[8]") == "_paged_impl"
    assert xtrace.op_name("fusion.12") == "fusion"
    assert xtrace.module_name("jit__step_chunk(123456)") == "_step_chunk"
    assert xtrace.module_name("jit_prefill(9)") == "prefill"


def test_union_and_busy():
    dev = xtrace.DeviceTrace(
        ops=[(0, 10, "a"), (5, 20, "b"), (30, 40, "a")], modules=[]
    )
    assert xtrace.union(dev.ops) == [(0, 20), (30, 40)]
    assert xtrace.busy_seconds(dev) == pytest.approx(30e-9)
    assert xtrace.op_seconds(dev) == {
        "a": pytest.approx(20e-9), "b": pytest.approx(15e-9)
    }


def test_idle_gaps_go_to_the_innermost_covering_host_span():
    dev = xtrace.DeviceTrace(
        ops=[(0, 100_000, "a"), (200_000, 300_000, "a"),
             (300_010, 400_000, "a"), (900_000, 1_000_000, "a")],
        modules=[],
    )
    host = [
        (90_000, 950_000, "chipbench.tick"),
        (110_000, 190_000, "PjitFunction(prefill)"),
    ]
    gaps = xtrace.idle_gaps(dev, host)
    assert gaps["PjitFunction(prefill)"] == pytest.approx(100e-6)
    assert gaps["chipbench.tick"] == pytest.approx(500e-6)
    assert gaps["gaps_under_20us"] == pytest.approx(10e-9)


def test_recorded_trace_reduces(trace):
    (dev,) = trace.devices
    busy = xtrace.busy_seconds(dev)
    runs, seconds = xtrace.module_seconds(dev)["apply"]
    assert runs == 5
    # Operations run inside their programs: busy time is within 1% of
    # the programs' own time, and no operation name exceeds it.
    assert busy == pytest.approx(seconds, rel=0.01)
    ops = xtrace.op_seconds(dev)
    assert max(ops.values()) <= busy
    assert xtrace.top(ops, 3)[0][0] == "fusion"
    gaps = xtrace.idle_gaps(dev, trace.host)
    span = (max(e for _, e, _ in dev.ops) - min(s for s, _, _ in dev.ops)) / 1e9
    assert sum(gaps.values()) == pytest.approx(span - busy, rel=1e-6)


US = 1_000  # the intervals below are written in microseconds
OPEN, CLOSE = xtrace.WINDOW_OPEN, xtrace.WINDOW_CLOSE
#: The window's marks: it runs from 1,010 (the open mark's END) to
#: 9,000 (the close mark's START), 7,990 us.
MARKS = [(1_000, 1_010, OPEN), (9_000, 9_005, CLOSE)]
TICKS = [(1_020, 5_000, "chipbench.tick"), (5_010, 8_990, "chipbench.tick")]

#: name -> (operations of each device, host events, window us, mean busy
#: us, device 0's idle us by name).
BLOCKS = {
    # (a) A device that is never idle, whose work overhangs BOTH marks:
    # what dsv32_longgen32k's traced 8 s are when no admission falls in
    # them. The parent's run.py divided the whole trace's union (here
    # 8,600 us) by t1 - t0 of the host's clock (here ~8,000 us) and
    # printed busy_s > window_s: 7.979275 over 7.978985 s on PR 55's
    # change side, seed 3100000007, which the driver refused.
    "overhang_both_marks_no_gap": (
        [[(700, 5_000), (5_000, 9_300)]], MARKS + TICKS, 7_990, 7_990, {},
    ),
    # (b) One admission's gap inside the window, and launch latency.
    "gap_inside": (
        [[(700, 4_000), (4_500, 7_000), (7_010, 9_300)]],
        MARKS + TICKS + [(4_100, 4_400, "TransferToDevice")],
        7_990, 7_480, {"TransferToDevice": 500, "gaps_under_20us": 10},
    ),
    # An open loop's server sleeps before its first and after its last
    # tick: both tails are the window's, under whatever the host did.
    "idle_at_both_edges": (
        [[(2_000, 3_000)]],
        MARKS + [(1_100, 1_950, "sleep"), (1_900, 3_100, "chipbench.tick")],
        7_990, 1_000, {"sleep": 990, "no_host_event": 6_000},
    ),
    "busy_outside_the_window_only": (
        [[(100, 900), (9_100, 9_500)]], MARKS, 7_990, 0,
        {"no_host_event": 7_990},
    ),
    # (c) No marks: the extent of device 0's operations.
    "no_marks": (
        [[(700, 4_000), (4_500, 9_300)]], TICKS, 8_600, 8_100,
        {"chipbench.tick": 500},
    ),
    "open_mark_only": (
        [[(700, 4_000), (4_500, 9_300)]], MARKS[:1] + TICKS, 8_290, 7_790,
        {"chipbench.tick": 500},
    ),
    # The mean over the devices, each cut at the same two edges.
    "two_devices": (
        [[(700, 9_300)], [(0, 2_010), (8_000, 8_990), (9_001, 9_900)]],
        MARKS + TICKS, 7_990, (7_990 + 1_000 + 990) / 2, {},
    ),
}


def _block(devices, host):
    return xtrace.Trace(
        [
            xtrace.DeviceTrace(
                [(s * US, e * US, "fusion") for s, e in ops], []
            )
            for ops in devices
        ],
        [(s * US, e * US, name) for s, e, name in host],
    )


@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_the_device_block_is_cut_at_the_marks(case):
    devices, host, window_us, busy_us, idle_us = BLOCKS[case]
    trace = _block(devices, host)
    seconds, breakdown = bench_run.traced_device(trace)
    assert seconds["window_s"] == pytest.approx(window_us / 1e6, rel=1e-12)
    assert seconds["busy_s"] == pytest.approx(busy_us / 1e6, rel=1e-12)
    assert 0 <= seconds["busy_s"] <= seconds["window_s"]
    assert dict(breakdown["idle_gaps"]) == {
        k: pytest.approx(v / 1e6) for k, v in idle_us.items()
    }
    lo, hi = xtrace.device_window(trace)
    busy0 = sum(
        e - s for s, e in xtrace.busy_between(trace.devices[0], lo, hi)
    )
    assert sum(idle_us.values()) * US == (hi - lo) - busy0
    # The operations' table is the whole trace's.
    assert dict(breakdown["device_ops"])["fusion"] == pytest.approx(
        sum(e - s for s, e in devices[0]) / 1e6
    )


def test_a_never_idle_window_reads_busy_equal_to_window_not_above():
    """Case (a) against the parent's arithmetic: the whole trace's
    union, which the parent printed as busy_s, is longer than the
    window; cut at the marks it is the window, to the nanosecond."""
    trace = _block(*BLOCKS["overhang_both_marks_no_gap"][:2])
    seconds, breakdown = bench_run.traced_device(trace)
    assert xtrace.busy_seconds(trace.devices[0]) > seconds["window_s"]
    assert seconds["busy_s"] == seconds["window_s"]
    assert breakdown["idle_gaps"] == []


def test_recorded_trace_gives_a_device_block(trace):
    """(d) A trace recorded before the marks existed falls back to its
    operations' extent, where clipping changes nothing."""
    (dev,) = trace.devices
    seconds, breakdown = bench_run.traced_device(trace)
    assert 0 < seconds["busy_s"] <= seconds["window_s"]
    assert seconds["busy_s"] == pytest.approx(xtrace.busy_seconds(dev))
    gaps = xtrace.idle_gaps(dev, trace.host, *xtrace.device_window(trace))
    assert gaps == xtrace.idle_gaps(dev, trace.host)
    assert sum(gaps.values()) == pytest.approx(
        seconds["window_s"] - seconds["busy_s"], rel=1e-6
    )
    assert [v for _, v in breakdown["idle_gaps"]] == sorted(
        gaps.values(), reverse=True
    )[:10]


class _StubDriver:
    """What ``lm_engine.measure`` needs of a driver, with every span it
    opens written to ``log``."""

    def __init__(self, annotate, log):
        self.annotate, self.log = annotate, log
        self.live = {0: None}
        self.srv = types.SimpleNamespace(
            stats=lambda: {"prefill_tokens": 0}
        )

    def refill(self):
        pass

    def tick(self):
        with self.annotate("chipbench.tick"):
            self.log.append("tick")


@pytest.fixture
def profiler_log(monkeypatch):
    import jax

    log = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda *a, **k: log.append("start_trace")
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: log.append("stop_trace")
    )
    return log


def _measure(annotate, log, trace_dir):
    return lm_engine.measure(
        _StubDriver(annotate, log), {"loop": "closed"}, [], 0.05, 0,
        trace_dir,
    )


def test_measure_marks_the_traced_window_once_at_each_edge(profiler_log):
    log = profiler_log

    @contextlib.contextmanager
    def annotate(name):
        log.append(f"{name}>")
        yield
        log.append(f"<{name}")

    out = _measure(annotate, log, "unused")
    ticks = [i for i, x in enumerate(log) if x == "tick"]
    assert len(ticks) > 1
    # start_trace, the open mark, every tick, the close mark, stop_trace.
    assert log[:3] == ["start_trace", f"{OPEN}>", f"<{OPEN}"]
    assert log[-3:] == [f"{CLOSE}>", f"<{CLOSE}", "stop_trace"]
    assert log.index(f"<{OPEN}") < ticks[0] and ticks[-1] < log.index(f"{CLOSE}>")
    for mark in (OPEN, CLOSE):
        assert log.count(f"{mark}>") == log.count(f"<{mark}") == 1
    tr = out["trace"]
    assert tr["on"] and out["t_open"] <= tr["t0"] < tr["t1"] == out["t_close"]


@pytest.mark.parametrize("annotate", ["recording", "nullcontext"])
def test_an_untraced_measure_marks_nothing(profiler_log, annotate):
    log = profiler_log
    traced = _measure(contextlib.nullcontext, [], "unused")
    log.clear()

    @contextlib.contextmanager
    def recording(name):
        log.append(name)
        yield

    out = _measure(
        recording if annotate == "recording" else contextlib.nullcontext,
        log, None,
    )
    assert set(log) <= {"chipbench.tick", "tick"} and "tick" in log
    assert sorted(out) == sorted(traced)
    assert sorted(out["trace"]) == sorted(traced["trace"])
    assert not out["trace"]["on"]
