"""The trace reducer on a trace recorded on a TPU
(benchmarks/results/r03) and on hand-made intervals."""

from pathlib import Path

import pytest

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
