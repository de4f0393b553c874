"""``chipbench/decode_runs.py``: a decode kernel's roofline sums its
floor and its device seconds over the SAME runs of the decode program,
those the trace holds whole, each with the tick that launched it.
Hand-made traces, no chip."""

import pytest

from chipbench import xing4_readers as xr
from chipbench import xtrace
from chipbench.decode_runs import (
    LAUNCH, PROGRAM, TICK, decode_runs, seconds_in,
)

MS = 1_000_000
RUN = 185 * MS  # a run of the decode program: 8 steps of ~23 ms
KERNEL_MS = 9  # of it, the kernel


def _loop(m, start_ns=1_000 * MS, spans=True, idle=(), cut_ns=None):
    """The driver's loop over ``m`` ticks inside a traced window, as the
    pipelined server leaves it (my chip runs, PR 56, gigachat35): tick k
    launches run k and then waits for run k-1, so run 0 (launched before
    the trace; its event starts where the trace does, 3 ms before the
    open mark) ends inside tick 1, and run m is on the device when the
    trace stops, 13 ms into it. Ticks named in ``idle`` launch nothing
    (an open loop's server with every row in prefill). ``cut_ns``: the
    DEVICE's events stop there (the event under way ends there, later
    ones are not in the trace), the host's go on. Returns (trace,
    records): one second of the records' clock is 1e9 ns of the
    trace's."""
    host, modules, ops, ticks, contexts = [], [], [], [], []
    lo = start_ns
    host.append((lo - 10, lo, xtrace.WINDOW_OPEN))
    # run 0: launched before the trace, its last 100 ms inside it
    modules.append((lo - 3 * MS, lo + 100 * MS, PROGRAM))
    ops.append((lo + 10 * MS, lo + (10 + KERNEL_MS) * MS, xr.KERNEL))
    free = lo + 100 * MS  # when the device is free again
    at = lo + 1 * MS
    for k in range(1, m + 1):
        t_start = at
        if k not in idle:
            host.append((at + 1 * MS, at + 2 * MS, LAUNCH))
            run = (max(free, at + 2 * MS), max(free, at + 2 * MS) + RUN)
            modules.append((*run, PROGRAM))
            ops.append((run[0] + MS, run[0] + (1 + KERNEL_MS) * MS, xr.KERNEL))
            at, free = max(free, at + 2 * MS) + MS, run[1]  # waits run k-1
        else:
            at += 2 * MS
        host.append((t_start, at, TICK))
        ticks.append((t_start / 1e9, at / 1e9, 0 if k in idle else 2, 4000))
        contexts.append(() if k in idle else (1000, 3000))
        at += MS // 10
    host.append((at, at + 10, xtrace.WINDOW_CLOSE))
    cut_ns = at + 13 * MS if cut_ns is None else cut_ns
    modules = [(s, min(e, cut_ns), n) for s, e, n in modules if s < cut_ns]
    ops = [(s, min(e, cut_ns), n) for s, e, n in ops if s < cut_ns]
    if not spans:
        host = [h for h in host if h[2] != LAUNCH]
    rec = dict(
        shape=dict(heads=32, layers=1, latent_row=576, latent_values=512),
        serving=dict(chunk=8), itemsize=2,
        trace=dict(t0=lo / 1e9, t1=at / 1e9), ticks=ticks,
        tick_contexts=contexts,
    )
    return xtrace.Trace([xtrace.DeviceTrace(ops, modules)], host), rec


def _floor_of_a_tick():
    from chipbench import xing4_yardstick as xy
    from chipbench import yardstick

    return sum(
        yardstick.floor_seconds(
            *xy.latent_decode_cost(4000 + 2 * j, 2, 32, 576, 512, 2),
            "TPU v5e",
        )
        for j in range(8)
    )


TRUE_SHARE = 100.0 * _floor_of_a_tick() / (KERNEL_MS / 1e3)


@pytest.mark.parametrize("m", [3, 4, 40])
def test_a_window_of_m_ticks_holds_m_less_one_runs_whole(m):
    """Tick k's run ends inside tick k+1; the last tick's is on the
    device when the trace stops, and the run launched before the trace
    started before the first launch: neither counts."""
    trace, rec = _loop(m)
    runs = decode_runs(trace, rec)
    assert [i for i, _, _ in runs] == list(range(m - 1))
    assert seconds_in(trace, runs, (xr.KERNEL,)) == pytest.approx(
        (m - 1) * KERNEL_MS / 1e3
    )
    # The stub of run m ends BEFORE the close mark in one window of
    # three (my chip runs, PR 56: 7,859.46-7,872.97 ms of a window that
    # closed at 7,873.26): the mark does not tell a stub from a run,
    # that the device went on to nothing else does.
    close = next(s for s, _, n in trace.host if n == xtrace.WINDOW_CLOSE)
    cut, _ = _loop(m, cut_ns=close - MS // 2)
    stub = max(cut.devices[0].modules)
    assert stub[1] < close and stub[1] - stub[0] < RUN
    assert decode_runs(cut, rec) == runs


@pytest.mark.parametrize("m", [3, 4, 40])
def test_the_roofline_is_the_same_however_short_the_window(m):
    """Every run does the same work here, so the share is one number
    whatever m. The arithmetic this replaces summed the floors of the m
    ticks over the kernel's seconds in everything the trace held (the
    tail of run 0, runs 1..m-1, and of run m what ran before the trace
    stopped: here its kernel call too): m floors over m + 1 calls in
    this trace. m floors over m - 1 + 0.08 calls is the driver's
    107.666% where 8 s of 40 ticks read 74.7% (m = 3: 3 / 2.08 = 1.442
    = 107.666 / 74.67)."""
    trace, rec = _loop(m)
    assert xr.latent_decode_roofline(trace, rec, "TPU v5e") == (
        pytest.approx(TRUE_SHARE)
    )
    whole = xtrace.op_seconds(trace.devices[0])[xr.KERNEL]
    old = 100.0 * m * _floor_of_a_tick() / whole
    assert old == pytest.approx(TRUE_SHARE * m / (m + 1))


@pytest.mark.parametrize("after_run", [3, 12, 27])
def test_device_events_that_stop_early_do_not_raise_the_share(after_run):
    """The device's events end inside run ``after_run + 1`` of a
    40-tick window and the host's go on: the old arithmetic read 40
    ticks' floors over what the device recorded (over 105% from 27 runs
    down), the runs held whole read what they read."""
    whole, rec = _loop(40)
    _, s, _ = decode_runs(whole, rec)[after_run]
    trace, rec = _loop(40, cut_ns=s + 5 * MS)
    runs = decode_runs(trace, rec)
    assert [i for i, _, _ in runs] == list(range(after_run))
    assert xr.latent_decode_roofline(trace, rec, "TPU v5e") == (
        pytest.approx(TRUE_SHARE)
    )
    seconds = xtrace.op_seconds(trace.devices[0])[xr.KERNEL]
    old = 100.0 * 40 * _floor_of_a_tick() / seconds
    assert old > 1.3 * TRUE_SHARE


@pytest.mark.parametrize("case", ["no_launch_spans", "a_tick_missing",
                                  "no_trace", "no_device"])
def test_a_trace_that_cannot_be_paired_gives_nothing(case):
    trace, rec = _loop(4, spans=case != "no_launch_spans")
    if case == "a_tick_missing":
        trace.host.remove(next(h for h in trace.host if h[2] == TICK))
    if case == "no_trace":
        trace = None
    if case == "no_device":
        trace = xtrace.Trace([], trace.host)
    assert decode_runs(trace, rec) == []
    assert xr.latent_decode_roofline(trace, rec, "TPU v5e") is None


def test_a_tick_that_launched_nothing_is_no_runs_tick():
    """An open loop: tick 2 had every live row in prefill and launched
    nothing, so the second run is tick 3's."""
    trace, rec = _loop(5, idle=(2,))
    assert [i for i, _, _ in decode_runs(trace, rec)] == [0, 2, 3]


def test_operations_outside_the_runs_are_not_the_decode_programs():
    """``gmm`` runs in prefill programs too; the counters it is read
    against count decode steps only."""
    trace, rec = _loop(4)
    runs = decode_runs(trace, rec)
    _, s, e = runs[0]
    dev = trace.devices[0]
    dev.ops += [(s + 20 * MS, s + 23 * MS, "gmm"),  # in run 1
                (e - MS, e + MS, "gmm"),  # overhangs its end
                (s - 6 * MS, s - 1 * MS, "gmm")]  # a prefill pass before
    assert seconds_in(trace, runs, ("gmm",)) == pytest.approx(0.003)
    assert xtrace.op_seconds(dev)["gmm"] == pytest.approx(0.010)
