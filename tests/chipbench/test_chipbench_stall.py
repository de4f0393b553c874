"""The stall watch: a tick that outlasts its limit is caught with the
main thread's stack, a quick one is not, and the summary of the
window's longest tick says whether the watch kept waking."""

import time

import pytest

from chipbench import stall


@pytest.fixture
def watch(monkeypatch):
    monkeypatch.setattr(stall, "PERIOD_S", 0.01)
    monkeypatch.setattr(stall, "LATE_S", 0.1)
    w = stall.StallWatch()
    w.start()
    yield w
    if w._thread.is_alive():
        w.stop()


def _tick(watch, seconds):
    t0 = time.perf_counter()
    watch.tick_t0 = t0
    time.sleep(seconds)
    watch.tick_t0 = None
    return t0, time.perf_counter()


@pytest.mark.parametrize("seconds, caught", [(0.3, 1), (0.02, 0)])
def test_a_tick_is_caught_only_past_the_limit(watch, seconds, caught):
    t_open = time.perf_counter()
    t0, t1 = _tick(watch, seconds)
    time.sleep(0.05)  # a wake after the tick: the CPU account is closed
    watch.stop()
    assert len(watch.caught) == caught
    lines = watch.lines(t0, t1, t_open)
    assert lines[0].startswith("stall watch: in the longest tick")
    if caught:
        at, late, text = watch.caught[0]
        assert at == t0 and 0.1 <= late < seconds
        assert "thread MainThread:" in text and "_tick" in text
        assert "tasks: " in text and "CPU from then to" in text
        assert any("had lasted" in ln for ln in lines)


def test_a_stalled_tick_is_caught_once_and_the_watch_kept_waking(watch):
    t0, t1 = _tick(watch, 0.4)
    _tick(watch, 0.02)
    watch.stop()
    assert len(watch.caught) == 1
    inside = [s for s in watch.samples if t0 <= s[0] <= t1]
    assert len(inside) >= 10  # a sleeping main thread holds nothing
    assert inside[-1][1] - inside[0][1] < 0.2  # and burns no CPU


def test_host_counters_are_numbers_and_do_not_fall(watch):
    watch.stop()
    first, last = watch.edges
    assert set(first) == set(last)
    for key, value in first.items():
        assert isinstance(value, float) and last[key] >= value
