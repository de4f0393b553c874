"""Windowed metrics on hand-made event lists: events outside the
window are ignored, a gap needs both ends inside."""

import pytest

from chipbench import window as win

# (t, request, index); window is (10, 20]
EVENTS = [
    (9.0, 1, 0), (9.9, 1, 1), (10.5, 1, 2), (11.0, 1, 3),
    (12.0, 2, 0), (12.4, 2, 1), (19.9, 2, 2), (20.5, 2, 3),
    (21.0, 3, 0),
]


def test_percentile_is_linear_interpolation():
    assert win.percentile([1, 2, 3, 4], 50) == 2.5
    assert win.percentile([10], 95) == 10
    assert win.percentile(list(range(101)), 95) == 95
    assert win.percentile([], 50) is None


def test_gaps_need_both_ends_in_the_window():
    gaps = win.token_gaps_ms(EVENTS, 10.0, 20.0)
    assert sorted(round(g) for g in gaps) == [400, 500, 7500]


def test_first_tokens_from_due_time_only_inside_the_window():
    due = {1: 8.0, 2: 11.5, 3: 20.9}
    assert win.first_token_ms(EVENTS, due, 10.0, 20.0) == [
        pytest.approx(500.0)
    ]


def test_token_rate_counts_window_events_only():
    assert win.tokens_in_window(EVENTS, 10.0, 20.0) == 5
    assert win.in_window(20.0, 10.0, 20.0) and not win.in_window(10.0, 10.0, 20.0)


def test_histogram_line_shows_the_modes():
    line = win.histogram_line("x", [1.0] * 10 + [9.0] * 2, bins=4)
    assert "n=12" in line and "1:10" in line and ":2" in line
    assert win.histogram_line("x", []) == "hist x: no samples"
