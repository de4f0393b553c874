"""Metrics from events inside the measured window, and nothing else.

An event is a token a request emitted: ``(t, request, index)``. A
request still in flight when the window closes is neither a failure
nor a sample; nothing waits for a request to complete. The window is
``(t_open, t_close]`` on the host's clock.
"""

from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    """Linear-interpolation percentile (numpy's default), None when
    there is no sample."""
    if not values:
        return None
    s = sorted(values)
    k = (len(s) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(s[lo] + (s[hi] - s[lo]) * (k - lo))


def in_window(t: float, t_open: float, t_close: float) -> bool:
    return t_open < t <= t_close


def token_gaps_ms(events, t_open: float, t_close: float) -> list[float]:
    """Gaps between consecutive tokens of one request, both ends in
    the window. ``events`` are (t, request, index) in time order."""
    last: dict = {}
    gaps = []
    for t, rid, idx in events:
        prev = last.get(rid)
        if (
            prev is not None
            and prev[1] + 1 == idx
            and in_window(prev[0], t_open, t_close)
            and in_window(t, t_open, t_close)
        ):
            gaps.append((t - prev[0]) * 1e3)
        last[rid] = (t, idx)
    return gaps


def first_token_ms(events, due: dict, t_open: float, t_close: float):
    """Time from a request's DUE time to its first token, for first
    tokens that fall in the window. ``due`` maps request -> due time
    on the same clock."""
    return [
        (t - due[rid]) * 1e3
        for t, rid, idx in events
        if idx == 0 and rid in due and in_window(t, t_open, t_close)
    ]


def tokens_in_window(events, t_open: float, t_close: float) -> int:
    return sum(1 for t, _, _ in events if in_window(t, t_open, t_close))


def histogram_line(name: str, values, bins: int = 12) -> str:
    """One printable line: a reader sees whether a judged percentile
    sits inside a mode or between two."""
    if not values:
        return f"hist {name}: no samples"
    lo, hi = min(values), max(values)
    width = (hi - lo) / bins or 1.0
    counts = [0] * bins
    for v in values:
        counts[min(bins - 1, int((v - lo) / width))] += 1
    cells = " ".join(
        f"{lo + i * width:.0f}:{c}" for i, c in enumerate(counts)
    )
    return (
        f"hist {name} n={len(values)} p50={percentile(values, 50):.1f} "
        f"p95={percentile(values, 95):.1f} | {cells}"
    )
