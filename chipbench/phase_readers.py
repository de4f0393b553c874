"""Readers of the ``tick.idle_*_ms`` metrics: the device's idle time
by the phase of the program's tick that the host was in.

``ContinuousBatcher.tick`` annotates itself (``EngineObs.region``): one
``engine.tick`` span a call, and inside it ``engine.admit``,
``engine.prefill`` (an ``engine.prefill_chunk`` a pass),
``engine.launch``, ``engine.fetch``, ``engine.commit`` and
``engine.update``, with ``engine.first_token`` around each blocking
first-token read. Under a profiler session they land in ``/host:CPU``
on the clock of the device's operations. They come from one thread and
nest, so at every instant one of them is innermost: each idle gap of
20 us and more between the first tick's start and the last one's end
is cut at the spans' edges and every piece goes to the span innermost
over it, the spans to six buckets, and each metric is its bucket's idle
time per ``engine.tick`` span. (Not to the one span over a gap's middle,
as ``xtrace.idle_gaps`` does for XLA's own events, which overlap across
threads: the gap a synchronous tick leaves runs from the end of one
decode step through fetch, commit, update, the caller and admit into
the next launch, and its middle sits on an edge.) A program without
the spans (any commit before they were added) gives None, and the line
leaves the metrics out.
"""

from __future__ import annotations

from collections import defaultdict

from chipbench import xtrace

TICK = "engine.tick"
OUTSIDE = "outside"  # idle under no engine.tick: the caller's loop
#: bucket -> the span names whose idle time it sums. ``engine.tick``
#: itself is what is left of a tick outside every named phase (the
#: cancel sweep, gauge refresh): host work like the commit loop, which
#: a pipelined tick would hide.
BUCKETS = {
    "admit": ("engine.admit", "engine.prefill", "engine.prefill_chunk"),
    "first_token": ("engine.first_token",),
    "launch": ("engine.launch",),
    "fetch": ("engine.fetch",),
    "commit": ("engine.commit", "engine.update", TICK),
    "outside": (OUTSIDE,),
}
_SPANS = frozenset(
    n for names in BUCKETS.values() for n in names if n != OUTSIDE
)


def innermost_pieces(spans):
    """Nested (start, end, name) spans of one thread, flattened:
    disjoint pieces in time order, each named by the span innermost
    over it."""
    pieces, stack, at = [], [], 0

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack and s > at:
            pieces.append((at, s, stack[-1][1]))
        stack.append((e, name))
        at = s
    close_until(float("inf"))
    return pieces


def idle_by_span(trace):
    """(idle seconds by span name, number of ticks), or None without a
    device trace or an ``engine.tick`` span. Spans the program nests
    under these but the buckets do not name (``engine.draft``) are
    left out, so their time goes to the parent. Kept on the trace: the
    harness calls six readers on the one object."""
    if trace is None or not trace.devices:
        return None
    if not hasattr(trace, "_tick_idle"):
        trace._tick_idle = _idle_by_span(trace)
    return trace._tick_idle


def _idle_by_span(trace):
    spans = [ev for ev in trace.host if ev[2] in _SPANS]
    ticks = [(s, e) for s, e, name in spans if name == TICK]
    if not ticks:
        return None
    lo, hi = min(s for s, _ in ticks), max(e for _, e in ticks)
    busy = xtrace.busy_between(trace.devices[0], lo, hi)
    if not busy:
        return None
    edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
    idle: dict[str, float] = defaultdict(float)
    pieces, k = innermost_pieces(spans), 0
    for s, e in zip(edges[::2], edges[1::2]):
        if e - s < xtrace.SHORT_GAP_NS:
            if e > s:
                idle["gaps_under_20us"] += (e - s) / 1e9
            continue
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        named, j = 0, k
        while j < len(pieces) and pieces[j][0] < e:
            a, b, name = pieces[j]
            part = min(b, e) - max(a, s)
            idle[name] += part / 1e9
            named += part
            j += 1
        if e - s > named:
            idle[OUTSIDE] += (e - s - named) / 1e9
    return dict(idle), len(ticks)


def _idle_ms(trace, bucket: str):
    found = idle_by_span(trace)
    if found is None:
        return None
    gaps, n_ticks = found
    return sum(gaps.get(n, 0.0) for n in BUCKETS[bucket]) / n_ticks * 1e3


def idle_admit_ms(trace, rec, kind):
    return _idle_ms(trace, "admit")


def idle_first_token_ms(trace, rec, kind):
    return _idle_ms(trace, "first_token")


def idle_launch_ms(trace, rec, kind):
    return _idle_ms(trace, "launch")


def idle_fetch_ms(trace, rec, kind):
    return _idle_ms(trace, "fetch")


def idle_commit_ms(trace, rec, kind):
    return _idle_ms(trace, "commit")


def idle_outside_ms(trace, rec, kind):
    return _idle_ms(trace, "outside")
