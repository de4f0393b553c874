"""Which runs of the decode program a trace holds whole, and the tick
that launched each: what a decode kernel's roofline sums its floor and
its device seconds over, so that both are of the SAME work.

``ContinuousBatcher.tick`` dispatches its decode program
(``_step_chunk``) inside an ``engine.launch`` span and commits the run
of the tick before, so a run executes a tick after the call that
launched it. A traced window of m ticks therefore holds the tail of a
run launched before it (its event cut at the trace's start), m - 1 runs
whole, and the head of the last (its event cut where the trace
stopped, a few milliseconds long). Floors summed over the m ticks
against the kernel's seconds over everything the trace holds are a
tick apart: 0.4% of an 8 s trace of 200 ms ticks, and a third and
more of a trace that holds three ticks or whose device events stop
before its host events do (PERF.md section 6: a reading of 107.7%
where every other run read 74.7% refused PR 56's first check). So:

- every ``engine.launch`` span in the trace lies in one
  ``chipbench.tick`` annotation, and those are, in order, the ticks of
  ``records["ticks"]`` inside ``trace.t0 .. trace.t1`` (the driver
  annotates every call and the trace starts and stops between calls);
- the device runs programs in the order they were dispatched, so the
  k-th run of the program that starts after the first launch began is
  the k-th launch's: the run cut at the trace's start began before it.
  (Where chunk passes of the tick before the trace hold the device past
  that instant, that run starts after it too and every pair is ONE
  tick off: as many floors as runs all the same, each off by what one
  tick adds to a context, 8 positions a row.)
- a run counts where the device started another program at or after
  its end: the event of a run that the trace's end cut (wherever the
  device's events end) is the device's last, and looks like a short
  run.

A trace without the spans (a program before they were added), or one
whose tick spans are not as many as the records' ticks, pairs nothing,
the readers return None and the line leaves their metrics out.
"""

from __future__ import annotations

from bisect import bisect_right

PROGRAM = "_step_chunk"
LAUNCH = "engine.launch"
TICK = "chipbench.tick"


def decode_runs(trace, rec) -> list[tuple[int, float, float]]:
    """(index into ``rec["ticks"]``, start_ns, end_ns) of every run of
    the decode program that the trace holds whole, in time order. Kept
    on the trace: a cell's readers share it."""
    if trace is None or not trace.devices:
        return []
    if not hasattr(trace, "_decode_runs"):
        trace._decode_runs = _pair(trace, rec)
    return trace._decode_runs


def _pair(trace, rec):
    tr = rec["trace"]
    inside = [
        i for i, t in enumerate(rec["ticks"])
        if tr["t0"] <= t[0] and t[1] <= tr["t1"]
    ]
    ticks = sorted((s, e) for s, e, name in trace.host if name == TICK)
    launches = sorted((s, e) for s, e, name in trace.host if name == LAUNCH)
    if not launches or len(ticks) != len(inside):
        return []
    starts = [s for s, _ in ticks]
    launched = []  # index into rec["ticks"], a launch
    for s, e in launches:
        k = bisect_right(starts, s) - 1
        if k < 0 or e > ticks[k][1]:
            return []  # a launch under no tick: not this driver's loop
        launched.append(inside[k])
    dev = trace.devices[0]
    runs = sorted(
        (s, e) for s, e, name in dev.modules
        if name == PROGRAM and s >= launches[0][0]
    )
    last_start = max(s for s, _, _ in dev.modules) if dev.modules else 0
    return [
        (i, s, e) for i, (s, e) in zip(launched, runs) if e <= last_start
    ]


def seconds_in(trace, runs, names) -> float:
    """Device seconds of the operations called one of ``names`` that
    ran inside ``runs`` (``decode_runs``'s)."""
    names = frozenset(names)
    starts = [s for _, s, _ in runs]
    total = 0
    for s, e, name in trace.devices[0].ops:
        if name in names:
            k = bisect_right(starts, s) - 1
            if k >= 0 and e <= runs[k][2]:
                total += e - s
    return total / 1e9
