"""Reduction of a JAX profiler trace (``*.xplane.pb``) to numbers:
device busy time, time per operation name, and the idle gaps by what
the host was doing in them. Read with ``jax.profiler.ProfileData`` and
nothing else.

A TPU's plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation (a Pallas kernel appears under its
jitted name, e.g. ``_paged_impl``), ``XLA Modules`` one per executed
program (``jit__step_chunk(...)``). Host threads are the lines of
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land there.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import warnings
from collections import defaultdict

_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?: =|\(|$)")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
#: Gaps shorter than this are launch latency between back-to-back
#: operations, not something the host did; they are summed apart.
SHORT_GAP_NS = 20_000
_WRAPPERS = frozenset({"while", "conditional", "call"})
#: The traced window's two edges, as ``lm_engine.measure`` marks them on
#: the profiler's clock: the window runs from the END of the open mark
#: to the START of the close mark.
WINDOW_OPEN = "chipbench.window_open"
WINDOW_CLOSE = "chipbench.window_close"


def op_name(event_name: str) -> str:
    """``%copy-start.31 = (f32[...]) ...`` -> ``copy-start``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def module_name(event_name: str) -> str:
    """``jit__step_chunk(1234)`` -> ``_step_chunk``."""
    return _MODULE.match(event_name).group(1)


@dataclasses.dataclass
class DeviceTrace:
    """One device's operations as (start_ns, end_ns, name) lists."""

    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    devices: list  # DeviceTrace per device plane, in plane order
    host: list  # (start_ns, end_ns, name) of every host-thread event
    #: ``host_attrs[i]`` is the attributes of ``host[i]`` as a dict (a
    #: ``TraceAnnotation``'s keyword arguments, e.g. the ``request``,
    #: ``pos0``, ``chunk_len`` and ``final`` of ``engine.prefill_chunk``;
    #: empty for most events). Beside the tuples and not in them: every
    #: reader unpacks ``(start, end, name)``.
    host_attrs: list = dataclasses.field(default_factory=list)


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return found[-1] if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, host_attrs = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         op_name(e.name))
                        for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    modules = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         module_name(e.name))
                        for e in line.events
                    ]
            if ops or modules:
                devices.append(DeviceTrace(ops, modules))
        elif plane.name == "/host:CPU":
            with warnings.catch_warnings():
                # jaxlib 0.9's binding warns at every ``.stats`` that
                # its iterator type has no ``__module__``.
                warnings.simplefilter("ignore", DeprecationWarning)
                for line in plane.lines:
                    for e in line.events:
                        host.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        )
                        host_attrs.append(dict(e.stats))
    return Trace(devices, host, host_attrs)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(dev: DeviceTrace) -> float:
    """Seconds in which at least one operation ran on the device, over
    the whole trace."""
    return sum(e - s for s, e in union(dev.ops)) / 1e9


def _clip(busy, lo_ns, hi_ns):
    return [
        (max(s, lo_ns), min(e, hi_ns))
        for s, e in busy if e > lo_ns and s < hi_ns
    ]


def busy_between(dev: DeviceTrace, lo_ns, hi_ns) -> list[tuple[float, float]]:
    """``union(dev.ops)`` clipped to ``[lo_ns, hi_ns]``: the intervals
    of the window in which an operation ran. An operation that
    overhangs an edge counts up to the edge."""
    return _clip(union(dev.ops), lo_ns, hi_ns)


def device_window(trace: Trace) -> tuple[float, float]:
    """(lo_ns, hi_ns) of the traced window, both on the profiler's
    clock: the end of the first ``WINDOW_OPEN`` mark and the start of
    the last ``WINDOW_CLOSE`` mark. An edge whose mark the trace does
    not hold (an engine that does not go through ``lm_engine.measure``)
    is device 0's first operation's start, or its last one's end. Not
    the extent of the ``chipbench.tick`` spans: an open loop's server
    sleeps between requests, and its idle tail belongs to the window."""
    opened = [e for _, e, name in trace.host if name == WINDOW_OPEN]
    closed = [s for s, _, name in trace.host if name == WINDOW_CLOSE]
    ops = trace.devices[0].ops
    lo = min(opened) if opened else min(s for s, _, _ in ops)
    hi = max(closed) if closed else max(e for _, e, _ in ops)
    return lo, hi


def op_seconds(dev: DeviceTrace) -> dict[str, float]:
    """Device seconds per operation name. Control-flow wrappers
    (``while`` around a scan's body) span the operations they run and
    do no work themselves, so they are left out."""
    total: dict[str, float] = defaultdict(float)
    for s, e, name in dev.ops:
        if name not in _WRAPPERS:
            total[name] += (e - s) / 1e9
    return dict(total)


def module_seconds(dev: DeviceTrace) -> dict[str, tuple[int, float]]:
    """(runs, device seconds) per program name."""
    out: dict[str, list] = defaultdict(lambda: [0, 0.0])
    for s, e, name in dev.modules:
        out[name][0] += 1
        out[name][1] += (e - s) / 1e9
    return {k: (n, t) for k, (n, t) in out.items()}


def idle_gaps(dev: DeviceTrace, host, t0_ns=None, t1_ns=None):
    """Idle seconds of the device between ``t0_ns`` and ``t1_ns`` (the
    first operation's start and the last one's end where not given) by
    the host event that covers the middle of each gap: the innermost
    (shortest) covering event, so a ``chipbench.*`` annotation is named
    only where nothing more specific (``PjitFunction_*``, a transfer)
    ran inside it. Gaps under ``SHORT_GAP_NS`` go to
    ``gaps_under_20us``. The values sum to the window less the busy
    time of ``busy_between`` over the same edges."""
    busy = union(dev.ops)
    if not busy:
        return {}
    t0 = busy[0][0] if t0_ns is None else t0_ns
    t1 = busy[-1][1] if t1_ns is None else t1_ns
    busy = _clip(busy, t0, t1)
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    gaps = [
        (edges[i], edges[i + 1])
        for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    host = sorted(host)
    out: dict[str, float] = defaultdict(float)
    j = 0
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            out["gaps_under_20us"] += (e - s) / 1e9
            continue
        mid = (s + e) / 2
        while j < len(host) and host[j][1] < s - 5e9:
            j += 1  # events that ended long before cannot cover
        best = None
        for hs, he, name in host[j:]:
            if hs > mid:
                break
            if he >= mid and (best is None or he - hs < best[0]):
                best = (he - hs, name)
        out[best[1] if best else "no_host_event"] += (e - s) / 1e9
    return dict(out)


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]
    ]
