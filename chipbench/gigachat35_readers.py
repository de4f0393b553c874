"""Reader of the per-layer metric GigaChat3.5 brings: what the
constant-size recurrent states hold on the device, beside
``kv.pool_peak_pct.batch`` for the pages. The kernels' metrics are the
two families' own (``solar_open2_readers``, ``xing4_readers``): the
builder's ``shape`` carries the keys both take. Returns None where the
program has no such gauge (a commit before recurrent state), and the
line then leaves the metric out."""

from __future__ import annotations


def state_gb(trace, rec, kind):
    """The gauge ``memory.state_bytes`` as it stood at the window's
    close (``stats()["state_bytes"]`` where the window's gauges lack
    it), in GB: every slot's ``(state, tail)`` of every block that
    keeps one. At a cell's fixed slots only a smaller representation
    lowers it."""
    nbytes = rec.get("gauges", {}).get("memory.state_bytes") or (
        rec.get("stats", {}).get("state_bytes")
    )
    return nbytes / 1e9 if nbytes else None
