"""Readers of the per-layer metrics: one small function each, named by
a metric file under ``metrics/`` as ``module:function``. A reader gets
``(trace, records, device_kind)``: the reduced device trace (None
without one), the run's host records, and the device the run was on.
One that finds nothing to read returns None and the harness leaves the
metric out of the line. A later PR adds a metric by adding a metric
file and, where none of these fits, a module of its own.
"""

from __future__ import annotations

from chipbench import window as win
from chipbench import xtrace, yardstick
from chipbench.decode_runs import decode_runs, seconds_in


def _device(trace):
    return trace.devices[0] if trace and trace.devices else None


def itl_p50_ms(trace, rec, kind):
    return win.percentile(rec["gaps_ms"], 50)


def itl_p95_ms(trace, rec, kind):
    return win.percentile(rec["gaps_ms"], 95)


def ttft_p50_ms(trace, rec, kind):
    return win.percentile(rec["ttft_ms"], 50)


def ttft_p95_ms(trace, rec, kind):
    return win.percentile(rec["ttft_ms"], 95)


def queue_wait_p95_ms(trace, rec, kind):
    h = rec["histograms"].get("continuous.queue_wait_s", {})
    samples = h.get("reservoir", {}).get("samples")
    p = win.percentile(samples, 95) if samples else None
    return None if p is None else p * 1e3


def slots_active_mean(trace, rec, kind):
    ns = [
        t[2] for t in rec["ticks"]
        if win.in_window(t[1], rec["t_open"], rec["t_close"]) and t[2]
    ]
    return sum(ns) / len(ns) if ns else None


def pool_peak_pct(trace, rec, kind):
    pages = rec["serving"]["pool_pages"] - 1  # page 0 is the trash page
    if not rec["pool_peak_pages"]:
        return None
    return 100.0 * rec["pool_peak_pages"] / pages


def tick_host_ms(trace, rec, kind):
    """Per tick, the part of the traced ticks in which no operation
    ran on the device: what the host's share of a tick costs. The
    ticks are the ``chipbench.tick`` annotations, so both ends are on
    the profiler's clock."""
    dev = _device(trace)
    spans = sorted(
        (s, e) for s, e, name in trace.host if name == "chipbench.tick"
    ) if trace else []
    if dev is None or not spans:
        return None
    lo, hi = spans[0][0], spans[-1][1]
    busy = sum(e - s for s, e in xtrace.busy_between(dev, lo, hi))
    return max(0.0, (hi - lo) - busy) / len(spans) / 1e6


def decode_step_ms(trace, rec, kind):
    dev = _device(trace)
    if dev is None:
        return None
    runs, seconds = xtrace.module_seconds(dev).get("_step_chunk", (0, 0.0))
    if not runs:
        return None
    return seconds / (runs * rec["serving"]["chunk"]) * 1e3


def prefill_ms_per_ktok(trace, rec, kind):
    dev = _device(trace)
    tokens = rec["trace"]["prefill1"] - rec["trace"]["prefill0"]
    if dev is None or tokens <= 0:
        return None
    seconds = sum(
        t for name, (_, t) in xtrace.module_seconds(dev).items()
        if name.startswith("prefill")
    )
    return seconds / tokens * 1e6


def _attention_shape(rec):
    """The builder's ``shape``: all a reader may know of the
    architecture."""
    s = rec["shape"]
    return s["heads"], s["kv_heads"], s["head_dim"], s["layers"]


def paged_decode_roofline(trace, rec, kind):
    """Bytes the decode kernel had to move in the decode runs the trace
    holds whole (every live row's context once per layer and step)
    over peak bandwidth, against the device time of ``_paged_impl``
    inside those runs."""
    runs = decode_runs(trace, rec)
    seconds = seconds_in(trace, runs, ("_paged_impl",)) if runs else None
    if not seconds:
        return None
    heads, kvh, hd, layers = _attention_shape(rec)
    chunk = rec["serving"]["chunk"]
    nbytes = 0
    for i, _, _ in runs:
        # the rows the books held live at the launch and their contexts
        rows, ctx = len(rec["tick_contexts"][i]), rec["ticks"][i][3]
        for j in range(chunk):
            nbytes += layers * yardstick.paged_decode_bytes(
                ctx + j * rows, rows, heads, kvh, hd, rec["itemsize"]
            )
    if not nbytes:
        return None
    return 100.0 * yardstick.floor_seconds(0, nbytes, kind) / seconds


def paged_chunk_roofline(trace, rec, kind):
    """Floor of the chunk-prefill attention calls of the requests whose
    first token fell in the traced window, against the device time of
    ``_chunk_impl``. Passes cut off at one edge of the window stand in
    for those cut off at the other."""
    dev = _device(trace)
    seconds = xtrace.op_seconds(dev).get("_chunk_impl") if dev else None
    if not seconds:
        return None
    heads, kvh, hd, layers = _attention_shape(rec)
    step = rec["serving"]["prefill_chunk"]
    tr = rec["trace"]
    floor = 0.0
    for t, rid, idx in rec["events"]:
        if idx or not tr["t0"] <= t <= tr["t1"]:
            continue
        n = rec["reqs"][rid]["prompt_len"]
        if n <= step:
            continue
        for pos0 in range(0, n, step):
            flops, nbytes = yardstick.paged_chunk_cost(
                pos0, min(step, n - pos0), heads, kvh, hd, rec["itemsize"]
            )
            floor += layers * yardstick.floor_seconds(flops, nbytes, kind)
    return 100.0 * floor / seconds if floor else None
