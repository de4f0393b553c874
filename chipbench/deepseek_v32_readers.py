"""Readers of the per-layer metrics DeepSeek-V3.2-Exp brings: the
selecting decode (score pass, top-k and selected read together)
against its token-granular bytes floor, its share of the decode
program, and how much of its cache a query read. Each returns None
where the trace or the counters have no such thing (a commit before
this architecture ran), and the line then leaves the metric out."""

from __future__ import annotations

from chipbench import deepseek_v32_yardstick as dy
from chipbench import xtrace, yardstick
from chipbench.decode_runs import decode_runs, seconds_in
from chipbench.k_exaone_readers import _op_seconds

#: The device operations of the score pass, the top-k and the selected
#: read, as a device trace names them (DEEPSEEK_V32.md, "operation
#: names"): ONE Pallas kernel does all three
#: (``adapt_tpu/ops/sparse_latent_attention.py``).
KERNELS = ("_sparse_latent_impl",)


def _seconds(trace) -> float:
    return sum(_op_seconds(trace, k) or 0.0 for k in KERNELS)


def sparse_latent_roofline(trace, rec, kind):
    """The bytes floor of selection and attention in the decode runs
    the trace holds whole (every live row's index keys and its selected
    rows once a layer and step, at the chip's HBM peak) against the
    device time, inside those runs, of the score pass, the top-k and
    the selected read together."""
    s, runs = rec["shape"], decode_runs(trace, rec)
    seconds = seconds_in(trace, runs, KERNELS) if runs else None
    if not seconds or "tick_contexts" not in rec or "index_row" not in s:
        return None
    nbytes = 0
    for i, _, _ in runs:
        contexts = rec["tick_contexts"][i]
        for j in range(rec["serving"]["chunk"]):
            nbytes += dy.sparse_latent_cost(
                [c + j for c in contexts], s["layers"], s["index_topk"],
                s["index_row"], s["latent_row"], rec["itemsize"],
            )
    floor = nbytes / yardstick.peaks(kind)[1]
    return 100.0 * floor / seconds if floor else None


def step_share_pct(trace, rec, kind):
    """Those operations' device time over the decode program's
    (``_step_chunk``): read beside ``model.decode_step_ms.batch``."""
    seconds = _seconds(trace)
    if not seconds:
        return None
    _, step = xtrace.module_seconds(trace.devices[0]).get(
        "_step_chunk", (0, 0.0)
    )
    return 100.0 * seconds / step if step else None


def selected_pct(trace, rec, kind):
    """``dsa.positions_selected`` over ``dsa.positions_scored`` in the
    window: how much of its cache a query read (100: the traffic never
    reached the top-k)."""
    c = rec.get("counters", {})
    scored = c.get("dsa.positions_scored")
    if not scored:
        return None
    return 100.0 * c.get("dsa.positions_selected", 0.0) / scored
