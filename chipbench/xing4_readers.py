"""Readers of the per-layer metrics Xing4.0 brings: the latent decode
kernel against its floor, and its share of the decode program. Each
returns None where the trace has no such operation (a commit before
this architecture ran), and the line then leaves the metric out."""

from __future__ import annotations

from chipbench import xing4_yardstick as xy
from chipbench import xtrace, yardstick
from chipbench.decode_runs import decode_runs, seconds_in
from chipbench.k_exaone_readers import _op_seconds

#: The kernel's name in a device trace
#: (``adapt_tpu/ops/latent_attention.py``).
KERNEL = "_latent_impl"


def latent_decode_roofline(trace, rec, kind):
    """The kernel's floor in the decode runs the trace holds whole
    (every live row's context once a layer and step, the larger of its
    bytes over peak bandwidth and its operations over peak rate)
    against the device time of the kernel inside those runs."""
    s, runs = rec["shape"], decode_runs(trace, rec)
    seconds = seconds_in(trace, runs, (KERNEL,)) if runs else None
    if not seconds or "tick_contexts" not in rec or "latent_row" not in s:
        return None
    floor = 0.0
    for i, _, _ in runs:
        contexts = rec["tick_contexts"][i]
        for j in range(rec["serving"]["chunk"]):
            flops, nbytes = xy.latent_decode_cost(
                sum(contexts) + j * len(contexts), len(contexts),
                s["heads"], s["latent_row"], s["latent_values"],
                rec["itemsize"],
            )
            floor += s["layers"] * yardstick.floor_seconds(flops, nbytes, kind)
    return 100.0 * floor / seconds if floor else None


def decode_share_pct(trace, rec, kind):
    """The kernel's device time over the decode program's
    (``_step_chunk``): whether the mechanism does the work."""
    seconds = _op_seconds(trace, KERNEL)
    if not seconds:
        return None
    _, step = xtrace.module_seconds(trace.devices[0]).get(
        "_step_chunk", (0, 0.0)
    )
    return 100.0 * seconds / step if step else None
