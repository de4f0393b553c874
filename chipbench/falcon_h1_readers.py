"""Readers of the per-layer metrics Falcon-H1 brings: the state-space
mixer's decode kernel against its floor, and its share of the decode
program. Each returns None where the trace has no such operation (a
commit before this architecture ran), and the line then leaves the
metric out."""

from __future__ import annotations

from chipbench import falcon_h1_yardstick as fy
from chipbench import xtrace, yardstick
from chipbench.decode_runs import decode_runs, seconds_in
from chipbench.k_exaone_readers import _op_seconds

#: The kernel's name in a device trace (``adapt_tpu/ops/ssm_step.py``).
KERNEL = "_ssm_step_impl"


def ssm_step_roofline(trace, rec, kind):
    """The state update's floor in the decode runs the trace holds
    whole (every live row's state once in and once out, in every step
    of the run's scan and every mixer layer) against the device time
    of the kernel inside those runs. The floor counts live rows only,
    the kernel also moves an idle row's state: the share errs low."""
    m, runs = rec["model"], decode_runs(trace, rec)
    seconds = seconds_in(trace, runs, (KERNEL,)) if runs else None
    if not seconds or "tick_contexts" not in rec or "mamba_n_heads" not in m:
        return None
    rows = sum(len(rec["tick_contexts"][i]) for i, _, _ in runs)
    flops, nbytes = fy.ssm_step_cost(
        rows * rec["serving"]["chunk"] * m["num_hidden_layers"],
        m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
        m["mamba_n_groups"], rec["itemsize"],
    )
    if not nbytes:
        return None
    return 100.0 * yardstick.floor_seconds(flops, nbytes, kind) / seconds


def ssm_step_share_pct(trace, rec, kind):
    """The kernel's device time over the decode program's
    (``_step_chunk``): whether the mechanism does the work."""
    seconds = _op_seconds(trace, KERNEL)
    if not seconds:
        return None
    _, step = xtrace.module_seconds(trace.devices[0]).get(
        "_step_chunk", (0, 0.0)
    )
    return 100.0 * seconds / step if step else None
