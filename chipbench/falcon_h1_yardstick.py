"""Count of the work the state-space mixer's decode step has to do,
from shapes and live rows (beside ``yardstick.py``, which stays as it
is: its peaks and ``floor_seconds`` are used from here). The same
count whatever implements the step."""

from __future__ import annotations


def ssm_step_cost(
    rows: int, heads: int, head_dim: int, d_state: int, groups: int,
    itemsize: int,
) -> tuple[int, int]:
    """(flops, bytes) of ONE mixer layer's state update for ``rows``
    live rows: each row's float32 state (heads x head_dim x d_state)
    read once and written once, and the row's ``x`` and ``y`` (heads x
    head_dim), ``B`` and ``C`` (groups x d_state) in the served type
    and ``dt`` (heads, float32). Per state element a decay, a
    multiply-add of the outer product and a multiply-add of the
    read-out: 5 flops against 8 bytes, far under the chip's ridge of
    240 flops a byte, so bytes are the bound."""
    state = heads * head_dim * d_state
    flops = rows * 5 * state
    per_row = (
        2 * state * 4
        + 2 * heads * head_dim * itemsize
        + 2 * groups * d_state * itemsize
        + heads * 4
    )
    return flops, rows * per_row
