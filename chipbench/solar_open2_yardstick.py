"""Count of the work a gated delta-rule layer's decode step has to do,
from shapes and live rows (beside ``yardstick.py``, which stays as it
is: its peaks and ``floor_seconds`` are used from here). The same
count whatever implements the step."""

from __future__ import annotations


def kda_step_cost(
    rows: int, heads: int, head_dim: int, itemsize: int
) -> tuple[int, int]:
    """(flops, bytes) of ONE linear-attention layer's state update for
    ``rows`` live rows: each row's float32 state (heads x head_dim x
    head_dim) read once and written once; the row's ``q``, ``k`` and
    ``v`` (heads x head_dim each) in the served type; ``g`` (heads x
    head_dim) and ``beta`` (heads) in float32; ``o`` (heads x head_dim)
    out in float32. Per state element a decay, a multiply-add for
    ``S~^T k``, one for the rank-one update and one for the read-out:
    7 flops against 8 bytes, far under the chip's ridge of 240 flops a
    byte, so bytes are the bound."""
    state = heads * head_dim * head_dim
    per_row = (
        2 * state * 4
        + 3 * heads * head_dim * itemsize
        + heads * head_dim * 4 + heads * 4
        + heads * head_dim * 4
    )
    return rows * 7 * state, rows * per_row
