"""Counts of the work K-EXAONE's two kernels have to do, from shapes
and the window's counters (beside ``yardstick.py``, which stays as it
is: its peaks and ``floor_seconds`` are used from here)."""

from __future__ import annotations

from chipbench import yardstick


def expert_product_cost(
    rows: int, experts_hit: int, dim: int, hidden: int, itemsize: int
) -> tuple[int, int]:
    """(flops, bytes) of the grouped product over the experts held:
    ``rows`` (token, expert) assignments, each through one expert's
    gate, up and down matrices (2 flops a multiply-add); the three
    matrices of each of the ``experts_hit`` (expert, step, layer)
    triples that got at least one row, read once; every row in and
    out at the model's width. At 8 rows an expert a weight byte meets
    8 flops, far under the chip's ridge of 240, so bytes are the bound
    in decode."""
    flops = rows * 2 * 3 * dim * hidden
    nbytes = experts_hit * 3 * dim * hidden * itemsize
    nbytes += rows * 2 * dim * itemsize
    return flops, nbytes


def grouped_decode_bytes(
    contexts, step: int, windows, heads: int, kv_heads: int, head_dim: int,
    itemsize: int,
) -> int:
    """Bytes the paged decode kernel has to move in ONE step over every
    layer: a full layer (window None) reads each live row's whole
    context, a window layer ``min(context, window)`` of it.
    ``contexts`` are the live rows' contexts when the tick began,
    ``step`` the scan's step (a row's context grows by one a step)."""
    rows = len(contexts)
    total = 0
    for window in windows:
        seen = sum(
            c + step if window is None else min(c + step, window)
            for c in contexts
        )
        total += yardstick.paged_decode_bytes(
            seen, rows, heads, kv_heads, head_dim, itemsize
        )
    return total
