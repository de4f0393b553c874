"""Count of the work the latent decode kernel has to do, from shapes
and live rows (beside ``yardstick.py``, which stays as it is: its
peaks and ``floor_seconds`` are used from here). The same count
whatever implements the step."""

from __future__ import annotations


def latent_decode_cost(
    context_tokens: int, rows: int, heads: int, row: int, values: int,
    itemsize: int,
) -> tuple[int, int]:
    """(flops, bytes) of ONE layer's absorbed decode attention:
    ``context_tokens`` is the sum over live rows of the positions each
    attends. Every cached position's ``row`` values are read ONCE (all
    heads share them), the queries come in ``row`` wide and the
    weighted latents go out ``values`` wide, a head. A cached position
    meets every head in a score product over ``row`` and a value
    product over ``values``, 2 flops a multiply-add: ``2 x heads x
    (row + values)`` flops against ``row x itemsize`` bytes, 60 flops a
    byte in bfloat16 at 32 heads, a quarter of the chip's ridge of
    240, so bytes are the bound; the reader takes the larger floor."""
    flops = 2 * heads * (row + values) * context_tokens
    nbytes = context_tokens * row * itemsize
    nbytes += rows * heads * (row + values) * itemsize
    return flops, nbytes
