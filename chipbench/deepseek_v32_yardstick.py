"""Count of the work a SELECTING latent decode has to do, from shapes
and live rows (beside ``yardstick.py``, which stays as it is: its
peaks are used from the reader). The same count whichever form the
selected read ships in: the floor is token-granular."""

from __future__ import annotations


def sparse_latent_cost(
    contexts, layers: int, top_k: int, index_row: int, row: int,
    itemsize: int,
) -> int:
    """Bytes ``layers`` selecting layers have to move for ONE decode
    step of live rows at ``contexts`` (each row's positions cached,
    its newest included): every live position's index key once (the
    score pass: ``ctx x index_row`` values) and the ``min(ctx, top_k)``
    selected positions' latent rows once (``row`` values each). The
    queries, the scores and the outputs are left out: under a percent
    of it at a context of thousands. The operations are far under the
    chip's ridge in both passes (128 index dims x 64 heads x 2 flops
    against 256 bytes a key; the attention's 2 x heads x (row + values)
    a selected row), so bytes are the bound."""
    scored = sum(contexts)
    selected = sum(min(c, top_k) for c in contexts)
    return layers * itemsize * (scored * index_row + selected * row)
