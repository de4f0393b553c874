"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second first-class long-context strategy next to
:mod:`adapt_tpu.parallel.ring_attention` (neither exists in the reference —
SURVEY.md §2.2: no attention at all). Where ring attention rotates K/V
blocks around the ``sp`` ring (P-1 neighbor hops, O(S/P) memory, best when
S is huge), Ulysses does two ``lax.all_to_all`` collectives: re-shard the
[B, H, S/P, D] sequence shards into [B, H/P, S, D] head shards, run FULL
(unsharded-sequence) attention on the local heads, and all-to-all back.
Two collectives total instead of P-1 hops — the better trade when heads
are plentiful and S fits per chip; both strategies expose the same
sharded-in/sharded-out contract, so callers pick per workload.

Constraint: num_heads % axis_size == 0 (heads shard across the axis).
The local attention defaults to :func:`adapt_tpu.ops.attention.
flash_attention`, whose measured dispatch (``scores_over_budget`` — the
SAME predicate the kernel's own forward/backward and ring attention's
``"auto"`` consult, so the three can't drift) sees the post-all-to-all
local shape [B, H/P, S, D]: sub-budget scores run XLA's fused path,
super-budget runs the streaming Pallas kernel. Any custom
``attn_fn(q, k, v, causal=...)`` overrides.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    attn_fn: Callable | None = None,
) -> jax.Array:
    """Sequence-parallel attention via head/sequence all-to-all.

    q, k, v: [B, H, S, D] with S divisible by the axis size and H divisible
    by the axis size; sharded on S over ``axis`` in and out.
    """
    if attn_fn is None:
        # The measured dispatch IS the default: flash_attention routes by
        # scores_over_budget on the exact local block it will compute
        # ([B, H/P, S, D] after the head/sequence swap).
        from adapt_tpu.ops.attention import flash_attention

        attn_fn = flash_attention

    num_ranks = mesh.shape[axis]
    _, h, s, _ = q.shape
    if s % num_ranks:
        raise ValueError(f"sequence {s} not divisible by axis size {num_ranks}")
    if h % num_ranks:
        raise ValueError(f"heads {h} not divisible by axis size {num_ranks}")

    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def swapped(q_l, k_l, v_l):
        # [B, H, S/P, D] -> [B, H/P, S, D]: every rank trades sequence
        # shards for head shards (one all-to-all per tensor, on ICI).
        def to_heads(x):
            return lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        o = attn_fn(
            to_heads(q_l), to_heads(k_l), to_heads(v_l), causal=causal
        )
        # [B, H/P, S, D] -> [B, H, S/P, D]: swap back.
        return lax.all_to_all(o, axis, split_axis=2, concat_axis=1, tiled=True)

    return swapped(q, k, v)
