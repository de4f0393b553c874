"""Ring attention: sequence parallelism for long contexts.

Not in the reference (SURVEY.md §2.2: no attention at all), but first-class
here: sequences too long for one chip's HBM are sharded over an ``sp`` mesh
axis; each device holds a [S/P] slice of Q, K, V. K/V blocks rotate around
the ring via ``lax.ppermute`` (ICI neighbor hops) while each device
accumulates its Q-block's attention with the streaming (online-softmax)
update, so the full S x S score matrix never materializes — compute stays
flash-style blockwise and memory per chip is O(S/P).

The accumulator update is the standard two-pass-free softmax: carrying
running max ``m``, normalizer ``l``, and unnormalized output ``o``; each
incoming K/V block rescales the accumulators by ``exp(m - m_new)``.
Causal masking uses *global* positions recovered from ring step and rank,
so the result matches single-device causal attention exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


_NEG_INF = -1e30


def _attention_block(q, k, v, mask, m, l, o):
    """One online-softmax accumulation step.

    q: [B, H, Sq, D]; k, v: [B, H, Skv, D]; mask: [Sq, Skv] additive.
    m, l: [B, H, Sq, 1]; o: [B, H, Sq, D].
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + mask
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def stripe_sequence(x: jax.Array, num_ranks: int, axis: int = 2) -> jax.Array:
    """Permute a sequence axis into the STRIPED ring layout: rank r's
    shard holds tokens {r, r + P, r + 2P, ...} instead of a contiguous
    block. ``stripe(x)[..., r*s_local + i, ...] = x[..., i*P + r, ...]``.
    Apply to q/k/v before ``ring_attention(..., layout="striped")`` and
    :func:`unstripe_sequence` to the output (a reshape-transpose; under
    GSPMD it lowers to one all-to-all–class relayout at the boundary,
    paid once per sequence, not per ring step)."""
    s = x.shape[axis]
    if s % num_ranks:
        raise ValueError(f"sequence {s} not divisible by {num_ranks}")
    parts = jnp.moveaxis(x, axis, 0).reshape(
        s // num_ranks, num_ranks, *x.shape[:axis], *x.shape[axis + 1:]
    )
    return jnp.moveaxis(
        jnp.swapaxes(parts, 0, 1).reshape(s, *x.shape[:axis],
                                          *x.shape[axis + 1:]),
        0, axis,
    )


def unstripe_sequence(x: jax.Array, num_ranks: int, axis: int = 2) -> jax.Array:
    """Inverse of :func:`stripe_sequence` — which is striping by the
    complementary factor (out[i*P + r] = x[r*(S/P) + i] both ways), so
    one permutation body serves both and cannot desynchronize."""
    s = x.shape[axis]
    if s % num_ranks:
        raise ValueError(f"sequence {s} not divisible by {num_ranks}")
    return stripe_sequence(x, s // num_ranks, axis)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    block_impl: str = "jnp",
    layout: str = "contiguous",
) -> jax.Array:
    """Sequence-parallel attention over ``axis``.

    q, k, v: [B, H, S, D] with S divisible by the axis size; inputs/outputs
    are sharded on the S dimension over ``axis`` (pass global arrays under
    jit; GSPMD splits them per the shard_map specs).

    ``block_impl`` picks the per-device block compute:

    - ``"jnp"`` (default) — the fused-by-XLA online-softmax update
      below. Fully differentiable (training and serving); materializes
      one (S/P, S/P) score block per ring step, which is fine until
      shards are themselves long.
    - ``"flash"`` — the streaming Pallas kernel via
      :func:`adapt_tpu.ops.attention.flash_attention_with_lse`; per-step
      results merge by logsumexp, so per-device memory stays O(S/P * D)
      even at 32k-token *shards* (the regime where a materialized score
      block is itself gigabytes — same wall as
      ``benchmarks/results/r03/attn_longseq.json``). FORWARD-ONLY: the
      lse entry point has no VJP; ``jax.grad`` through it raises a
      ``NotImplementedError`` naming ``block_impl`` at this function's
      boundary — an explicit serving-path opt-in, which is why it is
      not the default.
    - ``"auto"`` — ``"flash"`` exactly when a single score block busts
      ``FLASH_SCORE_BYTES_BUDGET`` (the same measured predicate the
      kernel dispatch uses), ``"jnp"`` otherwise. For inference
      pipelines that want the memory ceiling lifted without thinking;
      carries the same forward-only caveat whenever it picks flash.

    ``layout`` is how global token positions map to shards:

    - ``"contiguous"`` (default) — rank r holds tokens [r*S/P, (r+1)*S/P).
      Under ``causal`` the ring is LOAD-IMBALANCED: rank 0's queries see
      only their own block while rank P-1's see everything, and because
      the ``ppermute`` rotation must run the same trip count on every
      rank, the idle lower-triangle steps are latency floor, not saved
      work (the flash path's ``lax.cond`` computes both branches under
      SPMD).
    - ``"striped"`` — rank r holds tokens {r, r+P, ...} (pre-permute
      q/k/v with :func:`stripe_sequence`, un-permute the output with
      :func:`unstripe_sequence`; the output of this function is in
      striped order). Every causal ring step becomes a triangular block
      with diagonal shift 0 (src <= rank) or 1 (src > rank) — uniformly
      HALF the work on every rank at every step, with no cond at all:
      the flash path passes the traced shift to the kernel's
      ``causal_shift`` and rides its block-skip, the jnp path's mask
      just uses striped positions. This is the classic striped-attention
      balance fix; ~2x over contiguous causal at long S.
    """
    num_ranks = mesh.shape[axis]
    seq = q.shape[2]
    if seq % num_ranks:
        raise ValueError(f"sequence {seq} not divisible by ring size {num_ranks}")
    s_local = seq // num_ranks
    ring = [(i, (i + 1) % num_ranks) for i in range(num_ranks)]

    if block_impl not in ("auto", "jnp", "flash"):
        raise ValueError(
            f"block_impl={block_impl!r}: expected 'auto', 'jnp' or 'flash'"
        )
    if layout not in ("contiguous", "striped"):
        raise ValueError(
            f"layout={layout!r}: expected 'contiguous' or 'striped'"
        )
    if block_impl == "auto":
        from adapt_tpu.ops.attention import scores_over_budget

        local_shape = (q.shape[0], q.shape[1], s_local, q.shape[3])
        block_impl = (
            "flash" if scores_over_budget(local_shape, local_shape) else "jnp"
        )
    if block_impl == "flash":
        # custom_vjp wrapper so differentiating (e.g. a training run whose
        # sequence length grew past the budget while "auto" silently
        # switched to flash) fails at THIS boundary with a message naming
        # block_impl — not deep inside pallas_call internals.
        kw = dict(
            mesh=mesh,
            axis=axis,
            causal=causal,
            num_ranks=num_ranks,
            s_local=s_local,
            ring=ring,
            striped=layout == "striped",
        )

        @jax.custom_vjp
        def run(q, k, v):
            return _ring_attention_flash(q, k, v, **kw)

        def fwd(q, k, v):
            return _ring_attention_flash(q, k, v, **kw), None

        def bwd(_, g):
            raise NotImplementedError(
                "ring_attention block_impl='flash' (including 'auto' "
                "resolving to flash at this shard shape) is forward-only: "
                "the streaming-kernel lse entry point has no VJP. Use "
                "block_impl='jnp' for training, or shrink the per-shard "
                "score block under FLASH_SCORE_BYTES_BUDGET."
            )

        run.defvjp(fwd, bwd)
        return run(q, k, v)

    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def ringed(q_l, k_l, v_l):
        rank = lax.axis_index(axis)
        b, h, sq, d = q_l.shape
        local = jnp.arange(s_local)
        q_pos = (
            local * num_ranks + rank
            if layout == "striped"
            else rank * s_local + local
        )

        def step(carry, i):
            m, l, o, k_cur, v_cur = carry
            # After i hops of forward rotation, this rank holds the K/V
            # block that originated at rank - i (mod P).
            src = jnp.mod(rank - i, num_ranks)
            kv_pos = (
                local * num_ranks + src
                if layout == "striped"
                else src * s_local + local
            )
            if causal:
                mask = jnp.where(
                    q_pos[:, None] >= kv_pos[None, :], 0.0, _NEG_INF
                ).astype(q_l.dtype)
            else:
                mask = jnp.zeros((s_local, s_local), q_l.dtype)
            m, l, o = _attention_block(q_l, k_cur, v_cur, mask, m, l, o)
            k_nxt = lax.ppermute(k_cur, axis, ring)
            v_nxt = lax.ppermute(v_cur, axis, ring)
            return (m, l, o, k_nxt, v_nxt), None

        init = (
            *lax.pcast(
                (
                    jnp.full((b, h, sq, 1), _NEG_INF, q_l.dtype),
                    jnp.zeros((b, h, sq, 1), q_l.dtype),
                    jnp.zeros((b, h, sq, d), q_l.dtype),
                ),
                (axis,),
                to="varying",
            ),
            k_l,
            v_l,
        )
        (m, l, o, _, _), _ = lax.scan(step, init, jnp.arange(num_ranks))
        return o / jnp.maximum(l, 1e-20)

    return ringed(q, k, v)


def _ring_attention_flash(
    q, k, v, mesh, axis, causal, num_ranks, s_local, ring, striped=False
):
    """Ring attention whose per-device block compute is the streaming
    Pallas kernel; per-step normalized results combine exactly via the
    logsumexp merge (see ``flash_attention_with_lse``'s contract).

    Under causal masking every (rank, step) block is all-or-nothing
    except the diagonal: the K/V block that originated at ``src`` is
    fully visible when ``src < rank``, fully masked when ``src > rank``,
    and plain causal when ``src == rank`` (step 0) — so no positional
    mask tensor is ever built; the diagonal runs the kernel's own causal
    path and masked steps contribute ``lse = -inf`` to the merge.

    The CONTIGUOUS layout's ``lax.cond`` on ``src < rank`` is
    *correctness* masking, not a compute skip: under SPMD the predicate
    is device-varying, so XLA lowers the cond to running both branches
    and selecting — every rank pays the full kernel on its dead steps
    too. Shortening the loop per-rank cannot fix this: the ``ppermute``
    rotation must run the same number of times on every rank or the
    collective deadlocks, so the contiguous causal ring's lower triangle
    is latency floor, not saved work.

    ``striped=True`` IS the classic layout fix: with tokens striped
    round-robin (see :func:`stripe_sequence`), every (rank, step) causal
    block is a triangle with diagonal shift ``src > rank`` — no cond, no
    dead blocks; each step passes the traced shift to the kernel's
    ``causal_shift`` and its block-level skip does ~half the work,
    uniformly on every rank."""
    from adapt_tpu.ops.attention import flash_attention_with_lse

    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def ringed(q_l, k_l, v_l):
        rank = lax.axis_index(axis)
        # Step 0: the diagonal block (q and K/V positions coincide).
        o0, lse = flash_attention_with_lse(q_l, k_l, v_l, causal=causal)
        o = o0.astype(jnp.float32)
        k_cur = lax.ppermute(k_l, axis, ring)
        v_cur = lax.ppermute(v_l, axis, ring)

        def step(carry, i):
            o, lse, k_cur, v_cur = carry
            src = jnp.mod(rank - i, num_ranks)

            def live(_):
                o_j, lse_j = flash_attention_with_lse(
                    q_l, k_cur, v_cur, causal=False
                )
                return o_j.astype(jnp.float32), lse_j

            def dead(_):
                return (
                    jnp.zeros(o.shape, jnp.float32),
                    jnp.full(lse.shape, _NEG_INF, jnp.float32),
                )

            if causal and striped:
                # Balanced path: every step is a shift-0/1 triangle —
                # the kernel's own causal block-skip does ~half the
                # work on every rank, no cond, no dead blocks.
                o_j, lse_j = flash_attention_with_lse(
                    q_l, k_cur, v_cur, causal=True,
                    causal_shift=(src > rank).astype(jnp.int32),
                )
                o_j = o_j.astype(jnp.float32)
            elif causal:
                o_j, lse_j = lax.cond(src < rank, live, dead, None)
            else:
                o_j, lse_j = live(None)
            m = jnp.maximum(lse, lse_j)
            w_a = jnp.exp(lse - m)
            w_b = jnp.exp(lse_j - m)
            denom = w_a + w_b
            o_new = (
                o * w_a[..., None] + o_j * w_b[..., None]
            ) / denom[..., None]
            lse_new = m + jnp.log(denom)
            # Collectives stay unconditional (outside the cond).
            k_nxt = lax.ppermute(k_cur, axis, ring)
            v_nxt = lax.ppermute(v_cur, axis, ring)
            return (o_new, lse_new, k_nxt, v_nxt), None

        (o, lse, _, _), _ = lax.scan(
            step, (o, lse, k_cur, v_cur), jnp.arange(1, num_ranks)
        )
        return o.astype(q_l.dtype)

    return ringed(q, k, v)


def full_attention(q, k, v, causal: bool = False) -> jax.Array:
    """Single-device oracle — delegates to the one canonical reference in
    :mod:`adapt_tpu.ops.attention` (same causal convention: absolute
    position i attends j <= i)."""
    from adapt_tpu.ops.attention import attention_reference

    return attention_reference(q, k, v, causal=causal)
