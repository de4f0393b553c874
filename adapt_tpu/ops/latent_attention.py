"""Paged attention over a LATENT cache (multi-head latent attention,
DeepSeek-V2/V3): a position stores ONE row ``[c_kv | k_r]`` — the
normed KV latent and the rotated key part every head shares — and not
K and V a head. A block's pool is ``(num_pages, row, page_size)``:
what one position stores is a row of ``row`` values, there are no K|V
halves and there is no head axis (``runtime/paged.alloc_kv_pools``).
A page keeps its positions on the minor axis: 576 values a position
do not fill whole 128-lane tiles, and a TPU lays such a plane out with
the page's 128 positions on the lanes whatever its logical shape, so
the shape says what the memory is and nothing is padded.

Decode reads it in the ABSORBED form. The caller folds ``W_UK`` into
the query (``q~_h = [q_nope,h W_UK,h^T | q_rope,h]``, ``row`` wide), so
a head's score against a position is ``q~_h . row`` and its value is
the row's first ``v_width`` lanes (``c_kv``); ``W_UV`` goes on after.
Every head of a slot therefore attends the SAME rows: the kernel
(``_latent_impl``) fetches a page once and runs all heads against it,
the page read once for scores and values.

The kernel's grid is ``(slots, page steps)``, a step covering
``pages`` table-mapped pages of one slot (the pool is handed to the
call ``pages`` times, each operand's index map naming its own page of
the step): one page is 147 KB in bfloat16 at 576 values a position,
and a grid step costs 0.15-0.35 us on a v5e before it moves a byte, so
a step of one page would spend as long starting as fetching. The
page table and the slots' positions ride as scalar prefetch, as in
``ops/paged_attention``; an operand whose page lies past the slot's
last live one names the block it held the step before, so the
pipeline issues no copy for it. The step's pages meet the queries in
independent products (bfloat16 operands, float32 accumulation) and
share ONE online-softmax update.

Chunked prefill over the same pool (:func:`latent_chunk_attention`)
is plain ``jax.numpy``: it gathers the window's pages and attends
them in the absorbed form too. It serves prompts longer than a
prefill chunk; no kernel is built for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapt_tpu.ops.decode_attention import _NEG_INF, _init_softmax_scratch
from adapt_tpu.ops.dispatch import (
    on_tpu,
    pallas_interpret,
    record_kernel_choice,
    record_kernel_dispatch,
    resolve_prefer,
)

_VMEM = pltpu.VMEM

#: What the pages of one grid step, double-buffered, may take of VMEM
#: (a quarter of Mosaic's 16 MB scope: q, the output, the float32
#: scores of every page and the accumulator need the rest).
LATENT_STEP_PAGES_BUDGET = 4 * 2 ** 20


def pages_to_rows(pages):
    """(..., row, P) pages as they live -> (..., P, row) rows by
    position."""
    return jnp.swapaxes(pages, -1, -2)


def rows_to_pages(rows, page: int):
    """(n * P, row) rows by position -> (n, row, P) pages as a latent
    pool holds them."""
    return jnp.swapaxes(rows.reshape(-1, page, rows.shape[-1]), 1, 2)


def _write_kernel(phys_ref, off_ref, new_ref, pool_ref, out_ref):
    """One slot a grid step: its page in, the new row laid over
    position ``off``'s lane, the page out (aliased onto the pool)."""
    del phys_ref  # consumed by the index maps
    off = off_ref[pl.program_id(0)]
    held = pool_ref[0].astype(jnp.float32)  # (row, page)
    lane = jax.lax.broadcasted_iota(jnp.int32, held.shape, 1)
    out_ref[0] = jnp.where(
        lane == off, new_ref[0].astype(jnp.float32), held
    ).astype(out_ref.dtype)


@jax.jit
def _latent_write_impl(pool, new, phys, off):
    b, row = new.shape
    page = pool.shape[2]

    def page_map(s, phys_ref, off_ref):
        return (phys_ref[s], 0, 0)

    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec(
                    (1, row, 1), lambda s, *_: (s, 0, 0), memory_space=_VMEM
                ),
                pl.BlockSpec((1, row, page), page_map, memory_space=_VMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, row, page), page_map, memory_space=_VMEM
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # Operands count the two prefetched vectors: the pool is the
        # fourth, and what no step names stays as it was.
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=pallas_interpret(),
    )(phys, off, new.astype(pool.dtype)[..., None], pool)


def append_latent_paged(pool, new, phys, off, prefer: str | None = None):
    """THE per-token write into a latent pool: ``pool`` (num_pages,
    w, P), ``new`` (b, w) one row a slot, ``phys``/``off`` (b,) int32;
    slot i's row lands on ``pool[phys[i], :, off[i]]``. Dead rows
    arrive routed to the trash page by the caller (several may land
    there, unread); no two live slots share a page.

    A position is a LANE of its page (the layout a plane this shape
    has on a TPU: 576 values a position do not fill whole lane tiles,
    so the compiler puts the page's 128 positions on the lanes), and a
    scatter of one lane a slot had the whole pool relaid out around
    it, there and back, at every step (compiled for a described v5e,
    PR 43). On a TPU the write is therefore a Pallas kernel
    (``_latent_write_impl``): a grid step takes one slot's page in,
    lays the row over its lane and puts the page back in place, 2 x
    147 KB a slot and layer. Elsewhere it is the scatter."""
    if resolve_prefer(
        "latent_write", prefer, latent_unsupported(pool), on_tpu()
    ):
        return _latent_write_impl(
            pool, new, jnp.asarray(phys, jnp.int32),
            jnp.asarray(off, jnp.int32),
        )
    return pool.at[phys, :, off].set(new.astype(pool.dtype))


def _live_scores(q, rows, pos, limit, sm_scale):
    """float32 scores of ``q`` (..., n, w) against ``rows`` (..., L,
    w), the positions past each query's ``limit`` (..., n) masked."""
    s = jnp.einsum(
        "...nw,...lw->...nl", q, rows, preferred_element_type=jnp.float32
    ) * sm_scale
    return jnp.where(pos <= limit[..., None], s, _NEG_INF)


def latent_attention_reference(q, pool, page_table, index, sm_scale,
                               v_width):
    """jnp oracle of the absorbed decode: gather each slot's pages
    into a contiguous window and attend it. q (b, h, w); ``pool``
    (num_pages, w, P); page_table (b, pages_per_slot) int32; index
    scalar or (b,), a slot's newest live position. -> (b, h,
    v_width)."""
    b = q.shape[0]
    rows = pages_to_rows(pool[page_table]).reshape(b, -1, pool.shape[1])
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,))
    s = _live_scores(
        q, rows, jnp.arange(rows.shape[1]),
        jnp.broadcast_to(idx[:, None], q.shape[:2]), sm_scale,
    )
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhl,blv->bhv", p.astype(rows.dtype), rows[..., :v_width],
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def latent_chunk_attention(q, pool, pages, pos0, sm_scale, v_width):
    """Chunk-prefill attention over a latent window, absorbed form:
    q (h, C, w) at positions ``[pos0, pos0 + C)``; ``pages`` (n,)
    covers ``[0, pos0 + C)`` (trash padding past it is masked by
    position). The caller has written the chunk's rows first. Plain
    ``jax.numpy`` (booked as ``latent_chunk`` on the XLA path).
    -> (h, C, v_width)."""
    record_kernel_dispatch("latent_chunk", "xla")
    rows = pages_to_rows(pool[jnp.asarray(pages, jnp.int32)]).reshape(
        -1, pool.shape[1]
    )
    limit = jnp.broadcast_to(pos0 + jnp.arange(q.shape[1]), q.shape[:2])
    s = _live_scores(q, rows, jnp.arange(rows.shape[0]), limit, sm_scale)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "hcl,lv->hcv", p.astype(rows.dtype), rows[:, :v_width],
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def latent_pages_per_step(pages_per_slot: int, page: int, row: int,
                          itemsize: int) -> int:
    """Pages one grid step of the decode kernel covers: the largest
    power of two, at most the slot's pages, whose double-buffered
    blocks fit ``LATENT_STEP_PAGES_BUDGET``: 8 of a bfloat16 pool at
    128 positions a page and 576 values a position. Derived from the
    operands, never set."""
    block = 2 * page * row * itemsize
    pages = 1
    while (
        pages * 2 <= pages_per_slot
        and pages * 2 * block <= LATENT_STEP_PAGES_BUDGET
    ):
        pages *= 2
    return pages


def latent_unsupported(pool) -> str | None:
    """None when the kernel can serve this pool, else the constraint
    broken (``resolve_prefer``'s ``unsupported``)."""
    if pool.ndim != 3:
        return f"a latent pool is (pages, row, page), got {pool.shape}"
    if pool.shape[2] % 128:
        return f"page_size {pool.shape[2]} is not a multiple of 128"
    return None


def _latent_kernel(table_ref, idx_ref, q_ref, *refs, page, steps, pages,
                   sm_scale, v_width):
    """``pages`` pages of one slot a grid step, grid (slots, steps).
    ``q_ref`` (1, h, w); each of the ``pages`` pool operands a (1,
    w, page) block, the page its index map took from the prefetched
    table. The body scores every page of the step against all heads
    (independent products), takes ONE online-softmax update over them
    and weights the positions' first ``v_width`` values. A step wholly past
    the slot's newest position (every step of a dead row, whose index
    is negative) skips the body and fetched nothing."""
    del table_ref  # consumed by the index maps
    kv_refs, (o_ref, m_scr, l_scr, acc_scr) = refs[:pages], refs[pages:]
    slot, j = pl.program_id(0), pl.program_id(1)
    idx = idx_ref[slot]
    heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def _step():
        q = q_ref[0]
        scores = []
        for i, kv_ref in enumerate(kv_refs):
            s = jax.lax.dot_general(
                q, kv_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (h, w) x (w, page) -> (h, page)
            cols = (j * pages + i) * page + jax.lax.broadcasted_iota(
                jnp.int32, (heads, page), 1
            )
            scores.append(jnp.where(cols <= idx, s, _NEG_INF))
        m = m_scr[...]
        m_new = m
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        l_new, acc = l_scr[...] * alpha, acc_scr[...] * alpha
        for s, kv_ref in zip(scores, kv_refs):
            p = jnp.exp(s - m_new)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            v = kv_ref[0][:v_width]  # (v_width, page)
            acc = acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    pl.when(j * pages * page <= idx)(_step)

    @pl.when(j == steps - 1)
    def _emit():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "v_width", "pages")
)
def _latent_impl(q, pool, page_table, index, sm_scale, v_width, pages):
    b, heads, row = q.shape
    page = pool.shape[2]
    pages_per_slot = page_table.shape[1]
    steps = -(-pages_per_slot // pages)
    prefetch = [
        jnp.asarray(page_table, jnp.int32),
        jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
    ]

    def row_map(s, j, *_):
        return (s, 0, 0)

    def kv_map(i, s, j, table_ref, idx_ref):
        # Operand i holds page j * pages + i of the slot. Past the
        # slot's last live page it names the page it held the step
        # before (the last live one congruent to i), so nothing is
        # copied for it; a slot with no live page for this operand
        # (or a dead row) names its nearest table entry.
        last = jnp.minimum(
            jnp.maximum(idx_ref[s], 0) // page, pages_per_slot - 1
        )
        mine = jnp.where(last >= i, last - (last - i) % pages, last)
        return (table_ref[s, jnp.minimum(j * pages + i, mine)], 0, 0)

    # The pool stays in HBM and the kernel streams it from there
    # (``_paged_impl``); the interpreter knows no memory spaces.
    if not pallas_interpret():
        pool = pltpu.with_memory_space_constraint(
            pool, memory_space=pltpu.HBM
        )
    kernel = functools.partial(
        _latent_kernel, page=page, steps=steps, pages=pages,
        sm_scale=sm_scale, v_width=v_width,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, steps),
            in_specs=[
                pl.BlockSpec((1, heads, row), row_map, memory_space=_VMEM)
            ] + [
                pl.BlockSpec(
                    (1, row, page), functools.partial(kv_map, i),
                    memory_space=_VMEM,
                )
                for i in range(pages)
            ],
            out_specs=pl.BlockSpec(
                (1, heads, v_width), row_map, memory_space=_VMEM
            ),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
    )(*prefetch, q, *([pool] * pages))


def latent_paged_attention(q, pool, page_table, index, *, sm_scale,
                           v_width, prefer: str | None = None):
    """Absorbed decode attention over a latent paged cache: q (b, h,
    w) queries with ``W_UK`` folded in, ``pool`` (num_pages, w, P),
    ``page_table`` (b, pages_per_slot) int32, ``index`` scalar or (b,)
    each slot's newest position (negative: a dead row, which reads
    nothing and gets zeros). -> (b, h, v_width), the probabilities'
    weighting of the positions' first ``v_width`` values.

    ``prefer`` as ``ops.paged_attention.paged_attention``: None = the
    kernel on a real TPU, the gather oracle elsewhere; ``"pallas"`` /
    ``"xla"`` force. The books (``kernel_dispatch_stats()
    ["latent_decode"]``) say which path a program was built on and how
    many pages a grid step covers."""
    if resolve_prefer(
        "latent_decode", prefer, latent_unsupported(pool), on_tpu()
    ):
        pages = latent_pages_per_step(
            page_table.shape[1], pool.shape[2], pool.shape[1],
            pool.dtype.itemsize,
        )
        record_kernel_choice("latent_decode", pages_per_step=pages)
        return _latent_impl(
            q, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(index, jnp.int32), sm_scale=float(sm_scale),
            v_width=v_width, pages=pages,
        )
    return latent_attention_reference(
        q, pool, page_table, index, sm_scale, v_width
    )
