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

The kernel's grid is ``(slots,)``, and it walks a slot's LIVE pages
itself: the pool is handed to the call once and stays where it lives,
the page table and the slots' positions ride as scalar prefetch (as
in ``ops/paged_attention``), and a loop inside the kernel copies
``pages`` table-mapped pages an iteration into one of two VMEM buffers
while the other's are consumed, ``idx // page + 1`` pages in all: a
page past the slot's newest position is neither copied nor scored,
and a slot's last iteration has the next live slot's first pages in
flight before it waits on its own. (A page axis on the grid takes
its 8 steps a slot whether they move a byte or not, 0.75 us each on a
v5e: 46% of the bytes floor in ``xing4_longgen8k`` where the walk
reads 81%, PERF.md section 6, PR 44.) One page is 147 KB in bfloat16
at 576 values a position; an iteration's pages meet the queries in
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

#: What the pages of one iteration, double-buffered, may take of VMEM
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
    """Pages one iteration of the decode kernel's walk covers: the
    largest power of two, at most the slot's pages, whose two buffers
    fit ``LATENT_STEP_PAGES_BUDGET``: 8 of a bfloat16 pool at 128
    positions a page and 576 values a position (4 read 15% slower a
    call on a v5e, 16 the same: PERF.md section 6, PR 44). Derived
    from the operands, never set."""
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


def _latent_kernel(table_ref, idx_ref, q_ref, pool_ref, o_ref, buf, sems,
                   cur_ref, m_scr, l_scr, acc_scr, *, page, pages, sm_scale,
                   v_width):
    """One slot a grid step, grid (slots,); the slot's LIVE pages are
    walked here, ``pages`` an iteration. ``q_ref`` (1, h, w);
    ``pool_ref`` the whole pool where it lives; ``buf`` (2, pages, w,
    page) the two buffers the iterations alternate between, ``sems``
    one DMA semaphore a buffer, ``cur_ref`` (SMEM) the buffer the next
    iteration to be consumed lands in. Buffers, semaphores and
    ``cur_ref`` outlive a grid step: a slot's last iteration has the
    first pages of the next live slot in flight before it waits on its
    own, so a slot begins with its copies already under way (the first
    live slot's are started at grid step 0). An iteration of ``pages``
    live pages scores them all against all heads (independent
    products), takes ONE online-softmax update over them and weights
    the positions' first ``v_width`` values; a slot's last, shorter
    iteration takes its pages in groups of ``pages / 2``, ... , 1 by
    the bits of their number, an update a group (a page at a time read
    2.4x a whole iteration's pace a page on a v5e). A page past the
    slot's newest position is neither copied nor scored; a dead row
    (negative index) has none and gets zeros."""
    slot, slots = pl.program_id(0), pl.num_programs(0)
    heads = q_ref.shape[1]

    def live_pages(s):
        return jnp.maximum(idx_ref[s], -1) // page + 1

    def copies(s, t, b, then):
        # ``then`` (start or wait) each live page's copy of slot s's
        # iteration t into buffer b. The table is read under the
        # guard: its row ends where the slot's pages may.
        live = live_pages(s)
        for i in range(pages):
            @pl.when(t * pages + i < live)
            def _(i=i):
                then(pltpu.make_async_copy(
                    pool_ref.at[table_ref[s, t * pages + i]],
                    buf.at[b, i], sems.at[b],
                ))

    def start(s, t, b):
        copies(s, t, b, lambda c: c.start())

    def next_live(s):
        # the first slot after s that has a page, ``slots`` if none
        return jax.lax.while_loop(
            lambda n: (n < slots) & (idx_ref[jnp.minimum(n, slots - 1)] < 0),
            lambda n: n + 1, s + 1,
        )

    def start_first_of(s, b):
        pl.when(s < slots)(lambda: start(s, 0, b))

    @pl.when(slot == 0)
    def _first():
        cur_ref[0] = 0
        start_first_of(next_live(-1), 0)

    def attend(kv_refs, first):
        # kv_refs: (w, page) pages ``first``, ``first + 1``, ... of the
        # slot, every one live.
        q, idx = q_ref[0], idx_ref[slot]
        scores = []
        for i, kv_ref in enumerate(kv_refs):
            s = jax.lax.dot_general(
                q, kv_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (h, w) x (w, page) -> (h, page)
            cols = (first + i) * page + jax.lax.broadcasted_iota(
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
            v = kv_ref[:v_width]  # (v_width, page)
            acc = acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    _init_softmax_scratch(m_scr, l_scr, acc_scr)
    groups = [pages >> k for k in range(pages.bit_length())]
    live = live_pages(slot)
    iters = (live + pages - 1) // pages
    base = cur_ref[0]

    def iteration(t, _):
        b = (base + t) % 2
        # The copies after this iteration's go out before it waits on
        # its own: the slot's next, or the next live slot's first.
        pl.when(t + 1 < iters)(lambda: start(slot, t + 1, 1 - b))
        pl.when(t + 1 == iters)(
            lambda: start_first_of(next_live(slot), 1 - b)
        )
        copies(slot, t, b, lambda c: c.wait())
        here = jnp.minimum(live - t * pages, pages)
        # ``here`` live pages, taken in groups of pages, pages / 2, ...,
        # 1 by its bits: a whole iteration is one group, a slot's last
        # one at most log2(pages), and no dead page is in any.
        for g in groups:
            @pl.when(here & g != 0)
            def _(g=g):
                first = 0 if 2 * g >= pages else here - here % (2 * g)
                attend(
                    [buf.at[b, first + i] for i in range(g)],
                    t * pages + first,
                )

    jax.lax.fori_loop(0, iters, iteration, None)
    cur_ref[0] = (base + iters) % 2
    o_ref[0] = (
        acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "v_width", "pages")
)
def _latent_impl(q, pool, page_table, index, sm_scale, v_width, pages):
    b, heads, row = q.shape
    page = pool.shape[2]
    assert pages & (pages - 1) == 0, pages  # the walk halves its groups
    prefetch = [
        jnp.asarray(page_table, jnp.int32),
        jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
    ]

    def row_map(s, *_):
        return (s, 0, 0)

    kernel = functools.partial(
        _latent_kernel, page=page, pages=pages, sm_scale=sm_scale,
        v_width=v_width,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, row), row_map, memory_space=_VMEM),
                # The pool stays where it lives; the kernel copies the
                # pages it reads out of it.
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, heads, v_width), row_map, memory_space=_VMEM
            ),
            scratch_shapes=[
                pltpu.VMEM((2, pages, row, page), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, v_width), q.dtype),
        # The buffers carry one slot's look-ahead into the next: the
        # slots run in order (a v5e has one core to run them on).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=pallas_interpret(),
    )(*prefetch, q, pool)


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
    ["latent_decode"]``) say which path a program was built on, how
    many pages an iteration of the kernel's walk covers and the grid
    steps a call takes (the slots)."""
    if resolve_prefer(
        "latent_decode", prefer, latent_unsupported(pool), on_tpu()
    ):
        pages = latent_pages_per_step(
            page_table.shape[1], pool.shape[2], pool.shape[1],
            pool.dtype.itemsize,
        )
        record_kernel_choice(
            "latent_decode", pages_per_step=pages, grid_steps=q.shape[0]
        )
        return _latent_impl(
            q, pool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(index, jnp.int32), sm_scale=float(sm_scale),
            v_width=v_width, pages=pages,
        )
    return latent_attention_reference(
        q, pool, page_table, index, sm_scale, v_width
    )
