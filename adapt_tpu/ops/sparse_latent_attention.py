"""Latent attention that reads a SELECTION of its cache (DeepSeek
sparse attention: a lightning indexer picks ``top_k`` cached positions
a query, and the latent attention's softmax runs over those alone).

A selecting block keeps TWO planes under one page table
(``runtime/paged.alloc_kv_pools``): the latent rows ``[c_kv | k_r]``
as every latent block keeps them, ``(pool_pages, row, page)``, and ONE
index key a position, ``(pool_pages, index_dim, page)``, in a plane of
its own, so that the score pass streams 128-value keys and never the
576-value rows. Both keep a page's positions on the minor axis (the
format ``ops/latent_attention`` explains), so the write kernel, the
whole-prompt scatter and the chunk passes' page scatter are the latent
pool's, called a plane.

    I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s))          (float32)
    S(t)    = {s <= t : I(t, s) >= the top_k-th largest I(t, .)}
    out     = softmax over S(t) of the absorbed scores, as MLA

THE SELECTION IS A THRESHOLD: the ``top_k``-th largest score of a
query is found by bisection on the scores' bits (31 counting passes
over one row, no sort), and a position is read where its score is at
least that. Scores that tie AT the threshold are all kept (exact ties
of float32 sums of 64 terms; a context of at most ``top_k`` keeps every
position). One rule in the kernel and in the ``jax.numpy`` forms.

Decode (:func:`sparse_latent_paged_attention`) is ONE kernel a layer
on a TPU (``_sparse_latent_impl``), grid ``(slots,)``: a slot's live
index pages are walked and scored into VMEM, the threshold is bisected
there, and the slot's live latent pages are walked as
``ops/latent_attention._latent_kernel`` walks them with every position
under the threshold masked. With positions on the minor axis a chosen
position costs its whole page, and at contexts where every page holds
a chosen one the walk reads what the dense one reads: the form was
chosen by measurement against a positions-major plane and a gather of
the listed rows (``scripts/sparse_latent_bench.py``; PERF.md section
3). Elsewhere, and under ``prefer="xla"``, the ``jax.numpy`` oracle.

The full forward, the whole-prompt prefill and the chunk passes take
the MASKED form (:func:`selected_latent_attention`): index scores of
the queries against every key of the window, the threshold a query,
``-inf`` elsewhere, then the absorbed attention, all of it in blocks
of queries and keys so that no (queries, window) array stands beside
64 index heads or 128 attention heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapt_tpu.ops.decode_attention import _NEG_INF, _init_softmax_scratch
from adapt_tpu.ops.dispatch import (
    on_tpu,
    pallas_interpret,
    record_kernel_choice,
    resolve_prefer,
)
from adapt_tpu.ops.latent_attention import (
    latent_pages_per_step,
    latent_unsupported,
    pages_to_rows,
)

_VMEM = pltpu.VMEM
_INT_MIN = -(2 ** 31)

#: Queries and keys a block of the masked form covers.
_Q_BLOCK = 512
_K_BLOCK = 1024


def order_keys(scores):
    """float32 scores -> int32 keys of the same order (``a < b`` iff
    ``key(a) < key(b)``; -inf lowest)."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _bisect(count, k, lead=()):
    """The largest int32 ``t`` with ``count(t) >= k`` (``count`` falls
    as ``t`` rises), the smallest int32 where none has: the sign first,
    then bit by bit, 32 counting passes and no sort."""
    t = jnp.where(
        count(jnp.zeros(lead, jnp.int32)) >= k, 0, _INT_MIN
    ).astype(jnp.int32)

    def body(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(cand) >= k, cand, t)

    return lax.fori_loop(0, 31, body, t)


def kth_largest_key(keys, k: int):
    """(..., L) int32 order keys -> (...,) the largest key ``t`` with
    ``count(keys >= t) >= k``: the k-th largest where L >= k, the
    smallest int32 (every position kept) elsewhere."""
    return _bisect(
        lambda t: jnp.sum(keys >= t[..., None], axis=-1, dtype=jnp.int32),
        k, keys.shape[:-1],
    )


def index_scores(q_i, w, keys_i):
    """The lightning indexer: q_i (..., n, j, d) a query's index heads,
    w (..., n, j) float32 their weights, keys_i (..., L, d) ONE key a
    position -> (..., n, L) float32 ``sum_j w_j ReLU(q_j . k)``."""
    s = jnp.einsum(
        "...njd,...ld->...njl", q_i, keys_i,
        preferred_element_type=jnp.float32,
    )
    return jnp.einsum("...njl,...nj->...nl", jax.nn.relu(s), w)


def select(scores, live, top_k: int):
    """(..., L) float32 index scores and the positions a query may read
    (``live``, bool) -> bool (..., L): the positions it does read."""
    keys = order_keys(jnp.where(live, scores, -jnp.inf))
    return live & (keys >= kth_largest_key(keys, top_k)[..., None])


def sparse_latent_reference(q, q_i, w, pool, ipool, page_table, index,
                            sm_scale, v_width, top_k):
    """jnp oracle of the selecting decode: gather each slot's pages of
    both planes, score, select, attend. q (b, h, row) absorbed queries;
    q_i (b, j, d), w (b, j) the indexer's; ``pool`` (pages, row, P),
    ``ipool`` (pages, d, P); index scalar or (b,) a slot's newest
    position (negative: dead, zeros). -> (b, h, v_width)."""
    b = q.shape[0]
    table = jnp.asarray(page_table, jnp.int32)
    rows = pages_to_rows(pool[table]).reshape(b, -1, pool.shape[1])
    keys_i = pages_to_rows(ipool[table]).reshape(b, -1, ipool.shape[1])
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,))
    live = jnp.arange(rows.shape[1])[None] <= idx[:, None]  # (b, L)
    chosen = select(
        index_scores(q_i[:, None], w[:, None], keys_i)[:, 0], live, top_k
    )
    s = jnp.einsum(
        "bhw,blw->bhl", q, rows, preferred_element_type=jnp.float32
    ) * sm_scale
    s = jnp.where(chosen[:, None], s, _NEG_INF)
    p = jnp.where(chosen[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum(
        "bhl,blv->bhv", p.astype(rows.dtype), rows[..., :v_width],
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def _blocks(n: int, block: int) -> tuple[int, int]:
    """(block size, padded length) for ``n`` items in blocks of at most
    ``block``."""
    size = min(block, n)
    return size, -(-n // size) * size


def selected_latent_attention(q, q_i, w, rows, keys_i, q_pos, sm_scale,
                              v_width, top_k):
    """The MASKED form, in blocks: q (h, C, row) absorbed queries at
    absolute positions ``q_pos`` (C,); q_i (C, j, d), w (C, j) their
    indexer operands; ``rows`` (L, row) and ``keys_i`` (L, d) the
    window by position, position s at index s. Query t reads the
    positions ``s <= q_pos[t]`` its index scores select. A block of
    ``_Q_BLOCK`` queries at a time: its index scores against the whole
    window (key block by key block), its thresholds, then an
    online-softmax walk over the key blocks up to its last position.
    -> (h, C, v_width) of ``q``'s type."""
    h, c, row = q.shape
    n_keys = rows.shape[0]
    cq, c_pad = _blocks(c, _Q_BLOCK)
    kb, l_pad = _blocks(n_keys, _K_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, c_pad - c), (0, 0)))
    q_i = jnp.pad(q_i, ((0, c_pad - c), (0, 0), (0, 0)))
    w = jnp.pad(w, ((0, c_pad - c), (0, 0)))
    # A padded query reads position 0 alone; a padded key is past
    # every query.
    q_pos = jnp.pad(jnp.asarray(q_pos, jnp.int32), (0, c_pad - c))
    rows = jnp.pad(rows, ((0, l_pad - n_keys), (0, 0)))
    keys_i = jnp.pad(keys_i, ((0, l_pad - n_keys), (0, 0)))
    k_blocks = keys_i.reshape(l_pad // kb, kb, -1)
    pos = jnp.arange(l_pad)

    def one(args):
        qb, qib, wb, qp = args  # (h, cq, row), (cq, j, d), (cq, j), (cq,)
        scores = lax.map(
            lambda k: index_scores(qib, wb, k), k_blocks
        )  # (blocks, cq, kb)
        scores = jnp.moveaxis(scores, 0, 1).reshape(cq, l_pad)
        chosen = select(scores, pos[None] <= qp[:, None], top_k)

        def step(i, carry):
            m, l, acc = carry
            at = i * kb
            r = lax.dynamic_slice_in_dim(rows, at, kb)
            keep = lax.dynamic_slice_in_dim(chosen, at, kb, axis=1)
            s = jnp.einsum(
                "hcw,lw->hcl", qb, r, preferred_element_type=jnp.float32
            ) * sm_scale
            s = jnp.where(keep[None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(keep[None], jnp.exp(s - m_new), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "hcl,lv->hcv", p.astype(r.dtype), r[:, :v_width],
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc

        init = (
            jnp.full((h, cq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((h, cq, 1), jnp.float32),
            jnp.zeros((h, cq, v_width), jnp.float32),
        )
        _, l, acc = lax.fori_loop(0, jnp.max(qp) // kb + 1, step, init)
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    out = lax.map(one, (
        jnp.moveaxis(q.reshape(h, c_pad // cq, cq, row), 1, 0),
        q_i.reshape(c_pad // cq, cq, *q_i.shape[1:]),
        w.reshape(c_pad // cq, cq, -1),
        q_pos.reshape(c_pad // cq, cq),
    ))  # (blocks, h, cq, v)
    return jnp.moveaxis(out, 0, 1).reshape(h, c_pad, v_width)[:, :c]


def _sparse_kernel(table_ref, idx_ref, q_ref, qi_ref, w_ref, pool_ref,
                   ipool_ref, o_ref, buf, ibuf, sems, isems, key_scr, m_scr,
                   l_scr, acc_scr, *, page, pages, sm_scale, v_width, top_k):
    """One slot a grid step. ``q_ref`` (1, h, row) absorbed queries,
    ``qi_ref`` (1, j, d) index queries, ``w_ref`` (1, j, 1) float32 their
    weights; both pools stay where they live. Three phases, each walking
    ``pages`` table-mapped pages an iteration through two buffers:

    1. the slot's live INDEX pages: ``sum_j w_j ReLU(qI_j . kI)`` a
       position, as an order key into ``key_scr`` (pages, page) int32
       (a position past the slot's newest holds the lowest key);
    2. the ``top_k``-th largest key by bisection over ``key_scr`` (the
       first latent pages already in flight);
    3. the slot's live LATENT pages, as ``_latent_kernel`` attends
       them, a position under the threshold masked out."""
    slot = pl.program_id(0)
    heads = q_ref.shape[1]
    idx = idx_ref[slot]
    live = jnp.maximum(idx, -1) // page + 1
    iters = (live + pages - 1) // pages
    groups = [pages >> k for k in range(pages.bit_length())]

    def copies(src, dst, sem, t, b, then):
        for i in range(pages):
            @pl.when(t * pages + i < live)
            def _(i=i):
                then(pltpu.make_async_copy(
                    src.at[table_ref[slot, t * pages + i]],
                    dst.at[b, i], sem.at[b],
                ))

    def walk(src, dst, sem, consume, started=False):
        # consume(refs, first): (w, page) pages first, first + 1, ...
        if not started:
            pl.when(iters > 0)(
                lambda: copies(src, dst, sem, 0, 0, lambda c: c.start())
            )

        def iteration(t, _):
            b = t % 2
            pl.when(t + 1 < iters)(lambda: copies(
                src, dst, sem, t + 1, 1 - b, lambda c: c.start()
            ))
            copies(src, dst, sem, t, b, lambda c: c.wait())
            here = jnp.minimum(live - t * pages, pages)
            for g in groups:
                @pl.when(here & g != 0)
                def _(g=g):
                    first = 0 if 2 * g >= pages else here - here % (2 * g)
                    consume(
                        [dst.at[b, first + i] for i in range(g)],
                        t * pages + first,
                    )

        lax.fori_loop(0, iters, iteration, None)

    def cols_of(pg, rows_):
        return pg * page + lax.broadcasted_iota(jnp.int32, (rows_, page), 1)

    # -- 1: index scores -> order keys ------------------------------------
    key_scr[...] = jnp.full(key_scr.shape, _INT_MIN + 1, jnp.int32)
    q_i, w = qi_ref[0], w_ref[0]  # (j, d), (j, 1)

    def score(k_refs, first):
        for i, k_ref in enumerate(k_refs):
            s = lax.dot_general(
                q_i, k_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (j, d) x (d, page) -> (j, page)
            s = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
            key_scr[pl.ds(first + i, 1), :] = jnp.where(
                cols_of(first + i, 1) <= idx, order_keys(s), _INT_MIN + 1
            )

    walk(ipool_ref, ibuf, isems, score)

    # -- 2: the threshold (latent pages already on their way) -------------
    pl.when(iters > 0)(
        lambda: copies(pool_ref, buf, sems, 0, 0, lambda c: c.start())
    )

    # A context of at most top_k positions keeps them all. (Counted in
    # float32: exact to 2^24 positions, and a sum Mosaic reduces.)
    thr = lax.cond(
        idx >= top_k,
        lambda: _bisect(
            lambda t: jnp.sum((key_scr[...] >= t).astype(jnp.float32)),
            top_k,
        ),
        lambda: jnp.int32(_INT_MIN),
    )

    # -- 3: the selected positions of the latent rows ---------------------
    _init_softmax_scratch(m_scr, l_scr, acc_scr)
    q = q_ref[0]

    def attend(kv_refs, first):
        scores, keeps = [], []
        for i, kv_ref in enumerate(kv_refs):
            s = lax.dot_general(
                q, kv_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (h, row) x (row, page) -> (h, page)
            keep = (key_scr[pl.ds(first + i, 1), :] >= thr) & (
                cols_of(first + i, 1) <= idx
            )
            keep = jnp.broadcast_to(keep, (heads, page))
            keeps.append(keep)
            scores.append(jnp.where(keep, s, _NEG_INF))
        m = m_scr[...]
        m_new = m
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        l_new, acc = l_scr[...] * alpha, acc_scr[...] * alpha
        for s, keep, kv_ref in zip(scores, keeps, kv_refs):
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            v = kv_ref[:v_width]  # (v_width, page)
            acc = acc + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    walk(pool_ref, buf, sems, attend, started=True)
    o_ref[0] = (
        acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "v_width", "top_k", "pages")
)
def _sparse_latent_impl(q, q_i, w, pool, ipool, page_table, index,
                        sm_scale, v_width, top_k, pages):
    b, heads, row = q.shape
    page = pool.shape[2]
    assert pages & (pages - 1) == 0, pages  # the walk halves its groups
    prefetch = [
        jnp.asarray(page_table, jnp.int32),
        jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
    ]
    key_rows = -(-page_table.shape[1] // 8) * 8

    def row_map(s, *_):
        return (s, 0, 0)

    kernel = functools.partial(
        _sparse_kernel, page=page, pages=pages, sm_scale=sm_scale,
        v_width=v_width, top_k=top_k,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, row), row_map, memory_space=_VMEM),
                pl.BlockSpec(
                    (1,) + q_i.shape[1:], row_map, memory_space=_VMEM
                ),
                pl.BlockSpec(
                    (1, w.shape[1], 1), row_map, memory_space=_VMEM
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, heads, v_width), row_map, memory_space=_VMEM
            ),
            scratch_shapes=[
                pltpu.VMEM((2, pages, row, page), pool.dtype),
                pltpu.VMEM((2, pages, ipool.shape[1], page), ipool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((key_rows, page), jnp.int32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=pallas_interpret(),
    )(*prefetch, q, q_i, w.astype(jnp.float32)[..., None], pool, ipool)


def sparse_latent_paged_attention(q, q_i, w, pool, ipool, page_table, index,
                                  *, sm_scale, v_width, top_k,
                                  prefer: str | None = None):
    """Selecting decode over a latent paged cache of two planes: q (b,
    h, row) absorbed queries, q_i (b, j, d) and w (b, j) the indexer's
    queries and weights, ``pool`` / ``ipool`` the latent rows and the
    index keys under ONE ``page_table`` (b, pages_per_slot), ``index``
    each slot's newest position (negative: dead, zeros). -> (b, h,
    v_width). ``prefer`` as ``latent_paged_attention``; the books
    (``kernel_dispatch_stats()["sparse_latent_decode"]``) name the
    form: ``positions_minor`` 1 (a page keeps its positions on the
    minor axis and the read walks whole pages under a mask)."""
    unsupported = latent_unsupported(pool) or latent_unsupported(ipool)
    if resolve_prefer("sparse_latent_decode", prefer, unsupported, on_tpu()):
        pages = latent_pages_per_step(
            page_table.shape[1], pool.shape[2], pool.shape[1],
            pool.dtype.itemsize,
        )
        record_kernel_choice(
            "sparse_latent_decode", positions_minor=1, pages_per_step=pages,
            grid_steps=q.shape[0],
        )
        return _sparse_latent_impl(
            q, q_i, w, pool, ipool, jnp.asarray(page_table, jnp.int32),
            jnp.asarray(index, jnp.int32), sm_scale=float(sm_scale),
            v_width=v_width, top_k=top_k, pages=pages,
        )
    return sparse_latent_reference(
        q, q_i, w, pool, ipool, page_table, index, sm_scale, v_width, top_k
    )
