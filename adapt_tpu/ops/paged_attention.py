"""Paged decode attention: KV cache pages + a scalar-prefetched kernel.

Contiguous per-slot KV caches (``runtime/continuous.py``) reserve
``slots x max_len`` positions in HBM whatever the actual request mix —
a short request in a long-context server wastes almost its whole strip.
Paged KV (the vLLM idea, TPU-native here) carves the cache into
fixed-size PAGES in one shared pool; each slot owns just the pages its
live window touches, and a page table maps logical position blocks to
physical pages. Capacity then scales with actual resident tokens, not
with ``slots x max_len``.

The TPU part: attention over a paged cache must NOT gather pages into a
contiguous buffer first (that would write + re-read the whole window,
doubling HBM traffic — the exact cost paging exists to avoid). The
Pallas kernels here read pages where they live: the page table rides as
a SCALAR-PREFETCH operand (``pltpu.PrefetchScalarGridSpec``) and says
which physical page a position block is, while the online-softmax state
carries across the blocks. The verify and chunk kernels let the
pipeline fetch them (the pool's ``index_map`` consults the table to
pick each grid step's page); the decode kernel copies them itself
(below). The decode and verify step body is ``ops/decode_attention``'s
``_attend_tile`` (same masks, same float32 softmax state, same fused
dequant); only the block FETCH differs, which is the whole point: one
attention discipline, two memory layouts.

The DECODE kernel's grid is ``(slots, head blocks)`` and the kernel
WALKS a row's live pages itself (``_walk_kernel``). Every plane of the
pool is handed to the call once and stays where it lives
(``memory_space=pl.ANY``: no blocked pool operand, no pin, no copy of
the pool in the compiled program); the table, the slots' newest
positions and, for ragged rows, their oldest ride as scalar prefetch,
and a loop inside the kernel runs from the page of ``valid_from`` to
the page of ``index``, nowhere else: a window layer that holds 3 pages
of a 15-ordinal table copies two or three, a row of a nearly empty
server's 30 dead slots costs a scalar read and a zeroed output block.
An iteration copies ``pages`` table-mapped blocks of ``(heads, page, 2
* head_dim)`` — one page of EVERY KV head of the row's head block,
contiguous in the pool — with ``pltpu.make_async_copy`` into one of two
VMEM buffers while the other's are consumed, and a row's last iteration
has the first pages of the next LIVE row in grid order in flight before
it waits on its own (buffers, semaphores and the buffer index outlive a
grid step). ``heads`` is the largest divisor of the head count that
fits a stated VMEM budget (``decode_heads_per_step``: 16 of 16 at
head_dim 128, 25 of 25 at 64), ``pages`` the smallest power of two
whose blocks keep ~0.75 MB in flight (``decode_pages_per_step``: 1
where a block is 0.8-1 MB, 2 at K-EXAONE's 512 KB, 4 at Falcon-H1's
256 KB); both are derived from the operands, the per-shard ones under
tensor parallelism. A block of heads gives the scheduler independent
chains (product, reduce, exp, product) to overlap in ONE batched
``_attend_tile`` a page (PERF.md section 6, PR 28); a row's last,
shorter iteration takes its pages in groups of ``pages / 2``, ..., 1.
What it replaced kept the page axis on the grid, ``(slots, head
blocks, pages a slot)``: a step outside a row's live window fetched
nothing and skipped its body but was still a step, 0.75 us on a v5e,
and a live one ran at the pipeline's pace and not its copy's: 9,728
steps a decode step of ``kexaone_longgen`` for ~1,900 live blocks, 59%
of the bytes floor in ``cgpt1b3_batchgen`` where the walk reads 75%
(the kernel alone; PERF.md section 6, PR 44 and PR 46). The verify
kernel keeps one head a step (``_verify_impl``).

The CHUNK-PREFILL kernel (``_chunk_impl``) folds heads the same way,
by its own sum (``chunk_heads_per_step``: a chunk's state is per query
ROW, so 5 of GPT-2-XL's 25 heads fit a step at 256 rows and one at
K-EXAONE's 2,048), its dead steps (the page list's power-of-two
padding, pages wholly under a sliding window) name the nearest live
block and fetch nothing, and over a native pool its step body is its
own: ``_attend_rows_on_lanes`` keeps a page's positions on the
sublanes and the chunk's query rows on the LANES, so both softmax
reductions run down the sublanes and the per-row state is lane-dense;
q and K reach the MXU in the pool's dtype. ``_attend_tile``'s layout,
a row's state on a sublane, is right for decode's 8 rows a head and
cost a chunk's hundreds three times the vector work (measured on a
v5e, PERF.md section 6, PR 42). Quantized pools keep ``_attend_tile``
under the fold.

The FLASH-SPLIT form (``split`` on every dispatcher, which no layer
above ``ops/`` passes) keeps the page axis on its grid,
``(slots, head blocks, split, pages a split)``, and lets the pipeline
fetch: each (row, split) grid point streams its own run of the slot's
pages with independent online-softmax scratch and emits unnormalized
partials (accumulator + running max + denominator); a single-pass
rescale combine reduces them — so a long-context slot's KV stream can
fan across TensorCores. Its dead steps name the block already resident
and fetch nothing. On a one-core chip the automatic split is 1
(``decode_attention.default_decode_split``: the split's axis is
``parallel``, and only another core can take it up), which is the
walk; the last split may be ragged (clamped in the index maps, masked
in the kernel).

Layouts:
- pool (one per decoder block; ``runtime/paged.alloc_kv_pools`` is the
  definition): ONE plane (num_pages, kv_heads, page_size, 2 * head_dim)
  in the native dtype (bf16/f32) — a position's K on lanes
  ``[0, head_dim)`` and its V on ``[head_dim, 2 * head_dim)`` of the
  same row (:func:`fuse_kv` / :func:`split_kv`). At head_dim 64 a row
  is exactly one 128-lane tile and at 128 two, so the plane lives
  row-major in HBM, the per-token append is one in-place scatter a
  block (``append_kv_paged``) and every kernel reads the plane as it
  lives. Inside a kernel K and V are the row's two halves: cut at a
  tile edge when head_dim fills whole tiles; otherwise the row is not
  cut at all — q is zero-padded over V's lanes, both products run over
  the whole row, and V's half of the accumulator is the output
  (``_acc_width``, ``_attend_fused``). OR a quantized ``(int8 values,
  k_scales, v_scales)`` TRIPLE — values fused the same way
  (num_pages, kv_heads, page_size, 2 * head_dim) int8, each scale plane
  (num_pages, kv_heads, page_size, 1) f32, one absmax scale per cached
  K/V vector (``ops/quantize.quantize_kv_vectors``, the same scheme as
  the dense int8 strips). int4 pools keep the triple with the
  VALUE plane packed two nibbles per int8 lane (a row is head_dim
  lanes, ``quantize_kv_vectors(..., "int4")``); the kernels detect the
  packed width against q's head_dim and unpack in VMEM, so the HBM
  stream is 4-bit. Quantized pools compose paging's
  resident-token capacity with int8's ~2-4x byte shrink: the scale
  planes ride the SAME page table (a page id addresses all three), and
  the kernels stream each as one chunked (page/128, 128) f32 tile per
  page — 4/head_dim of the int8 payload's bytes (one f32 per vector)
  — applying scales to the score/probability
  COLUMNS so the big cache operand stays int8 end to end (dequant fused
  in VMEM, the ``_decode_kernel`` discipline). On REAL TPUs the
  quantized kernel path additionally requires
  ``page % DECODE_BLOCK_K == 0`` so the scale tile fills a full f32
  (8, 128) tile (``kernel_unsupported`` — the dense int8 path's
  constraint); smaller quantized pages, and on hardware every int4
  pool (its unpack does not fit scoped VMEM), serve through the XLA
  oracle by that stated rule. Off-TPU the interpreter has no tiling,
  so CI parity drives every quantized kernel body at ordinary page
  sizes.
- page table: (slots, pages_per_slot) int32 physical page ids; entries
  past a slot's live window may be ANY valid page id (their positions
  are masked, their blocks' compute skipped; the decode kernel does not
  even read them — point them at page 0).
- q: (slots, kv_heads, g, head_dim) group-folded, as in
  ``decode_attention``.

``page_size`` must be a lane multiple (128); a row's walk (or, split,
its grid) covers at most ``pages_per_slot`` blocks of ``page_size``
positions, the table's width.

No reference analog (SURVEY.md §2.2: the reference is CNN-only) — this
is the framework's own serving-memory frontier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from adapt_tpu.ops.decode_attention import (
    DECODE_BLOCK_K,
    _NEG_INF,
    _attend_tile,
    _combine_splits,
    _init_softmax_scratch,
    check_head_parity,
    resolve_decode_split,
)
from adapt_tpu.ops.dispatch import (
    on_tpu,
    pallas_interpret,
    record_kernel_choice,
    resolve_prefer,
)
from adapt_tpu.ops.quantize import unpack_int4

_VMEM = pltpu.VMEM
DEFAULT_PAGE_SIZE = 128


def pool_values(pool):
    """The VALUE plane of a block's pool: the int8 member of a
    quantized ``(values, k_scales, v_scales)`` triple, the pool itself
    otherwise — the one place shape/head/page derivation looks, so
    every entry point sees through the tuple identically. Its last
    dimension is the FUSED row, K's lanes then V's."""
    return pool[0] if isinstance(pool, tuple) else pool


def pool_planes(pool) -> tuple:
    """The planes a block's pool (or a page-major chunk of one) is
    made of, in its own order: the fused plane alone, or ``(values,
    k_scales, v_scales)``."""
    return pool if isinstance(pool, tuple) else (pool,)


def fuse_kv(k, v):
    """K and V of the same positions -> the pool's representation of
    them: ONE row per position, K on lanes ``[0, w)`` and V on lanes
    ``[w, 2w)``. ``k``/``v`` are native arrays (..., w) or quantized
    ``(values, scales)`` pairs (``ops.quantize.quantize_kv_vectors``);
    a quantized pair fuses its VALUE rows and keeps the two scale
    columns beside them: ``(values (..., 2w), k_scales (..., 1),
    v_scales (..., 1))``. Every writer of a pool (the per-token append,
    the chunk and whole-prompt page scatters, the sp prefiller's page
    blocks) builds its rows here, so the lane convention has one
    definition."""
    if isinstance(k, tuple):
        return (jnp.concatenate([k[0], v[0]], axis=-1), k[1], v[1])
    return jnp.concatenate([k, v], axis=-1)


def split_kv(pool):
    """Inverse of :func:`fuse_kv`: a block's pool (or any array in its
    representation) -> ``(k, v)``, native arrays or ``(values,
    scales)`` pairs — the two-operand form the contiguous oracles
    (``decode_attention_reference``, ``verify_attention``) take."""
    vals = pool_values(pool)
    w = vals.shape[-1] // 2
    k, v = vals[..., :w], vals[..., w:]
    if isinstance(pool, tuple):
        return (k, pool[1]), (v, pool[2])
    return k, v


def append_kv_paged(pool, new, phys, off):
    """Paged twin of ``decode_attention.append_kv`` — THE per-token
    write into a page pool, shared by ``decode_step_paged`` (K == 1) and
    ``verify_chunk_paged`` (K chunk tokens per row).

    pool (num_pages, kv_h, P, w) — ONE plane of a block's pool: the
    fused K|V plane (w == 2 * head_dim, :func:`fuse_kv`) or a scale
    plane of a quantized pool (w == 1); new (b, kv_h, K, w);
    ``phys``/``off`` (b, K) int32: token (i, t) lands on
    ``pool[phys[i, t], :, off[i, t], :]``. Dead rows arrive routed to
    the trash page by the caller; rows that collide there overwrite
    each other, unread.

    The write must reach the pool in the layout the buffer already has:
    the Mosaic kernels pin row-major operands, and an XLA scatter whose
    window spans the head axis (``pool.at[phys, :, off, :]``) wants its
    window dimensions minor, so the compiler relaid the whole pool out
    around it — two to three copies of every plane at every decode step
    on a v5e. Which write is in place depends on where the lanes are,
    so it is chosen from the row width (measured on the chip, PERF.md
    section 6, PR 25 and PR 30):

    - ``w`` fills whole 128-lane tiles — every fused native or int8
      plane at head_dim >= 64: the resident layout is already
      row-major, and ONE scatter indexed over (page, head, offset) with
      only ``w`` as its window updates it in place. K and V of a token
      are one row, so a block costs one scatter a step.
    - ``w`` narrower than a lane tile (scale planes; a fused row at
      head_dim under 64, or packed int4 at 64): the buffer lives with
      the page axis on the lanes, and that scatter costs two
      relayouts. A ``dynamic_update_slice`` of one (1, kv_h, 1, w) slab
      per token under a ``fori_loop`` is in place in ANY layout, at 4-5
      us a token. The decode kernel reads that layout as it is
      (``_paged_impl``: pages swapped to (w, page), a bitcast); the
      verify and chunk kernels pin row-major and cost their programs
      one relayout a plane. The slab is sliced straight out of ``new``
      — a transposed or reshaped update operand drags the carry's
      layout with it and the copies come back.

    ``tests/test_chip_lowering.py`` counts the copies both leave in a
    program compiled for a v5e."""
    b, kvh, kc, w = new.shape
    new = new.astype(pool.dtype)
    if w % 128 == 0:
        return pool.at[
            phys[:, None, :], jnp.arange(kvh)[None, :, None],
            off[:, None, :], :,
        ].set(new)

    def write(n, pool):
        i, t = n // kc, n % kc
        slab = lax.dynamic_slice(new, (i, 0, t, 0), (1, kvh, 1, w))
        return lax.dynamic_update_slice(
            pool, slab, (phys[i, t], 0, off[i, t], 0)
        )

    return lax.fori_loop(0, b * kc, write, pool)


def _pool_planes(pool):
    """A block's pool as the kernels' operands: ``(kv, k_scales,
    v_scales)`` — scales ``None`` for native pools. THE one unpack the
    three kernel dispatchers share, so a future change to the pool's
    representation lands in one place."""
    planes = pool_planes(pool)
    return planes + (None,) * (3 - len(planes))


def _packed(q, kv, quantized) -> bool:
    """Whether a quantized value plane is int4-PACKED: its fused row
    holds ``2 * (head_dim // 2)`` lanes, q's own width."""
    return quantized and kv.shape[3] == q.shape[-1]


def kernel_unsupported(q, pool) -> str | None:
    """Shared pallas-dispatch gate for the three paged kernels: None
    when they can serve these operands, else the constraint broken (the
    reason auto dispatch routes to the XLA oracle, and the error a
    forced ``prefer="pallas"`` raises). Native pools need a
    lane-multiple page. ON HARDWARE quantized pools also need the scale
    tile to satisfy f32 (8, 128) tiling: a page carries page/128 rows
    of 128 scales, so ``page % DECODE_BLOCK_K == 0`` (smaller quantized
    pages would hand Mosaic a 1-sublane f32 tile); and int4 pools do
    not serve at all — measured on a v5e, the in-VMEM nibble unpack
    (``unpack_int4``'s stack + reshape interleave, minor dim 2 padded
    to 128 lanes) asks for 24.6 MB of scoped VMEM at that page size
    against Mosaic's 16 MB (ROADMAP A1 carries the lane-dense rewrite).
    The INTERPRETER has no tiling, so off-TPU the CI parity tests still
    drive every kernel body at ordinary page sizes."""
    vals = pool_values(pool)
    page = vals.shape[2]
    quantized = isinstance(pool, tuple)
    if page % 128:
        return f"page_size {page} is not a multiple of 128"
    if quantized and on_tpu():
        if _packed(q, vals, quantized):
            return (
                "int4 pools: the in-VMEM nibble unpack exceeds Mosaic's "
                "scoped VMEM limit on a TPU"
            )
        if page % DECODE_BLOCK_K:
            return (
                f"page_size {page} is not a multiple of {DECODE_BLOCK_K} "
                "(quantized pools: the scale tile must fill an f32 "
                "(8, 128) tile on a TPU)"
            )
    return None


def _head_sharded(fn, head_shard, sharded, replicated):
    """``fn(*sharded, *replicated)`` — directly, or per head shard.

    Under tensor parallelism the batcher's programs are GSPMD-
    partitioned, and Mosaic kernels cannot be partitioned
    automatically: a ``pallas_call`` there must sit inside a
    ``shard_map``. ``head_shard`` = ``(mesh, axis)`` runs ``fn`` once
    per shard of the KV-HEAD axis — dim 1 of every ``sharded`` operand
    (q, pools, scale planes) and of the output — with ``replicated``
    operands (page table, positions) whole on every shard. The kernels
    derive grid and GQA fold from the head count they are given, so the
    per-shard body is the single-device kernel unchanged. ``None``
    entries (absent scales / valid_from) are closed over, not mapped."""
    args = [*sharded, *replicated]
    if head_shard is None:
        return fn(*args)
    mesh, axis = head_shard
    live = [i for i, a in enumerate(args) if a is not None]
    heads = P(None, axis)

    def body(*xs):
        full = [None] * len(args)
        for i, x in zip(live, xs):
            full[i] = x
        return fn(*full)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(heads if i < len(sharded) else P() for i in live),
        out_specs=heads,
        check_vma=False,
    )(*(args[i] for i in live))


def _gather_window(pool, page_table):
    """Each slot's pages of every plane of ``pool`` as one contiguous
    window, split into the oracles' two operands: ``(k, v)``, each
    (b, kvh, pages * P, w) or a ``(values, scales)`` pair of them."""
    b = page_table.shape[0]

    def gather(plane):
        g_ = plane[page_table]  # (b, pages, kvh, P, w)
        g_ = jnp.moveaxis(g_, 2, 1)
        return g_.reshape(b, plane.shape[1], -1, plane.shape[3])

    return split_kv(jax.tree.map(gather, pool))


def paged_attention_reference(q, pool, page_table, index, valid_from=None):
    """jnp oracle: gather each slot's pages into a contiguous window,
    split the fused rows into K and V, then run the contiguous
    decode-attention oracle (which owns the quantized
    score/probability-column scale application — one definition, so
    paged int8 decode matches the dense int8 slot path
    value-for-value). This is the semantics definition AND the
    materializing schedule the kernel exists to beat.

    q (b, kvh, g, hd); ``pool`` a block's pool — the fused (num_pages,
    kvh, P, 2 * hd) plane or a quantized ``(values, k_scales,
    v_scales)`` triple; page_table (b, pages_per_slot) int32; index
    scalar or (b,)."""
    from adapt_tpu.ops.decode_attention import decode_attention_reference

    cache_k, cache_v = _gather_window(pool, page_table)
    return decode_attention_reference(
        q, cache_k, cache_v, index, valid_from
    )


#: What one grid step of the decode kernel may hold in VMEM. Mosaic
#: scopes a kernel to 16 MB on a TensorCore; the step plans for half of
#: it. What Mosaic itself counts against the 16 MB, read from its
#: refusals of oversized blocks when compiling for a described v5e, is
#: the pipelined blocks plus 0.2-0.7 MB; the other half is for what
#: neither sum sees (the compiler's own scratch and spills).
DECODE_STEP_VMEM_BUDGET = 8 * 2 ** 20


def _lanes(width: int) -> int:
    """A row's width in VMEM: whole 128-lane tiles."""
    return -(-width // 128) * 128


def _acc_width(head_dim: int) -> int:
    """Lanes of the kernels' accumulator (and of q as they read it)
    over a fused K|V row. A head_dim of whole lane tiles splits the row
    at a tile edge, for nothing, and the accumulator is V's width. A
    narrower one (64: the row is ONE tile) is not split at all: q
    arrives zero-padded to the row, the score product contracts the
    whole row (V's lanes meet exact zeros), the probabilities weight
    the whole row, and V's half of the accumulator is the output — two
    products a lane tile wide either way on a 128-wide MXU, and no
    lane shuffle of the streamed block (slicing the block at lane 64
    instead read 11% slower on a v5e: PERF.md section 6, PR 30)."""
    return head_dim if head_dim % 128 == 0 else 2 * head_dim


def decode_step_vmem_bytes(heads, page, row_width, itemsize, scales,
                           gq=8, head_dim=None, pages=1) -> int:
    """VMEM one grid step of the decode kernel asks for when an
    iteration of its walk covers ``pages`` pages of ``heads`` KV heads
    of a fused plane (``row_width`` = the plane's last dimension, K|V):
    the kernel's own two buffers of ``pages`` blocks (and their two
    scale tiles a page), one being copied into while the other is
    consumed; q and the output block, double-buffered by the pipeline;
    the per-head softmax state; and the body's float32 working set,
    which is ONE page's whatever ``pages`` is (one head's rows widened
    on their way into the products, the block's score-shaped rows).
    Rows narrower than a lane tile arrive transposed, the page on the
    lanes, and pad nothing; q, the output and the state do. (The
    flash-split form's pipeline double-buffers one block a step: the
    same sum at ``pages`` 1.)"""
    hd = head_dim or row_width // 2
    acc = _lanes(_acc_width(hd))
    stream = 2 * pages * heads * page * row_width * itemsize
    if scales:
        stream += 2 * 2 * pages * heads * max(page // 128, 8) * 128 * 4
    rows = 2 * heads * gq * (acc + _lanes(hd)) * 4
    state = heads * (2 * 8 * 128 + gq * acc) * 4
    working = (page * _lanes(row_width) + heads * 6 * gq * page) * 4
    return stream + rows + state + working


def decode_heads_per_step(kv_heads, page, row_width, itemsize, scales,
                          gq=8, head_dim=None) -> int:
    """KV heads one grid step of the decode kernel covers: the largest
    divisor of ``kv_heads`` (the per-shard count under tensor
    parallelism) whose step fits ``DECODE_STEP_VMEM_BUDGET``. A page's
    block is one copy and one body, each with a price before it moves
    a byte (0.15-0.25 us a step on a v5e while the pipeline fetched),
    and the body is a chain of dependent operations, so a block should
    cover as many independent heads as fit: 16 heads of a bf16 page at head_dim
    128 are 1 MB of fused rows, 25 heads at head_dim 64 0.8 MB; an int8
    pool at 1024-position pages comes out at 8 of 16 (5 of 25) by the
    same sum. Derived from the operands, never set."""
    return _heads_that_fit(kv_heads, lambda heads: decode_step_vmem_bytes(
        heads, page, row_width, itemsize, scales, gq, head_dim
    ))


#: What an iteration of the decode kernel's walk should have in flight
#: before it covers a second page. Measured on a v5e at the cells'
#: standing populations, the kernel alone (PERF.md section 6, PR 46):
#: a block of 1 MB (``cgpt1b3_batchgen``) reads 2% SLOWER in twos and
#: one of 0.8 MB (GPT-2-XL) 6% faster at 8 slots and 2% slower at 32;
#: 512 KB alone (K-EXAONE, Solar-Open2) reads 8-14% slower than in
#: twos, 256 KB alone (Falcon-H1) 25% slower than in fours, and twice
#: as many again gains nothing anywhere.
DECODE_ITERATION_BYTES = 3 * 2 ** 18


def decode_pages_per_step(pages_per_slot, heads, page, row_width, itemsize,
                          scales, gq=8, head_dim=None) -> int:
    """Pages one iteration of the decode kernel's walk covers: the
    SMALLEST power of two whose blocks of ``heads`` KV heads reach
    ``DECODE_ITERATION_BYTES``, at most the slot's pages and what fits
    ``DECODE_STEP_VMEM_BUDGET`` in the kernel's two buffers. An
    iteration has a price of its own (a wait, a branch, the next
    copies' issue), so a thin block wants company; every page more is a
    body more to lower in every program that holds the kernel, so a
    block that fills the stream alone gets none. Derived from the
    operands, never set."""
    pages = 1
    while (
        pages * heads * page * row_width * itemsize < DECODE_ITERATION_BYTES
        and pages * 2 <= pages_per_slot
        and decode_step_vmem_bytes(
            heads, page, row_width, itemsize, scales, gq, head_dim,
            pages * 2,
        ) <= DECODE_STEP_VMEM_BUDGET
    ):
        pages *= 2
    return pages


def _heads_that_fit(kv_heads, step_bytes) -> int:
    """The largest divisor of ``kv_heads`` whose grid step
    (``step_bytes(heads)``) fits ``DECODE_STEP_VMEM_BUDGET``; 1 where
    not even one head does, which the kernel then tries anyway."""
    for heads in range(kv_heads, 1, -1):
        if kv_heads % heads == 0 and (
            step_bytes(heads) <= DECODE_STEP_VMEM_BUDGET
        ):
            return heads
    return 1


def _shard_heads(q, head_shard) -> int:
    """KV heads a kernel call sees: q's, or a shard's of them under a
    ``head_shard`` = ``(mesh, axis)``."""
    if head_shard is None:
        return q.shape[1]
    mesh, axis = head_shard
    return q.shape[1] // mesh.shape[axis]


def _attend_fused(q, kv, ksc, vsc, live, m_scr, l_scr, acc_scr, sm_scale,
                  packed, transposed=False):
    """One block of a FUSED K|V plane through
    ``decode_attention._attend_tile`` — the step body of the three
    paged kernels. ``kv`` is (..., block_k, 2w) — (..., 2w, block_k)
    when ``transposed`` — ``w`` the head_dim, halved when ``packed``
    (int4 nibbles; unpacked here, in VMEM, K's lanes then V's). ``q``
    and the accumulator are ``_acc_width(head_dim)`` lanes wide: at
    whole lane tiles K and V are the row's two halves, cut at a tile
    edge; narrower, the SAME block is both operands (``_acc_width``)
    and the kernel emits the accumulator's last head_dim lanes."""
    if packed:
        kv = unpack_int4(kv)
    hd = kv.shape[-2 if transposed else -1] // 2
    if q.shape[-1] == hd:  # cut at a tile edge (never a transposed row)
        assert not transposed
        k, v = kv[..., :hd], kv[..., hd:]
    else:  # q zero-padded over V's lanes: contract the whole row
        k = v = kv
    _attend_tile(
        q, k, v, ksc, vsc, live, m_scr, l_scr, acc_scr, sm_scale, False,
        transposed,
    )


def _pad_q_lanes(q, hd):
    """q zero-padded from head_dim to the accumulator's width
    (``_acc_width``): nothing at whole lane tiles."""
    pad = _acc_width(hd) - hd
    if not pad:
        return q
    return jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])


def _attend_rows_on_lanes(q, kv, live, m_scr, l_scr, acc_scr, sm_scale):
    """One page of a fused NATIVE K|V plane against a CHUNK of query
    rows, transposed: positions on the sublanes, query rows on the
    LANES — the chunk kernel's step body. ``q`` (heads, gc, aw), ``kv``
    (heads, page, 2 * hd), ``live`` (page, gc) bool, or None for a page
    every row attends whole; the state is a (heads, 1, gc) running max
    and denominator and a (heads, aw, gc) accumulator, V's rows its
    last head_dim (``_acc_width``).

    ``_attend_tile`` keeps a query row's state on a SUBLANE: its
    (gc, 1) max and denominator fill one lane of a vreg each, and both
    row reductions cross the lanes. That is the right shape for
    decode's 8 rows a head. A chunk has hundreds, and its step was all
    vector work (measured on a v5e, PERF.md section 6, PR 42: a step
    cost 0.2 us and each head's 256 x 128 tile 0.33 us more, whatever
    dtype the products' operands had). Here the reductions over a
    page's positions run DOWN the sublanes, elementwise from vreg to
    vreg, and the state is lane-dense: 2 vregs where it was 32, at a
    third of the time a tile. Same masks and the same float32 softmax
    state; q and K reach the MXU as they are (a bf16 x bf16 product
    accumulated in float32 is exact product by product) and the
    probabilities in V's dtype — what Mosaic's default precision made
    of the float32 operands before, so a bfloat16 pool's results are
    the ones it had."""
    hd = kv.shape[-1] // 2
    if q.shape[-1] == hd:  # cut at a tile edge
        k, v = kv[..., :hd], kv[..., hd:]
    else:  # q zero-padded over V's lanes: contract the whole row
        k = v = kv
    heads = ((0,), (0,))
    s = jax.lax.dot_general(
        k, q, (((2,), (2,)), heads), preferred_element_type=jnp.float32
    ) * sm_scale  # (heads, page, gc)
    if live is not None:
        s = jnp.where(live, s, _NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        v, p.astype(v.dtype), (((1,), (1,)), heads),
        preferred_element_type=jnp.float32,
    )  # (heads, aw, gc)


@functools.partial(jax.jit, static_argnames=("heads", "split", "pages"))
def _paged_impl(q, kv_pool, k_scales, v_scales, page_table, index,
                valid_from, heads=1, split=1, pages=1):
    b, kvh, g, hd = q.shape
    page = kv_pool.shape[2]
    row = kv_pool.shape[3]  # K|V: 2 * head_dim, head_dim for packed int4
    quantized = k_scales is not None
    packed = _packed(q, kv_pool, quantized)
    pages_per_slot = page_table.shape[1]
    has_vf = valid_from is not None
    pad_g = (-g) % 8
    if pad_g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_g), (0, 0)))
    gq = g + pad_g
    aw = _acc_width(hd)
    q = _pad_q_lanes(q, hd)

    # Scalar prefetch: the page table, each slot's newest live position
    # and, for ragged rows, its oldest. The kernels read all three:
    # which pages a row owns and where its live window lies.
    prefetch = [
        jnp.asarray(page_table, jnp.int32),
        jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
    ]
    if has_vf:
        prefetch.append(jnp.asarray(valid_from, jnp.int32))

    def row_map(s, hb, *rest):
        return (s, hb, 0, 0)

    # A fused row of whole lane tiles (head_dim >= 64) lives row-major
    # and is read as it lives. A NARROWER row (head_dim under 64) lives
    # in HBM with the page axis on the lanes (``append_kv_paged``), and
    # a Mosaic operand is pinned row-major: read as (pages, heads,
    # page, row) the whole plane was relaid out at every step. Swapped
    # to (pages, heads, row, page) row-major IS the layout the plane
    # has, so the swap moves nothing, a block's rows fill their lanes,
    # and the body contracts the other axis (``kv_transposed``). Packed
    # int4 rows unpack along their lanes and stay as stored.
    transposed = row % 128 != 0 and not packed
    kv_block = (heads, row, page) if transposed else (heads, page, row)
    if transposed:
        kv_pool = jnp.swapaxes(kv_pool, 2, 3)
    planes, blocks = [kv_pool], [kv_block]
    if quantized:
        # (pages, kvh, P, 1) f32 scale pools -> (pages, kvh, P/128,
        # 128) CHUNKED views (position = row*128 + lane — the dense
        # kernel's scale-tile trick, so a >=1024 page fills whole f32
        # (8, 128) tiles on hardware); table-addressed like the int8
        # payload, 4/head_dim of its bytes (one f32 per int8 vector).
        planes += [
            s.reshape(s.shape[0], kvh, page // 128, 128)
            for s in (k_scales, v_scales)
        ]
        blocks += [(heads, page // 128, 128)] * 2
    q_spec = pl.BlockSpec((1, heads, gq, aw), row_map, memory_space=_VMEM)
    kernel_kw = dict(
        page=page,
        num_pages=pages_per_slot,
        sm_scale=1.0 / (hd ** 0.5),
        quantized=quantized,
        has_vf=has_vf,
        packed=packed,
        transposed=transposed,
    )
    scratch = [
        pltpu.VMEM((heads, gq, 1), jnp.float32),
        pltpu.VMEM((heads, gq, 1), jnp.float32),
        pltpu.VMEM((heads, gq, aw), jnp.float32),
    ]
    head_blocks = kvh // heads
    if split == 1:
        # The walk: grid (slots, head blocks); every plane of the pool
        # is handed over once and stays where it lives, and the kernel
        # copies the pages a row's live window names out of it.
        assert pages & (pages - 1) == 0, pages  # the walk halves its groups
        out = pl.pallas_call(
            functools.partial(
                _walk_kernel, pages=pages, head_blocks=head_blocks,
                **kernel_kw,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(b, head_blocks),
                in_specs=[q_spec] + [
                    pl.BlockSpec(memory_space=pl.ANY) for _ in planes
                ],
                out_specs=pl.BlockSpec(
                    (1, heads, gq, hd), row_map, memory_space=_VMEM
                ),
                scratch_shapes=[
                    pltpu.VMEM((2, pages) + block, plane.dtype)
                    for plane, block in zip(planes, blocks)
                ] + [
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SMEM((1,), jnp.int32),
                ] + scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((b, kvh, gq, hd), q.dtype),
            # The buffers carry one row's look-ahead into the next: the
            # rows run in grid order (a v5e has one core to run them on).
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")
            ),
            interpret=pallas_interpret(),
        )(*prefetch, q, *planes)
        return out[:, :, :g, :]

    # Flash-decoding split over the slot's page list: the page axis on
    # the grid, each (slot, head block, split) streams its own run of
    # table entries through the pipeline and emits partials; the
    # single-pass rescale combine reduces them (dense discipline).
    bps = -(-pages_per_slot // split)  # pages per split (last may be ragged)

    def kv_map(s, hb, s_id, j, table_ref, idx_ref, *vf_ref):
        jg = s_id * bps + j
        # The walk stops at the slot's live window: a step past its last
        # live page (or before its first) names the block the step
        # before it held, so the pipeline issues no copy for it; a dead
        # row (negative index) names its first table entry throughout.
        last = jnp.minimum(
            jnp.maximum(idx_ref[s], 0) // page, pages_per_slot - 1
        )
        first = jnp.clip(vf_ref[0][s] // page, 0, last) if has_vf else 0
        return (table_ref[s, jnp.clip(jg, first, last)], hb, 0, 0)

    # The pool stays in HBM and the pipeline streams it from there. Left
    # to itself XLA may park a plane small enough in fast memory on its
    # way in (it did GPT-2-XL's 23 MB planes while they were still
    # relaid out: the kernel then read at 107% of its bytes floor on a
    # v5e, its time having left the fetch to a copy). (The interpreter
    # knows no memory spaces.)
    in_hbm = (lambda x: x) if pallas_interpret() else functools.partial(
        pltpu.with_memory_space_constraint, memory_space=pltpu.HBM
    )
    in_specs = [q_spec] + [
        pl.BlockSpec((1,) + block, kv_map, memory_space=_VMEM)
        for block in blocks
    ]

    def part_map(s, hb, s_id, *rest):
        return (s, s_id, hb, 0, 0)

    part = pl.BlockSpec((1, 1, heads, gq, hd), part_map, memory_space=_VMEM)
    part_shape = jax.ShapeDtypeStruct((b, split, kvh, gq, hd), jnp.float32)
    o_p, m_p, l_p = pl.pallas_call(
        functools.partial(_paged_kernel, bps=bps, **kernel_kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, head_blocks, split, bps),
            in_specs=in_specs,
            out_specs=(part, part, part),
            scratch_shapes=scratch,
        ),
        out_shape=(part_shape, part_shape, part_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            )
        ),
        interpret=pallas_interpret(),
    )(*prefetch, q, *map(in_hbm, planes))
    return _combine_splits(o_p, m_p, l_p, q.dtype)[:, :, :g, :]


def _emit_softmax(o_ref, parts, m_scr, l_scr, acc_scr):
    """The kernels' last step for a row: the normalized output, or —
    ``parts`` = the split form's ``(m_ref, l_ref)`` — the unnormalized
    partials for ``_combine_splits``. ``o_ref``'s block ends in
    (rows, head_dim); the accumulator may be wider (``_acc_width``):
    V's lanes are its last head_dim."""
    hd = o_ref.shape[-1]
    acc = acc_scr[...][..., acc_scr.shape[-1] - hd:]
    lead = (0,) * (len(o_ref.shape) - acc.ndim)
    if parts is None:
        o_ref[lead] = (
            acc / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)
        return
    # m/l broadcast across the lane axis so the partials share the
    # accumulator's tiling; the combine reads lane 0.
    m_ref, l_ref = parts
    o_ref[lead] = acc
    m_ref[lead] = jnp.broadcast_to(m_scr[...], acc.shape)
    l_ref[lead] = jnp.broadcast_to(l_scr[...], acc.shape)


def _unpack_refs(refs, has_vf, quantized):
    """The decode kernels' operand refs after the table and the
    positions: ``(valid_from or None, q, [kv, k scales, v scales],
    the rest)`` — the scale planes present for a quantized pool."""
    refs = list(refs)
    vf_ref = refs.pop(0) if has_vf else None
    q_ref = refs.pop(0)
    n = 3 if quantized else 1
    return vf_ref, q_ref, refs[:n], refs[n:]


def _walk_kernel(table_ref, idx_ref, *refs, page, pages, num_pages,
                 head_blocks, sm_scale, quantized, has_vf, packed=False,
                 transposed=False):
    """``heads`` KV heads of one slot a grid step, grid (slots, head
    blocks); the row's LIVE pages are walked here, ``pages`` an
    iteration: the ordinals from the page of ``valid_from`` (0 without
    one) to the page of ``index``, both read from the prefetched
    positions, so a window layer that holds 3 pages of a 15-ordinal
    table copies 2 or 3 and a dead row (negative index) none. Every
    plane of the pool arrives whole, where it lives; a page's block
    ``pool[table[s, j], hb * heads:(hb + 1) * heads]`` (and its two
    scale tiles, quantized) is copied into ``bufs`` — (2, pages, heads,
    page, 2 * hd) a plane, (2, pages, heads, 2 * hd, page) when
    ``transposed`` — the two buffers the iterations alternate between,
    ``sems`` one DMA semaphore a buffer, ``cur_ref`` (SMEM) the buffer
    the next iteration to be consumed lands in. Buffers, semaphores and
    ``cur_ref`` outlive a grid step: a row's last iteration has the
    first pages of the next live (slot, head block) in grid order in
    flight before it waits on its own, so a row begins with its copies
    already under way (the first live row's are started at grid step
    0) and a dead row costs a scalar read and a zeroed output block.
    The body is ONE ``_attend_fused`` a page block, the head axis
    leading (one attention discipline: its masks over ``[valid_from,
    index]``, its float32 softmax state in (heads, gq, .) scratch, its
    fused int8 dequant and int4 unpack); a row's last, shorter
    iteration takes its pages in groups of ``pages / 2``, ..., 1 by the
    bits of their number (PERF.md section 6, PR 44 and PR 46)."""
    vf_ref, q_ref, planes, rest = _unpack_refs(refs, has_vf, quantized)
    o_ref, *rest = rest
    bufs, (sems, cur_ref, m_scr, l_scr, acc_scr) = (
        rest[:len(planes)], rest[len(planes):]
    )
    slot, hb = pl.program_id(0), pl.program_id(1)
    slots = pl.num_programs(0)
    heads, gq = q_ref.shape[1], q_ref.shape[2]

    def window(s):
        # (first live ordinal, live pages) of slot s. (``lax.div``:
        # nothing here is negative, ``_chunk_live_pages``.)
        idx, p = idx_ref[s], jnp.int32(page)
        last = jnp.minimum(lax.div(jnp.maximum(idx, 0), p), num_pages - 1)
        first = 0
        if has_vf:
            first = jnp.minimum(lax.div(jnp.maximum(vf_ref[s], 0), p), last)
        return first, jnp.where(idx < 0, 0, last - first + 1)

    def copies(s, h, t, b, then, first, live):
        # ``then`` (start or wait) each live page's copies of iteration
        # t of (slot s, head block h), whose window is (first, live),
        # into buffer b. The table is read under the guard: only a live
        # ordinal's entry names a page.
        for i in range(pages):
            @pl.when(t * pages + i < live)
            def _(i=i):
                phys = table_ref[s, first + t * pages + i]
                for plane, buf in zip(planes, bufs):
                    then(pltpu.make_async_copy(
                        plane.at[phys, pl.ds(h * heads, heads)],
                        buf.at[b, i], sems.at[b],
                    ))

    def start(s, h, t, b, *win):
        copies(s, h, t, b, lambda c: c.start(), *(win or window(s)))

    def start_first_of(after, b):
        # head block 0 of the first slot after ``after`` that has a
        # page, found by a scalar loop over the prefetched positions.
        n = jax.lax.while_loop(
            lambda n: (n < slots) & (idx_ref[jnp.minimum(n, slots - 1)] < 0),
            lambda n: n + 1, after + 1,
        )
        pl.when(n < slots)(lambda: start(n, 0, 0, b))

    def start_next_row(b):
        # the first copies of the next live (slot, head block) in grid
        # order: this slot's next head block, else the next live slot's
        # first.
        if head_blocks == 1:
            start_first_of(slot, b)
            return
        pl.when(hb + 1 < head_blocks)(
            lambda: start(slot, hb + 1, 0, b, first, live)
        )
        pl.when(hb + 1 == head_blocks)(lambda: start_first_of(slot, b))

    @pl.when((slot == 0) & (hb == 0))
    def _first():
        cur_ref[0] = 0
        start_first_of(-1, 0)

    idx = idx_ref[slot]
    vf = vf_ref[slot] if has_vf else None

    def attend(b, i, ordinal):
        # buffer b's page i: the row's page ``ordinal``, live.
        cols = ordinal * page + jax.lax.broadcasted_iota(
            jnp.int32, (gq, page), 1
        )
        live = cols <= idx
        if has_vf:
            live = jnp.logical_and(live, cols >= vf)
        ksc, vsc = (
            buf[b, i].reshape(heads, 1, page) for buf in bufs[1:]
        ) if quantized else (None, None)
        _attend_fused(
            q_ref[0], bufs[0][b, i], ksc, vsc, live, m_scr, l_scr, acc_scr,
            sm_scale, packed, transposed,
        )

    _init_softmax_scratch(m_scr, l_scr, acc_scr)
    groups = [pages >> k for k in range(pages.bit_length())]
    first, live = window(slot)
    iters = lax.div(live + pages - 1, jnp.int32(pages))
    base = cur_ref[0]

    def iteration(t, _):
        b = lax.rem(base + t, 2)
        # The copies after this iteration's go out before it waits on
        # its own: the row's next, or the next live row's first.
        pl.when(t + 1 < iters)(
            lambda: start(slot, hb, t + 1, 1 - b, first, live)
        )
        pl.when(t + 1 == iters)(lambda: start_next_row(1 - b))
        copies(slot, hb, t, b, lambda c: c.wait(), first, live)
        if pages == 1:
            attend(b, 0, first + t)
            return
        here = jnp.minimum(live - t * pages, pages)
        # ``here`` live pages, taken in groups of pages, pages / 2, ...,
        # 1 by its bits: a whole iteration is one group, a row's last
        # one at most log2(pages), and no dead page is in any.
        for g in groups:
            @pl.when(here & g != 0)
            def _(g=g):
                at = 0 if 2 * g >= pages else here - lax.rem(here, 2 * g)
                for i in range(g):
                    attend(b, at + i, first + t * pages + at + i)

    jax.lax.fori_loop(0, iters, iteration, None)
    cur_ref[0] = lax.rem(base + iters, 2)
    _emit_softmax(o_ref, None, m_scr, l_scr, acc_scr)


def _paged_kernel(table_ref, idx_ref, *refs, page, num_pages, bps, sm_scale,
                  quantized, has_vf, packed=False, transposed=False):
    """The FLASH-SPLIT form of the decode kernel, the page axis on the
    grid: one page of ``heads`` KV heads of one slot per grid step,
    grid (slots, head blocks, split, bps); it emits each split's
    unnormalized partials (accumulator, running max, denominator) for
    ``_combine_splits``. The scalar-prefetched table is consumed by the
    index maps; the prefetched positions give this slot's live window
    ``[valid_from, index]``. The fused rows arrive as a (1, heads,
    page, 2 * hd) block ((1, heads, 2 * hd, page) when ``transposed``:
    head_dim under 64) and the body is ONE ``_attend_fused`` over the
    block, as ``_walk_kernel``'s. Quantized pools add chunked (1,
    heads, page/128, 128) f32 scale tiles of K and of V,
    table-addressed like the payload. A page outside the live window —
    every page of a dead row (negative index), the ragged tail of the
    last split — skips the body, and its step fetched nothing
    (``_paged_impl``'s ``kv_map``) but is a step all the same."""
    del table_ref  # consumed by the index maps
    vf_ref, q_ref, (kv_ref, *scale_refs), rest = _unpack_refs(
        refs, has_vf, quantized
    )
    o_ref, *parts, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(3)
    jg = pl.program_id(2) * bps + j
    heads, gq = q_ref.shape[1], q_ref.shape[2]
    slot = pl.program_id(0)
    idx = idx_ref[slot]
    vf = vf_ref[slot] if has_vf else None

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def _step():
        cols = jg * page + jax.lax.broadcasted_iota(
            jnp.int32, (gq, page), 1
        )
        live = cols <= idx
        if has_vf:
            live = jnp.logical_and(live, cols >= vf)
        ksc, vsc = (
            r[0].reshape(heads, 1, page) for r in scale_refs
        ) if quantized else (None, None)
        _attend_fused(
            q_ref[0], kv_ref[0], ksc, vsc, live, m_scr, l_scr, acc_scr,
            sm_scale, packed, transposed,
        )

    live_block = jnp.logical_and(jg * page <= idx, jg < num_pages)
    if has_vf:
        live_block = jnp.logical_and(live_block, (jg + 1) * page > vf)
    pl.when(live_block)(_step)

    @pl.when(j == bps - 1)
    def _emit():
        _emit_softmax(o_ref, parts, m_scr, l_scr, acc_scr)


def _chunk_kernel(pages_ref, pos0_ref, q_ref, kv_ref, *refs,
                  block_k, num_kv, sm_scale, chunk, window=None,
                  quantized=False, packed=False, lanes=True):
    """Chunk-query paged attention: q rows are a CHUNK of positions
    [pos0, pos0 + chunk) (GQA groups folded in, row = member*chunk + p)
    attending the paged window up to each row's own position — the
    per-row causal mask ``col <= pos0 + row % chunk``. Grid (head
    blocks, pages): one step covers one page of ``heads`` KV heads — a
    (1, heads, page, 2 * hd) block of fused rows against (heads, gc, aw)
    query rows — in ONE body with the head axis leading and per-head
    online-softmax state in scratch. ``lanes`` (every native pool): the
    body is ``_attend_rows_on_lanes``, the state (heads, 1, gc) and
    (heads, aw, gc), transposed back once, at the last step; a page
    every row attends whole skips the mask. Otherwise (quantized
    pools) it is ``_attend_fused``, the decode kernel's body
    (``_paged_kernel``) with a row-dependent diagonal instead of a
    shared index and (heads, gc, .) state, and adds chunked (1, heads,
    page/128, 128) f32 scale tiles applied to the score/probability
    columns in VMEM (``_decode_kernel``'s fused-dequant discipline;
    ``packed`` int4 pools unpack their nibbles there too). The page
    list and ``pos0`` are scalar-prefetched: the index maps read both
    (``_chunk_impl``'s ``kv_map``), the body reads ``pos0`` for its
    masks."""
    del pages_ref  # consumed by the index_maps
    refs = list(refs)
    ksc_ref = refs.pop(0) if quantized else None
    vsc_ref = refs.pop(0) if quantized else None
    o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)
    heads, gc = q_ref.shape[0], q_ref.shape[1]
    pos0 = pos0_ref[0]

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def _live(shape, row_axis):
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, row_axis)
        if gc != chunk:  # GQA members fold in: position = row % chunk
            pow2 = chunk & (chunk - 1) == 0
            rows = rows & (chunk - 1) if pow2 else rows % chunk
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - row_axis
        )
        live = cols <= pos0 + rows
        if window is not None:
            # Sliding window: row at absolute position p attends
            # (p - window, p].
            live = jnp.logical_and(live, cols > pos0 + rows - window)
        return live

    def _step(masked=True):
        if lanes:
            _attend_rows_on_lanes(
                q_ref[...], kv_ref[0],
                _live((block_k, gc), 1) if masked else None,
                m_scr, l_scr, acc_scr, sm_scale,
            )
            return
        _attend_fused(
            q_ref[...], kv_ref[0],
            ksc_ref[0].reshape(heads, 1, block_k) if quantized else None,
            vsc_ref[0].reshape(heads, 1, block_k) if quantized else None,
            _live((gc, block_k), 0), m_scr, l_scr, acc_scr, sm_scale,
            packed,
        )

    # Pages entirely past the chunk's last position are dead (the pow2
    # padding's trash pages land here too); under a sliding window so
    # are pages entirely below EVERY row's window (row 0's is lowest).
    # A dead step fetched nothing either (``_chunk_live_pages``).
    first, last = _chunk_live_pages(pos0, chunk, block_k, num_kv, window)
    live_block = jnp.logical_and(j >= first, j <= last)
    if lanes:
        # A page every row attends whole (under the diagonal, inside
        # the last row's window) needs no mask.
        whole = (j + 1) * block_k - 1 <= pos0
        if window is not None:
            whole = jnp.logical_and(
                whole, j * block_k > pos0 + chunk - 1 - window
            )
        pl.when(jnp.logical_and(live_block, whole))(
            functools.partial(_step, masked=False)
        )
        live_block = jnp.logical_and(live_block, jnp.logical_not(whole))
    pl.when(live_block)(_step)

    @pl.when(j == num_kv - 1)
    def _emit():
        if not lanes:
            _emit_softmax(o_ref, None, m_scr, l_scr, acc_scr)
            return
        hd = o_ref.shape[-1]
        acc = acc_scr[...][:, acc_scr.shape[1] - hd:, :]
        out = acc / jnp.maximum(l_scr[...], 1e-30)  # (heads, hd, gc)
        o_ref[...] = jnp.swapaxes(out, 1, 2).astype(o_ref.dtype)


def _chunk_live_pages(pos0, chunk, page, n, window):
    """``(first, last)`` entries of a chunk's page list that hold a
    position some row attends: the last is the page of the chunk's last
    position (the power-of-two padding's trash pages lie past it), the
    first the page of row 0's lowest position, ``pos0 - window + 1``,
    under a sliding window. The kernel's body runs on these and its
    index map names no other, so the steps outside fetch nothing.
    (``lax.div``: nothing here is negative, and a floor division's sign
    handling costs a kernel's lowering more than its body does.)"""
    def page_of(pos):
        return lax.div(pos, jnp.asarray(page, pos.dtype))

    last = jnp.minimum(page_of(pos0 + chunk - 1), n - 1)
    if window is None:
        return 0, last
    return jnp.minimum(page_of(jnp.maximum(pos0 - window + 1, 0)), last), last


def paged_chunk_attention_reference(q, pool, pages, pos0, chunk: int,
                                    window: int | None = None):
    """jnp oracle for the chunk-query kernel: gather the window, split
    the fused rows, mask ``col <= pos0 + row % chunk`` (banded by
    ``window`` when set), softmax, weight. q is (1, kv_h, g*C, hd)
    GROUP-FOLDED (row = member*C + position), pages (n,). A quantized
    ``(values, k_scales, v_scales)`` pool applies its scales to the
    score/probability columns, in ``decode_attention_reference``'s op
    order."""
    quantized = isinstance(pool, tuple)
    k, v = _gather_window(pool, jnp.asarray(pages)[None])
    sm = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    if quantized:
        (k, ksc), (v, vsc) = k, v
        if k.shape[-1] * 2 == q.shape[-1]:  # packed int4 nibbles
            k, v = unpack_int4(k), unpack_int4(v)
        s = jnp.einsum(
            "bhqd,bhkd->bhqk",
            q.astype(jnp.float32),
            k.astype(jnp.float32),
        ) * jnp.swapaxes(ksc, 2, 3) * sm
    else:
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * sm
    rows = jnp.arange(q.shape[2]) % chunk
    cols = jnp.arange(k.shape[2])
    live = cols[None, :] <= pos0 + rows[:, None]
    if window is not None:
        live = live & (cols[None, :] > pos0 + rows[:, None] - window)
    s = jnp.where(live[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if quantized:
        p = p * jnp.swapaxes(vsc, 2, 3)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    ).astype(q.dtype)


def _chunk_rows(gc: int, lanes: bool) -> int:
    """A KV head's query rows as the chunk kernel holds them: whole
    lane tiles where they lie on the lanes, whole sublane tiles
    otherwise (nothing to pad at a chunk of whole pages)."""
    return gc + (-gc) % (128 if lanes else 8)


def chunk_step_vmem_bytes(heads, gc, page, row_width, itemsize, scales,
                          head_dim=None, q_itemsize=None) -> int:
    """VMEM one grid step of the chunk kernel asks for when it covers
    ``heads`` KV heads of one page: the fused block (and its two scale
    tiles), q's rows and the output's, each double-buffered by the
    pipeline; the per-head softmax state; and the body's float32
    working set. A chunk's state is per query ROW, so ``gc`` (group x
    chunk rows a KV head) dominates where the decode kernel's sum is
    all block: the working set is four score-shaped (page, gc) arrays
    (scores, mask, exponentials, what the second product reads) and
    that product's accumulator-shaped result. The running max and the
    denominator are a sublane tile each with the rows on the lanes
    (native pools); under a quantized pool's body they are (gc, 1) and
    pad to a lane tile each. Compiling for a described v5e, Mosaic
    took every step whose sum was under 15.8 MB and refused every one
    over 20 MB at its scoped 16 MB."""
    hd = head_dim or row_width // 2
    acc = _lanes(_acc_width(hd))
    stream = 2 * heads * page * _lanes(row_width) * itemsize
    if scales:
        # the two scale tiles, and the block widened on its way in
        stream += 2 * 2 * heads * max(page // 128, 8) * 128 * 4
        stream += heads * page * _lanes(row_width) * 4
    rows = 2 * heads * gc * (acc + _lanes(hd)) * (q_itemsize or itemsize)
    state = heads * gc * (2 * (128 if scales else 8) + acc) * 4
    working = heads * gc * (4 * page + acc) * 4
    return stream + rows + state + working


def chunk_heads_per_step(kv_heads, gc, page, row_width, itemsize, scales,
                         head_dim=None, q_itemsize=None) -> int:
    """KV heads one grid step of the chunk kernel covers: the largest
    divisor of ``kv_heads`` (the per-shard count under tensor
    parallelism) whose step fits ``DECODE_STEP_VMEM_BUDGET``, by
    ``decode_heads_per_step``'s rule over the chunk's own sum. A grid
    step costs 0.2 us on a v5e before it moves a byte: 5 of 25 heads at
    GPT-2-XL's 256 rows of head_dim 64 (25 do not fit Mosaic's 16 MB);
    1 at K-EXAONE's 2,048 rows and Falcon-H1's 1,280, where a head's
    tile is a step's worth of work already. Derived from the operands,
    never set."""
    return _heads_that_fit(kv_heads, lambda heads: chunk_step_vmem_bytes(
        heads, gc, page, row_width, itemsize, scales, head_dim, q_itemsize
    ))


@functools.partial(
    jax.jit, static_argnames=("chunk", "window", "heads", "lanes")
)
def _chunk_impl(q, kv_pool, k_scales, v_scales, pages, pos0, chunk,
                window=None, heads=1, lanes=None):
    _, kvh, gc, hd = q.shape
    page = kv_pool.shape[2]
    row = kv_pool.shape[3]  # K|V: 2 * head_dim, head_dim for packed int4
    n = pages.shape[0]
    quantized = k_scales is not None
    packed = _packed(q, kv_pool, quantized)
    if lanes is None:
        # A native pool's chunk rows lie on the lanes
        # (``_attend_rows_on_lanes``); int8 / int4 values are the
        # scales' arithmetic and keep the decode kernels' body.
        lanes = not quantized
    gcp = _chunk_rows(gc, lanes)
    if gcp != gc:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gcp - gc), (0, 0)))
    aw = _acc_width(hd)
    qf = _pad_q_lanes(q, hd).reshape(kvh, gcp, aw)
    # Scalar prefetch: the page list and the chunk's first position.
    # The index maps read both; the body reads ``pos0`` for its masks.
    prefetch = [
        jnp.asarray(pages, jnp.int32),
        jnp.reshape(jnp.asarray(pos0, jnp.int32), (1,)),
    ]

    def q_map(hb, j, pages_ref, pos0_ref):
        del j, pages_ref, pos0_ref
        return (hb, 0, 0)

    def kv_map(hb, j, pages_ref, pos0_ref):
        # A dead step (a trash page past the chunk's last position, a
        # page wholly under every row's window) names the nearest live
        # entry — the block the step beside it holds — so the pipeline
        # issues no copy for it.
        first, last = _chunk_live_pages(pos0_ref[0], chunk, page, n, window)
        return (pages_ref[jnp.clip(j, first, last)], hb, 0, 0)

    in_specs = [
        pl.BlockSpec((heads, gcp, aw), q_map, memory_space=_VMEM),
        pl.BlockSpec((1, heads, page, row), kv_map, memory_space=_VMEM),
    ]
    operands = [qf, kv_pool]
    if quantized:
        # Chunked (P/128, 128) scale views as in _paged_impl, addressed
        # by the payload's own index map.
        for s in (k_scales, v_scales):
            operands.append(
                s.reshape(s.shape[0], kvh, page // 128, 128)
            )
            in_specs.append(
                pl.BlockSpec(
                    (1, heads, page // 128, 128), kv_map,
                    memory_space=_VMEM,
                )
            )
    state = (heads, 1, gcp) if lanes else (heads, gcp, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(kvh // heads, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((heads, gcp, hd), q_map, memory_space=_VMEM),
        scratch_shapes=[
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(
                (heads, aw, gcp) if lanes else (heads, gcp, aw), jnp.float32
            ),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel,
            block_k=page,
            num_kv=n,
            sm_scale=1.0 / (hd ** 0.5),
            chunk=chunk,
            window=window,
            quantized=quantized,
            packed=packed,
            lanes=lanes,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, gcp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
    )(*prefetch, *operands)
    return out.reshape(1, kvh, gcp, hd)[:, :, :gc, :]


def paged_chunk_attention(
    q: jax.Array,
    pool,
    pages: jax.Array,
    pos0,
    chunk: int,
    prefer: str | None = None,
    window: int | None = None,
    head_shard=None,
) -> jax.Array:
    """Chunk-prefill attention over a paged window, in place — the
    incremental-prefill counterpart of :func:`paged_attention` (no
    gathered strip, no scatter-back; the caller writes the chunk's
    fused K|V pages first, this reads the window page by page).

    q (1, kv_h, g*chunk, hd) group-folded; ``pages`` (n,) covers the
    whole live window [0, pos0 + chunk) (pow2 padding to the trash page
    is fine — those positions are past every row's mask, and the
    kernel neither fetches nor reads them). ``pool`` is a
    block's pool: the fused plane or a quantized ``(values, k_scales,
    v_scales)`` triple. Dispatch and ``head_shard`` as
    :func:`paged_attention`. The KV heads one grid step covers
    (``chunk_heads_per_step``) derive from the given (per-shard, under
    TP) operands, and the books say what was derived
    (``kernel_dispatch_stats()["paged_chunk"]``: ``heads_per_step``)."""
    check_head_parity(q.shape[1], pool_values(pool).shape[1])
    if resolve_prefer(
        "paged_chunk", prefer, kernel_unsupported(q, pool), on_tpu()
    ):
        kv, ks, vs = _pool_planes(pool)
        heads = chunk_heads_per_step(
            _shard_heads(q, head_shard),
            _chunk_rows(q.shape[2], ks is None), kv.shape[2],
            kv.shape[3], kv.dtype.itemsize, ks is not None, q.shape[3],
            q.dtype.itemsize,
        )
        record_kernel_choice("paged_chunk", heads_per_step=heads)
        return _head_sharded(
            functools.partial(
                _chunk_impl, chunk=chunk, window=window, heads=heads
            ),
            head_shard,
            (q, kv, ks, vs),
            (jnp.asarray(pages, jnp.int32), jnp.asarray(pos0, jnp.int32)),
        )
    return paged_chunk_attention_reference(
        q, pool, pages, pos0, chunk, window
    )


def paged_verify_attention_reference(q, pool, page_table, index,
                                     chunk: int, window: int | None = None,
                                     tree_tail: int = 0):
    """jnp oracle for the batched paged VERIFY: gather each slot's pages
    into a contiguous window, split the fused rows and run the
    contiguous verify oracle (``ops/decode_attention.verify_attention``,
    which owns the quantized scale application) — per-row diagonal
    ``col <= index[b] + row % chunk``. q (b, kv_h, g*chunk, hd)
    group-folded K-major; ``index`` (b,) per-slot base positions
    (negative = dead row, fully masked)."""
    from adapt_tpu.ops.decode_attention import verify_attention

    cache_k, cache_v = _gather_window(pool, page_table)
    return verify_attention(
        q, cache_k, cache_v, index, chunk, window=window,
        tree_tail=tree_tail,
    )


def _verify_kernel(table_ref, q_ref, kv_ref, idx_ref, *refs,
                   block_k, num_kv, sm_scale, chunk, window=None,
                   quantized=False, packed=False, tree_tail=0, bps=None):
    """Batched chunk-query paged attention: one (batch, kv_head) row of
    K-major verify rows streams ITS page-table row innermost (scalar
    prefetch, as ``_paged_kernel``) with ``_chunk_kernel``'s per-row
    diagonal mask anchored at this slot's OWN base position
    (``idx_ref`` SMEM, whole vector, read by ``program_id(0)``) — the
    speculative verify over a paged cache.
    Dead rows (negative index) skip every block and emit zeros.
    Quantized pools add chunked (page/128, 128) f32 scale tiles applied to the
    score/probability columns in VMEM (the fused dequant; ``packed``
    int4 pools unpack their nibbles there). ``tree_tail`` = w marks the
    chunk's last w rows as TREE LEAVES: each attends the chain prefix
    (depth ``chunk - 1 - w``) plus its OWN physical slot only — the
    tree-draft verify mask (``ops.decode_attention.verify_attention``).
    ``bps`` non-None selects the FLASH-SPLIT grid (b * kv_h, split,
    bps): partial (acc, m, l) emission per split with the caller's
    rescale combine, the ``_decode_split_kernel`` discipline."""
    del table_ref  # consumed by the index_maps
    split_mode = bps is not None
    refs = list(refs)
    ksc_ref = refs.pop(0) if quantized else None
    vsc_ref = refs.pop(0) if quantized else None
    if split_mode:
        o_ref, *parts, m_scr, l_scr, acc_scr = refs
        j = pl.program_id(2)
        jg = pl.program_id(1) * bps + j  # global page index (clamped map)
        last_j = bps - 1
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        parts = None
        j = pl.program_id(1)
        jg = j
        last_j = num_kv - 1
    gc = q_ref.shape[1]
    idx = idx_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def _step():
        rows = jax.lax.broadcasted_iota(jnp.int32, (gc, block_k), 0) % chunk
        cols = jg * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (gc, block_k), 1
        )
        if tree_tail:
            depth = jnp.minimum(rows, chunk - 1 - tree_tail)
        else:
            depth = rows
        live = cols <= idx + depth
        if window is not None:
            live = jnp.logical_and(live, cols > idx + depth - window)
        if tree_tail:
            # A leaf row's own physical slot is live even though it sits
            # past the chain edge; siblings' slots stay masked.
            live = jnp.logical_or(live, cols == idx + rows)
        _attend_fused(
            q_ref[0], kv_ref[0, 0],
            ksc_ref[0, 0].reshape(1, block_k) if quantized else None,
            vsc_ref[0, 0].reshape(1, block_k) if quantized else None,
            live, m_scr, l_scr, acc_scr, sm_scale, packed,
        )

    # Pages wholly past this slot's last chunk position are dead (every
    # page, for a negative dead-row index); under a sliding window so
    # are pages wholly below row 0's window. The ragged split tail's
    # clamped pages mask here too (jg >= num_kv).
    live_block = jg * block_k <= idx + chunk - 1
    if split_mode:
        live_block = jnp.logical_and(live_block, jg < num_kv)
    if window is not None:
        live_block = jnp.logical_and(
            live_block, (jg + 1) * block_k - 1 > idx - window
        )
    pl.when(live_block)(_step)

    @pl.when(j == last_j)
    def _emit():
        _emit_softmax(o_ref, parts, m_scr, l_scr, acc_scr)


@functools.partial(
    jax.jit, static_argnames=("chunk", "window", "tree_tail", "split")
)
def _verify_impl(q, kv_pool, k_scales, v_scales, page_table, index,
                 chunk, window=None, tree_tail=0, split=1):
    b, kvh, gc, hd = q.shape
    page = kv_pool.shape[2]
    row = kv_pool.shape[3]  # K|V: 2 * head_dim, head_dim for packed int4
    pages_per_slot = page_table.shape[1]
    quantized = k_scales is not None
    packed = _packed(q, kv_pool, quantized)
    pad_g = (-gc) % 8
    if pad_g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_g), (0, 0)))
    gcp = gc + pad_g
    aw = _acc_width(hd)
    qf = _pad_q_lanes(q, hd).reshape(b * kvh, gcp, aw)
    idx = jnp.repeat(
        jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
        kvh,
    )
    bps = -(-pages_per_slot // split)

    def blk(bh, *js):
        if split == 1:
            (j,) = js
            return j
        s_id, j = js
        return jnp.minimum(s_id * bps + j, pages_per_slot - 1)

    def q_map(bh, *js_table):
        return (bh, 0, 0)

    def kv_map(bh, *js_table):
        *js, table_ref = js_table
        return (table_ref[bh // kvh, blk(bh, *js)], bh % kvh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, gcp, aw), q_map, memory_space=_VMEM),
        pl.BlockSpec((1, 1, page, row), kv_map, memory_space=_VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qf, kv_pool, idx]
    if quantized:
        # Chunked (P/128, 128) scale views as in _paged_impl.
        for s in (k_scales, v_scales):
            operands.append(
                s.reshape(s.shape[0], kvh, page // 128, 128)
            )
            in_specs.append(
                pl.BlockSpec(
                    (1, 1, page // 128, 128), kv_map, memory_space=_VMEM
                )
            )
    scratch = [
        pltpu.VMEM((gcp, 1), jnp.float32),
        pltpu.VMEM((gcp, 1), jnp.float32),
        pltpu.VMEM((gcp, aw), jnp.float32),
    ]
    if split == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * kvh, pages_per_slot),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, gcp, hd), q_map, memory_space=_VMEM),
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            functools.partial(
                _verify_kernel,
                block_k=page,
                num_kv=pages_per_slot,
                sm_scale=1.0 / (hd ** 0.5),
                chunk=chunk,
                window=window,
                quantized=quantized,
                packed=packed,
                tree_tail=tree_tail,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b * kvh, gcp, hd), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=pallas_interpret(),
        )(jnp.asarray(page_table, jnp.int32), *operands)
        return out.reshape(b, kvh, gcp, hd)[:, :, :gc, :]

    def part_map(bh, s_id, j, table_ref):
        del j, table_ref
        return (bh, s_id, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * kvh, split, bps),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, gcp, hd), part_map, memory_space=_VMEM),
            pl.BlockSpec((1, 1, gcp, hd), part_map, memory_space=_VMEM),
            pl.BlockSpec((1, 1, gcp, hd), part_map, memory_space=_VMEM),
        ),
        scratch_shapes=scratch,
    )
    o_p, m_p, l_p = pl.pallas_call(
        functools.partial(
            _verify_kernel,
            block_k=page,
            num_kv=pages_per_slot,
            sm_scale=1.0 / (hd ** 0.5),
            chunk=chunk,
            window=window,
            quantized=quantized,
            packed=packed,
            tree_tail=tree_tail,
            bps=bps,
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b * kvh, split, gcp, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * kvh, split, gcp, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * kvh, split, gcp, hd), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
    )(jnp.asarray(page_table, jnp.int32), *operands)
    out = _combine_splits(o_p, m_p, l_p, q.dtype)
    return out.reshape(b, kvh, gcp, hd)[:, :, :gc, :]


def paged_verify_attention(
    q: jax.Array,
    pool,
    page_table: jax.Array,
    index,
    chunk: int,
    prefer: str | None = None,
    window: int | None = None,
    tree_tail: int = 0,
    split: int | None = None,
    head_shard=None,
) -> jax.Array:
    """Batched multi-token verify attention over a paged KV cache — the
    speculative-decode counterpart of :func:`paged_attention` (K chunk
    rows per slot, each masked to its own ``index[b] + t`` diagonal;
    the caller has already scattered the chunk's K/V into the pages).

    ``pool`` is a block's pool: the fused plane or a quantized
    ``(values, k_scales, v_scales)`` triple (the caller scattered the
    chunk's quantized rows into all three; an int4-PACKED value plane
    carries ``head_dim`` nibble lanes for K|V). ``tree_tail`` marks the
    chunk's last w rows as tree-draft leaves
    (``decode_attention.verify_attention``'s mask). ``split`` is the
    flash-decoding page-axis split (None = auto on TPU, 1 off-TPU).
    Dispatch and ``head_shard`` as :func:`paged_attention`. Grids and
    the GQA fold derive from the shapes given — the per-shard head
    count under tensor parallelism — so q and pool must carry the same
    head count (``decode_attention.check_head_parity``)."""
    check_head_parity(q.shape[1], pool_values(pool).shape[1])
    if resolve_prefer(
        "paged_verify", prefer, kernel_unsupported(q, pool), on_tpu()
    ):
        return _head_sharded(
            functools.partial(
                _verify_impl, chunk=chunk, window=window,
                tree_tail=tree_tail,
                split=resolve_decode_split(page_table.shape[1], split),
            ),
            head_shard,
            (q, *_pool_planes(pool)),
            (jnp.asarray(page_table, jnp.int32),
             jnp.asarray(index, jnp.int32)),
        )
    return paged_verify_attention_reference(
        q, pool, page_table, index, chunk, window, tree_tail
    )


def paged_attention(
    q: jax.Array,
    pool,
    page_table: jax.Array,
    index,
    valid_from=None,
    prefer: str | None = None,
    split: int | None = None,
    head_shard=None,
) -> jax.Array:
    """Decode attention over a paged KV cache.

    ``pool`` is a block's pool (``runtime/paged.alloc_kv_pools``): the
    fused K|V plane, or a quantized ``(int8 values, k_scales,
    v_scales)`` triple (one scale per cached vector — the
    module-docstring layout).

    ``prefer``: None = auto — the kernel on a real TPU whenever the page
    size is a lane multiple (the gather oracle materializes the whole
    window, the exact traffic paging exists to avoid), the oracle
    everywhere else (off-TPU the kernel only has the Pallas INTERPRETER,
    orders of magnitude slower than XLA's gather — tests opt in with
    ``prefer="pallas"``). ``"pallas"`` / ``"xla"`` force; a forced
    kernel on an unsupported page size raises
    (``dispatch.resolve_prefer``). ``head_shard`` = ``(mesh, axis)``
    under tensor parallelism: the kernel runs per KV-head shard inside
    a ``shard_map`` (``_head_sharded``); the oracle needs none — GSPMD
    partitions its einsums. ``split`` is
    the flash-decoding split along the slot's page list (None = auto:
    ``decode_attention.default_decode_split`` of pages_per_slot and the
    device's cores on a real TPU — 1 on a one-core chip — and 1
    off-TPU; 1 = the single-stream kernel). The heads one grid step
    covers (``decode_heads_per_step``), the grid and the GQA fold
    derive from the given (per-shard, under TP) operands — q and pool
    must agree (``decode_attention.check_head_parity``) — and the
    books say what was derived (``kernel_dispatch_stats()
    ["paged_decode"]``: ``heads_per_step``, ``split``, the
    ``pages_per_step`` an iteration of the unsplit kernel's walk
    covers (``decode_pages_per_step``) and the ``grid_steps`` a call
    takes: the rows, or every page of every row under a split)."""
    check_head_parity(q.shape[1], pool_values(pool).shape[1])
    if resolve_prefer(
        "paged_decode", prefer, kernel_unsupported(q, pool), on_tpu()
    ):
        kv, ks, vs = _pool_planes(pool)
        geometry = (
            kv.shape[2], kv.shape[3], kv.dtype.itemsize, ks is not None,
            q.shape[2] + (-q.shape[2]) % 8, q.shape[3],
        )
        kvh = _shard_heads(q, head_shard)
        heads = decode_heads_per_step(kvh, *geometry)
        split = resolve_decode_split(page_table.shape[1], split)
        # The split form keeps the page axis on its grid; the walk's
        # grid is the rows, and an iteration of it covers ``pages``.
        pages = 1 if split > 1 else decode_pages_per_step(
            page_table.shape[1], heads, *geometry
        )
        rows = q.shape[0] * (kvh // heads)
        record_kernel_choice(
            "paged_decode", heads_per_step=heads, split=split,
            pages_per_step=pages,
            grid_steps=rows if split == 1
            else rows * split * -(-page_table.shape[1] // split),
        )
        return _head_sharded(
            functools.partial(
                _paged_impl, heads=heads, split=split, pages=pages
            ),
            head_shard,
            (q, kv, ks, vs),
            (jnp.asarray(page_table, jnp.int32),
             jnp.asarray(index, jnp.int32),
             None if valid_from is None
             else jnp.asarray(valid_from, jnp.int32)),
        )
    return paged_attention_reference(
        q, pool, page_table, index, valid_from
    )
