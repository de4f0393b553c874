"""Decode-time (single-token) cached attention as a Pallas TPU kernel.

The serving hot path: at every decode step each query token attends over
the whole KV cache — a (b, kv_h, g, L) score row, no S x S anything —
and the step is HBM-bandwidth-bound (the cache is read end to end per
token). The XLA path (``decode_attention_reference``, the exact einsum
schedule ``models/transformer_lm.CausalSelfAttention.decode_step`` has
always run) handles the native-dtype cache well, but the r04 hardware
A/B (``benchmarks/results/r04/lm_decode_long_{native,int8}.json``)
showed the int8 cache ~12% SLOWER than bf16 despite carrying ~1.9x
fewer bytes: XLA does not reliably keep the per-step dequantize fused
to the HBM stream. This kernel exists to close that gap the TPU-native
way — the int8 values stream from HBM and dequantize in VMEM, so the
bytes that cross the HBM bus are the int8 bytes.

Layout (one kernel for native and int8 caches):

- grid = (batch * kv_heads, L / block_k); the cache-position axis is the
  innermost (sequential) dimension, online-softmax state (running max,
  denom, accumulator) persists across it in VMEM scratch — the same
  discipline as ``ops/attention``'s streaming kernel, with q a single
  (g, head_dim) tile (GQA query groups folded into query ROWS, matching
  ``CausalSelfAttention._group_q``; g is zero-padded to a sublane
  multiple).
- int8 scales (one f32 per cached key/value vector, the product
  quantization granularity) ride as a (b*kv_h, L/128, 128) chunked view
  — the same bytes as the (b, kv_h, L, 1) product layout, 1/16th of the
  int8 payload, never 8-row-broadcast — and are applied to the score /
  probability COLUMNS, so the only op on the big cache operand is the
  int8 contribution to the dot.
- the live window (positions <= index, >= valid_from for ragged rows)
  is masked via SMEM scalars; blocks entirely outside the window skip
  their compute (``pl.when``), which matters early in a long-max_len
  decode where most of the cache is still dead.

Dispatch: ``prefer=None`` ("auto") consults ``decode_kernel_wins`` —
measured on hardware like ``ops/attention``'s budget (artifact:
``benchmarks/results/r04/lm_decode_*``; see the function docstring for
the current rule). ``prefer="pallas"``/``"xla"`` force a path (tests,
the A/B driver). Off-TPU the kernel runs through the Pallas
interpreter, so the virtual-mesh tests exercise the same code path.

No reference analog (the reference is CNN-only, SURVEY.md §2.2) — this
is the framework's own serving frontier, the decode-side counterpart of
``ops/attention``'s long-context prefill kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapt_tpu.ops.dispatch import (
    device_cores,
    on_tpu,
    pallas_interpret,
    resolve_prefer,
)
from adapt_tpu.ops.quantize import unpack_int4

_VMEM = pltpu.VMEM
_NEG_INF = -1e30


def default_decode_split(num_blocks: int, cores: int = 1) -> int:
    """Auto-derived flash-decoding split factor for a cache of
    ``num_blocks`` position blocks (pages, for the paged layout) on a
    device of ``cores`` TensorCores: the largest power of two <= 8 and
    <= ``cores`` that still leaves every split at least two blocks of
    work. A split's axis is ``parallel``, which only another core can
    take up: on ONE core the grid runs in order whatever its axes are
    called, and a split adds a ragged step, three float32 partial
    outputs and the combine pass (timed on a v5e at the benchmark's
    three shapes, PERF.md section 6, PR 28: split 2 lost to 1 at every
    one). Short caches stay unsplit on any device."""
    s = 1
    while s < min(8, cores) and num_blocks >= 4 * s:
        s *= 2
    return s


def resolve_decode_split(num_blocks: int, split: int | None) -> int:
    """THE split-resolution rule every kernel dispatcher shares (decode
    / paged decode / paged verify — one definition, so the auto rule
    cannot fork across them): an explicit ``split`` wins; None
    auto-derives on real TPUs from the block count and the cores the
    device reports, and stays 1 off-TPU, where the interpreter gains
    nothing from fan-out."""
    if split is not None:
        return split
    return default_decode_split(num_blocks, device_cores()) if on_tpu() else 1

#: Cache-position block per grid step for QUANTIZED caches. 1024 = 8
#: sublanes x 128 lanes of the chunked scale view, the smallest block
#: whose scale tile satisfies TPU (8, 128) tiling without broadcast
#: padding — int8 caches therefore need max_len % 1024 == 0. NATIVE
#: caches carry no scale tiles, so their block can shrink to 256 and the
#: kernel serves short-context configs too (the headline max_len-256 row
#: streams its cache at ~0.26 efficiency on the XLA einsum path — the
#: analytic decomposition in benchmarks/README.md — which is exactly the
#: access pattern this kernel replaces).
DECODE_BLOCK_K = 1024
_MIN_NATIVE_BLOCK_K = 256


def check_head_parity(q_heads: int, cache_heads: int) -> None:
    """Every decode/verify primitive derives its grid, GQA fold and
    block sizes from the head count of the operands it is GIVEN — which
    under tensor parallelism is the PER-SHARD count (kv_heads / tp
    inside a shard_map body; the global count under GSPMD, where the
    partitioner divides it). The one mistake that silently breaks this
    is mixing a sharded cache with globally-shaped queries (or vice
    versa) across a partial TP migration: the einsums would
    broadcast-fail deep inside XLA. Fail here, by name, instead."""
    if q_heads != cache_heads:
        raise ValueError(
            f"q carries {q_heads} KV-head rows but the cache carries "
            f"{cache_heads}: both operands must use the same (per-shard) "
            "head count — under tensor parallelism shard queries and "
            "caches together (runtime/continuous shards both on the "
            "head axis)"
        )


def default_block_k(cache_len: int, quantized: bool) -> int:
    """Largest supported cache block for this (cache_len, dtype):
    quantized caches are pinned to the scale-tile block; native caches
    take the largest of 1024/512/256 dividing the cache."""
    if quantized:
        return DECODE_BLOCK_K
    for bk in (1024, 512, _MIN_NATIVE_BLOCK_K):
        if cache_len % bk == 0:
            return bk
    return DECODE_BLOCK_K  # _supported() False: XLA on auto, raise if forced


def decode_kernel_wins(cache_len: int, quantized: bool) -> bool:
    """THE auto-dispatch predicate for decode attention, in one place
    like ``ops/attention.scores_over_budget``. Current rule: XLA
    everywhere — the kernel ships behind ``prefer="pallas"`` until its
    hardware A/B (``benchmarks/lm_decode.py --decode-attn pallas``)
    lands; retune this predicate from that artifact, not from
    intuition."""
    del cache_len, quantized
    return False


def _supported(cache_len: int, block_k: int, quantized: bool) -> bool:
    if cache_len % block_k:
        return False
    # int8 scale tiles need (block_k//128) >= 8 rows per (8, 128) tile.
    return not quantized or block_k % DECODE_BLOCK_K == 0


def _attend_tile(q, k, v, ksc, vsc, live, m_scr, l_scr, acc_scr,
                 sm_scale, packed, kv_transposed=False):
    """One cache tile's online-softmax update — THE shared step body of
    every decode/verify/chunk kernel (split or not), so the int8 fused
    dequant, the int4 nibble unpack and the masking discipline cannot
    fork across grid layouts. ``q`` (gq, hd); ``k``/``v`` (block_k, hd)
    native/int8, or (block_k, hd // 2) packed int4 (``packed``);
    ``ksc``/``vsc`` (1, block_k) f32 column scales or None; ``live``
    (gq, block_k) bool mask. Mutates the (gq, 1)/(gq, 1)/(gq, hd)
    scratch refs in place. Every operand but ``live`` may carry LEADING
    axes (the paged decode kernel's block of heads): the two products
    batch over them and the softmax is elementwise, so a block of heads
    is one call whose independent rows the scheduler overlaps — a loop
    of single-head calls runs each head's chain (product, reduce, exp,
    product) behind the last one's, at 4x the time on a v5e.
    ``kv_transposed``: ``k``/``v`` arrive as (hd, block_k), positions on
    the lanes (how a pool of rows narrower than a lane tile lives in
    HBM); the two products contract the other axis and nothing else
    changes. ``k`` and ``v`` may be ONE array — a paged pool's fused
    K|V row read whole (``paged_attention._attend_fused``) — and are
    then widened once."""
    fused = v is k
    if packed:
        # Unpack two nibbles per streamed int8 lane in VMEM — the HBM
        # stream stays 4-bit; only the registers see head_dim lanes.
        k = unpack_int4(k)
        v = unpack_int4(v)
    lead = tuple(range(q.ndim - 2))
    row, col = q.ndim - 2, q.ndim - 1
    k_hd, v_pos = (row, col) if kv_transposed else (col, row)
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = k if fused else v.astype(jnp.float32)
    s = (
        jax.lax.dot_general(
            q, k, (((col,), (k_hd,)), (lead, lead)),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )  # (..., gq, block_k)
    if ksc is not None:
        # One f32 scale per column of this block: the per-vector scale
        # factors exactly OUT of the dot, applied to the small score
        # row instead of the big cache operand.
        s = s * ksc
    s = jnp.where(live, s, _NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p * vsc if vsc is not None else p
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        pv, v, (((col,), (v_pos,)), (lead, lead)),
        preferred_element_type=jnp.float32,
    )


def _init_softmax_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _decode_kernel(
    q_ref,
    k_ref,
    v_ref,
    idx_ref,
    *refs,
    block_k,
    num_kv,
    sm_scale,
    quantized,
    has_vf,
    packed=False,
):
    """One (batch, kv_head) row: stream cache blocks innermost, online
    softmax in scratch. ``q_ref`` (1, gq, hd) — gq = GQA group rows,
    sublane-padded; ``k_ref``/``v_ref`` (1, block_k, hd) int8 or native
    (``packed``: (1, block_k, hd // 2) int4 nibbles, unpacked in VMEM);
    scale tiles (1, 8, 128) f32 chunked views covering this block's
    positions row-major.
    ``idx_ref``/``vf_ref`` whole (b * kv_h,) SMEM
    vectors, this row's scalar read by ``program_id(0)`` (Mosaic
    refuses a (1,) block of a longer rank-1 array)."""
    refs = list(refs)
    ksc_ref = refs.pop(0) if quantized else None
    vsc_ref = refs.pop(0) if quantized else None
    vf_ref = refs.pop(0) if has_vf else None
    o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)
    gq = q_ref.shape[1]
    idx = idx_ref[pl.program_id(0)]
    vf = vf_ref[pl.program_id(0)] if has_vf else None

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def _step():
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (gq, block_k), 1
        )
        live = cols <= idx
        if has_vf:
            live = jnp.logical_and(live, cols >= vf)
        _attend_tile(
            q_ref[0], k_ref[0], v_ref[0],
            ksc_ref[0].reshape(1, block_k) if quantized else None,
            vsc_ref[0].reshape(1, block_k) if quantized else None,
            live, m_scr, l_scr, acc_scr, sm_scale, packed,
        )

    # Blocks entirely past the write index (the still-dead cache tail)
    # or entirely inside ragged left padding contribute nothing.
    live_block = j * block_k <= idx
    if has_vf:
        live_block = jnp.logical_and(live_block, (j + 1) * block_k > vf)
    pl.when(live_block)(_step)

    @pl.when(j == num_kv - 1)
    def _emit():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def _decode_split_kernel(
    q_ref,
    k_ref,
    v_ref,
    idx_ref,
    *refs,
    block_k,
    num_kv,
    bps,
    sm_scale,
    quantized,
    has_vf,
    packed=False,
):
    """Flash-decoding split variant of :func:`_decode_kernel`: grid
    (b * kv_h, split, bps) — each (row, split) streams ITS ``bps``
    cache blocks with its own online-softmax scratch and emits
    UNNORMALIZED partials (f32 accumulator + running max + denominator)
    instead of a normalized output; the caller's single-pass rescale
    combine (:func:`_combine_splits`) reduces them. Splits are
    independent, so the grid's split axis is ``parallel`` — a
    long-context row's KV stream fans across compute units instead of
    one sequential walk. The last split may be RAGGED (``split * bps >
    num_kv``): its out-of-range blocks clamp in the index maps and mask
    here, contributing nothing."""
    refs = list(refs)
    ksc_ref = refs.pop(0) if quantized else None
    vsc_ref = refs.pop(0) if quantized else None
    vf_ref = refs.pop(0) if has_vf else None
    o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    s_id = pl.program_id(1)
    j = pl.program_id(2)
    jg = s_id * bps + j  # global block index
    gq = q_ref.shape[1]
    idx = idx_ref[pl.program_id(0)]
    vf = vf_ref[pl.program_id(0)] if has_vf else None

    @pl.when(j == 0)
    def _init():
        _init_softmax_scratch(m_scr, l_scr, acc_scr)

    def _step():
        cols = jg * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (gq, block_k), 1
        )
        live = cols <= idx
        if has_vf:
            live = jnp.logical_and(live, cols >= vf)
        _attend_tile(
            q_ref[0], k_ref[0], v_ref[0],
            ksc_ref[0].reshape(1, block_k) if quantized else None,
            vsc_ref[0].reshape(1, block_k) if quantized else None,
            live, m_scr, l_scr, acc_scr, sm_scale, packed,
        )

    live_block = jnp.logical_and(jg < num_kv, jg * block_k <= idx)
    if has_vf:
        live_block = jnp.logical_and(live_block, (jg + 1) * block_k > vf)
    pl.when(live_block)(_step)

    @pl.when(j == bps - 1)
    def _emit():
        hd = o_ref.shape[-1]
        o_ref[0, 0] = acc_scr[...]
        # m/l broadcast across the lane axis so the partial outputs
        # share the accumulator's (gq, hd) tiling; the combine reads
        # lane 0.
        m_ref[0, 0] = jnp.broadcast_to(m_scr[...], (gq, hd))
        l_ref[0, 0] = jnp.broadcast_to(l_scr[...], (gq, hd))


def _combine_splits(o_parts, m_parts, l_parts, out_dtype):
    """Single-pass rescale combine of flash-decoding split partials:
    ``o`` (rows, split, gq, hd) unnormalized f32 accumulators, ``m``/
    ``l`` running max / denominator broadcast over the lane axis (lane
    0 read). A split whose every block was dead carries (m = -inf,
    l = 0) and contributes nothing; an all-dead row emits finite
    garbage (0) exactly like the unsplit kernel's ``acc / max(l,
    eps)``."""
    m = m_parts[..., :1]  # (rows, split, gq, 1)
    l = l_parts[..., :1]
    m_star = jnp.max(m, axis=1, keepdims=True)
    alpha = jnp.exp(m - m_star)
    denom = jnp.sum(l * alpha, axis=1)  # (rows, gq, 1)
    out = jnp.sum(o_parts * alpha, axis=1)
    return (out / jnp.maximum(denom, 1e-30)).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "split"))
def _decode_impl(q, k_vals, v_vals, k_scales, v_scales, index, valid_from,
                 block_k, split=1):
    b, kvh, g, hd = q.shape
    cache_len = k_vals.shape[2]
    hdk = k_vals.shape[3]  # head_dim // 2 for packed int4 pools
    num_kv = cache_len // block_k
    quantized = k_scales is not None
    packed = quantized and hdk * 2 == hd
    has_vf = valid_from is not None
    pad_g = (-g) % 8  # sublane-pad the query rows
    if pad_g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_g), (0, 0)))
    gq = g + pad_g

    qf = q.reshape(b * kvh, gq, hd)
    kf = k_vals.reshape(b * kvh, cache_len, hdk)
    vf = v_vals.reshape(b * kvh, cache_len, hdk)
    idx = jnp.repeat(
        jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
        kvh,
    )
    sm_scale = 1.0 / (hd ** 0.5)
    bps = -(-num_kv // split)  # blocks per split; last split may be ragged

    def blk(bh, *js):
        # Global block index from the (possibly split) grid point,
        # clamped for the ragged tail (masked in-kernel).
        if split == 1:
            (j,) = js
            return j
        s_id, j = js
        return jnp.minimum(s_id * bps + j, num_kv - 1)

    def row_map(bh, *js):
        del js
        return (bh, 0, 0)

    def kv_map(bh, *js):
        return (bh, blk(bh, *js), 0)

    in_specs = [
        pl.BlockSpec((1, gq, hd), row_map, memory_space=_VMEM),
        pl.BlockSpec((1, block_k, hdk), kv_map, memory_space=_VMEM),
        pl.BlockSpec((1, block_k, hdk), kv_map, memory_space=_VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qf, kf, vf, idx]
    if quantized:
        # (b, kvh, L, 1) f32 -> (b*kvh, L/128, 128) chunked view: the
        # same bytes row-major (position = row*128 + lane), one (1, 8,
        # 128) tile per 1024-position block — no broadcast inflation.
        chunk = lambda s: s.reshape(b * kvh, cache_len // 128, 128)
        rows_per_block = block_k // 128
        for s in (k_scales, v_scales):
            operands.append(chunk(s.astype(jnp.float32)))
            in_specs.append(
                pl.BlockSpec(
                    (1, rows_per_block, 128), kv_map, memory_space=_VMEM
                )
            )
    if has_vf:
        operands.append(jnp.repeat(jnp.asarray(valid_from, jnp.int32), kvh))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    scratch = [
        pltpu.VMEM((gq, 1), jnp.float32),
        pltpu.VMEM((gq, 1), jnp.float32),
        pltpu.VMEM((gq, hd), jnp.float32),
    ]
    if split == 1:
        out = pl.pallas_call(
            functools.partial(
                _decode_kernel,
                block_k=block_k,
                num_kv=num_kv,
                sm_scale=sm_scale,
                quantized=quantized,
                has_vf=has_vf,
                packed=packed,
            ),
            grid=(b * kvh, num_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, gq, hd), row_map, memory_space=_VMEM
            ),
            out_shape=jax.ShapeDtypeStruct((b * kvh, gq, hd), q.dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=pallas_interpret(),
        )(*operands)
        return out.reshape(b, kvh, gq, hd)[:, :, :g, :]

    # Flash-decoding split: (row, split) partials + single-pass rescale.
    def part_map(bh, s_id, j):
        del j
        return (bh, s_id, 0, 0)

    o_p, m_p, l_p = pl.pallas_call(
        functools.partial(
            _decode_split_kernel,
            block_k=block_k,
            num_kv=num_kv,
            bps=bps,
            sm_scale=sm_scale,
            quantized=quantized,
            has_vf=has_vf,
            packed=packed,
        ),
        grid=(b * kvh, split, bps),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, gq, hd), part_map, memory_space=_VMEM),
            pl.BlockSpec((1, 1, gq, hd), part_map, memory_space=_VMEM),
            pl.BlockSpec((1, 1, gq, hd), part_map, memory_space=_VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * kvh, split, gq, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * kvh, split, gq, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * kvh, split, gq, hd), jnp.float32),
        ),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
    )(*operands)
    out = _combine_splits(o_p, m_p, l_p, q.dtype)
    return out.reshape(b, kvh, gq, hd)[:, :, :g, :]


def append_kv(cache, new, index):
    """Multi-token-per-slot cache append — THE cached-decode write
    primitive, shared by single-token ``decode_step`` (K == 1) and the
    speculative verify paths (K == draft_k + 1).

    cache (b, h, L, hd); new (b, h, K, hd); ``index`` scalar (whole
    batch writes at one position — the ``generate()`` lockstep and the
    single-request verify) or (b,) (each ROW writes its K tokens at its
    own position — batched speculation, where slots desynchronize; a
    vmapped ``dynamic_update_slice``, one fused scatter under XLA, not
    b copies). XLA clamps the start index, so callers must reserve
    K - 1 slack positions past the largest live index (the trash-slack
    discipline ``runtime/continuous`` and ``models/speculative`` cache
    allocations follow) — a clamped garbage write lands in masked space
    instead of silently shifting onto live positions."""
    if jnp.ndim(index):
        return jax.vmap(
            lambda c, n, i: lax.dynamic_update_slice(c, n, (0, i, 0))
        )(cache, new, index)
    return lax.dynamic_update_slice(cache, new, (0, 0, index, 0))


def verify_attention(q, cache_k, cache_v, index, chunk: int, window=None,
                     tree_tail: int = 0):
    """Multi-token VERIFY attention: K chunk rows per slot, each
    attending the cache up to its OWN position — the speculative-decode
    primitive (K causal logits for one weight stream).

    q (b, kv_h, g*chunk, hd) group-folded with K-major rows (row =
    member*chunk + t, ``CausalSelfAttention._group_q`` on a (b, h, K,
    hd) query); caches (b, kv_h, L, hd) with the chunk's K/V already
    appended (``append_kv``); ``index`` scalar or (b,) is the cache
    position of chunk token 0, so row t's live window is
    ``col <= index + t`` (banded below by ``window`` when set). A
    negative per-row index marks a DEAD row (idle slot): every position
    masks out and the output is finite garbage nothing reads — the same
    discipline as the batcher's trash slot.

    Caches may be ``(int8 values, f32 scales)`` pairs — the SAME
    quantized layout (and the same score/probability-column scale
    application, in the same op order) as
    ``decode_attention_reference``, so a quantized verify chunk's K
    logits equal what K sequential quantized ``decode_step`` calls
    produce: the speculative-verify path over an int8 cache.

    ``tree_tail`` = w > 0 marks the chunk's LAST w rows as TREE LEAVES
    (grouped draft proposals sharing the chain prefix — speculative
    tree drafts): leaf row r attends the whole chain (cols <= index +
    chain, chain = chunk - 1 - w) PLUS its own physical slot (col ==
    index + r) and nothing of its siblings, so one verify pass scores
    every leaf candidate for logical position chain + 1 at once. Chain
    rows keep the ordinary per-row diagonal (their own slot is inside
    it).

    The einsum schedule is ``decode_attention_reference``'s with a
    per-row diagonal instead of a shared newest position; XLA-only for
    now (``decode_kernel_wins`` rules the streaming kernel out
    everywhere until its hardware A/B lands, and verify amortizes the
    cache stream over K rows already)."""
    quantized = isinstance(cache_k, tuple)
    sm = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    if quantized:
        (kvl, ksc), (vvl, vsc) = cache_k, cache_v
        check_head_parity(q.shape[1], kvl.shape[1])
        if kvl.shape[-1] * 2 == q.shape[-1]:  # packed int4 nibbles
            kvl, vvl = unpack_int4(kvl), unpack_int4(vvl)
        # Scales factor OUT of the per-vector dot: apply them to the
        # score columns in decode_attention_reference's exact op order,
        # so per-row values match the sequential quantized decode.
        s = jnp.einsum(
            "bhqd,bhkd->bhqk",
            q.astype(jnp.float32),
            kvl.astype(jnp.float32),
        ) * jnp.swapaxes(ksc, 2, 3) * sm
        n_pos = kvl.shape[2]
    else:
        check_head_parity(q.shape[1], cache_k.shape[1])
        s = (
            jnp.einsum(
                "bhqd,bhkd->bhqk",
                q.astype(jnp.float32),
                cache_k.astype(jnp.float32),
            )
            * sm
        )  # (b, kv_h, g*chunk, L)
        n_pos = cache_k.shape[2]
    cols = jnp.arange(n_pos)
    rows = jnp.arange(q.shape[2]) % chunk  # row -> chunk position t
    # Tree leaves attend up to the CHAIN edge (depth), chain rows up to
    # their own diagonal; every row's own physical slot is always live
    # (for chain rows it already is — own <= edge).
    depth = (
        jnp.minimum(rows, chunk - 1 - tree_tail) if tree_tail else rows
    )
    if jnp.ndim(index):
        edge = index[:, None, None] + depth[None, :, None]  # (b, g*K, 1)
        live = cols[None, None, :] <= edge
        if window is not None:
            live = live & (cols[None, None, :] > edge - window)
        if tree_tail:
            own = index[:, None, None] + rows[None, :, None]
            live = live | (cols[None, None, :] == own)
        s = jnp.where(live[:, None], s, _NEG_INF)
    else:
        edge = index + depth[:, None]  # (g*K, 1)
        live = cols[None, :] <= edge
        if window is not None:
            live = live & (cols[None, :] > edge - window)
        if tree_tail:
            own = index + rows[:, None]
            live = live | (cols[None, :] == own)
        s = jnp.where(live[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if quantized:
        o = jnp.einsum(
            "bhqk,bhkd->bhqd",
            p * jnp.swapaxes(vsc, 2, 3),
            vvl.astype(jnp.float32),
        )
    else:
        o = jnp.einsum(
            "bhqk,bhkd->bhqd", p, cache_v.astype(jnp.float32)
        )
    return o.astype(q.dtype)


def decode_attention_reference(q, cache_k, cache_v, index, valid_from=None):
    """The XLA oracle — the exact einsum schedule ``decode_step`` has
    always run (f32 scores, position mask over the full buffer, scales
    applied to the score/probability rows for int8 caches), lifted here
    so both paths share one definition.

    q: (b, kv_h, g, hd) group-folded queries; caches (b, kv_h, L, hd)
    arrays or ``(int8 values, f32 scales)`` pairs; ``index`` scalar or
    (b,); returns (b, kv_h, g, hd) in q's dtype."""
    quantized = isinstance(cache_k, tuple)
    sm = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    if quantized:
        (kvl, ksc), (vvl, vsc) = cache_k, cache_v
        if kvl.shape[-1] * 2 == q.shape[-1]:  # packed int4 nibbles
            kvl, vvl = unpack_int4(kvl), unpack_int4(vvl)
        s = jnp.einsum(
            "bhqd,bhkd->bhqk",
            q.astype(jnp.float32),
            kvl.astype(jnp.float32),
        ) * jnp.swapaxes(ksc, 2, 3) * sm
        n_pos = kvl.shape[2]
    else:
        s = (
            jnp.einsum(
                "bhqd,bhkd->bhqk",
                q.astype(jnp.float32),
                cache_k.astype(jnp.float32),
            )
            * sm
        )
        n_pos = cache_k.shape[2]
    positions = jnp.arange(n_pos)
    live = positions[None, :] <= (
        index[:, None] if jnp.ndim(index) else index
    )
    if valid_from is not None:
        live = live & (positions[None, :] >= valid_from[:, None])
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if quantized:
        o = jnp.einsum(
            "bhqk,bhkd->bhqd",
            p * jnp.swapaxes(vsc, 2, 3),
            vvl.astype(jnp.float32),
        )
    else:
        o = jnp.einsum(
            "bhqk,bhkd->bhqd", p, cache_v.astype(jnp.float32)
        )
    return o.astype(q.dtype)


def decode_attention(
    q: jax.Array,
    cache_k,
    cache_v,
    index,
    valid_from=None,
    prefer: str | None = None,
    block_k: int | None = None,
    split: int | None = None,
) -> jax.Array:
    """Cached decode attention over the live window ``[valid_from,
    index]`` of a KV cache.

    q: (b, kv_h, g, hd) — GQA groups already folded into query rows
    (``CausalSelfAttention._group_q``; g = heads//kv_h x tokens).
    Caches: (b, kv_h, L, hd) arrays, or ``(int8 values, f32 scales)``
    pairs with one scale per cached vector. ``index`` (scalar or (b,))
    is the newest live position — the caller has already written this
    step's K/V there. Returns (b, kv_h, g, hd).

    ``prefer``: None = auto (``decode_kernel_wins``, the measured rule),
    ``"xla"`` = the einsum oracle, ``"pallas"`` = the streaming kernel
    (raises when L doesn't divide into supported blocks: native caches
    need L % 256 == 0, int8 caches L % 1024 == 0 — the scale-tile
    layout). ``block_k`` None picks the
    largest supported block (``default_block_k``). ``split`` is the
    flash-decoding KV-length split factor: None auto-derives
    (``default_decode_split`` of the block count on real TPUs; 1
    off-TPU, where the interpreter gains nothing from fan-out), 1 runs
    the original single-stream kernel bit-exactly, > 1 fans the cache
    stream across independent grid splits with a single-pass rescale
    combine. Caches may also be PACKED int4 pairs (values
    ``head_dim // 2`` wide — ``ops.quantize.quantize_kv_vectors(...,
    "int4")``); the kernels unpack nibbles in VMEM so the HBM stream
    stays 4-bit. Every grid/fold/block
    derives from the shapes GIVEN — the per-shard head count under
    tensor parallelism — so a q/cache head mismatch fails loud
    (``check_head_parity``)."""
    quantized = isinstance(cache_k, tuple)
    check_head_parity(
        q.shape[1], (cache_k[0] if quantized else cache_k).shape[1]
    )
    cache_len = (cache_k[0] if quantized else cache_k).shape[2]
    if block_k is None:
        block_k = default_block_k(cache_len, quantized)
    unsupported = None
    if not _supported(cache_len, block_k, quantized):
        unsupported = (
            f"cache_len {cache_len} does not divide into block_k "
            f"{block_k} (native caches need a multiple of "
            f"{_MIN_NATIVE_BLOCK_K}, quantized caches of {DECODE_BLOCK_K})"
        )
    elif quantized and on_tpu() and cache_k[0].shape[-1] * 2 == q.shape[-1]:
        # Measured on a v5e (paged twins, same tile body): ROADMAP A1.
        unsupported = (
            "int4 caches: the in-VMEM nibble unpack exceeds Mosaic's "
            "scoped VMEM limit on a TPU"
        )
    if resolve_prefer(
        "decode", prefer, unsupported,
        decode_kernel_wins(cache_len, quantized),
    ):
        split = resolve_decode_split(cache_len // block_k, split)
        if quantized:
            (kvl, ksc), (vvl, vsc) = cache_k, cache_v
            return _decode_impl(
                q, kvl, vvl, ksc, vsc, index, valid_from, block_k, split
            )
        return _decode_impl(
            q, cache_k, cache_v, None, None, index, valid_from, block_k,
            split,
        )
    return decode_attention_reference(
        q, cache_k, cache_v, index, valid_from
    )
