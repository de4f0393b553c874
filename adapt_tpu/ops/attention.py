"""Fused blockwise (flash-style) attention as a Pallas TPU kernel.

Not in the reference (SURVEY.md §2.2: CNN-only, no attention anywhere) but
first-class here: the attention entry point for the ViT workload
(BASELINE.md config 5), the decoder LM, and ring attention's opt-in
long-shard block compute. Dispatch between this kernel and XLA's fused
attention is *measured* (see ``FLASH_SCORE_BYTES_BUDGET`` below): XLA
wins while scores fit, the kernel exists for the long-context regime —
scores live in VMEM one (block_q, block_k) tile at a time with
online-softmax accumulation, so memory is O(S * D) instead of O(S^2) and
the matmuls stay on the MXU.

Grid: (batch*heads, S/block_q, S/block_k), k innermost. Each program
holds ONE q tile and ONE K/V tile in VMEM; K/V stream from HBM block by
block while the running (max, denom, acc) online-softmax state persists
in VMEM scratch — O(block) VMEM at any sequence length. The backward is
the same discipline in reverse: two streaming passes (dQ, then dK/dV)
recompute score blocks against the saved row logsumexp.

Off-TPU the kernel runs through the Pallas interpreter, so tests on the
virtual CPU mesh exercise the same code path; ``attention_reference`` is
the jnp oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapt_tpu.ops.dispatch import pallas_interpret, record_kernel_dispatch

_VMEM = pltpu.VMEM
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30

#: Measured dispatch budget (real v5e chip, artifacts
#: benchmarks/results/r03/attn_longseq.json and the
#: end-to-end ViT A/B in tpu_vit_b16_ab.json): XLA's fused attention
#: beats the Pallas kernel while the materialized f32 score tensor
#: (batch*heads*s_q*s_k*4 bytes) is small — end-to-end ViT-B/16 ran 1.9x
#: faster through XLA (3,360 vs 1,781 img/s) — but score memory grows
#: O(S^2): at 2 GiB+ it crowds out everything else in 16 GiB HBM (and at
#: s=32k, 51.5 GiB, XLA simply OOMs) while the streaming kernel stays
#: O(S*D). Past the budget the throughput data is NON-monotonic, not a
#: clean crossover: attn_longseq.json has flash 5% faster at
#: (1, 12, 8192) = 3 GiB scores but XLA 24% faster again at 16384 =
#: 12 GiB. The dispatch keys on capacity, not that noisy margin: a
#: 12 GiB transient score tensor in 16 GiB HBM leaves nothing for
#: weights/caches/activations in a real serving process (the standalone
#: sweep that survives it has the chip to itself), so past ~2 GiB the
#: O(S*D) kernel wins on headroom even where XLA wins the sweep.
#: ``prefer=`` overrides when sweep throughput is all that matters.
FLASH_SCORE_BYTES_BUDGET = 2 << 30

#: Absolute guard on top of the byte budget: at or past this key length
#: the kernel is used regardless of batch (a tiny-batch long sequence can
#: sneak under the byte budget while still being the regime XLA handles
#: worst).
FLASH_MIN_SEQ = 32768


def scores_over_budget(q_shape, k_shape) -> bool:
    """THE dispatch predicate, shared by forward dispatch, the backward
    branch choice, and ring attention's block_impl="auto" — one place to
    retune so the three can't drift apart. True -> the materialized f32
    score tensor is past the measured budget (or the absolute length
    guard) and the streaming kernel is the right path."""
    b, h, s_q, _ = q_shape
    s_k = k_shape[2]
    return (
        b * h * s_q * s_k * 4 > FLASH_SCORE_BYTES_BUDGET
        or s_k >= FLASH_MIN_SEQ
    )


def _oracle_shape(q_shape, k_shape, causal, block_k) -> bool:
    """The one shape class the kernel itself refuses (mirrors
    ``_flash_impl``'s fallback): causal ragged-key cross-attention,
    where absolute-position masking over padded interiors is
    ill-defined."""
    s_q, s_k = q_shape[2], k_shape[2]
    bk = min(block_k, max(s_k, 8))
    return bool(causal and ((-s_k) % bk) and s_q != s_k)


def _attn_kernel(
    q_ref,
    k_ref,
    v_ref,
    *refs,
    block_k,
    num_kv,
    causal,
    sm_scale,
    valid_k,
    has_vf=False,
    has_shift=False,
    window=None,
):
    """Grid = (batch*heads, q_blocks, k_blocks); the k dimension is the
    innermost (sequential) axis, so only ONE (block_q, d) q tile and ONE
    (block_k, d) K/V tile are VMEM-resident at a time — K/V stream from
    HBM block by block and the online-softmax state (running max, denom,
    accumulator) persists across k steps in VMEM scratch. Per-program
    VMEM is O(block_q * (d + block_k)) regardless of sequence length,
    which is what lets the kernel run 32k+ sequences that OOM both the
    naive full-K/V-in-VMEM layout (scoped-vmem) and XLA's materialized
    S x S scores (HBM) — measured in
    benchmarks/results/r03/attn_longseq.json.

    ``has_vf``: an extra per-(batch, head) scalar input ``vf`` (SMEM)
    masks keys at positions < vf — ragged LEFT padding (the LM's masked
    prefill), so ragged batches stay on the streaming path at long S
    instead of falling back to the materialized oracle. Key blocks
    entirely inside the padding skip their compute.

    ``has_shift``: a traced SMEM scalar offsets the causal diagonal —
    row i attends cols <= i - shift. Striped ring attention's per-step
    blocks (``parallel/ring_attention.py`` layout="striped") are exactly
    shift-0/shift-1 triangles, so every ring step runs this kernel's
    causal skip path instead of an SPMD ``lax.cond`` that computes dead
    blocks anyway."""
    refs = list(refs)
    vf_ref = refs.pop(0) if has_vf else None
    shift_ref = refs.pop(0) if has_shift else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    q_start = pl.program_id(1) * block_q
    # Whole-array SMEM vectors: this (batch, head) row's scalar is read
    # by program_id (Mosaic refuses a (1,) block of a longer rank-1
    # array).
    vf = vf_ref[pl.program_id(0)] if has_vf else None
    shift = shift_ref[0] if has_shift else 0

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # (block_q, block_k)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if valid_k != num_kv * block_k:
            # Ragged tail: keys beyond the true sequence are zero padding
            # (ViT's 197 = 14^2 + CLS is the canonical offender) — mask
            # them out of the softmax like causal masks the future.
            s = jnp.where(cols < valid_k, s, _NEG_INF)
        if has_vf:
            # Ragged head: keys before this row's first real token are
            # left padding.
            s = jnp.where(cols >= vf, s, _NEG_INF)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(rows >= cols + shift, s, _NEG_INF)
            if window is not None:
                # Sliding band: row i attends cols in (i - window, i].
                s = jnp.where(cols > rows - window, s, _NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    # K blocks strictly after this q block (causal), entirely inside the
    # left padding (vf), or entirely behind every row's sliding window
    # contribute nothing — skip their compute entirely (the DMA still
    # lands, the MXU stays idle).
    live = None
    if causal:
        live = j * block_k + shift <= q_start + block_q - 1
        if window is not None:
            # Lowest row's band floor: cols <= q_start - window are dead
            # for every row in the tile.
            live = jnp.logical_and(
                live, (j + 1) * block_k - 1 > q_start - window
            )
    if has_vf:
        past_pad = (j + 1) * block_k > vf
        live = past_pad if live is None else jnp.logical_and(live, past_pad)
    if live is not None:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(j == num_kv - 1)
    def _emit():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)
        # Per-row logsumexp — the O(S) softmax residual the streaming
        # backward recomputes scores against (saving it is what lets the
        # backward stay O(S*D) instead of keeping S x S probabilities).
        # Stored 8-row-broadcast: TPU lowering needs the last two block
        # dims divisible by (8, 128), so the row vector rides in a
        # (1, 8, block_q) tile (row 0 is read back; x8 on an O(S) tensor
        # is noise next to the O(S*D) tensors).
        lse = (
            m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        ).reshape(1, 1, -1)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    prefer: str | None = None,
    valid_from: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Fused attention over (batch, heads, seq, head_dim) tensors.

    Dispatch is perf-measured, not dogmatic: while the materialized
    f32 score tensor stays under ``FLASH_SCORE_BYTES_BUDGET`` the XLA
    path wins on the real chip (end-to-end ViT-B/16: 1.9x — artifacts
    ``benchmarks/results/r03/attn_longseq.json`` / ``tpu_vit_b16_ab``);
    past it the streaming Pallas kernel takes over — O(S*D) HBM and
    O(block) VMEM, serving 32k+ sequences where XLA's scores exceed HBM
    outright. ``prefer="pallas"`` or ``"xla"`` forces a path (tests, the
    SP block compute, and the sweeps themselves use this).

    Differentiable at every length: sub-budget shapes recompute the
    backward through the jnp oracle (one materialized pass — fastest
    where scores fit), super-budget shapes run the streaming Pallas
    backward (two passes, dQ then dK/dV, recomputing score blocks
    against the saved row logsumexp) — O(S*D) HBM either direction, so
    long-context gradients survive where a materialized recompute OOMs.

    Non-block-divisible sequence lengths (ViT's 197) run the kernel via
    internal zero-padding with key masking; the only oracle fallback left
    is causal ragged-key cross-attention (s_q != s_k), where
    absolute-position masking over padded interiors is ill-defined.

    ``valid_from`` (b,) masks each row's keys at positions < its value —
    ragged LEFT padding (the LM's masked prefill). The kernel carries the
    mask as a per-(batch, head) SMEM scalar, so ragged batches ride the
    same measured dispatch as dense ones (kernel at long S where the
    materialized oracle would OOM). Fully-padded query rows (position
    < vf) have UNSPECIFIED contents — zeros when every k-block was
    skipped, a uniform V average when the row shares a k-block with live
    keys (which is also what the oracle emits) — no caller may read
    them; valid rows match the oracle exactly.

    ``window`` (requires ``causal``, no ``causal_shift``) bands the
    mask Mistral-style — row i attends (i - window, i] — in BOTH
    directions: the streaming forward and backward mask and
    compute-skip blocks outside the band, so a long windowed prefill
    streams O(S*D) instead of materializing O(S^2) scores.
    """
    if prefer not in (None, "pallas", "xla"):
        raise ValueError(
            f"prefer={prefer!r}: expected None, 'pallas' or 'xla'"
        )
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if prefer is None:
        prefer = "pallas" if scores_over_budget(q.shape, k.shape) else "xla"
    record_kernel_dispatch("flash", prefer)
    if prefer == "xla":
        return attention_reference(
            q, k, v, causal=causal, valid_from=valid_from, window=window
        )
    if valid_from is None:
        return _flash_vjp(q, k, v, causal, block_q, block_k, window)
    return _flash_ragged_vjp(
        q, k, v, jnp.asarray(valid_from, jnp.int32), causal, block_q,
        block_k, window,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, block_q, block_k, window=None):
    return _flash_impl(q, k, v, causal, block_q, block_k, window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_ragged_vjp(q, k, v, valid_from, causal, block_q, block_k,
                      window=None):
    """valid_from travels as a regular (traced) operand — custom_vjp
    nondiff_argnums may not hold tracers, and the bwd returns None for
    its (integer, gradient-free) cotangent."""
    return _flash_impl(
        q, k, v, causal, block_q, block_k, valid_from=valid_from,
        window=window,
    )


def _flash_ragged_fwd(q, k, v, valid_from, causal, block_q, block_k,
                      window=None):
    if _bwd_streams(q.shape, k.shape, causal, block_q, block_k):
        out, lse = _flash_impl(
            q, k, v, causal, block_q, block_k,
            with_lse=True, valid_from=valid_from, window=window,
        )
        return out, (q, k, v, valid_from, out, lse)
    out = _flash_impl(
        q, k, v, causal, block_q, block_k, valid_from=valid_from,
        window=window,
    )
    return out, (q, k, v, valid_from, None, None)


def _flash_ragged_bwd(causal, block_q, block_k, window, residuals, do):
    q, k, v, valid_from, out, lse = residuals
    if out is None:  # materialized-recompute branch (scores fit)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=causal, valid_from=valid_from,
                window=window,
            ),
            q,
            k,
            v,
        )
        return (*vjp(do), None)
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, out, lse, do,
        causal=causal, block_q=block_q, block_k=block_k,
        valid_from=valid_from, window=window,
    )
    return dq, dk, dv, None


_flash_ragged_vjp.defvjp(_flash_ragged_fwd, _flash_ragged_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    causal_shift: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming-kernel attention returning ``(out, lse)`` where ``lse``
    is the per-row logsumexp of the scaled scores, shape (b, h, s_q),
    f32. The lse is what lets partial attention results merge exactly:
    given per-key-block ``(o_j, lse_j)``, the blockwise combine

        m = max(lse_a, lse_b)
        o = (o_a * exp(lse_a - m) + o_b * exp(lse_b - m))
            / (exp(lse_a - m) + exp(lse_b - m))
        lse = m + log(exp(lse_a - m) + exp(lse_b - m))

    reproduces full-softmax attention — the contract ring attention's
    flash block path builds on (``parallel/ring_attention.py``).

    Forward-only: this entry point bypasses the custom-VJP wrapper (an
    lse output would need its own streaming VJP); differentiating
    through it fails at the pallas_call. Use :func:`flash_attention` for
    training paths.

    ``causal_shift`` (traced int scalar, requires ``causal=True``)
    offsets the diagonal: row i attends cols <= i - shift. Rows with no
    live key (i < shift) emit ``lse ~= -inf`` with UNSPECIFIED out
    contents — the merge weight ``exp(lse - m)`` zeroes them, which is
    the neutral element striped ring attention's shift-1 steps rely on.
    """
    return _flash_impl(
        q, k, v, causal, block_q, block_k, with_lse=True,
        causal_shift=causal_shift,
    )


def _bwd_streams(q_shape, k_shape, causal, block_q, block_k) -> bool:
    """Static decision (shapes only) shared by fwd and bwd: does the
    backward run the streaming Pallas passes? False -> one materialized
    jnp-oracle recompute, which is faster wherever scores fit and is the
    only option on the causal ragged-cross-attention shape the forward
    itself oracles."""
    return scores_over_budget(q_shape, k_shape) and not _oracle_shape(
        q_shape, k_shape, causal, block_k
    )


def _flash_fwd(q, k, v, causal, block_q, block_k, window=None):
    # Save the O(S) logsumexp (and keep `out` alive) only when the
    # backward will actually stream; the oracle branch re-derives
    # everything from (q, k, v).
    if _bwd_streams(q.shape, k.shape, causal, block_q, block_k):
        out, lse = _flash_impl(
            q, k, v, causal, block_q, block_k, with_lse=True,
            window=window,
        )
        return out, (q, k, v, out, lse)
    out = _flash_impl(q, k, v, causal, block_q, block_k, window=window)
    return out, (q, k, v, None, None)


def _flash_bwd(causal, block_q, block_k, window, residuals, do):
    q, k, v, out, lse = residuals
    if out is None:  # fwd decided on the materialized-recompute branch
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=causal, window=window
            ),
            q,
            k,
            v,
        )
        return vjp(do)
    return _flash_bwd_impl(
        q, k, v, out, lse, do,
        causal=causal, block_q=block_q, block_k=block_k, window=window,
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "with_lse", "window"),
)
def _flash_impl(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    with_lse: bool = False,
    valid_from: jax.Array | None = None,
    causal_shift: jax.Array | None = None,
    window: int | None = None,
):
    if causal_shift is not None and not causal:
        raise ValueError("causal_shift requires causal=True")
    if window is not None and (not causal or causal_shift is not None):
        raise ValueError("window requires causal=True without causal_shift")
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    block_q = min(block_q, max(s_q, 8))
    block_k = min(block_k, max(s_k, 8))
    # Ragged sequences (ViT's 197) are zero-padded up to whole blocks;
    # padded KEY positions are masked inside the kernel (valid_k), padded
    # QUERY rows compute garbage that is sliced off below. Only degenerate
    # cross-attention raggedness under causal falls back to the oracle
    # (absolute-position masking with padded interior is ill-defined).
    pad_q = (-s_q) % block_q
    pad_k = (-s_k) % block_k
    if causal and pad_k and s_q != s_k:
        return (
            _reference_with_lse(q, k, v, causal, valid_from, causal_shift,
                                window)
            if with_lse
            else attention_reference(
                q, k, v, causal=causal, valid_from=valid_from,
                causal_shift=causal_shift, window=window,
            )
        )
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    sm_scale = 1.0 / math.sqrt(d)
    sp_q, sp_k = s_q + pad_q, s_k + pad_k
    num_kv = sp_k // block_k
    qf = q.reshape(b * h, sp_q, d)
    kf = k.reshape(b * h, sp_k, d)
    vf = v.reshape(b * h, sp_k, d)
    kernel = functools.partial(
        _attn_kernel,
        block_k=block_k,
        num_kv=num_kv,
        causal=causal,
        sm_scale=sm_scale,
        valid_k=s_k,
        has_vf=valid_from is not None,
        has_shift=causal_shift is not None,
        window=window,
    )
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec(
            (1, block_q, d),
            lambda bh, qi, kj: (bh, qi, 0),
            memory_space=_VMEM,
        ),
        pl.BlockSpec(
            (1, block_k, d),
            lambda bh, qi, kj: (bh, kj, 0),
            memory_space=_VMEM,
        ),
        pl.BlockSpec(
            (1, block_k, d),
            lambda bh, qi, kj: (bh, kj, 0),
            memory_space=_VMEM,
        ),
    ]
    operands = [qf, kf, vf]
    if valid_from is not None:
        # Per-(batch, head) left-pad scalars ride whole in SMEM.
        operands.append(
            jnp.repeat(jnp.asarray(valid_from, jnp.int32), h)
        )
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if causal_shift is not None:
        # One global diagonal-offset scalar in SMEM (traced: striped
        # ring varies it per step without recompiling).
        operands.append(
            jnp.reshape(jnp.asarray(causal_shift, jnp.int32), (1,))
        )
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out, lse = pl.pallas_call(
        kernel,
        # K/V stream one block per innermost grid step; scratch carries
        # the online-softmax state across them (TPU grids iterate
        # sequentially, innermost-fastest, so the state is coherent).
        grid=(b * h, sp_q // block_q, num_kv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, block_q, d),
                lambda bh, qi, kj: (bh, qi, 0),
                memory_space=_VMEM,
            ),
            pl.BlockSpec(
                (1, 8, block_q),
                lambda bh, qi, kj: (bh, 0, qi),
                memory_space=_VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, sp_q), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(),
    )(*operands)
    out = out.reshape(b, h, sp_q, d)[:, :, :s_q, :]
    if not with_lse:
        return out
    return out, lse[:, 0, :].reshape(b, h, sp_q)[:, :, :s_q]


def _causal_mask(s_q, s_k, causal_shift=None):
    """THE oracle causal mask (row i attends cols <= i - shift) — shared
    by both reference paths so the masking convention cannot fork."""
    if causal_shift is not None:
        return (
            jnp.arange(s_q)[:, None] >= jnp.arange(s_k)[None, :] + causal_shift
        )
    return jnp.tril(jnp.ones((s_q, s_k), bool))


def _reference_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    valid_from: jax.Array | None = None,
    causal_shift: jax.Array | None = None,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Oracle-path ``(out, lse)`` computing the score matrix ONCE (the
    fallback exists because scores are expensive to materialize —
    don't pay for them twice)."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(d)
    if causal:
        s = jnp.where(_causal_mask(*s.shape[-2:], causal_shift), s, _NEG_INF)
    if window is not None:
        s_q, s_k = s.shape[-2:]
        band = (
            jnp.arange(s_k)[None, :] > jnp.arange(s_q)[:, None] - window
        )
        s = jnp.where(band[None, None], s, _NEG_INF)
    if valid_from is not None:
        cols = jnp.arange(s.shape[-1])
        live = cols[None, :] >= valid_from[:, None]
        s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )
    return out, lse


def _bwd_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    *refs,
    block_k,
    num_kv,
    causal,
    sm_scale,
    valid_k,
    has_vf=False,
    window=None,
):
    """dQ pass: grid (bh, q_blocks, k_blocks), K/V streaming innermost;
    dq accumulates in VMEM scratch. Scores recompute blockwise against
    the saved row logsumexp, so nothing S x S ever exists."""
    if has_vf:
        vf_ref, dq_ref, dq_scr = refs
    else:
        dq_ref, dq_scr = refs
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    q_start = pl.program_id(1) * block_q
    vf = vf_ref[pl.program_id(0)] if has_vf else None

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0:1, :].T  # (block_q, 1); rows 1-7 are broadcast
        delta = delta_ref[0, 0:1, :].T
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if valid_k != num_kv * block_k:
            s = jnp.where(cols < valid_k, s, _NEG_INF)
        if has_vf:
            s = jnp.where(cols >= vf, s, _NEG_INF)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(rows >= cols, s, _NEG_INF)
            if window is not None:
                s = jnp.where(cols > rows - window, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = None
    if causal:
        live = j * block_k <= q_start + block_q - 1
        if window is not None:
            live = jnp.logical_and(
                live, (j + 1) * block_k - 1 > q_start - window
            )
    if has_vf:
        past_pad = (j + 1) * block_k > vf
        live = past_pad if live is None else jnp.logical_and(live, past_pad)
    if live is not None:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(j == num_kv - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    *refs,
    block_q,
    num_q,
    causal,
    sm_scale,
    valid_k,
    sp_k,
    has_vf=False,
    window=None,
):
    """dK/dV pass: grid (bh, k_blocks, q_blocks), Q/dO streaming
    innermost; dk/dv accumulate in VMEM scratch."""
    if has_vf:
        vf_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    i = pl.program_id(2)
    block_k = k_ref.shape[1]
    k_start = pl.program_id(1) * block_k
    q_start = i * block_q
    vf = vf_ref[pl.program_id(0)] if has_vf else None

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0:1, :].T  # (block_q, 1); rows 1-7 are broadcast
        delta = delta_ref[0, 0:1, :].T
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # (block_q, block_k)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if valid_k != sp_k:
            s = jnp.where(cols < valid_k, s, _NEG_INF)
        if has_vf:
            s = jnp.where(cols >= vf, s, _NEG_INF)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(rows >= cols, s, _NEG_INF)
            if window is not None:
                s = jnp.where(cols > rows - window, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = None
    if causal:
        # Q blocks entirely before this K block see none of it.
        live = q_start + block_q - 1 >= k_start
        if window is not None:
            # Q blocks entirely past this K block's window: every row i
            # needs a col c with i < c + window.
            live = jnp.logical_and(
                live, q_start < k_start + block_k + window - 1
            )
    if has_vf:
        # A K block entirely inside the left padding gets zero gradient.
        past_pad = k_start + block_k > vf
        live = past_pad if live is None else jnp.logical_and(live, past_pad)
    if live is not None:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(i == num_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "window")
)
def _flash_bwd_impl(
    q, k, v, out, lse, do, *, causal, block_q, block_k, valid_from=None,
    window=None,
):
    """Streaming flash backward: two Pallas passes (dQ, then dK/dV), each
    recomputing score blocks against the saved logsumexp — O(S*D) HBM
    and O(block) VMEM like the forward, so gradients survive sequence
    lengths whose materialized S x S recompute OOMs
    (benchmarks/results/r03/attn_longseq.json documents the forward-side
    wall; this is the backward-side counterpart)."""
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    block_q = min(block_q, max(s_q, 8))
    block_k = min(block_k, max(s_k, 8))
    pad_q = (-s_q) % block_q
    pad_k = (-s_k) % block_k
    if valid_from is not None:
        # Ragged left padding: a fully-padded q row (position < vf) saved
        # lse ~= -1e30 (everything masked); exp(s - lse) would then be
        # exp(~0) = 1 instead of 0 and the row would pollute dK/dV. Clamp
        # so masked scores stay masked: exp(-1e30 - (-1e20)) == 0, while
        # any row with one live key has lse far above the clamp.
        lse = jnp.maximum(lse, -1e20)
    # delta_i = rowsum(dO_i * O_i): the only extra residual the backward
    # needs, O(S) — computed once outside the kernels.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        # Padded rows: zero q/do/delta make every contribution vanish;
        # lse=0 keeps exp(s - lse) finite (s is 0 there, p = 1, x 0 = 0).
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    sm_scale = 1.0 / math.sqrt(d)
    sp_q, sp_k = s_q + pad_q, s_k + pad_k
    num_q, num_kv = sp_q // block_q, sp_k // block_k
    qf = q.reshape(b * h, sp_q, d)
    kf = k.reshape(b * h, sp_k, d)
    vf = v.reshape(b * h, sp_k, d)
    dof = do.reshape(b * h, sp_q, d)
    # 8-row broadcast (TPU block-shape rule; see the forward's lse note).
    lsef = jnp.broadcast_to(
        lse.reshape(b * h, 1, sp_q), (b * h, 8, sp_q)
    )
    deltaf = jnp.broadcast_to(
        delta.reshape(b * h, 1, sp_q), (b * h, 8, sp_q)
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )
    interpret = pallas_interpret()
    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda bh, a, b_: (bh, a, 0), memory_space=_VMEM
    )
    row_spec = pl.BlockSpec(
        (1, 8, block_q), lambda bh, a, b_: (bh, 0, a), memory_space=_VMEM
    )
    kv_spec_dq = pl.BlockSpec(
        (1, block_k, d), lambda bh, a, b_: (bh, b_, 0), memory_space=_VMEM
    )
    vf_operands, vf_specs = [], []
    if valid_from is not None:
        vf_operands = [jnp.repeat(jnp.asarray(valid_from, jnp.int32), h)]
        vf_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            block_k=block_k,
            num_kv=num_kv,
            causal=causal,
            sm_scale=sm_scale,
            valid_k=s_k,
            has_vf=valid_from is not None,
            window=window,
        ),
        grid=(b * h, num_q, num_kv),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec,
                  row_spec, *vf_specs],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf, *vf_operands)

    q_spec_kv = pl.BlockSpec(
        (1, block_q, d), lambda bh, a, b_: (bh, b_, 0), memory_space=_VMEM
    )
    row_spec_kv = pl.BlockSpec(
        (1, 8, block_q), lambda bh, a, b_: (bh, 0, b_), memory_space=_VMEM
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda bh, a, b_: (bh, a, 0), memory_space=_VMEM
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            block_q=block_q,
            num_q=num_q,
            causal=causal,
            sm_scale=sm_scale,
            valid_k=s_k,
            sp_k=sp_k,
            has_vf=valid_from is not None,
            window=window,
        ),
        grid=(b * h, num_kv, num_q),
        in_specs=[
            q_spec_kv,
            kv_spec,
            kv_spec,
            q_spec_kv,
            row_spec_kv,
            row_spec_kv,
            *vf_specs,
        ],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf, *vf_operands)

    dq = dq.reshape(b, h, sp_q, d)[:, :, :s_q, :]
    dk = dk.reshape(b, h, sp_k, d)[:, :, :s_k, :]
    dv = dv.reshape(b, h, sp_k, d)[:, :, :s_k, :]
    return dq, dk, dv


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    valid_from: jax.Array | None = None,
    causal_shift: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Pure-jnp oracle: softmax(QK^T / sqrt(d)) V with optional masks.

    Causal convention (same as the kernel): query at absolute position i
    attends keys at absolute positions j <= i — top-left aligned, which is
    the identity convention for the self-attention (s_q == s_k) shapes the
    framework uses. ``valid_from`` (b,) additionally masks each row's
    keys at positions < valid_from[row] — left-padding in ragged batches
    (the LM's masked prefill). ``causal_shift`` offsets the causal
    diagonal (row i attends j <= i - shift; see
    :func:`flash_attention_with_lse`). ``window`` (requires ``causal``)
    bands the mask Mistral-style: row i attends j in
    (i - window, i] — the sliding-window LM's full-sequence forward.
    One oracle, one set of masking/precision conventions.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    d = q.shape[-1]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(d)
    if causal:
        s = jnp.where(_causal_mask(*s.shape[-2:], causal_shift), s, _NEG_INF)
    if window is not None:
        s_q, s_k = s.shape[-2:]
        band = (
            jnp.arange(s_k)[None, :]
            > jnp.arange(s_q)[:, None] - window
        )
        s = jnp.where(band[None, None], s, _NEG_INF)
    if valid_from is not None:
        cols = jnp.arange(s.shape[-1])
        live = cols[None, :] >= valid_from[:, None]  # (b, s_k)
        s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )
