"""Blockwise int8 quantization as a Pallas TPU kernel.

The TPU-native re-expression of the reference's per-hop lossy codec
(zfp+lz4 on every activation and weight crossing a socket,
``/root/reference/src/dispatcher.py:92-98``, ``src/node.py:122-125``).
On TPU the codec's job moves on-device: quantize in VMEM right before a
DCN-boundary transfer (4x smaller payload off-chip), dequantize on the
other side — ICI hops need no codec at all (SURVEY.md §2.3).

Layout: the flat tensor is viewed as (rows, 128) lanes and split into
row-blocks; each block of ``block_rows * 128`` elements gets one f32
scale (absmax / 127). Blockwise scales bound the quantization error per
block — the same locality argument zfp's 4^d blocks make.

Off-TPU (tests, CPU sim-mesh) the same kernels run through the Pallas
interpreter, so behavior is identical everywhere; ``*_reference`` are the
pure-jnp oracles used by the unit tests.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapt_tpu.ops.dispatch import pallas_interpret

_VMEM = pltpu.VMEM
_SMEM = pltpu.SMEM
LANES = 128
BLOCK_ROWS = 64  # one scale per 64*128 = 8192 elements


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """int8 payload + per-block scales + logical shape/dtype."""

    values: jax.Array  # (rows, 128) int8, padded
    scales: jax.Array  # (num_blocks, 1) f32
    shape: tuple[int, ...]
    dtype: jnp.dtype

    def tree_flatten(self):
        return (self.values, self.scales), (self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, scales = children
        shape, dtype = aux
        return cls(values, scales, shape, dtype)

    @property
    def nbytes_payload(self) -> int:
        return self.values.size + self.scales.size * 4


def _quant_kernel(x_ref, vals_ref, scale_ref):
    # scale_ref holds the FULL (num_blocks, 1) scales array in SMEM (TPU
    # tiling forbids (1, 1) VMEM blocks); each grid step writes its slot.
    amax = jnp.max(jnp.abs(x_ref[:]))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    scale_ref[pl.program_id(0), 0] = scale
    q = jnp.clip(jnp.round(x_ref[:] / scale), -127.0, 127.0)
    vals_ref[:] = q.astype(jnp.int8)


def _dequant_kernel(vals_ref, scale_ref, out_ref):
    out_ref[:] = vals_ref[:].astype(jnp.float32) * scale_ref[pl.program_id(0), 0]


def _to_rows(x: jax.Array) -> tuple[jax.Array, int]:
    """Flatten to (rows, LANES) f32, zero-padded to whole blocks."""
    flat = x.astype(jnp.float32).reshape(-1)
    block = BLOCK_ROWS * LANES
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), flat.size // block


@jax.jit
def quantize(x: jax.Array) -> QuantizedTensor:
    """Blockwise int8-quantize any-shape tensor (Pallas kernel)."""
    rows, num_blocks = _to_rows(x)
    vals, scales = pl.pallas_call(
        _quant_kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, LANES), lambda i: (i, 0), memory_space=_VMEM
            )
        ],
        out_specs=(
            pl.BlockSpec(
                (BLOCK_ROWS, LANES), lambda i: (i, 0), memory_space=_VMEM
            ),
            pl.BlockSpec(
                (num_blocks, 1), lambda i: (0, 0), memory_space=_SMEM
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(rows.shape, jnp.int8),
            jax.ShapeDtypeStruct((num_blocks, 1), jnp.float32),
        ),
        interpret=pallas_interpret(),
    )(rows)
    return QuantizedTensor(vals, scales, tuple(x.shape), x.dtype)


@jax.jit
def dequantize(qt: QuantizedTensor) -> jax.Array:
    """Inverse of :func:`quantize` (Pallas kernel).

    Deliberately NOT donated: the int8 values can never alias the f32
    output (dtype width mismatch), so donation here would be a
    per-compile XLA warning and nothing else — the decode path's real
    donation lives where buffers CAN alias (``ContinuousBatcher``'s
    caches and device-resident slot state)."""
    num_blocks = qt.scales.shape[0]
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, LANES), lambda i: (i, 0), memory_space=_VMEM
            ),
            pl.BlockSpec(
                (num_blocks, 1), lambda i: (0, 0), memory_space=_SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (BLOCK_ROWS, LANES), lambda i: (i, 0), memory_space=_VMEM
        ),
        out_shape=jax.ShapeDtypeStruct(qt.values.shape, jnp.float32),
        interpret=pallas_interpret(),
    )(qt.values, qt.scales)
    size = math.prod(qt.shape)
    return out.reshape(-1)[:size].reshape(qt.shape).astype(qt.dtype)


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack an even-width trailing axis of int values in [-8, 7] into
    int8 bytes, two NIBBLES per lane: element ``2i`` lands in the low
    nibble of byte ``i``, element ``2i + 1`` in the high nibble — the
    int4 KV pool layout (the HBM stream is half the int8 bytes).
    Returns ``(..., w // 2)`` int8."""
    q = q.astype(jnp.int32)
    lo, hi = q[..., 0::2], q[..., 1::2]
    p = (lo & 15) | ((hi & 15) << 4)
    # Explicit two's-complement wrap before the int8 cast: the packed
    # byte pattern is what matters, not its signed value.
    return jnp.where(p >= 128, p - 256, p).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: ``(..., w)`` int8 packed bytes ->
    ``(..., 2w)`` int32 nibble values in [-8, 7], interleaved back into
    element order. Pure lane arithmetic (mask / shift / stack), so the
    Pallas kernels run it in VMEM on the streamed int8 tile — the fused
    int4 dequant's unpack half."""
    p = packed.astype(jnp.int32)
    lo = ((p & 15) ^ 8) - 8  # sign-extend the low nibble
    hi = p >> 4  # arithmetic shift sign-extends the high nibble
    return jnp.stack([lo, hi], axis=-1).reshape(
        p.shape[:-1] + (p.shape[-1] * 2,)
    )


def quantize_kv_vectors(
    t: jax.Array, dtype: str = "int8"
) -> tuple[jax.Array, jax.Array]:
    """Per-vector absmax quantization over the trailing (head_dim) axis
    — THE KV-cache quantization scheme (one f32 scale per cached
    key/value vector), shared by ``CausalSelfAttention``, the
    decode-attention kernel tests and the on-chip smoke so the
    definition cannot fork.

    ``dtype="int8"`` returns ``(int8 values, f32 scales with
    keepdims)``. ``dtype="int4"`` quantizes to the 15-level [-7, 7]
    lattice and PACKS two nibbles per int8 lane (:func:`pack_int4`) —
    values ``(..., head_dim // 2)`` int8, scales unchanged — so the
    resident bytes are 4-bit while the scale plane keeps the int8
    layout (page tables, head sharding and handoff plans see the same
    pytree shape discipline)."""
    if dtype not in ("int8", "int4"):
        raise ValueError(
            f"dtype={dtype!r}: expected 'int8' or 'int4'"
        )
    qmax = 127.0 if dtype == "int8" else 7.0
    scale = (
        jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
        / qmax
    )
    scale = jnp.maximum(scale, 1e-8)
    vals = jnp.round(t.astype(jnp.float32) / scale).clip(-qmax, qmax)
    if dtype == "int4":
        if t.shape[-1] % 2:
            raise ValueError(
                f"int4 KV packing needs an even head_dim, got "
                f"{t.shape[-1]}"
            )
        return pack_int4(vals), scale
    return vals.astype(jnp.int8), scale


def quantize_params(tree):
    """int8-quantize every float MATRIX leaf (ndim >= 2) of a param
    pytree into :class:`QuantizedTensor` (the blockwise Pallas scheme
    above). 1-D leaves — biases, LayerNorm scales — stay native: they
    are O(dim) bytes (nothing to save) and their per-channel dynamic
    range is exactly where blockwise absmax hurts most. The use case is
    the speculative DRAFT model's weights
    (``SpeculativeConfig.draft_weight_dtype="int8"``): the draft
    replicates under tensor parallelism, so quantizing its resident
    weights cuts the per-chip cost of speculation ~4x (f32) while
    :func:`dequantize_params` restores f32 inside the draft programs."""

    def q(leaf):
        # leaf.dtype directly — jnp.asarray here would stage every
        # leaf (including the untouched 1-D ones) to device just to
        # read a dtype.
        if (
            hasattr(leaf, "ndim")
            and leaf.ndim >= 2
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ):
            return quantize(leaf)
        return leaf

    return jax.tree.map(q, tree)


def dequantize_params(tree):
    """Inverse of :func:`quantize_params`: dequantize every
    :class:`QuantizedTensor` leaf in place of itself, pass everything
    else through. Call INSIDE the consuming jitted program (the draft
    scan / draft prefill), so the persistent HBM residency stays int8
    and the f32 weights exist only for the program's lifetime."""
    return jax.tree.map(
        lambda l: dequantize(l) if isinstance(l, QuantizedTensor) else l,
        tree,
        is_leaf=lambda l: isinstance(l, QuantizedTensor),
    )


# -- page codec stack (hierarchical KV cache tiers, runtime/paged) -----------
#
# Host-side codecs for KV PAGES crossing a memory-hierarchy boundary:
# spills to the host-DRAM tier (``runtime/paged.HostKVTier``), readmits
# back into the pool, and the disaggregated MSG_KV_PAGES wire
# (``runtime/disagg.pack_handoff``) — the TPU-era re-expression of the
# reference's per-transfer lz4+zfp stack at page granularity. These run
# on numpy by construction: every call site already holds host bytes
# (a spilled page, a wire frame), so a device kernel would only add a
# round trip. The kernels' half of this DNA is the fused int8/int4
# dequant in ``ops/paged_attention`` — pages readmitted from a lossy
# tier flow straight back through it.
#
# Codec contract: ``decode_page(encode_page(x, c)) `` returns x's exact
# shape and dtype; "raw"/"lz" are BIT-EXACT (the WARM-tier / lossless
# wire setting), "int8"/"int4" are the repo's per-vector absmax
# schemes (one f32 scale per trailing-axis vector — the same lattice
# the quantized pools use), "zfp" is zfp-style mantissa truncation
# (keep sign/exponent/top mantissa bits, then lz the zero-heavy tail).
# Lossy codecs apply to FLOAT arrays only; on integer arrays (int8
# value planes of quantized pools, prompt ids on the wire) they
# degrade to "lz" — bit-exact — so a lossy tier can never corrupt
# already-quantized payloads.

PAGE_CODECS = ("raw", "lz", "int8", "int4", "zfp")
LOSSLESS_PAGE_CODECS = ("raw", "lz")
#: zfp-style truncation: mantissa bits KEPT (of f32's 23). 10 bits
#: bounds relative error at ~2^-11 per element — comfortably inside
#: the int8 per-vector scheme's error, and the truncated tail is what
#: makes the trailing lz pass actually save bytes.
ZFP_KEEP_BITS = 10


def _np():
    import numpy as np

    return np


def _np_pack_int4(q):
    """numpy twin of :func:`pack_int4` (same nibble layout)."""
    np = _np()
    q = q.astype(np.int32)
    lo, hi = q[..., 0::2] & 15, q[..., 1::2] & 15
    p = lo | (hi << 4)
    return np.where(p >= 128, p - 256, p).astype(np.int8)


def _np_unpack_int4(packed):
    """numpy twin of :func:`unpack_int4`."""
    np = _np()
    p = packed.astype(np.int32)
    lo = ((p & 15) ^ 8) - 8
    hi = p >> 4
    return np.stack([lo, hi], axis=-1).reshape(
        p.shape[:-1] + (p.shape[-1] * 2,)
    )


def encode_page(arr, codec: str) -> tuple[bytes, dict]:
    """Encode one host array for a tier boundary. Returns
    ``(payload, meta)``; ``meta`` carries everything
    :func:`decode_page` needs (shape, dtype, the codec actually
    applied — lossy requests on integer arrays record the "lz" they
    degraded to) plus ``raw_nbytes`` for compression accounting."""
    import zlib

    np = _np()
    if codec not in PAGE_CODECS:
        raise ValueError(
            f"codec={codec!r}: expected one of {PAGE_CODECS}"
        )
    arr = np.ascontiguousarray(arr)
    meta = {
        "shape": tuple(int(s) for s in arr.shape),
        "dtype": str(arr.dtype),
        "codec": codec,
        "raw_nbytes": int(arr.nbytes),
    }
    lossy = codec in ("int8", "int4", "zfp")
    if lossy and (
        not np.issubdtype(arr.dtype, np.floating)
        or (codec in ("int8", "int4") and arr.shape[-1] < 2)
    ):
        # Lossy on non-float degrades to lossless packing — a lossy
        # tier must never perturb already-quantized int payloads. The
        # per-vector absmax codecs also degrade on (..., 1) arrays
        # (quantized pools' SCALE planes): one f32 scale per single
        # element saves nothing and perturbs every later dequant.
        codec = "lz"
        meta["codec"] = "lz"
    if codec == "raw":
        return arr.tobytes(), meta
    if codec == "lz":
        return zlib.compress(arr.tobytes(), 1), meta
    if codec == "zfp":
        u = arr.astype(np.float32).view(np.uint32)
        mask = np.uint32(
            (0xFFFFFFFF << (23 - ZFP_KEEP_BITS)) & 0xFFFFFFFF
        )
        trunc = (u & mask).tobytes()
        return zlib.compress(trunc, 1), meta
    # int8 / int4: per-vector absmax over the trailing axis — the KV
    # quantization scheme (quantize_kv_vectors) on host numpy.
    qmax = 127.0 if codec == "int8" else 7.0
    f = arr.astype(np.float32)
    scale = np.maximum(
        np.abs(f).max(axis=-1, keepdims=True) / qmax, 1e-8
    ).astype(np.float32)
    q = np.clip(np.round(f / scale), -qmax, qmax)
    if codec == "int4":
        if arr.shape[-1] % 2:
            raise ValueError(
                f"int4 page codec needs an even trailing axis, got "
                f"{arr.shape[-1]}"
            )
        vals = _np_pack_int4(q)
    else:
        vals = q.astype(np.int8)
    return scale.tobytes() + vals.tobytes(), meta


def decode_page(payload, meta: dict):
    """Inverse of :func:`encode_page`: payload (bytes-like) + meta ->
    array of the original shape/dtype. Bit-exact for raw/lz; the lossy
    codecs return the dequantized/truncated values cast back."""
    import zlib

    np = _np()
    shape = tuple(meta["shape"])
    dtype = np.dtype(meta["dtype"])
    codec = meta["codec"]
    buf = bytes(payload)
    if codec == "raw":
        return np.frombuffer(buf, dtype).reshape(shape).copy()
    if codec == "lz":
        return (
            np.frombuffer(zlib.decompress(buf), dtype).reshape(shape).copy()
        )
    if codec == "zfp":
        u = np.frombuffer(zlib.decompress(buf), np.uint32).reshape(shape)
        return u.view(np.float32).astype(dtype)
    n_vec = 1
    for s in shape[:-1]:
        n_vec *= s
    scale = np.frombuffer(buf[: n_vec * 4], np.float32).reshape(
        shape[:-1] + (1,)
    )
    if codec == "int4":
        vals = np.frombuffer(buf[n_vec * 4:], np.int8).reshape(
            shape[:-1] + (shape[-1] // 2,)
        )
        q = _np_unpack_int4(vals)
    else:
        q = np.frombuffer(buf[n_vec * 4:], np.int8).reshape(shape)
    return (q.astype(np.float32) * scale).astype(dtype)


def page_codec_roundtrip(arr, codec: str):
    """``decode(encode(arr))`` — the one-call roundtrip tests and the
    kv_tiers micro driver pin bit-exactness (lossless) or error
    bounds (lossy) against."""
    payload, meta = encode_page(arr, codec)
    return decode_page(payload, meta)


# -- pure-jnp oracles (unit-test ground truth) -------------------------------


def quantize_reference(x: jax.Array) -> QuantizedTensor:
    rows, num_blocks = _to_rows(x)
    blocks = rows.reshape(num_blocks, -1)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / scales), -127.0, 127.0).astype(jnp.int8)
    return QuantizedTensor(
        q.reshape(rows.shape), scales, tuple(x.shape), x.dtype
    )


def dequantize_reference(qt: QuantizedTensor) -> jax.Array:
    num_blocks = qt.scales.shape[0]
    blocks = qt.values.reshape(num_blocks, -1).astype(jnp.float32)
    out = (blocks * qt.scales).reshape(-1)
    size = math.prod(qt.shape)
    return out[:size].reshape(qt.shape).astype(qt.dtype)
