"""The fixed parts of the yardstick: the table of peaks and the
functions that count a kernel's necessary bytes and operations from
its shapes. A later PR cannot edit these, so a roofline share means
the same thing in every PR.
"""

from __future__ import annotations

#: Published peaks of ONE chip: (bf16 FLOP/s, HBM bytes/s). Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s).
#: Keyed by ``jax.devices()[0].device_kind``. A device that is not
#: here is an error, never a default.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}


def peaks(device_kind: str) -> tuple[float, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            "chipbench/yardstick.PEAKS with its source"
        ) from None


def paged_decode_bytes(
    context_tokens: int, rows: int, heads: int, kv_heads: int,
    head_dim: int, itemsize: int,
) -> int:
    """Bytes one paged decode-attention call has to move: every cached
    K and V vector of every live row once, plus the query in and the
    output out. ``context_tokens`` is the sum over live rows of the
    positions each attends. Decode attention does 2 flops per byte of
    bf16 cache, far under the chip's 240 flops/byte ridge, so bytes
    are its bound."""
    kv = 2 * context_tokens * kv_heads * head_dim * itemsize
    qo = 2 * rows * heads * head_dim * itemsize
    return kv + qo


def paged_chunk_cost(
    pos0: int, chunk: int, heads: int, kv_heads: int, head_dim: int,
    itemsize: int,
) -> tuple[int, int]:
    """(flops, bytes) of one paged chunk-prefill attention call: chunk
    query rows at positions [pos0, pos0 + chunk) against the causal
    window before them. Row i sees pos0 + i + 1 keys; QK^T and PV are
    2 flops per multiply-add each."""
    keys_seen = chunk * pos0 + chunk * (chunk + 1) // 2
    flops = 2 * 2 * keys_seen * head_dim * heads
    kv = 2 * (pos0 + chunk) * kv_heads * head_dim * itemsize
    qo = 2 * chunk * heads * head_dim * itemsize
    return flops, kv + qo


def floor_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    peak_flops, peak_bytes = peaks(device_kind)
    return max(flops / peak_flops, nbytes / peak_bytes)
