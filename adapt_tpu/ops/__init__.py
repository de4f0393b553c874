from adapt_tpu.ops.quantize import (
    QuantizedTensor,
    dequantize,
    dequantize_params,
    dequantize_reference,
    pack_int4,
    quantize,
    quantize_kv_vectors,
    quantize_params,
    quantize_reference,
    unpack_int4,
)
from adapt_tpu.ops.attention import attention_reference, flash_attention
from adapt_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    default_decode_split,
    verify_attention,
)
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
    paged_chunk_attention,
    paged_chunk_attention_reference,
    paged_verify_attention,
    paged_verify_attention_reference,
    pool_values,
)

__all__ = [
    "QuantizedTensor",
    "attention_reference",
    "decode_attention",
    "decode_attention_reference",
    "default_decode_split",
    "dequantize",
    "dequantize_params",
    "dequantize_reference",
    "flash_attention",
    "kernel_dispatch_stats",
    "pack_int4",
    "unpack_int4",
    "paged_attention",
    "paged_attention_reference",
    "paged_chunk_attention",
    "paged_chunk_attention_reference",
    "paged_verify_attention",
    "paged_verify_attention_reference",
    "pool_values",
    "quantize",
    "quantize_kv_vectors",
    "quantize_params",
    "quantize_reference",
    "verify_attention",
]
