"""Decode-attention kernel tests (Pallas interpreter on the CPU mesh).

The serving hot path's attention — one token's query over the live
window of a KV cache — has two implementations that must agree:
``decode_attention_reference`` (the einsum schedule ``decode_step`` has
always run) and the streaming Pallas kernel (``prefer="pallas"``) that
dequantizes int8 caches in VMEM. The reference is the oracle; the
kernel must match it on every cache flavor (native/int8), head layout
(MHA/GQA), index form (scalar/per-row) and masking (dense/ragged).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import generate, transformer_lm
from adapt_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
)
from adapt_tpu.ops.quantize import quantize_kv_vectors as _quantize_kv


def _caches(rng, b, kvh, length, hd, quantized, live_upto):
    """Caches with real values up to ``live_upto`` and garbage past it
    (the dead tail must not leak into the output)."""
    kk, kv, kg = jax.random.split(rng, 3)
    k = jax.random.normal(kk, (b, kvh, length, hd), jnp.float32)
    v = jax.random.normal(kv, (b, kvh, length, hd), jnp.float32)
    # Huge garbage past the live window: a masking bug becomes loud.
    tail = (jnp.arange(length) > live_upto)[None, None, :, None]
    k = jnp.where(tail, 1e4 * jax.random.normal(kg, k.shape), k)
    v = jnp.where(tail, -1e4, v)
    if not quantized:
        return k, v
    return _quantize_kv(k), _quantize_kv(v)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("length", [1024, 2048])
def test_kernel_matches_reference(rng, quantized, length):
    b, kvh, g, hd = 2, 3, 1, 64
    index = jnp.asarray(length // 2 + 7, jnp.int32)
    ck, cv = _caches(rng, b, kvh, length, hd, quantized, length // 2 + 7)
    q = jax.random.normal(jax.random.fold_in(rng, 1), (b, kvh, g, hd))
    ref = decode_attention_reference(q, ck, cv, index)
    out = decode_attention(q, ck, cv, index, prefer="pallas")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_gqa_rows_and_per_row_index(rng, quantized):
    # g=4 query rows per KV head (sublane-padded to 8 inside the kernel)
    # and a per-row index: each batch row's live window differs.
    b, kvh, g, hd, length = 3, 2, 4, 64, 1024
    index = jnp.asarray([100, 1023, 512], jnp.int32)
    # Garbage sits strictly past every row's window (max index = 1023).
    ck, cv = _caches(rng, b, kvh, length, hd, quantized, 1023)
    q = jax.random.normal(jax.random.fold_in(rng, 2), (b, kvh, g, hd))
    ref = decode_attention_reference(q, ck, cv, index)
    out = decode_attention(q, ck, cv, index, prefer="pallas")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_ragged_valid_from(rng, quantized):
    b, kvh, g, hd, length = 2, 2, 1, 64, 2048
    index = jnp.asarray(1500, jnp.int32)
    valid_from = jnp.asarray([0, 1100], jnp.int32)  # row 1: left-padded
    ck, cv = _caches(rng, b, kvh, length, hd, quantized, 1500)
    q = jax.random.normal(jax.random.fold_in(rng, 3), (b, kvh, g, hd))
    ref = decode_attention_reference(q, ck, cv, index, valid_from)
    out = decode_attention(q, ck, cv, index, valid_from, prefer="pallas")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_kernel_early_index_skips_dead_tail(rng):
    # index in the first block: every later block is dead and skipped —
    # its garbage (1e4-scale K, -1e4 V) must not reach the output.
    b, kvh, g, hd, length = 1, 2, 1, 64, 4096
    index = jnp.asarray(17, jnp.int32)
    ck, cv = _caches(rng, b, kvh, length, hd, False, 17)
    q = jax.random.normal(jax.random.fold_in(rng, 4), (b, kvh, g, hd))
    ref = decode_attention_reference(q, ck, cv, index)
    out = decode_attention(q, ck, cv, index, prefer="pallas")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("length", [256, 512])
def test_short_native_cache_takes_the_kernel(rng, length):
    """Native caches shrink the kernel block to 256 (no scale tiles to
    satisfy), so the short-context serving configs — where the XLA
    einsum path streams the cache least efficiently — are kernel-
    eligible too."""
    from adapt_tpu.ops.decode_attention import _supported, default_block_k

    assert default_block_k(length, quantized=False) == min(length, 1024)
    # Vacuity guard: on a build without pallas-tpu the oracle would
    # serve both sides and this test would pass while testing nothing.
    assert _supported(length, default_block_k(length, False), False)
    b, kvh, g, hd = 2, 2, 2, 64
    index = jnp.asarray(length - 29, jnp.int32)
    ck, cv = _caches(rng, b, kvh, length, hd, False, length - 29)
    q = jax.random.normal(jax.random.fold_in(rng, 5), (b, kvh, g, hd))
    out = decode_attention(q, ck, cv, index, prefer="pallas")
    ref = decode_attention_reference(q, ck, cv, index)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_forced_kernel_on_unsupported_config_raises(rng):
    # Native 192 (not 256-divisible) and int8 256 (scale tiles need
    # 1024-divisible caches): a forced prefer="pallas" raises by name
    # instead of serving the oracle under the kernel's name; auto
    # dispatch routes to the oracle and books it.
    b, kvh, g, hd = 2, 2, 1, 64
    q = jax.random.normal(jax.random.fold_in(rng, 5), (b, kvh, g, hd))
    index = jnp.asarray(100, jnp.int32)
    ck, cv = _caches(rng, b, kvh, 192, hd, False, 100)
    with pytest.raises(ValueError, match="cache_len 192"):
        decode_attention(q, ck, cv, index, prefer="pallas")
    out = decode_attention(q, ck, cv, index)
    ref = decode_attention_reference(q, ck, cv, index)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    ck8, cv8 = _caches(rng, b, kvh, 256, hd, True, 100)
    with pytest.raises(ValueError, match="cache_len 256"):
        decode_attention(q, ck8, cv8, index, prefer="pallas")


def test_bad_prefer_raises(rng):
    q = jnp.zeros((1, 1, 1, 64))
    c = jnp.zeros((1, 1, 1024, 64))
    with pytest.raises(ValueError, match="prefer"):
        decode_attention(q, c, c, 0, prefer="cuda")


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_generate_pallas_decode_matches_xla(kv_dtype):
    # End-to-end: the whole generate() scan with the kernel per step
    # must reproduce the XLA path token-for-token (greedy).
    lm = transformer_lm(97, 64, 2, 4, 128, max_len=1024, kv_heads=2)
    rng = jax.random.PRNGKey(0)
    variables = lm.graph.init(rng, jnp.zeros((1, 8), jnp.int32))
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, 97, jnp.int32
    )
    base = generate(
        lm, variables, prompt, steps=6, kv_cache_dtype=kv_dtype,
        decode_attn="xla",
    )
    ker = generate(
        lm, variables, prompt, steps=6, kv_cache_dtype=kv_dtype,
        decode_attn="pallas",
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(ker))


def test_generate_bad_decode_attn_raises():
    lm = transformer_lm(97, 64, 2, 4, 128, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    prompt = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="decode_attn"):
        generate(lm, variables, prompt, steps=2, decode_attn="cuda")


def test_head_parity_guard_names_the_tp_mistake(rng):
    """Mixing a head-sharded cache with globally-shaped queries (the
    partial-TP-migration bug) must fail by name at the dispatch layer,
    not as a broadcast error deep inside an einsum — for the contiguous,
    verify and paged entry points alike."""
    from adapt_tpu.ops.decode_attention import verify_attention
    from adapt_tpu.ops.paged_attention import (
        paged_attention,
        paged_verify_attention,
    )

    q = jnp.zeros((2, 4, 2, 8))  # 4 KV-head rows
    cache = jnp.zeros((2, 2, 16, 8))  # ...but a 2-head (per-shard) cache
    with pytest.raises(ValueError, match="head count"):
        decode_attention(q, cache, cache, 3)
    with pytest.raises(ValueError, match="head count"):
        verify_attention(q, cache, cache, jnp.zeros((2,), jnp.int32), 2)
    pool = jnp.zeros((4, 2, 8, 16))  # fused K|V rows
    table = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="head count"):
        paged_attention(q, pool, table, 3)
    with pytest.raises(ValueError, match="head count"):
        paged_verify_attention(
            q, pool, table, jnp.zeros((2,), jnp.int32), 2
        )
