"""Rotary position embeddings across every decode path.

RoPE's contract here: q/k rotate by LOGICAL position in every schedule
(full forward, prefill, cached decode, verify_chunk, paged chunk
prefill), the cache stores post-rotation K, and — because logical
positions are used, not buffer positions — ragged rows stay
bitwise-equal to their solo runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import (
    apply_rope,
    generate,
    transformer_lm,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher


@pytest.fixture(scope="module")
def rlm_setup():
    lm = transformer_lm(
        43, 32, 2, 4, 64, max_len=96, kv_heads=2, pos="rope",
        name="rope_lm",
    )
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def test_rope_is_relative():
    """The defining property: shifting q AND k positions by a constant
    leaves attention scores unchanged (up to fp)."""
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (1, 2, 8, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 2, 8, 16))
    pos = jnp.arange(8)
    s0 = jnp.einsum(
        "bhqd,bhkd->bhqk", apply_rope(q, pos), apply_rope(k, pos)
    )
    s7 = jnp.einsum(
        "bhqd,bhkd->bhqk",
        apply_rope(q, pos + 37),
        apply_rope(k, pos + 37),
    )
    np.testing.assert_allclose(
        np.asarray(s0), np.asarray(s7), rtol=2e-4, atol=2e-4
    )


def test_rope_drops_pos_table(rlm_setup):
    lm, variables = rlm_setup
    assert "pos_embed" not in variables["embed"]["params"]


def test_rope_cached_decode_matches_full_forward(rlm_setup):
    lm, variables = rlm_setup
    prompt = jax.random.randint(
        jax.random.PRNGKey(2), (2, 12), 0, 43, jnp.int32
    )
    steps = 20
    from conftest import greedy_by_full_forward

    got = np.asarray(generate(lm, variables, prompt, steps))
    np.testing.assert_array_equal(
        got, greedy_by_full_forward(lm, variables, prompt, steps)
    )


def test_rope_ragged_rows_equal_solo_bitwise(rlm_setup):
    """Logical-position rotation: a left-padded row's angles equal its
    solo run's angles exactly, so even SAMPLED streams match for row 0
    and greedy matches for every row."""
    lm, variables = rlm_setup
    prompt = jax.random.randint(
        jax.random.PRNGKey(3), (3, 14), 0, 43, jnp.int32
    )
    lengths = jnp.asarray([14, 6, 9], jnp.int32)
    out = np.asarray(
        generate(lm, variables, prompt, 15, prompt_lengths=lengths)
    )
    for r in range(3):
        solo = np.asarray(
            generate(lm, variables, prompt[r:r + 1, : int(lengths[r])], 15)
        )[0]
        np.testing.assert_array_equal(out[r], solo, err_msg=f"row {r}")


def test_rope_composes_with_window_and_paged_serving(rlm_setup):
    """RoPE + sliding window + paged batcher + prefix cache + chunked
    prefill in one model: streams equal solo generate()."""
    lm = transformer_lm(
        43, 32, 2, 4, 64, max_len=128, kv_heads=2, pos="rope", window=20,
        name="rope_win_lm",
    )
    variables = lm.graph.init(
        jax.random.PRNGKey(4), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.RandomState(5)
    system = rng.randint(0, 43, size=32).astype(np.int32)
    p1 = np.concatenate([system, rng.randint(0, 43, size=6).astype(np.int32)])
    p2 = np.concatenate([system, rng.randint(0, 43, size=30).astype(np.int32)])
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged", page_size=16,
        prefill_chunk=16,
    )
    r1 = bat.submit(p1, 30)
    bat.tick()
    r2 = bat.submit(p2, 12)  # prefix hit + chunked suffix
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1],
        np.asarray(generate(lm, variables, jnp.asarray(p1)[None], 30))[0],
    )
    np.testing.assert_array_equal(
        out[r2],
        np.asarray(generate(lm, variables, jnp.asarray(p2)[None], 12))[0],
    )


def test_rope_speculative_lossless(rlm_setup):
    from adapt_tpu.models.speculative import speculative_generate

    lm, variables = rlm_setup
    prompt = jax.random.randint(
        jax.random.PRNGKey(6), (1, 9), 0, 43, jnp.int32
    )
    want = np.asarray(generate(lm, variables, prompt, 14))
    got = speculative_generate(
        lm, variables, prompt, 14, lm, variables, draft_k=4
    )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_rope_validation():
    with pytest.raises(ValueError, match="pos="):
        transformer_lm(43, 32, 2, 4, 64, pos="alibi")
