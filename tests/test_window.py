"""Sliding-window attention (Mistral-style) across every decode path.

The contract stack: the banded oracle defines semantics; cached decode
realizes the window as a dynamic ``valid_from`` (no kernel changes);
``verify_chunk`` and the paged chunk kernel band their masks; and the
paged batcher RECYCLES pages that fall wholly behind the window
mid-request, with refcounts protecting pages a slower sharer still
needs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import (
    generate,
    logits_full,
    transformer_lm,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher

W = 12


@pytest.fixture(scope="module")
def wlm_setup():
    lm = transformer_lm(
        41, 32, 2, 4, 64, max_len=96, kv_heads=2, window=W,
        name="windowed_lm",
    )
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def test_windowed_cached_decode_matches_full_forward(wlm_setup):
    """Greedy cached generate (window as dynamic valid_from) == stepwise
    argmax of the banded full forward, WELL past the window length so
    old positions actually fall out of every mask."""
    lm, variables = wlm_setup
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (2, 20), 0, 41, jnp.int32
    )
    steps = 30  # 20 + 30 = 50 positions >> window 12
    from conftest import greedy_by_full_forward

    got = np.asarray(generate(lm, variables, prompt, steps))
    np.testing.assert_array_equal(
        got, greedy_by_full_forward(lm, variables, prompt, steps)
    )


def test_window_actually_masks(wlm_setup):
    """Sanity that the window does something: perturbing a token far
    behind the window must NOT change the next-token logits, while
    perturbing one inside it must."""
    lm, variables = wlm_setup
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 41)
    base = np.asarray(logits_full(lm, variables, ids)[:, -1])
    far = ids.at[0, 5].set((ids[0, 5] + 1) % 41)  # pos 5 << 39 - 12
    near = ids.at[0, 35].set((ids[0, 35] + 1) % 41)
    np.testing.assert_array_equal(
        base, np.asarray(logits_full(lm, variables, far)[:, -1])
    )
    assert not np.array_equal(
        base, np.asarray(logits_full(lm, variables, near)[:, -1])
    )


def test_windowed_ragged_generate(wlm_setup):
    """Ragged left padding composes with the window (valid_from is the
    max of both) — greedy ragged rows equal their solo runs, well past
    the window. (Greedy on purpose: sampled keys fold the GLOBAL row
    index, so a solo run of row r>0 legitimately draws differently.)"""
    lm, variables = wlm_setup
    prompt = jax.random.randint(
        jax.random.PRNGKey(3), (3, 16), 0, 41, jnp.int32
    )
    lengths = jnp.asarray([16, 7, 11], jnp.int32)
    out = np.asarray(
        generate(lm, variables, prompt, 20, prompt_lengths=lengths)
    )
    for r in range(3):
        solo = np.asarray(
            generate(lm, variables, prompt[r:r + 1, : int(lengths[r])], 20)
        )[0]
        np.testing.assert_array_equal(out[r], solo, err_msg=f"row {r}")


def test_windowed_speculative_lossless(wlm_setup):
    """verify_chunk's banded mask: speculative decode stays greedy-
    lossless on the windowed model."""
    from adapt_tpu.models.speculative import speculative_generate

    lm, variables = wlm_setup
    prompt = jax.random.randint(
        jax.random.PRNGKey(5), (1, 10), 0, 41, jnp.int32
    )
    want = np.asarray(generate(lm, variables, prompt, 18))
    got, stats = speculative_generate(
        lm, variables, prompt, 18, lm, variables, draft_k=4,
        return_stats=True,
    )
    np.testing.assert_array_equal(np.asarray(got), want)
    assert stats["acceptance"] == 1.0  # self-draft upper bound


def test_windowed_paged_serving_recycles_pages(wlm_setup):
    """The rolling-window pool: serving a long windowed generation
    through paged slots releases pages behind the window mid-request
    (base advances, in_use stays bounded), streams match solo
    generate(), and freed pages admit a LATER request into a pool that
    never held two full windows' worth of live pages at once."""
    lm, variables = wlm_setup
    rng = np.random.RandomState(6)
    p1 = rng.randint(0, 41, size=20).astype(np.int32)
    p2 = rng.randint(0, 41, size=20).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged", page_size=16,
    )
    r1 = bat.submit(p1, 60)  # spans 80 positions = 5 pages
    mid_bases = []
    for _ in range(8):
        bat.tick()
        mid_bases.append(bat._pager.base(0))
    assert mid_bases[-1] > 0, "no pages recycled behind the window"
    r2 = bat.submit(p2, 10)
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1], np.asarray(generate(lm, variables, jnp.asarray(p1)[None], 60))[0]
    )
    np.testing.assert_array_equal(
        out[r2], np.asarray(generate(lm, variables, jnp.asarray(p2)[None], 10))[0]
    )
    st = bat._pager.stats()
    assert st.in_use == 0


def test_windowed_shared_prefix_release_respects_refcounts(wlm_setup):
    """Two live requests share prompt pages; the faster one's window
    rolls past them and releases its claim — the slower sharer's
    refcount must keep the pages alive until it releases too."""
    lm, variables = wlm_setup
    rng = np.random.RandomState(7)
    system = rng.randint(0, 41, size=32).astype(np.int32)  # 2 full pages
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged", page_size=16,
    )
    r1 = bat.submit(system, 40)  # long: window rolls past the prompt
    bat.tick()
    r2 = bat.submit(system, 40)
    out = bat.run()
    want = np.asarray(
        generate(lm, variables, jnp.asarray(system)[None], 40)
    )[0]
    np.testing.assert_array_equal(out[r1], want)
    np.testing.assert_array_equal(out[r2], want)


def test_windowed_chunked_prefill_greedy_parity(wlm_setup):
    """Chunked prefill under the window (banded chunk kernel/oracle):
    greedy output equals solo generate()."""
    lm, variables = wlm_setup
    rng = np.random.RandomState(8)
    long_p = rng.randint(0, 41, size=50).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=2, kv_layout="paged", page_size=16,
        prefill_chunk=16,
    )
    rid = bat.submit(long_p, 8)
    out = bat.run()
    np.testing.assert_array_equal(
        out[rid],
        np.asarray(generate(lm, variables, jnp.asarray(long_p)[None], 8))[0],
    )


def test_window_validation():
    with pytest.raises(ValueError, match="window"):
        transformer_lm(41, 32, 2, 4, 48, window=0)


# -- banded streaming kernel (fwd + bwd) --------------------------------------


def test_windowed_flash_kernel_matches_oracle(rng):
    """The streaming kernel's band mask (+ dead-block skip on both
    sides of the band) vs the banded oracle, across block boundaries
    and composed with ragged valid_from."""
    from adapt_tpu.ops.attention import attention_reference, flash_attention

    b, h, s, d = 2, 2, 512, 32
    q = jax.random.normal(rng, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, h, s, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, h, s, d))
    for win in (100, 128, 17):
        ref = attention_reference(q, k, v, causal=True, window=win)
        out = flash_attention(
            q, k, v, causal=True, window=win, prefer="pallas"
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"window {win}",
        )
    vf = jnp.asarray([0, 200], jnp.int32)
    ref = attention_reference(q, k, v, causal=True, window=100,
                              valid_from=vf)
    out = flash_attention(q, k, v, causal=True, window=100,
                          valid_from=vf, prefer="pallas")
    rows = np.arange(s)
    live_rows = rows >= np.asarray(vf)[:, None]  # padded rows unspecified
    np.testing.assert_allclose(
        np.asarray(out)[live_rows[:, None, :].repeat(2, 1)],
        np.asarray(ref)[live_rows[:, None, :].repeat(2, 1)],
        rtol=2e-5, atol=2e-5,
    )


def test_windowed_streaming_backward_matches_oracle(rng, monkeypatch):
    """Banded gradients through the two streaming passes (budget forced
    to 0 so the bwd streams) vs grads of the banded oracle."""
    import adapt_tpu.ops.attention as A

    monkeypatch.setattr(A, "FLASH_SCORE_BYTES_BUDGET", 0)
    b, h, s, d = 1, 2, 256, 32
    q = jax.random.normal(rng, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(rng, 3), (b, h, s, d))
    v = jax.random.normal(jax.random.fold_in(rng, 4), (b, h, s, d))

    def loss_flash(q, k, v):
        return jnp.sum(
            A.flash_attention(q, k, v, causal=True, window=60,
                              prefer="pallas") ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            A.attention_reference(q, k, v, causal=True, window=60) ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name}",
        )
