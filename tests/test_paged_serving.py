"""Paged KV cache, second file: the per-token write into the pool
against its scatter oracle, and the ``ContinuousBatcher`` emitting
token-for-token what ``generate()`` emits for each request alone over
paged pools (prefix cache, chunked prefill, a pool small enough to make
requests wait). Cut from ``test_paged.py`` (PR 54): under ``--dist
loadfile`` one file is one worker's, and that file was the whole run's
tail (584 s of 1,296)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import (
    BlockSpec,
    CausalSelfAttention,
    generate,
    lm_tiny,
)
from adapt_tpu.ops.paged_attention import append_kv_paged, fuse_kv, split_kv
from adapt_tpu.ops.quantize import quantize_kv_vectors
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import (
    alloc_kv_pools,
    insert_prefill_pages,
    kv_value_width,
)


# -- the pool writes: K on lanes [0, hd), V on [hd, 2hd), bit for bit ---------


def _scatter_oracle(pool, new, phys, off):
    """The advanced-index scatter ``append_kv_paged`` replaced, kept
    here as its oracle: ``pool[phys[i,t], :, off[i,t], :] <- new[i, :,
    t, :]``."""
    return pool.at[phys, :, off, :].set(
        jnp.swapaxes(new, 1, 2).astype(pool.dtype)
    )


def _two_planes(kv_dtype, npages, kvh, page, hd):
    """A block's K and V as the TWO planes a pool held until PR 30
    (arrays, or (values, scales) pairs): what the fused plane's two
    lane halves must equal after every write."""

    def fresh(seed):
        vals = jax.random.normal(
            jax.random.PRNGKey(seed), (npages, kvh, page, hd)
        )
        if kv_dtype == "native":
            return vals
        return quantize_kv_vectors(vals, kv_dtype)

    return fresh(1), fresh(2)


def _assert_halves_equal(got_pool, want_k, want_v):
    """``got_pool``'s lanes [0, w) == ``want_k`` and [w, 2w) ==
    ``want_v``, every plane, dtype and bit."""
    for got, want in zip(
        jax.tree.leaves(split_kv(got_pool)),
        jax.tree.leaves((want_k, want_v)),
    ):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["decode", "verify"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8", "int4"])
@pytest.mark.parametrize("hd", [16, 128])
def test_paged_write_equals_scatter_oracle(rng, mode, kv_dtype, hd):
    """``decode_step_paged`` / ``verify_chunk_paged`` leave the fused
    plane's K lanes and V lanes bit-equal to what the old scatter left
    in a K plane and a V plane: distinct rows, a dead row (``idx < 0``)
    whose table maps real pages landing on the trash page, two rows
    writing one physical page, a verify chunk crossing a page edge —
    native pools and int8 / int4-packed (values, K scales, V scales)
    triples, at a fused row under a lane tile (the row loop) and at
    whole ones (the head-indexed scatter)."""
    heads, kvh, page, npages = 4, 2, 8, 9
    dim = heads * hd
    kc = 1 if mode == "decode" else 5
    attn = CausalSelfAttention(BlockSpec(dim, heads, 0, kv_heads=kvh))
    kx, kp = jax.random.split(rng)
    x = jax.random.normal(kx, (4, kc, dim))
    params = attn.init(kp, x)
    # Row 0 and row 3 write the SAME physical page 5 (offsets apart);
    # row 1's chunk crosses from page 2 into page 6 in verify; row 2 is
    # dead and its table maps real pages (7, 8) that must stay clean.
    table = jnp.asarray([[5, 1], [2, 6], [7, 8], [3, 5]], jnp.int32)
    index = jnp.asarray([1, page - 2, -1, page + 2], jnp.int32)

    k_plane, v_plane = _two_planes(kv_dtype, npages, kvh, page, hd)
    pool = fuse_kv(k_plane, v_plane)
    if mode == "decode":
        _, got = attn.apply(
            params, x, pool, table, index, None, "xla",
            method="decode_step_paged",
        )
    else:
        _, got = attn.apply(
            params, x, pool, table, index, "xla",
            method="verify_chunk_paged",
        )
    assert jax.tree.structure(got) == jax.tree.structure(pool)
    _, k, v = attn.apply(params, x, method="_project")  # (b, kvh, K, hd)
    pos = jnp.maximum(index, 0)[:, None] + jnp.arange(kc)[None, :]
    phys = jnp.take_along_axis(table, pos // page, axis=1)
    phys = jnp.where((index >= 0)[:, None], phys, 0)
    off = pos % page
    want = []
    for plane, new in ((k_plane, k), (v_plane, v)):
        if kv_dtype != "native":
            new = quantize_kv_vectors(new, kv_dtype)
        want.append(jax.tree.map(
            lambda m, t: _scatter_oracle(m, t, phys, off), plane, new
        ))
    _assert_halves_equal(got, *want)
    for g, before in zip(jax.tree.leaves(got), jax.tree.leaves(pool)):
        # The dead row's own pages are untouched; its write sits on the
        # trash page.
        np.testing.assert_array_equal(
            np.asarray(g)[7:], np.asarray(before)[7:]
        )
        assert (np.asarray(g)[0] != np.asarray(before)[0]).any()


@pytest.mark.parametrize("mode", ["chunk", "insert"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8", "int4"])
@pytest.mark.parametrize("hd", [16, 64])
def test_paged_page_writes_land_k_and_v_on_their_lanes(
    rng, mode, kv_dtype, hd
):
    """The page-granular writers — ``prefill_chunk_paged`` (a chunk of
    two pages at a page-aligned ``pos0`` inside a pow2-padded window)
    and ``insert_prefill_pages`` over a whole prompt's rows (ragged
    last page, zero padded) — leave each lane half of every page they
    name equal to what the same write left in a K plane and a V plane,
    and touch no other page."""
    heads, kvh, page, npages = 4, 2, 8, 9
    dim = heads * hd
    attn = CausalSelfAttention(BlockSpec(dim, heads, 0, kv_heads=kvh))
    kx, kp = jax.random.split(rng)
    k_plane, v_plane = _two_planes(kv_dtype, npages, kvh, page, hd)
    pool = fuse_kv(k_plane, v_plane)
    if mode == "chunk":
        x = jax.random.normal(kx, (1, 2 * page, dim))
        params = attn.init(kp, x)
        pages = jnp.asarray([3, 5, 7, 0], jnp.int32)
        _, got = attn.apply(
            params, x, pool, pages, page, method="prefill_chunk_paged"
        )
        _, k, v = attn.apply(params, x, method="_project")
        written = [5, 7]

        def write(plane, t):  # (1, kvh, 2 * page, w) -> pages 5 and 7
            t = jnp.swapaxes(t[0].reshape(kvh, 2, page, -1), 0, 1)
            return plane.at[jnp.asarray(written)].set(t.astype(plane.dtype))
    else:
        x = jax.random.normal(kx, (1, 2 * page + 3, dim))
        params = attn.init(kp, x)
        quant = False if kv_dtype == "native" else kv_dtype
        _, ck, cv = attn.apply(
            params, x, 3 * page, None, quant, method="prefill"
        )
        written = [4, 1, 6]
        pages = jnp.asarray(written, jnp.int32)
        # The fused pool is donated to the insert: compare with copies.
        got = jax.tree.map(
            lambda plane, rows: insert_prefill_pages(
                jnp.array(plane), pages, rows
            ),
            pool, fuse_kv(ck, cv),
        )
        k, v = ck, cv

        def write(plane, t):
            return insert_prefill_pages(jnp.array(plane), pages, t)

    want = []
    for plane, new in ((k_plane, k), (v_plane, v)):
        if mode == "chunk" and kv_dtype != "native":
            new = quantize_kv_vectors(new, kv_dtype)
        want.append(jax.tree.map(write, plane, new))
    _assert_halves_equal(got, *want)
    untouched = [p for p in range(npages) if p not in written]
    for g, before in zip(jax.tree.leaves(got), jax.tree.leaves(pool)):
        np.testing.assert_array_equal(
            np.asarray(g)[untouched], np.asarray(before)[untouched]
        )
        assert (np.asarray(g)[written] != np.asarray(before)[written]).any()


@pytest.mark.parametrize("w", [4, 128])
def test_paged_write_dead_rows_collide_on_trash_only(w):
    """Several dead rows all target ``(page 0, offset 0)``: whichever
    lands last, nothing but the trash page differs from the oracle."""
    pool = jnp.zeros((4, 2, 8, w))
    new = jnp.arange(3 * 2 * w, dtype=jnp.float32).reshape(3, 2, 1, w) + 1
    phys = jnp.asarray([[0], [2], [0]], jnp.int32)
    off = jnp.asarray([[0], [3], [0]], jnp.int32)
    got = np.asarray(jax.jit(append_kv_paged)(pool, new, phys, off))
    want = np.asarray(_scatter_oracle(pool, new, phys, off))
    np.testing.assert_array_equal(got[1:], want[1:])
    assert any(
        (got[0, :, 0, :] == np.asarray(new)[i, :, 0, :]).all() for i in (0, 2)
    )
    assert (got[0, :, 1:, :] == 0).all()


# -- batcher equivalence -----------------------------------------------------


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=37, max_len=48)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def lm_setup_64():
    lm = lm_tiny(vocab=37, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def lm_setup_256():
    lm = lm_tiny(vocab=37, max_len=256)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


def test_paged_staggered_requests_match_generate(lm_setup):
    """Mixed greedy/sampled staggered traffic through paged slots ==
    per-request solo generate, and pages drain back to the pool."""
    lm, variables = lm_setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 9, 5, 12, 7)]
    steps = [6, 4, 8, 3, 5]
    kw = [
        {},
        {"temperature": 0.9, "top_k": 5, "rng": jax.random.PRNGKey(7)},
        {},
        {"temperature": 1.3, "rng": jax.random.PRNGKey(9)},
        {},
    ]
    bat = ContinuousBatcher(
        lm, variables, slots=3, chunk=4, kv_layout="paged", page_size=16
    )
    ids = {}
    for i in range(2):
        ids[bat.submit(prompts[i], steps[i], **kw[i])] = i
    bat.tick()
    for i in range(2, 5):
        ids[bat.submit(prompts[i], steps[i], **kw[i])] = i
    out = bat.run()
    assert set(out) == set(ids)
    for rid, i in ids.items():
        solo_kw = dict(kw[i])
        want = _solo(lm, variables, prompts[i], steps[i], **solo_kw)
        np.testing.assert_array_equal(out[rid], want, err_msg=f"req {i}")
    st = bat.stats()
    assert st["pages_in_use"] == 0 and st["pages_free"] == st["pool_pages"] - 1


def test_paged_small_pool_forces_waiting_but_completes(lm_setup):
    """A pool too small for all slots at once: admission stalls on pages
    (not slots), later requests run after earlier ones free theirs, and
    every output still matches solo generate."""
    lm, variables = lm_setup
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (11, 12, 13, 14)]
    steps = [6, 6, 6, 6]
    # Each request needs ceil(max(16, s0+6)/16) = 2 pages (spans 17..20).
    # Pool of 5 = trash + 4: TWO requests resident max, though there are
    # 3 slots.
    bat = ContinuousBatcher(
        lm, variables, slots=3, chunk=4, kv_layout="paged", page_size=16,
        pool_pages=5,
    )
    ids = {bat.submit(p, s): i
           for i, (p, s) in enumerate(zip(prompts, steps))}
    bat.tick()
    st = bat.stats()
    assert st["active"] == 2 and st["pages_in_use"] == 4  # page-bound
    out = bat.run()
    for rid, i in ids.items():
        want = _solo(lm, variables, prompts[i], steps[i])
        np.testing.assert_array_equal(out[rid], want, err_msg=f"req {i}")


def test_prefix_cache_reuses_pages_across_requests(lm_setup):
    """Same prompt served twice: the second admission shares the first's
    registered full pages (prefix hits, fewer fresh allocations) and
    still emits exactly the solo generate() stream — suffix-only
    prefill must be invisible in outputs."""
    lm, variables = lm_setup
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 37, size=37).astype(np.int32)  # 2 full pages
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged", page_size=16
    )
    r1 = bat.submit(prompt, 5)
    out1 = bat.run()
    assert bat._pager.stats().cached == 2  # two full pages registered
    r2 = bat.submit(prompt, 5)
    out2 = bat.run()
    want = _solo(lm, variables, prompt, 5)
    np.testing.assert_array_equal(out1[r1], want)
    np.testing.assert_array_equal(out2[r2], want)
    st = bat._pager.stats()
    assert st.prefix_hits == 2 and st.cached == 2


def test_prefix_cache_shared_system_prompt_live(lm_setup):
    """Two DIFFERENT requests sharing a long system prefix, resident
    simultaneously: the common full pages are shared in flight (rc=2 —
    observable as fewer pages in use than two solo windows) and both
    streams match solo generate()."""
    lm, variables = lm_setup
    rng = np.random.RandomState(8)
    system = rng.randint(0, 37, size=32).astype(np.int32)  # 2 full pages
    p1 = np.concatenate([system, rng.randint(0, 37, size=4).astype(np.int32)])
    p2 = np.concatenate([system, rng.randint(0, 37, size=7).astype(np.int32)])
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged", page_size=16
    )
    r1 = bat.submit(p1, 4)
    bat.tick()  # admit + register p1's prefix pages
    r2 = bat.submit(p2, 4,
                    temperature=0.8, top_k=6, rng=jax.random.PRNGKey(11))
    bat.tick()  # p2 admits against p1's live pages
    st = bat._pager.stats()
    # Window per request = ceil(max(bucket=48? (36/43 -> 64), s0+4)/16)
    # pages; sharing saves 2 of them while both are live.
    assert bat._pager.prefix_hits == 2
    out = bat.run()
    np.testing.assert_array_equal(out[r1], _solo(lm, variables, p1, 4))
    np.testing.assert_array_equal(
        out[r2],
        _solo(lm, variables, p2, 4, temperature=0.8, top_k=6,
              rng=jax.random.PRNGKey(11)),
    )
    assert st.in_use < 2 * (-(-max(64, p1.shape[0] + 4) // 16))


def test_prefix_cache_eviction_under_pressure(lm_setup):
    """A pool with no spare room: cached (rc=0) prefix pages are evicted
    to admit an unrelated request, and serving stays correct."""
    lm, variables = lm_setup
    rng = np.random.RandomState(9)
    p_a = rng.randint(0, 37, size=33).astype(np.int32)
    p_b = rng.randint(0, 37, size=33).astype(np.int32)
    # Window: bucket 48? buckets are powers of two + max_len: 8,16,32,48
    # -> 33 fits bucket 48 (max_len); span max(48, 39) = 48 -> 3 pages.
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=4, kv_layout="paged", page_size=16,
        pool_pages=4,  # exactly one window + trash: b must evict a's pages
    )
    ra = bat.submit(p_a, 5)
    out_a = bat.run()
    assert bat._pager.stats().cached == 2
    rb = bat.submit(p_b, 5)
    out_b = bat.run()
    np.testing.assert_array_equal(out_a[ra], _solo(lm, variables, p_a, 5))
    np.testing.assert_array_equal(out_b[rb], _solo(lm, variables, p_b, 5))
    # a's cached pages were evicted to make room; b's now sit in cache.
    assert bat._pager.stats().cached == 2
    # And a THIRD submit of p_a must recompute (its pages are gone) yet
    # still match.
    ra2 = bat.submit(p_a, 5)
    out_a2 = bat.run()
    np.testing.assert_array_equal(out_a2[ra2], _solo(lm, variables, p_a, 5))


def test_prefix_hit_suffix_bucket_rounds_past_span(lm_setup_64):
    """Regression: a short prefix hit (m=1) whose SUFFIX bucket
    re-rounds past the request's own span page count — the reservation
    must cover the suffix prefill's working strip, or _admit crashes
    (or silently corrupts shared pages under -O). s0=49, steps=5,
    P=16: span 64 -> 4 pages, but suffix 33 -> bucket 64 -> strip
    needs 5."""
    lm, variables = lm_setup_64
    rng = np.random.RandomState(11)
    first = rng.randint(0, 37, size=49).astype(np.int32)
    second = first.copy()
    second[20] = (second[20] + 1) % 37  # shares ONLY the first page
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=4, kv_layout="paged", page_size=16
    )
    r1 = bat.submit(first, 5)
    out1 = bat.run()
    r2 = bat.submit(second, 5)
    out2 = bat.run()
    assert bat._pager.prefix_hits == 1  # page 0 shared, page 1 missed
    np.testing.assert_array_equal(
        out1[r1], _solo(lm, variables, first, 5)
    )
    np.testing.assert_array_equal(
        out2[r2], _solo(lm, variables, second, 5)
    )


def test_chunked_prefill_matches_generate_and_interleaves(lm_setup_64):
    """A long prompt admitted with prefill_chunk=16 prefills one
    page-chunk per tick while an already-running request keeps
    decoding — the long admission must not stall it — and the chunked
    request's GREEDY output equals solo generate(). (Greedy is the
    contract: chunk boundaries change fp contraction widths, so the
    cached K/V can differ from the one-pass values at ulp scale —
    invisible to argmax, but able to flip a high-temperature
    categorical draw at a near-tie. The sampled stream's equivalence
    is distributional, not bitwise — documented on prefill_chunk.)"""
    lm, variables = lm_setup_64
    rng = np.random.RandomState(12)
    short = rng.randint(0, 37, size=4).astype(np.int32)
    long_p = rng.randint(0, 37, size=50).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=16,
        prefill_chunk=16,
    )
    r_short = bat.submit(short, 8,
                         temperature=0.9, top_k=5,
                         rng=jax.random.PRNGKey(13))
    bat.tick()  # short decoding
    emitted_before = len(bat.slots[0].tokens)
    r_long = bat.submit(long_p, 4)
    bat.tick()  # long prefills its first chunk; short keeps decoding
    assert bat.slots[1].pf_done >= 0  # still mid-prefill
    assert len(bat.slots[0].tokens) > emitted_before  # no stall
    out = bat.run()
    np.testing.assert_array_equal(
        out[r_short],
        _solo(lm, variables, short, 8, temperature=0.9, top_k=5,
              rng=jax.random.PRNGKey(13)),
    )
    np.testing.assert_array_equal(
        out[r_long], _solo(lm, variables, long_p, 4)
    )


def test_chunked_prefill_composes_with_prefix_cache(lm_setup_64):
    """Chunked prefill starts AFTER the shared prefix: a second long
    request with a cached 32-token prefix prefills only its remaining
    pages chunk by chunk, and matches solo generate()."""
    lm, variables = lm_setup_64
    rng = np.random.RandomState(13)
    system = rng.randint(0, 37, size=32).astype(np.int32)
    p1 = np.concatenate([system, rng.randint(0, 37, size=18).astype(np.int32)])
    p2 = np.concatenate([system, rng.randint(0, 37, size=20).astype(np.int32)])
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=2, kv_layout="paged", page_size=16,
        prefill_chunk=16,
    )
    r1 = bat.submit(p1, 4)
    out1 = bat.run()
    hits_before = bat._pager.prefix_hits
    r2 = bat.submit(p2, 4)
    bat.tick()
    # p2 shares the two system pages and chunk-prefills from there.
    assert bat._pager.prefix_hits == hits_before + 2
    out2 = bat.run()
    np.testing.assert_array_equal(out1[r1], _solo(lm, variables, p1, 4))
    np.testing.assert_array_equal(out2[r2], _solo(lm, variables, p2, 4))


def test_decode_during_chunked_prefill_cannot_corrupt_prompt_pages(
    lm_setup_256,
):
    """Regression: while a slot is mid-chunked-prefill it still rides
    the lockstep decode batch as a dead row — and a dead row OWNS real
    pages, so its garbage write must go to the trash page, not
    table[row, 0] (= the prompt's first page). Before the negative-pos
    sentinel, concurrent decode overwrote prompt positions 0..chunk-1
    every tick and the chunked request's stream diverged from token
    one."""
    lm, variables = lm_setup_256
    rng = np.random.RandomState(14)
    short = rng.randint(0, 37, size=5).astype(np.int32)
    long_p = rng.randint(0, 37, size=124).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=16,
        prefill_chunk=32,
    )
    r_short = bat.submit(short, 40)  # still decoding through the prefill
    bat.tick()
    r_long = bat.submit(long_p, 5)
    bat.tick()
    assert bat.slots[1].pf_done >= 0  # mid-prefill with decode running
    out = bat.run()
    np.testing.assert_array_equal(
        out[r_short], _solo(lm, variables, short, 40)
    )
    np.testing.assert_array_equal(
        out[r_long], _solo(lm, variables, long_p, 5)
    )


def test_chunked_prefill_validation(lm_setup):
    lm, variables = lm_setup
    with pytest.raises(ValueError, match="multiple"):
        # under one (default, 128-position) page
        ContinuousBatcher(lm, variables, prefill_chunk=16)
    with pytest.raises(ValueError, match="multiple"):
        ContinuousBatcher(lm, variables, kv_layout="paged", page_size=16,
                          prefill_chunk=24)


def test_paged_validation(lm_setup):
    lm, variables = lm_setup
    with pytest.raises(ValueError, match="kv_layout"):
        ContinuousBatcher(lm, variables, kv_layout="vram")
    # Paged + int8 is a supported COMPOSITION (tests/test_quant_serving
    # pins its behavior); construction must succeed with pool pairs.
    q = ContinuousBatcher(
        lm, variables, slots=2, kv_layout="paged", kv_cache_dtype="int8"
    )
    # (int8 values, f32 K scales, f32 V scales)
    assert isinstance(q._caches[0], tuple) and len(q._caches[0]) == 3
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_layout="paged", page_size=16,
        pool_pages=2,  # one allocatable page = 16 positions
    )
    with pytest.raises(ValueError, match="pages"):
        bat.submit(np.arange(10, dtype=np.int32), steps=20)  # needs 2
