"""Continuous batching: requests served through the batcher must emit
token-for-token what single-program ``generate()`` emits for each
request ALONE — slot scheduling, bucketed prefill, admission order,
lockstep ticking and where a request's pages end must be invisible in
outputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import generate, lm_tiny
from adapt_tpu.runtime.continuous import ContinuousBatcher


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=37, max_len=48)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


#: The stream-identity tests run at both ends of the page axis: at the
#: default page (128) every request of this 48-position model lives
#: inside one partial page; at 8 a request spans several pages and its
#: decode crosses page boundaries mid-stream.
PAGE_SIZES = pytest.mark.parametrize("page_size", [8, 128])


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


@PAGE_SIZES
@pytest.mark.parametrize("chunk", [1, 8])
def test_staggered_greedy_requests_match_generate(lm_setup, chunk, page_size):
    """Requests of different lengths arriving at different times (some
    mid-decode of others) each match their solo generate() output —
    whether ticks run one step (fully reactive) or a compiled 8-step
    chunk (whose mid-chunk garbage tails must be invisible)."""
    lm, variables = lm_setup
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 9, 5, 12, 7)]
    steps = [6, 4, 8, 3, 5]

    bat = ContinuousBatcher(
        lm, variables, slots=3, chunk=chunk, page_size=page_size
    )
    ids = {}
    for i in range(2):
        ids[bat.submit(prompts[i], steps[i])] = i
    bat.tick()
    bat.tick()
    for i in range(2, 5):  # arrive while the first two are mid-decode
        ids[bat.submit(prompts[i], steps[i])] = i
    out = bat.run()
    assert set(out) == set(ids)
    for rid, i in ids.items():
        want = _solo(lm, variables, prompts[i], steps[i])
        np.testing.assert_array_equal(out[rid], want, err_msg=f"req {i}")


@PAGE_SIZES
def test_sampled_requests_match_generate(lm_setup, page_size):
    """Per-request key schedules reproduce generate()'s sampled streams
    even when greedy and sampled requests share the lockstep batch."""
    lm, variables = lm_setup
    p1 = np.asarray([1, 2, 3, 4], np.int32)
    p2 = np.asarray([5, 6, 7], np.int32)
    p3 = np.asarray([8, 9, 10, 11, 12], np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, top_k=5, page_size=page_size
    )
    r1 = bat.submit(p1, 6, temperature=0.9, rng=jax.random.PRNGKey(7))
    r2 = bat.submit(p2, 5)  # greedy, same batch
    r3 = bat.submit(p3, 4, temperature=1.3, rng=jax.random.PRNGKey(9))
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1],
        _solo(lm, variables, p1, 6, temperature=0.9, top_k=5,
              rng=jax.random.PRNGKey(7)),
    )
    np.testing.assert_array_equal(out[r2], _solo(lm, variables, p2, 5))
    np.testing.assert_array_equal(
        out[r3],
        _solo(lm, variables, p3, 4, temperature=1.3, top_k=5,
              rng=jax.random.PRNGKey(9)),
    )


@PAGE_SIZES
def test_eos_frees_slot_stream_matches_prefix(lm_setup, page_size):
    """EOS finishes a request early: the emitted stream equals
    generate()'s output up to and including the first EOS (generate pads
    with EOS after; a server frees the slot instead)."""
    lm, variables = lm_setup
    p = np.asarray([4, 8, 15], np.int32)
    full = _solo(lm, variables, p, 8)
    eos = int(full[1])  # the second greedy token -> finishes after 2
    padded = _solo(lm, variables, p, 8, eos_id=eos)
    bat = ContinuousBatcher(lm, variables, slots=2, page_size=page_size)
    rid = bat.submit(p, 8, eos_id=eos)
    out = bat.run()
    n = len(out[rid])
    assert out[rid][-1] == eos and eos not in out[rid][:-1]
    np.testing.assert_array_equal(out[rid], padded[:n])


@PAGE_SIZES
def test_more_requests_than_slots(lm_setup, page_size):
    """Slots recycle: 7 requests drain through 2 slots."""
    lm, variables = lm_setup
    rng = np.random.RandomState(3)
    reqs = [rng.randint(0, 37, size=rng.randint(2, 10)).astype(np.int32)
            for _ in range(7)]
    bat = ContinuousBatcher(lm, variables, slots=2, page_size=page_size)
    ids = {bat.submit(p, 4): p for p in reqs}
    out = bat.run()
    assert set(out) == set(ids)
    for rid, p in ids.items():
        np.testing.assert_array_equal(
            out[rid], _solo(lm, variables, p, 4)
        )


def test_per_request_top_k_matches_generate(lm_setup):
    """Different top_k per request in ONE batch (traced per-row
    truncation): each stream equals its own generate(top_k=...) solo."""
    lm, variables = lm_setup
    p1 = np.asarray([1, 2, 3], np.int32)
    p2 = np.asarray([4, 5, 6, 7], np.int32)
    p3 = np.asarray([8, 9], np.int32)
    bat = ContinuousBatcher(lm, variables, slots=3)  # no default top_k
    r1 = bat.submit(p1, 5, temperature=0.8, top_k=3,
                    rng=jax.random.PRNGKey(21))
    r2 = bat.submit(p2, 5, temperature=1.1, top_k=12,
                    rng=jax.random.PRNGKey(22))
    r3 = bat.submit(p3, 5, temperature=0.9,  # untruncated
                    rng=jax.random.PRNGKey(23))
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1], _solo(lm, variables, p1, 5, temperature=0.8, top_k=3,
                       rng=jax.random.PRNGKey(21)))
    np.testing.assert_array_equal(
        out[r2], _solo(lm, variables, p2, 5, temperature=1.1, top_k=12,
                       rng=jax.random.PRNGKey(22)))
    np.testing.assert_array_equal(
        out[r3], _solo(lm, variables, p3, 5, temperature=0.9,
                       rng=jax.random.PRNGKey(23)))


@PAGE_SIZES
def test_int8_pools_match_generate_int8(lm_setup, page_size):
    """Quantized pools reproduce generate(kv_cache_dtype="int8")
    exactly — same absmax-per-vector scheme, so the only difference is
    where the cache lives."""
    lm, variables = lm_setup
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (4, 7, 3)]
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_cache_dtype="int8", chunk=4,
        page_size=page_size,
    )
    ids = {bat.submit(p, 6): p for p in prompts}
    out = bat.run()
    for rid, p in ids.items():
        want = _solo(lm, variables, p, 6, kv_cache_dtype="int8")
        np.testing.assert_array_equal(out[rid], want)


def test_validation(lm_setup):
    lm, variables = lm_setup
    bat = ContinuousBatcher(lm, variables, slots=2)
    with pytest.raises(ValueError, match="steps"):
        bat.submit(np.asarray([1], np.int32), 0)
    with pytest.raises(ValueError, match="max_len"):
        bat.submit(np.zeros(40, np.int32), 20)
    with pytest.raises(ValueError, match="rng"):
        bat.submit(np.asarray([1], np.int32), 2, temperature=0.5)
    with pytest.raises(ValueError, match="top_k"):
        ContinuousBatcher(lm, variables, slots=2, top_k=99)


def test_no_top_p_request_unaffected_by_nucleus_neighbor(lm_setup):
    """Regression: a sampled request WITHOUT top_p batched next to a
    nucleus request flows through the shared filter with p=1.0 — which
    must be an exact identity (f32 cumsum saturation once silently
    dropped sub-ulp-probability tokens there), so its stream still
    equals the filter-free solo generate()."""
    lm, variables = lm_setup
    p1 = np.asarray([7, 3, 1], np.int32)
    p2 = np.asarray([2, 8], np.int32)
    bat = ContinuousBatcher(lm, variables, slots=2)
    r1 = bat.submit(p1, 6, temperature=1.4, rng=jax.random.PRNGKey(33))
    r2 = bat.submit(p2, 6, temperature=0.8, top_p=0.5,
                    rng=jax.random.PRNGKey(34))
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1], _solo(lm, variables, p1, 6, temperature=1.4,
                       rng=jax.random.PRNGKey(33)))
    np.testing.assert_array_equal(
        out[r2], _solo(lm, variables, p2, 6, temperature=0.8, top_p=0.5,
                       rng=jax.random.PRNGKey(34)))


def test_per_request_top_p_matches_generate(lm_setup):
    """Mixed nucleus-p traffic in one batch matches each request's own
    generate(top_p=...) solo; a top_p=1.0 request rides the skip path."""
    lm, variables = lm_setup
    p1 = np.asarray([1, 5, 9], np.int32)
    p2 = np.asarray([2, 4], np.int32)
    bat = ContinuousBatcher(lm, variables, slots=2)
    r1 = bat.submit(p1, 5, temperature=0.9, top_p=0.6,
                    rng=jax.random.PRNGKey(31))
    r2 = bat.submit(p2, 5, temperature=1.2, top_p=1.0,
                    rng=jax.random.PRNGKey(32))
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1], _solo(lm, variables, p1, 5, temperature=0.9, top_p=0.6,
                       rng=jax.random.PRNGKey(31)))
    np.testing.assert_array_equal(
        out[r2], _solo(lm, variables, p2, 5, temperature=1.2, top_p=1.0,
                       rng=jax.random.PRNGKey(32)))


def test_stats_and_metrics(lm_setup):
    """Serving observability: occupancy/queue stats and the global
    counters move as traffic flows."""
    from adapt_tpu.utils.metrics import global_metrics

    lm, variables = lm_setup
    global_metrics().reset()
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2)
    s = bat.stats()
    assert s["slots"] == 2 and s["active"] == 0 and s["queued"] == 0
    for i in range(3):
        bat.submit(np.asarray([1 + i, 2, 3], np.int32), 4)
    assert bat.stats()["queued"] == 3
    bat.tick()
    mid = bat.stats()
    assert mid["active"] >= 1 and mid["admitted"] >= 2
    bat.run()
    end = bat.stats()
    assert end["active"] == 0 and end["completed"] == 3
    assert end["ticks"] >= 1 and end["finished_unclaimed"] == 0


def test_stats_are_instance_scoped(lm_setup):
    """Two batchers in one process must not report each other's traffic
    (stats() reads instance counters, not the process registry)."""
    lm, variables = lm_setup
    a = ContinuousBatcher(lm, variables, slots=2)
    a.submit(np.asarray([1, 2], np.int32), 3)
    a.run()
    b = ContinuousBatcher(lm, variables, slots=2)
    sb = b.stats()
    assert sb["admitted"] == 0 and sb["completed"] == 0 and sb["ticks"] == 0
    sa = a.stats()
    assert sa["admitted"] == 1 and sa["completed"] == 1


def test_threaded_serving_matches_generate(lm_setup):
    """start()/result(): submit from the caller thread while the server
    thread ticks; every stream still equals its solo generate()."""
    lm, variables = lm_setup
    rng = np.random.RandomState(9)
    with ContinuousBatcher(lm, variables, slots=2, chunk=4) as bat:
        reqs = []
        for i in range(5):
            p = rng.randint(0, 37, size=rng.randint(2, 8)).astype(np.int32)
            kw = (
                dict(temperature=0.9, top_k=7,
                     rng=jax.random.PRNGKey(60 + i))
                if i % 2
                else {}
            )
            reqs.append((bat.submit(p, 4 + i, **kw), p, 4 + i, kw))
        for rid, p, steps, kw in reqs:
            got = bat.result(rid, timeout=120.0)
            np.testing.assert_array_equal(
                got, _solo(lm, variables, p, steps, **kw)
            )
    # stopped: a late result() raises rather than hanging
    with pytest.raises((RuntimeError, TimeoutError)):
        bat.result(10_000, timeout=0.2)


def test_gqa_requests_match_generate():
    """A GQA model serves through the batcher: the pools allocate the
    smaller kv_heads layout and every stream still matches its solo
    generate()."""
    from adapt_tpu.models.transformer_lm import transformer_lm

    vocab = 31
    lm = transformer_lm(vocab=vocab, dim=32, depth=2, heads=4, mlp_dim=48,
                        max_len=48, kv_heads=2)
    variables = lm.graph.init(
        jax.random.PRNGKey(50), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.RandomState(51)
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in (3, 7, 5)]
    steps = [6, 4, 5]

    bat = ContinuousBatcher(lm, variables, slots=2, chunk=1, page_size=8)
    # 2 slots x 6 pages + trash; 2 kv heads (of 4 query heads), page 8,
    # head_dim 8: K|V fused on 16 lanes.
    assert bat._caches[0].shape == (13, 2, 8, 16)
    ids = {bat.submit(p, s): i
           for i, (p, s) in enumerate(zip(prompts, steps))}
    out = bat.run()
    for rid, i in ids.items():
        want = _solo(lm, variables, prompts[i], steps[i])
        np.testing.assert_array_equal(out[rid], want, err_msg=f"req {i}")


@PAGE_SIZES
def test_stop_sequences_truncate_at_first_match(lm_setup, page_size):
    """A stop sequence ends the stream at its first occurrence
    (inclusive); the emitted prefix equals solo generate()'s prefix."""
    lm, variables = lm_setup
    p = np.asarray([1, 2, 3], np.int32)
    full = _solo(lm, variables, p, 12)
    # Pick the stop sequence FROM the greedy stream so it must trigger.
    stop_seq = [int(full[4]), int(full[5])]
    bat = ContinuousBatcher(lm, variables, slots=2, page_size=page_size)
    rid = bat.submit(p, 12, stop=[stop_seq, [999]])
    out = bat.run()
    got = out[rid]
    assert list(got[-2:]) == stop_seq
    np.testing.assert_array_equal(got, full[: len(got)])
    assert len(got) <= 6  # ended at (or before) the planted match
    # A stop sequence that CANNOT occur (ids are always < vocab)
    # changes nothing — asserted unconditionally.
    rid2 = bat.submit(p, 12, stop=[[lm.vocab]])
    out2 = bat.run()
    np.testing.assert_array_equal(out2[rid2], full)


@PAGE_SIZES
def test_cancel_queued_and_midflight(lm_setup, page_size):
    lm, variables = lm_setup
    p1 = np.asarray([4, 5, 6, 7], np.int32)
    p2 = np.asarray([8, 9], np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=2, page_size=page_size
    )
    r1 = bat.submit(p1, 30)
    r2 = bat.submit(p2, 5)  # waits in queue (1 slot)
    bat.tick()
    assert bat.cancel(r2)  # still queued -> dropped, empty result
    bat.tick()
    assert bat.cancel(r1)  # mid-flight -> partial stream
    assert not bat.cancel(12345)  # unknown id
    out = bat.run()
    assert out[r2].shape == (0,)
    partial = out[r1]
    assert 0 < len(partial) < 30
    np.testing.assert_array_equal(
        partial, _solo(lm, variables, p1, 30)[: len(partial)]
    )
    assert bat.stats()["active"] == 0
    assert not bat._cancelled  # no leaked cancel markers
    assert bat.stats()["pages_in_use"] == 0  # the cancel freed its pages


def test_cancel_finished_request_returns_false(lm_setup):
    lm, variables = lm_setup
    p = np.asarray([1, 2], np.int32)
    bat = ContinuousBatcher(lm, variables, slots=1)
    rid = bat.submit(p, 3)
    out = bat.run()
    assert len(out[rid]) == 3
    assert not bat.cancel(rid)


def test_on_token_streams_every_committed_token(lm_setup):
    """The streaming callback sees exactly the final stream, in order,
    with correct indices — including the EOS token and across requests
    interleaved in one batcher."""
    lm, variables = lm_setup
    p1 = np.asarray([1, 2, 3], np.int32)
    p2 = np.asarray([4, 5], np.int32)
    streamed = {1: [], 2: []}

    def cb(tag):
        def on_token(rid, tok, idx):
            assert idx == len(streamed[tag])
            streamed[tag].append(tok)
        return on_token

    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2)
    full1 = _solo(lm, variables, p1, 8)
    r1 = bat.submit(p1, 8, on_token=cb(1))
    r2 = bat.submit(p2, 6, eos_id=int(_solo(lm, variables, p2, 6)[3]),
                    on_token=cb(2))
    out = bat.run()
    np.testing.assert_array_equal(np.asarray(streamed[1]), out[r1])
    np.testing.assert_array_equal(np.asarray(streamed[2]), out[r2])
    np.testing.assert_array_equal(out[r1], full1)
    assert streamed[2][-1] == out[r2][-1]  # EOS streamed too


def test_on_token_exception_surfaces_to_result_waiters(lm_setup):
    """A raising callback in threaded mode must not strand result()
    waiters in a timeout: the server stops and result() re-raises."""
    lm, variables = lm_setup

    def bad(rid, tok, idx):
        raise RuntimeError("boom-in-callback")

    bat = ContinuousBatcher(lm, variables, slots=1)
    bat.start()
    try:
        rid = bat.submit(np.asarray([1, 2], np.int32), 4, on_token=bad)
        with pytest.raises(RuntimeError) as ei:
            bat.result(rid, timeout=60.0)
        assert "boom-in-callback" in repr(ei.value.__cause__)
    finally:
        bat._stopping = True  # thread already dead; stop() would join it
        with bat._cv:
            bat._cv.notify_all()
        bat._server = None


def test_batcher_logprobs_match_generate(lm_setup):
    """Served logprobs equal generate(return_logprobs=True)'s for the
    same request — greedy and sampled, including the prefill-sampled
    first token."""
    lm, variables = lm_setup
    p1 = np.asarray([1, 2, 3], np.int32)
    p2 = np.asarray([4, 5, 6, 7], np.int32)
    bat = ContinuousBatcher(lm, variables, slots=2)
    r1 = bat.submit(p1, 6)
    r2 = bat.submit(p2, 5, temperature=0.9, top_k=5,
                    rng=jax.random.PRNGKey(7))
    out = bat.run()
    for rid, p, steps, kw in (
        (r1, p1, 6, {}),
        (r2, p2, 5, dict(temperature=0.9, top_k=5,
                         rng=jax.random.PRNGKey(7))),
    ):
        want_t, want_lp = generate(
            lm, variables, jnp.asarray(p)[None], steps,
            return_logprobs=True, **kw,
        )
        np.testing.assert_array_equal(out[rid], np.asarray(want_t)[0])
        np.testing.assert_allclose(
            bat.logprobs(rid), np.asarray(want_lp)[0],
            rtol=2e-4, atol=2e-4,
        )
    with pytest.raises(KeyError):
        bat.logprobs(r1)  # already claimed


def test_fused_staging_transfer_counts(lm_setup):
    """The device-resident hot-path contract, asserted via the batcher's
    transfer-counting shim (every host->device staging call funnels
    through ``_h2d``, surfaced as ``stats()["h2d_transfers"]``):

    - a STEADY-STATE decode tick stages ZERO host arrays (the old path
      staged 7 per tick — tokens/pos/keys/temps/top_ks/top_ps/greedy);
    - an admission stages O(1) fused vectors (prompt ids + one int
      vector + one float vector + key block + insert index + the
      device-row setter's three), NOT one transfer per sampling field;
    - a retirement is one O(1) row-clear dispatch.
    """
    lm, variables = lm_setup
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2, top_k=5)
    p = np.asarray([1, 2, 3], np.int32)

    before = bat.stats()["h2d_transfers"]
    # Max out the per-request sampling surface: temperature + top_k +
    # top_p + rng schedule. O(fields) staging would pay per field.
    r1 = bat.submit(p, 40, temperature=0.9, top_p=0.9,
                    rng=jax.random.PRNGKey(1))
    bat.tick()
    per_admission = bat.stats()["h2d_transfers"] - before
    assert per_admission <= 10, per_admission

    before = bat.stats()["h2d_transfers"]
    for _ in range(4):
        bat.tick()  # request still decoding: pure steady state
    assert bat.stats()["h2d_transfers"] == before

    # Greedy second request (fewest sampling fields) costs the same
    # fused admission — the O(1)-not-O(fields) claim. Long enough not
    # to retire inside the measured tick (retiring is a +1 row-clear).
    before = bat.stats()["h2d_transfers"]
    r2 = bat.submit(p, 20)
    bat.tick()
    greedy_admission = bat.stats()["h2d_transfers"] - before
    assert greedy_admission == per_admission, (
        greedy_admission, per_admission,
    )
    out = bat.run()
    assert set(out) == {r1, r2}


def test_slots_layout_left_in_pr29(lm_setup):
    """``kv_layout=`` survives only as a keyword callers outside the
    package still pass: the default batcher is paged, ``"paged"`` is
    accepted, and the per-slot dense layout raises by name."""
    lm, variables = lm_setup
    bat = ContinuousBatcher(lm, variables, slots=2)
    assert bat.stats()["pool_pages"] == 2 * 1 + 1  # ceil(48 / 128) a slot
    assert not hasattr(bat, "_paged")
    ContinuousBatcher(lm, variables, slots=2, kv_layout="paged")
    for layout in ("slots", "vram"):
        with pytest.raises(ValueError, match="PR 29"):
            ContinuousBatcher(lm, variables, slots=2, kv_layout=layout)
