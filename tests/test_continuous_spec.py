"""Batched speculative decoding in the continuous batcher is LOSSLESS
per row: whatever the draft proposes and however acceptance staggers
across slots, every request's emitted stream must equal its solo
``generate()`` output token-for-token — across staggered admissions,
retirements, cancels, EOS/stop boundaries, and both KV layouts (dense
slot strips and paged pools). The fixed-shape contract rides along:
the spec tick compiles exactly TWO programs (draft scan, fused verify)
however rows desynchronize, and a steady-state spec tick stages zero
host arrays."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import SpeculativeConfig
from adapt_tpu.models.transformer_lm import (
    generate,
    lm_tiny,
    transformer_lm,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher


@pytest.fixture(scope="module")
def lm_setup():
    # Deliberately SMALLER than lm_tiny (2 blocks, dim 32): every
    # batcher instance compiles its own verify/admission programs, and
    # losslessness is a scheduling property, not a model-size one —
    # tier-1 wall time is the budget here (ROADMAP.md).
    lm = transformer_lm(37, 32, 2, 2, 64, max_len=48, name="spec_target")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def draft_setup():
    # Same vocab, smaller independent model: a REAL draft whose
    # proposals are mostly wrong (adversarial acceptance).
    draft = transformer_lm(37, 16, 1, 1, 32, max_len=48, name="draft")
    variables = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    return draft, variables


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


def test_spec_staggered_desync_matches_generate(lm_setup):
    """Perfect draft (the target itself), staggered arrivals, mixed
    lengths: every stream equals solo generate(), acceptance is 1.0,
    and the tick count proves multi-token commits (fewer verify passes
    than emitted tokens — the tokens-per-weight-stream win)."""
    lm, variables = lm_setup
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 9, 5, 12, 7)]
    steps = [9, 14, 8, 3, 11]
    bat = ContinuousBatcher(
        lm, variables, slots=3, draft_lm=lm, draft_variables=variables,
        speculative=SpeculativeConfig(draft_k=3),
    )
    ids = {}
    for i in range(2):
        ids[bat.submit(prompts[i], steps[i])] = i
    bat.tick()
    bat.tick()
    for i in range(2, 5):  # arrive while the first two are mid-decode
        ids[bat.submit(prompts[i], steps[i])] = i
    out = bat.run()
    for rid, i in ids.items():
        np.testing.assert_array_equal(
            out[rid], _solo(lm, variables, prompts[i], steps[i]),
            err_msg=f"req {i}",
        )
    s = bat.stats()
    assert s["spec_acceptance"] == 1.0
    # A perfect draft commits draft_k + 1 = 4 tokens per slot-tick past
    # the prefill token; the plain tick commits chunk of them per
    # compiled pass. The whole 45-token workload must take well under
    # one tick per token.
    assert s["ticks"] < sum(steps)
    # Logprob carry-through: the spec tick's fused verify records the
    # same per-token scores generate(return_logprobs=True) reports.
    rid0 = next(r for r, i in ids.items() if i == 0)
    want_t, want_lp = generate(
        lm, variables, jnp.asarray(prompts[0])[None], steps[0],
        return_logprobs=True,
    )
    np.testing.assert_allclose(
        bat.logprobs(rid0), np.asarray(want_lp)[0], rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("draft_k", [1, 4])
def test_spec_adversarial_draft_lossless(lm_setup, draft_setup, draft_k):
    """An independent (mostly-rejected) draft changes ONLY the tick
    count — rows at acceptance 0 still advance one correction token per
    tick and match generate() exactly."""
    lm, variables = lm_setup
    draft, dvars = draft_setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (4, 7, 2)]
    bat = ContinuousBatcher(
        lm, variables, slots=2, draft_lm=draft, draft_variables=dvars,
        speculative=SpeculativeConfig(draft_k=draft_k),
    )
    ids = {bat.submit(p, 7): p for p in prompts}
    out = bat.run()
    for rid, p in ids.items():
        np.testing.assert_array_equal(
            out[rid], _solo(lm, variables, p, 7)
        )
    assert 0.0 <= bat.stats()["spec_acceptance"] <= 1.0


def test_spec_paged_with_prefix_sharing(lm_setup, draft_setup):
    """Speculation over the paged layout composes with prefix caching:
    requests sharing a prompt prefix (one admitted via shared pages)
    still match their solo streams, and pages free on retirement."""
    lm, variables = lm_setup
    draft, dvars = draft_setup
    shared = np.arange(1, 17, dtype=np.int32)  # two full 8-token pages
    p2 = np.concatenate([shared, [20, 21]]).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_layout="paged", page_size=8,
        draft_lm=draft, draft_variables=dvars,
    )
    r1 = bat.submit(shared, 6)
    r2 = bat.submit(p2, 8)
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1], _solo(lm, variables, shared, 6)
    )
    np.testing.assert_array_equal(out[r2], _solo(lm, variables, p2, 8))
    s = bat.stats()
    assert s["prefix_hits"] >= 1  # r2 rode r1's registered pages
    assert s["pages_in_use"] == 0  # slack pages came back too


def test_spec_eos_stop_cancel_at_acceptance_boundaries(lm_setup):
    """EOS inside an accepted block finishes the request there (the
    rest of the block is discarded garbage); stop sequences and cancels
    latch through the same commit path."""
    lm, variables = lm_setup
    p = np.asarray([4, 8, 15], np.int32)
    full = _solo(lm, variables, p, 10)
    eos = int(full[3])  # finishes after 4 tokens, mid-accepted-block
    bat = ContinuousBatcher(
        lm, variables, slots=2, draft_lm=lm, draft_variables=variables,
        speculative=SpeculativeConfig(draft_k=4),
    )
    r_eos = bat.submit(p, 10, eos_id=eos)
    stop_seq = [int(full[1]), int(full[2])]
    r_stop = bat.submit(p, 10, stop=[stop_seq])
    out = bat.run()
    n = len(out[r_eos])
    assert out[r_eos][-1] == eos and eos not in out[r_eos][:-1]
    np.testing.assert_array_equal(
        out[r_eos], _solo(lm, variables, p, 10, eos_id=eos)[:n]
    )
    assert list(out[r_stop][-2:]) == stop_seq
    np.testing.assert_array_equal(
        out[r_stop], full[: len(out[r_stop])]
    )
    # Cancel mid-flight: partial stream, slot freed, no leaked markers.
    r_long = bat.submit(np.asarray([1, 2], np.int32), 30)
    bat.tick()
    assert bat.cancel(r_long)
    out = bat.run()
    partial = out[r_long]
    assert 0 < len(partial) < 30
    np.testing.assert_array_equal(
        partial,
        _solo(lm, variables, np.asarray([1, 2], np.int32), 30)[
            : len(partial)
        ],
    )
    assert not bat._cancelled


def test_spec_tick_fixed_shape_zero_h2d_and_observability(
    lm_setup, draft_setup,
):
    """The TPU shape contract, counter-asserted: across a whole
    staggered workload the spec tick compiles exactly TWO programs (the
    draft scan and the fused verify) — per-slot acceptance history
    never forks a variant — and a steady-state spec tick stages ZERO
    host arrays (the PR-1 fused-staging contract carried through). The
    observability carry-through rides the same workload:
    continuous.spec_acceptance gauge + spec_accepted_per_tick histogram
    in the registry, decode.draft / decode.verify spans in the tracer
    tagged with the tick's request ids."""
    from adapt_tpu.utils.metrics import global_metrics
    from adapt_tpu.utils.profiling import global_compile_sentinel
    from adapt_tpu.utils.tracing import global_tracer

    lm, variables = lm_setup
    draft, dvars = draft_setup
    global_metrics().reset()
    tracer = global_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = True
    try:
        # The two-program guard is expressed through the compile
        # sentinel's PUBLIC API (utils.profiling): constructing the
        # batcher registers both decode programs (and re-arms their
        # warmup); compiles() reads the watched jit cache sizes — no
        # raw _cache_size() poking.
        sentinel = global_compile_sentinel()
        bat = ContinuousBatcher(
            lm, variables, slots=2, draft_lm=draft, draft_variables=dvars,
        )
        assert {
            "continuous.spec_verify", "speculative.draft_chunk"
        } <= set(sentinel.watched())
        verify_before = sentinel.compiles("continuous.spec_verify")
        r1 = bat.submit(np.asarray([1, 2, 3], np.int32), 40)
        bat.tick()  # admission + first round compiles both programs
        # Exactly ONE verify variant for this batcher (self is the jit
        # key; draft_chunk may already be warm from an
        # identically-shaped earlier batcher — the draft scan is shared
        # across instances by design, its own fixed-shape evidence).
        assert (
            sentinel.compiles("continuous.spec_verify") - verify_before
            == 1
        )
        draft_entries = sentinel.compiles("speculative.draft_chunk")
        verify_entries = sentinel.compiles("continuous.spec_verify")
        before = bat.stats()["h2d_transfers"]
        for _ in range(4):
            bat.tick()  # pure steady state: desynchronized acceptance
        assert bat.stats()["h2d_transfers"] == before
        # Staggered churn: admissions, retirements, a second wave —
        # none of it may add a compiled variant to either decode
        # program.
        r2 = bat.submit(np.asarray([5, 6], np.int32), 3)
        out = {}
        out.update(bat.run())
        r3 = bat.submit(np.asarray([9, 9, 9, 9, 9], np.int32), 6)
        out.update(bat.run())
        assert set(out) == {r1, r2, r3}
        assert sentinel.compiles("speculative.draft_chunk") == draft_entries
        assert (
            sentinel.compiles("continuous.spec_verify") == verify_entries
        )
        snap = global_metrics().snapshot()
        assert "continuous.spec_acceptance" in snap["gauges"]
        assert (
            snap["histograms"]["continuous.spec_accepted_per_tick"][
                "count"
            ]
            >= 1
        )
        spans = {s.name for s in tracer.spans()}
        assert {"decode.draft", "decode.verify"} <= spans
        assert any(
            s.name == "decode.verify" and r1 in s.attrs["requests"]
            for s in tracer.spans()
        )
    finally:
        tracer.enabled = was_enabled


def test_spec_validation(lm_setup, draft_setup):
    lm, variables = lm_setup
    draft, dvars = draft_setup
    # temperature>0 is SERVED speculatively now (speculative sampling,
    # lossless in distribution) — the old greedy-only rejection was a
    # synchronous submit-time ValueError, so its absence is checked at
    # submit; the served streams themselves are covered end-to-end in
    # test_radix_fanout.py (no need to pay a spec compile here).
    bat = ContinuousBatcher(
        lm, variables, slots=2, draft_lm=draft, draft_variables=dvars
    )
    rid = bat.submit(np.asarray([1], np.int32), 2, temperature=0.7,
                     rng=jax.random.PRNGKey(0))
    assert bat.cancel(rid)
    with pytest.raises(ValueError, match="draft_variables"):
        ContinuousBatcher(lm, variables, slots=2, draft_lm=draft)
    with pytest.raises(ValueError, match="requires draft_lm"):
        ContinuousBatcher(
            lm, variables, slots=2, speculative=SpeculativeConfig()
        )
    with pytest.raises(ValueError, match="vocab"):
        other = lm_tiny(vocab=17, max_len=48)
        ovars = other.graph.init(
            jax.random.PRNGKey(3), jnp.zeros((1, 4), jnp.int32)
        )
        ContinuousBatcher(
            lm, variables, slots=2, draft_lm=other, draft_variables=ovars
        )
    with pytest.raises(ValueError, match="max_len"):
        short = lm_tiny(vocab=37, max_len=32)
        svars = short.graph.init(
            jax.random.PRNGKey(4), jnp.zeros((1, 4), jnp.int32)
        )
        ContinuousBatcher(
            lm, variables, slots=2, draft_lm=short, draft_variables=svars
        )
    # Spec + int8 caches is a supported composition now
    # (tests/test_quant_serving pins losslessness vs generate(int8)).
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_cache_dtype="int8",
        draft_lm=draft, draft_variables=dvars,
    )
    assert isinstance(bat._caches[0], tuple)  # (values, K scales, V scales)
    with pytest.raises(ValueError, match="draft_k"):
        SpeculativeConfig(draft_k=0)


# -- slow parameterizations: the batched-losslessness fuzz ---------------


@pytest.mark.slow
@pytest.mark.parametrize("page_size", [128, 8])
@pytest.mark.parametrize("perfect", [True, False])
def test_spec_fuzz_staggered_lossless(lm_setup, draft_setup, page_size,
                                      perfect):
    """Randomized serving traffic against the speculative tick:
    staggered admits, retirements, cancels, mixed prompt lengths and
    step counts, perfect and adversarial drafts, one page a request
    and several — every surviving stream token-for-token equals its solo
    generate()."""
    lm, variables = lm_setup
    draft, dvars = draft_setup
    d_lm, d_vars = (lm, variables) if perfect else (draft, dvars)
    rng = np.random.RandomState(17 if perfect else 18)
    bat = ContinuousBatcher(
        lm, variables, slots=3, draft_lm=d_lm, draft_variables=d_vars,
        speculative=SpeculativeConfig(draft_k=3), page_size=page_size,
    )
    want, cancelled = {}, set()
    pending = []
    for i in range(12):
        n = int(rng.randint(1, 14))
        steps = int(rng.randint(1, 12))
        p = rng.randint(0, 37, size=n).astype(np.int32)
        pending.append((p, steps))
    submitted = {}
    out = {}
    k = 0
    while pending or submitted:
        # admit a burst of 0-2 requests
        for _ in range(int(rng.randint(0, 3))):
            if not pending:
                break
            p, steps = pending.pop()
            rid = bat.submit(p, steps)
            submitted[rid] = (p, steps)
        bat.tick()
        k += 1
        # occasionally cancel a live request
        if submitted and rng.rand() < 0.15:
            rid = list(submitted)[int(rng.randint(len(submitted)))]
            if bat.cancel(rid):
                cancelled.add(rid)
        with bat._cv:
            done_now = [r for r in submitted if r in bat._done]
        for r in done_now:
            want[r] = submitted.pop(r)
        assert k < 500
    out = bat.run()
    for rid, (p, steps) in want.items():
        got = out[rid]
        solo = _solo(lm, variables, p, steps)
        if rid in cancelled:
            np.testing.assert_array_equal(got, solo[: len(got)])
        else:
            np.testing.assert_array_equal(got, solo, err_msg=f"req {rid}")


@pytest.mark.slow
def test_spec_gqa_rope_window_paged_lossless(draft_setup):
    """The serving-era architecture knobs compose with batched
    speculation: a GQA + RoPE + sliding-window target served paged,
    with mid-request page recycling behind the window, still matches
    solo generate() per row."""
    vocab = 37
    lm = transformer_lm(vocab, 32, 2, 4, 48, max_len=48, kv_heads=2,
                        window=16, pos="rope")
    variables = lm.graph.init(
        jax.random.PRNGKey(50), jnp.zeros((1, 4), jnp.int32)
    )
    draft, dvars = draft_setup
    rng = np.random.RandomState(51)
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in (3, 9, 17)]
    steps = [24, 12, 30]
    for d_lm, d_vars in ((lm, variables), (draft, dvars)):
        bat = ContinuousBatcher(
            lm, variables, slots=2, kv_layout="paged", page_size=8,
            draft_lm=d_lm, draft_variables=d_vars,
            speculative=SpeculativeConfig(draft_k=2),
        )
        ids = {bat.submit(p, s): i
               for i, (p, s) in enumerate(zip(prompts, steps))}
        out = bat.run()
        for rid, i in ids.items():
            np.testing.assert_array_equal(
                out[rid], _solo(lm, variables, prompts[i], steps[i]),
                err_msg=f"req {i} draft={'self' if d_lm is lm else 'adv'}",
            )
