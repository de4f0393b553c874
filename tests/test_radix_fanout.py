"""Radix prefix cache fan-out and temperature>0 speculation (the
ISSUE-18 surface): ``submit_fanout(prompt, n)`` must be invisible in
outputs — every greedy sibling bit-identical to a solo ``generate()``,
every sampled sibling equal to a serial submit under its split of the
caller's key — while the pager books exactly n-1 copy-on-write forks
and drains balanced. The speculative-sampling verify (accept/reject +
residual resample) rides along: top_k=1 pins it to the greedy stream
with zero statistics, and a seed-pinned distributional gate checks
losslessness IN DISTRIBUTION at real temperatures."""

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
    lm = lm_tiny(vocab=37, max_len=48)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def spec_setup():
    # The test_continuous_spec target: deliberately SMALLER than
    # lm_tiny — losslessness is a scheduling property, not a
    # model-size one, and tier-1 wall time is the budget (ROADMAP.md).
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


# -- copy-on-write fan-out ----------------------------------------------------


def test_fanout_greedy_paged_bit_identical_and_cow_books(lm_setup):
    """``submit_fanout(prompt, n)`` on a paged batcher: every greedy
    sibling's stream is bit-identical to a solo generate() of the same
    prompt, the group books n-1 copy-on-write forks (siblings after
    the first fork the shared last prompt page instead of re-running
    the suffix pass), and the pool drains balanced — no leaked group
    claims, partition exact."""
    lm, variables = lm_setup
    rng = np.random.RandomState(21)
    prompt = rng.randint(0, 37, size=19).astype(np.int32)  # 2 full pages
    bat = ContinuousBatcher(
        lm, variables, slots=4, chunk=4, kv_layout="paged", page_size=8
    )
    rids = bat.submit_fanout(prompt, 3, 5)
    assert len(rids) == len(set(rids)) == 3
    out = bat.run()
    want = _solo(lm, variables, prompt, 5)
    for j, r in enumerate(rids):
        np.testing.assert_array_equal(out[r], want, err_msg=f"sibling {j}")
    st = bat.stats()
    assert st["cow_forks"] == 2
    assert st["fanout_groups"] == 0 and st["pages_in_use"] == 0
    # free already counts the evictable rc=0 cached pages.
    assert st["pages_free"] == st["pool_pages"] - 1


def test_fanout_width_one_degrades_to_plain_submit(lm_setup):
    """n == 1 takes the plain submit path: the same stream, no fan-out
    group and nothing forked."""
    lm, variables = lm_setup
    prompt = np.asarray([5, 6, 7, 8], np.int32)
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=4, page_size=8)
    (rid,) = bat.submit_fanout(prompt, 1, 4)
    assert not bat._fanout_groups  # no group was ever made
    out = bat.run()
    np.testing.assert_array_equal(
        out[rid], _solo(lm, variables, prompt, 4)
    )
    st = bat.stats()
    assert st["cow_forks"] == 0 and st["fanout_groups"] == 0


def test_fanout_sampled_splits_rng_per_sibling(lm_setup):
    """temperature > 0 fan-out: each sibling samples under its own
    split of the caller's key (parallel-sampling semantics — streams
    diverge by design) and equals a serial submit with that split.
    Sampled siblings run the ordinary suffix pass (divergent first
    tokens cannot reuse a forked greedy commit), so no CoW forks are
    booked; only the full prefix pages are shared. Width >= 1 and the
    rng requirement are validated synchronously."""
    lm, variables = lm_setup
    rng = np.random.RandomState(22)
    prompt = rng.randint(0, 37, size=19).astype(np.int32)
    key = jax.random.PRNGKey(11)
    bat = ContinuousBatcher(
        lm, variables, slots=3, chunk=4, kv_layout="paged", page_size=8
    )
    rids = bat.submit_fanout(prompt, 3, 5, temperature=0.9, rng=key)
    out = bat.run()
    for j, (r, k) in enumerate(zip(rids, jax.random.split(key, 3))):
        want = _solo(lm, variables, prompt, 5, temperature=0.9, rng=k)
        np.testing.assert_array_equal(out[r], want, err_msg=f"sibling {j}")
    st = bat.stats()
    assert st["cow_forks"] == 0
    assert st["fanout_groups"] == 0 and st["pages_in_use"] == 0
    with pytest.raises(ValueError, match="rng"):
        bat.submit_fanout(prompt, 2, 3, temperature=0.5)
    with pytest.raises(ValueError, match="width"):
        bat.submit_fanout(prompt, 0, 3)


def test_fanout_cancel_queued_sibling_keeps_group_books_clean(lm_setup):
    """Cancelling a still-queued sibling shrinks the group without
    wedging it: the survivors stream bit-identically (the second
    sibling still forks), the cancelled one returns empty, and the
    group's page claim is released when the last survivor admits."""
    lm, variables = lm_setup
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, 37, size=19).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=4, kv_layout="paged", page_size=8
    )
    rids = bat.submit_fanout(prompt, 3, 4)
    bat.tick()  # admit sibling 0; 1 and 2 queue behind the one slot
    assert bat.cancel(rids[2])
    out = bat.run()
    want = _solo(lm, variables, prompt, 4)
    np.testing.assert_array_equal(out[rids[0]], want)
    np.testing.assert_array_equal(out[rids[1]], want)
    assert out[rids[2]].shape == (0,)
    st = bat.stats()
    assert st["cow_forks"] == 1  # sibling 1 forked before the group died
    assert st["fanout_groups"] == 0 and st["pages_in_use"] == 0


# -- temperature>0 speculation ------------------------------------------------


@pytest.mark.parametrize("page_size", [128, 8])
def test_spec_sampling_topk1_matches_greedy(spec_setup, draft_setup, page_size):
    """Deterministic end-to-end probe of the temperature>0 verify:
    top_k=1 shapes the target to a point mass on its argmax, so the
    speculative-SAMPLING path (accept u < p_t/p_d, residual resample
    on reject) must commit exactly the greedy stream — the adversarial
    draft makes most proposals miss the argmax, so the reject +
    residual-resample branch is exercised with zero statistics."""
    lm, variables = spec_setup
    draft, dvars = draft_setup
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (4, 9, 6)]
    bat = ContinuousBatcher(
        lm, variables, slots=2, draft_lm=draft, draft_variables=dvars,
        speculative=SpeculativeConfig(draft_k=3), page_size=page_size,
    )
    ids = {
        bat.submit(
            p, 8, temperature=0.7, top_k=1, rng=jax.random.PRNGKey(i)
        ): p
        for i, p in enumerate(prompts)
    }
    out = bat.run()
    for rid, p in ids.items():
        np.testing.assert_array_equal(
            out[rid], _solo(lm, variables, p, 8)
        )


@pytest.mark.statistical
def test_spec_sampling_statistical(spec_setup):
    """The seed-pinned distributional gate for temperature>0
    speculation: over many submits of one prompt, the spec batcher's
    token marginal matches a non-spec batcher's (loose total-variation
    bound — lossless IN DISTRIBUTION, not bit-identical), while the
    self-draft's acceptance stays above 1/draft_k, i.e. each verify
    pass commits MORE than the one correction token a spec-less tick
    would (the whole point of speculating at temperature>0)."""
    lm, variables = spec_setup
    prompt = np.asarray([3, 1, 4, 1], np.int32)
    # temp 0.3 concentrates the tiny model's target enough that the
    # self-draft's argmax proposals carry real target mass (acceptance
    # ~0.5; at temp 0.5 this model measures ~0.2 and each verify pass
    # commits barely more than its correction token) while leaving
    # several tokens of support for the distributional comparison.
    steps, m, draft_k, temp = 3, 72, 4, 0.3
    counts = {}
    for arm in ("nonspec", "spec"):
        extra = (
            dict(
                draft_lm=lm, draft_variables=variables,
                speculative=SpeculativeConfig(draft_k=draft_k),
            )
            if arm == "spec"
            else {}
        )
        bat = ContinuousBatcher(lm, variables, slots=4, **extra)
        hist = np.zeros(37, np.int64)
        for lo in range(0, m, 12):  # batches: stay inside queue bounds
            rids = [
                bat.submit(
                    prompt, steps, temperature=temp,
                    rng=jax.random.PRNGKey(i),
                )
                for i in range(lo, min(lo + 12, m))
            ]
            out = bat.run()
            for r in rids:
                assert len(out[r]) == steps
                np.add.at(hist, out[r], 1)
        counts[arm] = hist
        if arm == "spec":
            acc = bat.stats()["spec_acceptance"]
            assert acc > 1.0 / draft_k, acc
    p = counts["nonspec"] / counts["nonspec"].sum()
    q = counts["spec"] / counts["spec"].sum()
    tvd = 0.5 * float(np.abs(p - q).sum())
    # Loose bound: ~2x the pinned seeds' sampling noise. A failure
    # after an intentional sampling change means re-deriving the
    # pinned expectation, not loosening this (conftest marker note).
    assert tvd < 0.35, tvd
