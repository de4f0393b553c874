"""Tensor-parallel continuous serving: a batcher sharded over a 4-device
sim mesh must be INVISIBLE in outputs — bit-identical greedy streams vs
the tp=1 batcher and the single-device ``generate()`` across staggered
admits/retires/cancels, inside one page and across pages, including speculative mode —
while per-device KV bytes shrink to logical/tp, the two-program compile
footprint holds, and a steady-state tick still stages zero host arrays."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import ParallelConfig, SpeculativeConfig
from adapt_tpu.models.transformer_lm import generate, transformer_lm
from adapt_tpu.runtime.continuous import ContinuousBatcher


@pytest.fixture(scope="module")
def lm_setup():
    # GQA with kv_heads divisible by the tp=4 mesh: the KV cache's head
    # axis is what shards, so this is the shape class TP serving exists
    # for (heads=8 queries folding 2-per-KV-head on every shard).
    lm = transformer_lm(37, 32, 2, 8, 64, max_len=48, kv_heads=4,
                        name="tp_target")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def draft_setup():
    # Small independent draft; stays REPLICATED under TP by design.
    draft = transformer_lm(37, 16, 1, 1, 32, max_len=48, name="tp_draft")
    variables = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    return draft, variables


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


def _bat(lm, variables, sim_mesh, tp, **kw):
    return ContinuousBatcher(
        lm, variables, mesh=sim_mesh(tp), parallel=ParallelConfig(tp=tp),
        **kw,
    )


def _staggered_run(bat, prompts, steps, cancel_idx=None):
    """Staggered admits + a mid-flight cancel; returns {req_id: idx} and
    the output dict."""
    ids = {}
    for i in range(2):
        ids[bat.submit(prompts[i], steps[i])] = i
    bat.tick()
    bat.tick()
    for i in range(2, len(prompts)):
        ids[bat.submit(prompts[i], steps[i])] = i
    cancelled = None
    if cancel_idx is not None:
        cancelled = next(r for r, i in ids.items() if i == cancel_idx)
        bat.tick()
        assert bat.cancel(cancelled)
    return ids, cancelled, bat.run()


@pytest.mark.parametrize("page_size", [128, 8])
def test_tp4_bit_identical_to_tp1_staggered(lm_setup, sim_mesh, page_size):
    """tp=4 and tp=1 batchers run the same staggered workload (admits,
    retirements, a mid-flight cancel): every stream is bit-identical
    between them AND equals its solo single-device generate(); the tp=4
    caches hold exactly logical/4 bytes per device."""
    lm, variables = lm_setup
    rng = np.random.RandomState(1)
    # Request 0 is long-running and admitted in the FIRST wave, so the
    # mid-flight cancel below always hits a slot-bound request (a
    # queued-cancel would return an empty stream and test nothing).
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 9, 5, 12, 7)]
    steps = [20, 4, 8, 3, 6]
    kw = dict(slots=3, chunk=2, page_size=page_size)
    outs = {}
    for tp in (1, 4):
        bat = _bat(lm, variables, sim_mesh, tp, **kw)
        ids, cancelled, out = _staggered_run(
            bat, prompts, steps, cancel_idx=0
        )
        outs[tp] = {ids[r]: out[r] for r in ids}
        st = bat.stats()
        assert st["tp"] == tp
        assert st["cache_bytes_per_device"] * tp == st["cache_bytes"]
        assert st["active"] == 0
    for i in range(5):
        np.testing.assert_array_equal(
            outs[4][i], outs[1][i], err_msg=f"req {i}: tp4 != tp1"
        )
        solo = _solo(lm, variables, prompts[i], steps[i])
        if i == 0:  # cancelled mid-flight: partial prefix of solo
            got = outs[4][i]
            assert 0 < len(got) < steps[i]
            np.testing.assert_array_equal(got, solo[: len(got)])
        else:
            np.testing.assert_array_equal(
                outs[4][i], solo, err_msg=f"req {i}: tp4 != generate"
            )


@pytest.mark.parametrize("page_size", [128, 8])
def test_tp4_speculative_lossless(lm_setup, draft_setup, sim_mesh, page_size):
    """Batched speculation under tp=4 (target sharded, draft replicated)
    stays per-row lossless vs solo single-device generate() inside one
    page and across pages, and the whole workload compiles exactly ONE verify variant
    (the tp4-vs-tp1 bitwise claim is pinned by the non-spec test above;
    a tp=1 spec batcher here would only re-pay its compiles)."""
    from adapt_tpu.utils.profiling import global_compile_sentinel

    lm, variables = lm_setup
    draft, dvars = draft_setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (4, 7, 2)]
    steps = [7, 9, 5]
    kw = dict(slots=2, draft_lm=draft, draft_variables=dvars,
              speculative=SpeculativeConfig(draft_k=3),
              page_size=page_size)
    sentinel = global_compile_sentinel()
    bat = _bat(lm, variables, sim_mesh, 4, **kw)
    before = sentinel.compiles("continuous.spec_verify")
    ids = {bat.submit(p, s): i
           for i, (p, s) in enumerate(zip(prompts, steps))}
    out = bat.run()
    assert 0.0 <= bat.stats()["spec_acceptance"] <= 1.0
    # Two-program steady state survives GSPMD: this batcher's whole
    # staggered workload compiled exactly ONE verify variant.
    assert sentinel.compiles("continuous.spec_verify") - before == 1
    for rid, i in ids.items():
        np.testing.assert_array_equal(
            out[rid], _solo(lm, variables, prompts[i], steps[i]),
            err_msg=f"req {i}",
        )


def test_tp4_two_programs_and_zero_h2d(lm_setup, sim_mesh):
    """The hot-path invariants survive sharding: across churn the tp=4
    batcher keeps the step-chunk program at ONE compiled variant (the
    compile sentinel's watch — GSPMD partitioning must not fork shapes),
    and a steady-state tick stages zero host arrays."""
    from adapt_tpu.utils.profiling import global_compile_sentinel

    lm, variables = lm_setup
    sentinel = global_compile_sentinel()
    bat = _bat(lm, variables, sim_mesh, 4, slots=2, chunk=2)
    before = sentinel.compiles("continuous.step_chunk")
    r1 = bat.submit(np.asarray([1, 2, 3], np.int32), 30)
    bat.tick()
    assert sentinel.compiles("continuous.step_chunk") - before == 1
    h0 = bat.stats()["h2d_transfers"]
    for _ in range(4):
        bat.tick()  # pure steady state under the mesh
    assert bat.stats()["h2d_transfers"] == h0
    entries = sentinel.compiles("continuous.step_chunk")
    # Churn: a second wave admits, retires, and re-admits — no variant
    # may be added to the decode program.
    r2 = bat.submit(np.asarray([5, 6], np.int32), 3)
    out = bat.run()
    r3 = bat.submit(np.asarray([9, 9, 9, 9], np.int32), 5)
    out.update(bat.run())
    assert set(out) == {r1, r2, r3}
    assert sentinel.compiles("continuous.step_chunk") == entries


def test_tp_memory_gauges_per_device(lm_setup, sim_mesh):
    """The memory sources split logical vs per-device bytes:
    memory.pool_bytes_per_device == pool_bytes / tp under a
    head-sharded mesh, and equal to it on one device."""
    lm, variables = lm_setup
    for kw in ({}, {"page_size": 8}):
        sharded = _bat(lm, variables, sim_mesh, 4, slots=2, **kw)
        ms = sharded._memory_stats()
        assert (
            ms["memory.pool_bytes_per_device"] * 4
            == ms["memory.pool_bytes"]
        )
    # tp=1 (and no-mesh) batchers report per-device == logical.
    flat = ContinuousBatcher(lm, variables, slots=2)
    ms = flat._memory_stats()
    assert ms["memory.pool_bytes_per_device"] == ms["memory.pool_bytes"]
    assert flat.stats()["tp"] == 1


def test_tp_validation(lm_setup, sim_mesh):
    """Config/mesh mismatches and indivisible models fail eagerly, by
    name — not as opaque GSPMD errors mid-admission."""
    lm, variables = lm_setup
    mesh = sim_mesh(4)
    with pytest.raises(ValueError, match="requires a mesh"):
        ContinuousBatcher(
            lm, variables, slots=2, parallel=ParallelConfig(tp=4)
        )
    with pytest.raises(ValueError, match="!= mesh"):
        ContinuousBatcher(
            lm, variables, slots=2, mesh=mesh,
            parallel=ParallelConfig(tp=2),
        )
    with pytest.raises(ValueError, match="axis"):
        ContinuousBatcher(
            lm, variables, slots=2, mesh=sim_mesh(4, axis="dp"),
        )
    with pytest.raises(ValueError, match="tp"):
        ParallelConfig(tp=0)
    # kv_heads=2 does not divide tp=4: the GQA-aware check fires.
    odd = transformer_lm(37, 32, 1, 4, 64, max_len=48, kv_heads=2,
                         name="tp_odd")
    ovars = odd.graph.init(
        jax.random.PRNGKey(2), jnp.zeros((1, 4), jnp.int32)
    )
    with pytest.raises(ValueError, match="KV"):
        ContinuousBatcher(odd, ovars, slots=2, mesh=mesh)


def test_tp_sampled_and_mixed_traffic(lm_setup, sim_mesh):
    """Sampled requests (per-request key schedules, top-k/top-p
    truncation) ride the sharded programs unchanged: each stream equals
    its solo generate() with the same knobs."""
    lm, variables = lm_setup
    p1 = np.asarray([1, 2, 3], np.int32)
    p2 = np.asarray([4, 5, 6, 7], np.int32)
    bat = _bat(lm, variables, sim_mesh, 4, slots=2)
    r1 = bat.submit(p1, 6, temperature=0.9, top_k=5,
                    rng=jax.random.PRNGKey(21))
    r2 = bat.submit(p2, 5)
    out = bat.run()
    np.testing.assert_array_equal(
        out[r1],
        _solo(lm, variables, p1, 6, temperature=0.9, top_k=5,
              rng=jax.random.PRNGKey(21)),
    )
    np.testing.assert_array_equal(out[r2], _solo(lm, variables, p2, 5))
