"""Quantized KV serving everywhere: int8 is a property of the pool's
format that every program family composes with — speculative verify,
prefix caching, chunked prefill, tensor parallelism — not a special
mode of one path.

The contract stack: quantized batcher streams are bit-identical to the
same-quantized solo path (``generate(kv_cache_dtype="int8")``) on the
whole-prompt-prefill paths across staggered admits/retires/cancels,
inside one page and across pages, including speculative mode; top-1 agreement vs native fp32
stays above a bound; the hot-path invariants (zero h2d per steady tick,
two-program compile footprint) survive quantization; and the memory
gauges report the capacity win honestly (scale planes counted,
``memory.kv_bytes_ratio`` observable)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import ParallelConfig, SpeculativeConfig
from adapt_tpu.models.transformer_lm import (
    generate,
    lm_tiny,
    logits_full,
    transformer_lm,
)
from adapt_tpu.ops.quantize import (
    QuantizedTensor,
    dequantize_params,
    quantize_params,
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
    # Small spec-sized target + independent draft (the
    # test_continuous_spec sizing rationale: losslessness is a
    # scheduling property, tier-1 wall time is the budget).
    lm = transformer_lm(37, 32, 2, 2, 64, max_len=48, name="q_target")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    draft = transformer_lm(37, 16, 1, 1, 32, max_len=48, name="q_draft")
    dvars = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables, draft, dvars


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


# -- quantized paged pools ---------------------------------------------------


def test_int8_paged_staggered_matches_generate_int8(lm_setup):
    """Quantized PAGED pools reproduce generate(kv_cache_dtype="int8")
    exactly across staggered admits/retires/cancels."""
    lm, variables = lm_setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 9, 5, 12, 7)]
    # Request 0 is long-running and admitted in the FIRST wave, so the
    # mid-flight cancel below always hits a slot-bound request.
    steps = [20, 4, 8, 3, 6]
    bat = ContinuousBatcher(
        lm, variables, slots=3, chunk=4, kv_layout="paged", page_size=16,
        kv_cache_dtype="int8",
    )
    ids = {}
    for i in range(2):
        ids[bat.submit(prompts[i], steps[i])] = i
    bat.tick()
    for i in range(2, 5):  # arrive while the first two are mid-decode
        ids[bat.submit(prompts[i], steps[i])] = i
    bat.tick()
    cancelled = next(r for r, i in ids.items() if i == 0)
    assert bat.cancel(cancelled)
    out = bat.run()
    assert set(out) == set(ids)
    for rid, i in ids.items():
        want = _solo(lm, variables, prompts[i], steps[i],
                     kv_cache_dtype="int8")
        if rid == cancelled:
            got = out[rid]
            assert 0 < len(got) < steps[i]
            np.testing.assert_array_equal(got, want[: len(got)])
        else:
            np.testing.assert_array_equal(
                out[rid], want, err_msg=f"req {i}"
            )
    st = bat.stats()
    assert st["pages_in_use"] == 0  # pairs drained back to the pool
    # int8 values + f32 scale planes vs f32 native: (hd + 4) / (4 * hd).
    hd = lm.graph.node(lm.block_names[0]).module.head_dim
    assert st["cache_bytes_ratio"] == pytest.approx((hd + 4) / (4 * hd))


def test_int8_paged_prefix_cache_reuses_quantized_pages(lm_setup):
    """Prefix-cached QUANTIZED pages carry their scales: the second
    admission shares the first's pages (hits counted) and reproduces
    the exact cached prefix — the stream still equals the solo
    quantized path for this workload."""
    lm, variables = lm_setup
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 37, size=37).astype(np.int32)  # 2 full pages
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged", page_size=16,
        kv_cache_dtype="int8",
    )
    r1 = bat.submit(prompt, 5)
    out1 = bat.run()
    assert bat._pager.stats().cached == 2
    # The shared pages' SCALE plane is live (registered pages hold real
    # quantized prompt K/V, not zeros) — the reuse-stays-exact
    # precondition.
    k_scales = np.asarray(bat._caches[0][1])  # (values, K scales, V scales)
    shared = [p for p in range(1, bat._pool_pages)
              if p in bat._pager._key_of]
    assert shared and all(k_scales[p].any() for p in shared)
    r2 = bat.submit(prompt, 5)
    out2 = bat.run()
    st = bat._pager.stats()
    assert st.prefix_hits == 2 and st.cached == 2
    want = _solo(lm, variables, prompt, 5, kv_cache_dtype="int8")
    np.testing.assert_array_equal(out1[r1], want)
    np.testing.assert_array_equal(out2[r2], want)


def test_int8_chunked_prefill_matches_generate_int8(lm_setup):
    """Chunked prefill over quantized pools: one page-chunk pass per
    tick, chunk K/V quantized at each write, greedy stream equal to the
    solo quantized path for this workload (the suffix passes attend the
    already-quantized window — documented fine print; greedy holds
    here)."""
    lm, variables = lm_setup
    rng = np.random.RandomState(12)
    short = rng.randint(0, 37, size=4).astype(np.int32)
    long_p = rng.randint(0, 37, size=40).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=16,
        prefill_chunk=16, kv_cache_dtype="int8",
    )
    r_short = bat.submit(short, 8)
    bat.tick()
    r_long = bat.submit(long_p, 4)
    bat.tick()  # long mid-prefill while short decodes
    assert bat.slots[1].pf_done >= 0
    out = bat.run()
    np.testing.assert_array_equal(
        out[r_short], _solo(lm, variables, short, 8, kv_cache_dtype="int8")
    )
    np.testing.assert_array_equal(
        out[r_long], _solo(lm, variables, long_p, 4, kv_cache_dtype="int8")
    )


def test_int8_top1_agreement_vs_fp32_both_page_sizes(lm_setup):
    """Quantization is allowed to perturb logits, not to wreck them:
    served int8 greedy streams agree with the native fp32 stream on the
    overwhelming majority of positions, at both page sizes."""
    lm, variables = lm_setup
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (4, 7, 3)]
    agree, total = 0, 0
    for page_size in (128, 16):
        bat = ContinuousBatcher(
            lm, variables, slots=2, kv_cache_dtype="int8",
            page_size=page_size,
        )
        ids = {bat.submit(p, 8): p for p in prompts}
        out = bat.run()
        for rid, p in ids.items():
            native = _solo(lm, variables, p, 8)
            agree += int((out[rid] == native).sum())
            total += 8
    assert total == 48
    assert agree / total >= 0.75, f"top-1 agreement {agree}/{total}"


def test_int8_paged_hot_path_invariants(lm_setup):
    """The hot-path contracts survive quantization: a steady-state int8
    paged tick stages ZERO host arrays, and churn (admit/retire/
    re-admit) adds no compiled variant to the decode program
    (sentinel-checked, the PR-4 public API)."""
    from adapt_tpu.utils.profiling import global_compile_sentinel

    lm, variables = lm_setup
    sentinel = global_compile_sentinel()
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=16,
        kv_cache_dtype="int8",
    )
    before = sentinel.compiles("continuous.step_chunk")
    r1 = bat.submit(np.asarray([1, 2, 3], np.int32), 30)
    bat.tick()
    assert sentinel.compiles("continuous.step_chunk") - before == 1
    h0 = bat.stats()["h2d_transfers"]
    for _ in range(4):
        bat.tick()  # pure steady state over quantized pools
    assert bat.stats()["h2d_transfers"] == h0
    entries = sentinel.compiles("continuous.step_chunk")
    r2 = bat.submit(np.asarray([5, 6], np.int32), 3)
    out = bat.run()
    r3 = bat.submit(np.asarray([9, 9, 9, 9], np.int32), 5)
    out.update(bat.run())
    assert set(out) == {r1, r2, r3}
    assert sentinel.compiles("continuous.step_chunk") == entries


# -- quantized speculative verify --------------------------------------------


@pytest.mark.parametrize("page_size", [128, 8])
def test_int8_spec_lossless_vs_generate_int8(spec_setup, page_size):
    """Speculative decoding over int8 pools: the verify chunk
    quantizes its multi-token appends through the shared absmax scheme,
    so every stream equals the solo quantized greedy path
    token-for-token — whatever the draft proposes, at both page
    sizes."""
    lm, variables, draft, dvars = spec_setup
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 9, 5)]
    steps = [9, 14, 8]
    # Adversarial independent draft at both page sizes; the perfect
    # draft (the target itself — exercises acceptance > 0, multi-token
    # commits) rides one only: acceptance depth is page-blind, and each
    # extra spec batcher is a full compile bill against the tier-1
    # wall-time budget.
    drafts = [(draft, dvars)]
    if page_size == 128:
        drafts.append((lm, variables))
    for d_lm, d_vars in drafts:
        bat = ContinuousBatcher(
            lm, variables, slots=2, kv_cache_dtype="int8",
            draft_lm=d_lm, draft_variables=d_vars,
            speculative=SpeculativeConfig(draft_k=3),
            page_size=page_size,
        )
        ids = {bat.submit(p, s): (p, s)
               for p, s in zip(prompts, steps)}
        out = bat.run()
        for rid, (p, s) in ids.items():
            np.testing.assert_array_equal(
                out[rid],
                _solo(lm, variables, p, s, kv_cache_dtype="int8"),
                err_msg=f"page_size={page_size} "
                        f"draft={'self' if d_lm is lm else 'adv'}",
            )
        assert 0.0 <= bat.stats()["spec_acceptance"] <= 1.0


def test_int8_spec_two_programs_zero_h2d(spec_setup):
    """The spec tick's fixed-shape contract holds under quantization:
    exactly ONE verify variant for the whole staggered workload and
    zero host arrays per steady-state tick."""
    from adapt_tpu.utils.profiling import global_compile_sentinel

    lm, variables, draft, dvars = spec_setup
    sentinel = global_compile_sentinel()
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_cache_dtype="int8",
        draft_lm=draft, draft_variables=dvars,
    )
    before = sentinel.compiles("continuous.spec_verify")
    r1 = bat.submit(np.asarray([1, 2, 3], np.int32), 30)
    bat.tick()
    assert sentinel.compiles("continuous.spec_verify") - before == 1
    h0 = bat.stats()["h2d_transfers"]
    for _ in range(4):
        bat.tick()
    assert bat.stats()["h2d_transfers"] == h0
    entries = sentinel.compiles("continuous.spec_verify")
    r2 = bat.submit(np.asarray([5, 6], np.int32), 3)
    out = bat.run()
    assert set(out) == {r1, r2}
    assert sentinel.compiles("continuous.spec_verify") == entries


# -- int8 draft weights ------------------------------------------------------


def test_int8_draft_weights_top1_agreement():
    """Blockwise int8 draft WEIGHTS (quantize_params/dequantize_params)
    perturb the draft's logits only slightly: top-1 agreement vs the
    f32 draft stays high over a full-sequence forward. (The served
    stream never depends on the draft — that's the losslessness test
    below — so agreement is purely an acceptance-rate economy.)"""
    draft = transformer_lm(37, 16, 1, 1, 32, max_len=48, name="agr_draft")
    dvars = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    qvars = quantize_params(dvars)
    # Matrix leaves quantized, 1-D (bias/LN) leaves untouched.
    leaves = jax.tree.leaves(
        qvars, is_leaf=lambda l: isinstance(l, QuantizedTensor)
    )
    assert any(isinstance(l, QuantizedTensor) for l in leaves)
    assert all(
        isinstance(l, QuantizedTensor) or l.ndim <= 1 for l in leaves
    )
    ids = jnp.asarray(
        [[1, 5, 9, 2, 8, 3, 7, 4, 6, 11, 13, 17, 22, 30, 35, 12]],
        jnp.int32,
    )
    lg32 = np.asarray(logits_full(draft, dvars, ids))
    lg8 = np.asarray(logits_full(draft, dequantize_params(qvars), ids))
    agreement = float((lg32.argmax(-1) == lg8.argmax(-1)).mean())
    assert agreement >= 0.8, f"top-1 agreement {agreement}"


def test_int8_draft_weights_serving_lossless(spec_setup):
    """draft_weight_dtype="int8": the batcher stores the draft's
    weights quantized (observable: QuantizedTensor leaves in
    _draft_variables) and every stream STILL equals solo generate() —
    draft quality moves acceptance, never tokens. Composes with int8
    target caches."""
    lm, variables, draft, dvars = spec_setup
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_cache_dtype="int8",
        draft_lm=draft, draft_variables=dvars,
        speculative=SpeculativeConfig(draft_k=3, draft_weight_dtype="int8"),
    )
    stored = jax.tree.leaves(
        bat._draft_variables,
        is_leaf=lambda l: isinstance(l, QuantizedTensor),
    )
    assert any(isinstance(l, QuantizedTensor) for l in stored)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32) for n in (3, 8)]
    ids = {bat.submit(p, 8): p for p in prompts}
    out = bat.run()
    for rid, p in ids.items():
        np.testing.assert_array_equal(
            out[rid], _solo(lm, variables, p, 8, kv_cache_dtype="int8")
        )
    with pytest.raises(ValueError, match="draft_weight_dtype"):
        SpeculativeConfig(draft_weight_dtype="fp4")


# -- memory accounting -------------------------------------------------------


def test_memory_kv_bytes_ratio_gauge(lm_setup):
    """memory.pool_bytes counts the scale planes, and
    memory.kv_bytes_ratio reports quantized ÷ native-equivalent (1.0
    for native batchers) whatever the page size."""
    lm, variables = lm_setup
    hd = lm.graph.node(lm.block_names[0]).module.head_dim
    want_ratio = (hd + 4) / (4 * hd)  # int8 + f32 scales vs f32 native

    for kw in ({}, {"page_size": 16}):
        native = ContinuousBatcher(lm, variables, slots=2, **kw)
        assert native._memory_stats()["memory.kv_bytes_ratio"] == 1.0
        quant = ContinuousBatcher(
            lm, variables, slots=2, kv_cache_dtype="int8", **kw
        )
        ms = quant._memory_stats()
        assert ms["memory.kv_bytes_ratio"] == pytest.approx(want_ratio)
        # Scale planes are INSIDE pool_bytes: values alone would be
        # hd/(4hd).
        values_only = sum(
            x.nbytes for x in jax.tree.leaves(quant._caches)
            if x.dtype == jnp.int8
        )
        assert ms["memory.pool_bytes"] > values_only
        assert ms["memory.pool_bytes"] == pytest.approx(
            native._memory_stats()["memory.pool_bytes"] * want_ratio
        )


# -- tensor parallelism ------------------------------------------------------


def test_tp4_quantized_pool_bytes_and_stream(sim_mesh):
    """tp=4 quantized POOLS (both pytree members, int8 values and f32
    scale planes, must head-shard together): per-device bytes ==
    logical/4 exactly, and the quantized stream still equals the
    single-device solo quantized path."""
    lm = transformer_lm(37, 32, 2, 8, 64, max_len=48, kv_heads=4,
                        name="q_tp_target")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    p = np.asarray([1, 2, 3], np.int32)
    want = _solo(lm, variables, p, 6, kv_cache_dtype="int8")
    bat = ContinuousBatcher(
        lm, variables, slots=2, kv_cache_dtype="int8",
        kv_layout="paged", page_size=8,
        mesh=sim_mesh(4), parallel=ParallelConfig(tp=4),
    )
    rid = bat.submit(p, 6)
    out = bat.run()
    st = bat.stats()
    assert st["cache_bytes_per_device"] * 4 == st["cache_bytes"]
    # Every leaf shards: int8 values AND f32 scale planes both hold
    # 1/4 of their logical bytes per device.
    for leaf in jax.tree.leaves(bat._caches):
        assert leaf.addressable_shards[0].data.nbytes * 4 == leaf.nbytes
    np.testing.assert_array_equal(out[rid], want)
