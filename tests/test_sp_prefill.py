"""Sequence-parallel long-context prefill (ISSUE 15): ring attention
at serving shapes, and the sp-sharded prefill path end to end.

Pinned contracts:

- **Ring parity at serving shapes**: ``parallel.ring_attention`` vs
  the dense oracle in ``ops/attention`` — GQA head counts (repeated
  KV), causal masking whose live/dead split spans multiple ring
  steps, and the odd-last-chunk recipe (pad to the ring size under a
  causal mask, slice the real prefix).
- **Page equality, to what a reordered sum allows**:
  ``parallel.sp_prefill.SPPrefiller`` pages equal the single-device
  chunked prefill's pages — native, int8 and int4 pools, sp in
  {2, 4}, GQA + rope models, and the sp x tp composed mesh against
  the tp-sharded chunked prefill (tp math is compared at matched tp,
  the PR-5 discipline). The FIRST block's pages (projection, rope,
  quantisation, the ring transport: everything token-local) are
  byte-equal. Later blocks sit downstream of attention, where the sp
  pass reduces each row's softmax sum and its p @ V product over the
  whole span (``nb`` pages of columns) and a chunk pass over its own
  power-of-two window: masked columns add exact zeros, but XLA orders
  a reduction by its width, so the two sums differ by a rounding
  (read on the CPU, PR 29: 9.5e-7 on values up to 3.4, pages whose
  chunk window IS the span byte-equal). Those pages are held to
  ``_PAGE_ULPS`` float32 ulps of the plane's largest magnitude, int8
  values to one quantum.
- **Serving**: greedy streams through an sp-enabled batcher are
  bit-identical to the plain batcher's; admissions land through the
  prefix cache (suffix-only pass); steady decode ticks stay at ZERO
  h2d transfers; the disagg tier's sp dispatch serves prompts whose
  pages exceed its pool.
- **Recovery**: killing a device shared by the decode mesh and the
  sp ring re-shards the batcher AND rebuilds the prefiller on
  surviving devices; streams stay bit-identical and later long
  admissions still take the sp path.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.config import ParallelConfig, PrefillConfig
from adapt_tpu.models.transformer_lm import transformer_lm
from adapt_tpu.parallel.ring_attention import full_attention, ring_attention
from adapt_tpu.parallel.sp_prefill import (
    SPPrefiller,
    build_sp_mesh,
    ring_collect,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.disagg import DisaggServer, PrefillWorker
from adapt_tpu.config import DisaggConfig

VOCAB = 61
PAGE = 8


@pytest.fixture(scope="module")
def lm_setup():
    lm = transformer_lm(VOCAB, 32, 2, 2, 64, max_len=96, name="sp_lm")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def gqa_lm_setup():
    # GQA (4 query heads sharing 2 KV heads) + rope: the serving-shape
    # composition the ring/sp paths must keep exact.
    lm = transformer_lm(
        VOCAB, 32, 2, 4, 64, max_len=96, kv_heads=2, pos="rope",
        name="sp_gqa_lm",
    )
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def _worker_pages(lm, variables, prompt, dtype, chunk=PAGE, tag=""):
    w = PrefillWorker(
        lm, variables, page_size=PAGE, prefill_chunk=chunk,
        kv_cache_dtype=dtype, name=f"ref{tag}{dtype}",
    )
    w.submit(1, prompt)
    outs = []
    while not outs:
        outs = w.step()
    return outs[0].blocks


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


#: Float32 ulps (of a plane's largest magnitude) a page downstream of
#: attention may differ by: the two passes round one sum differently
#: (module docstring). Read: under 3 of these; a wrong mask, position
#: or scale is off by thousands.
_PAGE_ULPS = 16


def _assert_pages_match(ref, got):
    """Block 0 byte-equal; later blocks within ``_PAGE_ULPS`` ulps
    (float planes) and one quantum (int8 value planes)."""
    assert len(ref) == len(got)
    _assert_tree_equal(ref[0], got[0])
    for b, (rp, gp) in enumerate(zip(ref[1:], got[1:]), start=1):
        la, lb = jax.tree.leaves(rp), jax.tree.leaves(gp)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            assert x.shape == y.shape and x.dtype == y.dtype
            if x.dtype == np.int8:
                gap = np.abs(x.astype(np.int16) - y.astype(np.int16))
                assert gap.max() <= 1, f"block {b}: {gap.max()} quanta"
            else:
                tol = (
                    _PAGE_ULPS * np.finfo(np.float32).eps
                    * float(np.abs(x).max())
                )
                gap = float(np.abs(x - y).max())
                assert gap <= tol, f"block {b}: {gap} > {tol}"


# -- ring attention parity at serving shapes (satellite) -------------------


def _rand_qkv(rng, b, h, s, d, kv_heads=None):
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    kvh = kv_heads or h
    k = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    if kvh != h:
        # Adjacent-block repeat — the GQA convention (_repeat_kv).
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
    return q, k, v


@pytest.mark.parametrize("kv_heads", [None, 2, 1])
def test_ring_attention_gqa_serving_shapes(sim_mesh, kv_heads):
    """Ring attention matches the dense oracle at GQA head counts
    (repeated KV per the model convention) — causal and full."""
    mesh = sim_mesh(4, axis="sp")
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, 1, 4, 32, 16, kv_heads)
    for causal in (False, True):
        out = ring_attention(q, k, v, mesh, axis="sp", causal=causal)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


def test_ring_attention_causal_across_ring_steps(sim_mesh):
    """Causal masking stays exact when the live/dead boundary crosses
    several ring steps (8 ranks, 5 tokens per shard)."""
    mesh = sim_mesh(8, axis="sp")
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, 2, 2, 40, 8)
    out = ring_attention(q, k, v, mesh, axis="sp", causal=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_attention_odd_last_chunk(sim_mesh):
    """A sequence that does not divide the ring raises by name, and
    the documented recipe — pad to the ring size, run CAUSAL, slice
    the real prefix — matches the unpadded oracle (padded keys sit at
    positions after every real query, so the causal mask removes
    them)."""
    mesh = sim_mesh(4, axis="sp")
    rng = np.random.default_rng(2)
    s = 27  # odd last chunk: 27 = 3 full 8-token shards + 3
    q, k, v = _rand_qkv(rng, 1, 2, s, 8)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, k, v, mesh, axis="sp", causal=True)
    pad = (-s) % 4
    pq, pk, pv = (
        jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
        for t in (q, k, v)
    )
    out = ring_attention(pq, pk, pv, mesh, axis="sp", causal=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out)[:, :, :s], np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_attention_flash_block_parity(sim_mesh):
    """The streaming-kernel per-device block (``block_impl="flash"``,
    Pallas in interpreter mode on CPU) merges by logsumexp to the same
    result as the dense oracle at serving shapes — contiguous and
    striped causal layouts."""
    mesh = sim_mesh(2, axis="sp")
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, 1, 2, 32, 16)
    ref = full_attention(q, k, v, causal=True)
    out = ring_attention(
        q, k, v, mesh, axis="sp", causal=True, block_impl="flash"
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_collect_is_exact_concatenation(sim_mesh):
    """The sp path's ring transport: P-1 ppermute hops reassemble the
    full window bit-exactly on every rank."""
    mesh = sim_mesh(4, axis="sp")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    out = ring_collect(x, mesh, "sp", seq_dim=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# -- sp prefill page equality ----------------------------------------------


@pytest.mark.parametrize(
    "dtype",
    [
        "native",
        "int8",
        # int4 rides the same quantize-then-ring path; slow-marked to
        # keep the tier-1 window lean (native + int8 are the
        # acceptance pins).
        pytest.param("int4", marks=pytest.mark.slow),
    ],
)
def test_sp_pages_match_chunked_prefill(lm_setup, sim_mesh, dtype):
    """The tentpole pin: sp-prefilled pages are the single-device
    chunked prefill's (``_assert_pages_match``) — native, int8 and
    packed-int4 pools, sp=2 (and sp=4 on the native arm)."""
    lm, variables = lm_setup
    prompt = np.random.default_rng(7).integers(
        1, VOCAB, size=41
    ).astype(np.int32)
    ref = _worker_pages(lm, variables, prompt, dtype, tag="a")
    for sp in (2, 4) if dtype == "native" else (2,):
        pf = SPPrefiller(
            lm, variables, build_sp_mesh(sp), PAGE,
            kv_cache_dtype=dtype, name=f"t{sp}{dtype}",
        )
        m, blocks = pf.prefill(prompt)
        assert m == 5
        _assert_pages_match(ref, blocks)
        # The last page's chunk pass attends 8 pages of columns, the
        # sp pass's whole span: same width, same sum, same bytes.
        _assert_tree_equal(
            jax.tree.map(lambda t: t[4], ref),
            jax.tree.map(lambda t: t[4], blocks),
        )
        pf.close()


def test_sp_pages_match_gqa_rope(gqa_lm_setup, sim_mesh):
    """GQA + rope at sp=2: the grouped-query fold and the rotary
    positions survive the sequence split."""
    lm, variables = gqa_lm_setup
    prompt = np.random.default_rng(8).integers(
        1, VOCAB, size=37
    ).astype(np.int32)
    ref = _worker_pages(lm, variables, prompt, "native", tag="g")
    pf = SPPrefiller(
        lm, variables, build_sp_mesh(2), PAGE, name="tg",
    )
    m, blocks = pf.prefill(prompt)
    assert m == 4
    _assert_pages_match(ref, blocks)
    pf.close()


def test_sp_tp_composed_pages_match(lm_setup, sim_mesh):
    """sp x tp composition: a (sp=2, tp=2) prefiller's pages are the
    tp=2 batcher's OWN chunked prefill's (tp math compares at matched
    tp — the PR-5 discipline; tp=2 vs tp=1 was never bitwise, only
    stream-identical)."""
    lm, variables = lm_setup
    mesh = sim_mesh(2, axis="tp")
    prompt = np.random.default_rng(9).integers(
        1, VOCAB, size=41
    ).astype(np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE, prefill_chunk=PAGE, mesh=mesh,
        parallel=ParallelConfig(tp=2),
    )
    bat.submit(prompt, 8)
    for _ in range(8):
        bat.tick()
        if bat.slots[0].req is not None and bat.slots[0].pf_done < 0:
            break
    owned = bat._pager.owned(0)[:5]
    ref = jax.tree.map(
        lambda pool: np.asarray(pool[np.asarray(owned)]), bat._caches
    )
    pf = SPPrefiller(
        lm, variables, build_sp_mesh(2, 2), PAGE, tp_axis="tp",
        name="ttp",
    )
    m, blocks = pf.prefill(prompt)
    assert m == 5
    _assert_pages_match(ref, blocks)
    pf.close()
    bat.close()


# -- serving end to end ----------------------------------------------------


def _run_streams(lm, variables, prompts, steps, **kw):
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE, prefill_chunk=2 * PAGE, **kw,
    )
    rids = [bat.submit(p, steps) for p in prompts]
    outs = bat.run()
    streams = [outs[r] for r in rids]
    return bat, streams


def test_sp_batcher_streams_bit_identical(lm_setup, sim_mesh):
    """Greedy streams through the sp-enabled batcher equal the plain
    batcher token for token; long admissions take the sp path and
    land as prefix hits; steady decode ticks stay at zero h2d."""
    lm, variables = lm_setup
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (41, 7, 33, 25)
    ]
    ref_bat, ref = _run_streams(lm, variables, prompts, 8)
    ref_bat.close()
    bat, got = _run_streams(
        lm, variables, prompts, 8,
        prefill=PrefillConfig(sp_threshold=24, sp_width=2),
    )
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    st = bat.stats()
    assert st["sp_prefills"] == 3  # 41, 33, 25 >= threshold 24
    assert st["sp_width"] == 2
    # The sp landings are prefix hits (suffix-only admission).
    assert st["prefix_hits"] >= 3
    # Steady-state decode ticks stage nothing after an sp admission.
    rid = bat.submit(prompts[0], 24)  # re-admit: full prefix hit
    bat.tick()
    h2d0 = bat.stats()["h2d_transfers"]
    for _ in range(2):
        bat.tick()
    assert bat.stats()["h2d_transfers"] == h2d0
    bat.run()
    bat.close()


def test_prefill_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        PrefillConfig(sp_threshold=8, sp_width=3)
    with pytest.raises(ValueError, match="sp_threshold"):
        PrefillConfig(sp_threshold=0, sp_width=2)
    assert not PrefillConfig().enabled
    assert not PrefillConfig(sp_threshold=8, sp_width=1).enabled
    assert PrefillConfig(sp_threshold=8, sp_width=2).enabled


def test_sp_mesh_tp_mismatch_raises(lm_setup, sim_mesh):
    """A tp=2 batcher refuses an sp mesh without its tp axis — sp
    pages must be what ITS tp-sharded prefill would write."""
    lm, variables = lm_setup
    mesh = sim_mesh(2, axis="tp")
    with pytest.raises(ValueError, match="tp axis"):
        ContinuousBatcher(
            lm, variables, slots=2, kv_layout="paged", page_size=PAGE,
            mesh=mesh, parallel=ParallelConfig(tp=2),
            prefill=PrefillConfig(sp_threshold=24, sp_width=2),
            sp_mesh=build_sp_mesh(2),  # sp-only: no tp axis
        )


def test_disagg_sp_serves_past_pool_capacity(lm_setup, sim_mesh):
    """The prefill tier's sp dispatch: prompts whose full pages exceed
    the worker pool disaggregate anyway (the sp program holds the span
    sp-sharded, never in the pool) and stream bit-identically to the
    collocated reference."""
    lm, variables = lm_setup
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (41, 7, 60)
    ]

    def run(sp_cfg, tag):
        decode = ContinuousBatcher(
            lm, variables, slots=2, chunk=4, kv_layout="paged",
            page_size=PAGE,
        )
        worker = PrefillWorker(
            lm, variables, page_size=PAGE, prefill_chunk=2 * PAGE,
            pool_pages=3, name=f"w{tag}", prefill=sp_cfg,
        )
        srv = DisaggServer(
            decode, worker,
            DisaggConfig(prompt_threshold=24, busy_prompt_threshold=24),
        )
        sids = [srv.submit(p, 8) for p in prompts]
        outs = srv.run()
        st = worker.stats()
        srv.close()
        decode.close()
        return [outs[s] for s in sids], st

    # Pool of 2 allocatable pages: without sp the 41/60-token prompts
    # CANNOT disaggregate (placement falls back collocated).
    ref, st0 = run(None, "off")
    assert st0["handoffs"] == 0
    got, st1 = run(PrefillConfig(sp_threshold=24, sp_width=2), "on")
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert st1["sp_prefills"] == 2
    assert st1["handoffs"] == 2


@pytest.mark.slow
def test_sp_recovery_rebuilds_ring(lm_setup, sim_mesh):
    """Kill a device shared by the tp=2 decode mesh and the
    (sp=2, tp=2) ring mid-stream: the batcher re-shards to tp=1,
    the prefiller rebuilds on surviving devices, migrated streams
    stay bit-identical, and a LATER long admission still takes the
    sp path on the rebuilt ring."""
    from adapt_tpu.control.registry import DeviceHealthMonitor

    lm, variables = lm_setup
    mesh = sim_mesh(2, axis="tp")
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(1, VOCAB, size=n).astype(np.int32)
        for n in (41, 33)
    ]
    # Uninterrupted reference (plain batcher, no sp, no mesh).
    ref_bat, ref = _run_streams(lm, variables, prompts, 12)
    ref_bat.close()

    health = DeviceHealthMonitor()
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE, prefill_chunk=2 * PAGE, mesh=mesh,
        parallel=ParallelConfig(tp=2), health=health,
        prefill=PrefillConfig(sp_threshold=24, sp_width=2),
        sp_mesh=build_sp_mesh(2, 2),
    )
    rids = [bat.submit(p, 12) for p in prompts]
    for _ in range(2):
        bat.tick()
    assert bat.stats()["sp_prefills"] == 2
    victim = list(mesh.devices.flat)[1]
    health.kill(victim)
    outs = bat.run()
    st = bat.stats()
    assert st["tp"] == 1
    assert st["recoveries"] == 1
    for rid, want in zip(rids, ref):
        np.testing.assert_array_equal(outs[rid], want)
    # The rebuilt ring still sp-prefills fresh long admissions.
    assert st["sp_width"] == 2
    p_new = rng.integers(1, VOCAB, size=39).astype(np.int32)
    rid = bat.submit(p_new, 8)
    got = bat.run()[rid]
    assert bat.stats()["sp_prefills"] == 3
    solo_bat, solo = _run_streams(lm, variables, [p_new], 8)
    solo_bat.close()
    np.testing.assert_array_equal(got, solo[0])
    bat.close()
