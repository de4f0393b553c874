"""Hierarchical KV cache: page codecs, the host-DRAM spill tier, and
the spill -> evict -> readmit serving path (ISSUE 14 acceptance).

The structural pins: lossless spill/readmit roundtrips are bit-exact
(a greedy stream whose prefix pages were evicted to the host tier and
readmitted equals an uninterrupted run), readmits count
``paged.prefix_hits``, spill work respects the per-tick budget, lossy
COLD codecs only ever see rc=0 spilled pages (never live-slot state),
and the whole thing composes with int8 pools, tp=2 head sharding,
speculative mode and the disaggregated wire.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import (
    CacheTierConfig,
    DisaggConfig,
    ParallelConfig,
    SpeculativeConfig,
)
from adapt_tpu.models.transformer_lm import lm_tiny
from adapt_tpu.ops.quantize import (
    LOSSLESS_PAGE_CODECS,
    PAGE_CODECS,
    decode_page,
    encode_page,
    page_codec_roundtrip,
)
from adapt_tpu.parallel.sharding import fetch_head_shards
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.disagg import (
    DisaggServer,
    HandoffError,
    PrefillWorker,
    pack_handoff,
    unpack_handoff,
    loopback,
)
from adapt_tpu.runtime.paged import HostKVTier, Pager
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.tracing import global_flight_recorder

VOCAB = 37
PAGE = 8
STEPS = 8


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=VOCAB, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def _mk(lm, variables, pool_pages, tier=None, dtype="native", **kw):
    kws = dict(
        slots=1, chunk=4, kv_layout="paged", page_size=PAGE,
        pool_pages=pool_pages, kv_cache_dtype=dtype,
    )
    kws.update(kw)
    if tier is not None:
        kws["cache_tier"] = tier
    return ContinuousBatcher(lm, variables, **kws)


def _prompts(seed=0, n=4, size=2 * PAGE + 4):
    rng = np.random.RandomState(seed)
    A = rng.randint(0, VOCAB, size=size).astype(np.int32)
    flood = [
        rng.randint(0, VOCAB, size=size).astype(np.int32)
        for _ in range(n)
    ]
    return A, flood


def _evict_then_rereference(bat, A, flood):
    """Register A's prefix pages, flood-evict them, re-reference A.
    Returns A's second-reference stream."""
    bat.submit(A, STEPS)
    bat.run()
    for p in flood:
        bat.submit(p, STEPS)
    bat.run()
    rid = bat.submit(A, STEPS)
    return bat.run()[rid]


def _reference_stream(lm, variables, A, flood, **kw):
    """The uninterrupted run: big pool, same traffic — A's second
    reference is an ordinary HBM prefix hit."""
    ref = _mk(lm, variables, 64, **kw)
    try:
        return _evict_then_rereference(ref, A, flood)
    finally:
        ref.close()


# -- page codecs -------------------------------------------------------------


@pytest.mark.parametrize("codec", PAGE_CODECS)
@pytest.mark.parametrize(
    "dtype", [np.float32, np.int8, np.int32]
)
def test_page_codec_roundtrip_shapes(codec, dtype):
    rng = np.random.RandomState(0)
    x = (rng.randn(2, 3, 8, 16) * 3).astype(dtype)
    y = page_codec_roundtrip(x, codec)
    assert y.shape == x.shape and y.dtype == x.dtype
    if codec in LOSSLESS_PAGE_CODECS:
        np.testing.assert_array_equal(y, x)
    elif not np.issubdtype(np.dtype(dtype), np.floating):
        # Lossy on integer arrays degrades to lossless packing — the
        # guard that keeps lossy tiers away from already-quantized
        # int8 value planes and prompt ids.
        np.testing.assert_array_equal(y, x)
        _, meta = encode_page(x, codec)
        assert meta["codec"] == "lz"
    else:
        # Bounded error: zfp keeps 10 mantissa bits (rel err ~2^-11);
        # int8/int4 are the per-vector absmax lattices.
        err = np.abs(y.astype(np.float64) - x.astype(np.float64))
        amax = np.abs(x).max(axis=-1, keepdims=True)
        bound = {"zfp": 2.0**-10, "int8": 1.0 / 127, "int4": 1.0 / 7}[
            codec
        ]
        assert (err <= amax * bound + 1e-6).all()


def test_page_codec_meta_and_errors():
    x = np.zeros((4, 16), np.float32)
    payload, meta = encode_page(x, "lz")
    assert len(payload) < meta["raw_nbytes"]  # zeros compress
    np.testing.assert_array_equal(decode_page(payload, meta), x)
    with pytest.raises(ValueError):
        encode_page(x, "snappy")
    with pytest.raises(ValueError):
        encode_page(np.zeros((4, 15), np.float32), "int4")  # odd lane


# -- the host tier (unit) ----------------------------------------------------


def _blocks(rng, kvh=2, hd=4, quant=False):
    """One page of two blocks' pools: fused K|V rows, beside them the K
    and the V scale planes when quantized."""
    def block():
        if quant:
            return (
                rng.randint(-127, 127, (kvh, PAGE, 2 * hd)).astype(np.int8),
                rng.rand(kvh, PAGE, 1).astype(np.float32),
                rng.rand(kvh, PAGE, 1).astype(np.float32),
            )
        return rng.randn(kvh, PAGE, 2 * hd).astype(np.float32)

    return [block() for _ in range(2)]


def test_host_tier_warm_cold_demotion_and_drop():
    cfg = CacheTierConfig(
        host_capacity_pages=4, warm_capacity_pages=2, cold_codec="int8"
    )
    tier = HostKVTier(cfg)
    rng = np.random.RandomState(0)
    pages = {}
    for i in range(6):
        key = b"k%d" % i
        pages[key] = _blocks(rng)
        tier.put(key, pages[key])
    st = tier.stats()
    assert st.pages == 4 and st.warm == 2 and st.cold == 2
    assert st.dropped == 2 and st.spilled == 6
    # Warm readmits bit-exact; cold went through the lossy codec.
    for k, v in zip(jax.tree.leaves(pages[b"k5"]),
                    jax.tree.leaves(tier.get(b"k5"))):
        np.testing.assert_array_equal(k, v)
    cold = tier.get(b"k3")
    for k, v in zip(jax.tree.leaves(pages[b"k3"]),
                    jax.tree.leaves(cold)):
        assert v.shape == k.shape and v.dtype == k.dtype
        assert np.allclose(k, v, atol=0.1)
    assert tier.get(b"k0") is None  # dropped off the cold end
    assert not tier.contains(b"k0") and tier.contains(b"k4")


def test_host_tier_quantized_members_and_saved_bytes():
    """int8-pool pages carry (values, K scales, V scales) planes; lossy
    cold codecs must pass the int8 value plane through bit-exact."""
    cfg = CacheTierConfig(
        host_capacity_pages=2, warm_capacity_pages=0, cold_codec="int4"
    )
    tier = HostKVTier(cfg)
    rng = np.random.RandomState(1)
    blocks = _blocks(rng, quant=True)
    tier.put(b"q", blocks)
    got = tier.get(b"q")
    for want, have in zip(blocks, got):
        assert isinstance(have, tuple) and len(have) == 3
        # the value plane (int8) is bit-exact even under a lossy codec
        np.testing.assert_array_equal(want[0], have[0])
        # scale planes (f32) may quantize, but keep shape/dtype
        for w, h in zip(want[1:], have[1:]):
            assert h.dtype == np.float32 and h.shape == w.shape


def test_host_tier_disk_backing(tmp_path):
    cfg = CacheTierConfig(
        host_capacity_pages=1, warm_capacity_pages=1,
        disk_dir=str(tmp_path),
    )
    tier = HostKVTier(cfg)
    rng = np.random.RandomState(2)
    a, b = _blocks(rng), _blocks(rng)
    tier.put(b"a", a)
    tier.put(b"b", b)  # demotes "a" past capacity -> disk, not dropped
    st = tier.stats()
    assert st.dropped == 0 and st.disk == 1 and st.pages == 1
    assert tier.contains(b"a")
    for k, v in zip(jax.tree.leaves(a), jax.tree.leaves(tier.get(b"a"))):
        np.testing.assert_array_equal(k, v)


def test_pager_evict_hook_and_residency():
    p = Pager(4, 1, 4)
    seen = []
    p.evict_hook = lambda page, key: seen.append(key)
    p.adopt_cached([b"a", b"b", b"c"])
    assert p.resident(b"a") and [k for _, k in p.cached_pages()] == [
        b"a", b"b", b"c",
    ]
    p.evict_cached(1)  # sweep fires the hook
    assert seen == [b"a"] and not p.resident(b"a")
    p.alloc(0, 2)  # 0 free -> demand eviction fires it too
    assert seen == [b"a", b"b"]
    assert p.resident(b"c")


def test_fetch_head_shards_matches_logical(sim_mesh):
    from adapt_tpu.parallel.sharding import kv_head_sharding

    mesh = sim_mesh(2)
    x = jnp.arange(3 * 4 * 8 * 2, dtype=jnp.float32).reshape(3, 4, 8, 2)
    xs = jax.device_put(x, kv_head_sharding(mesh, "tp"))
    got = fetch_head_shards(xs, 1)
    np.testing.assert_array_equal(got, np.asarray(x[1]))


# -- the serving path --------------------------------------------------------


@pytest.mark.parametrize("dtype", ["native", "int8"])
def test_spill_evict_readmit_bit_identical(lm_setup, dtype):
    """THE acceptance pin: flood pressure evicts A's registered prefix
    pages into the host tier; A's re-reference readmits them through
    the adopt_cached landing path, counts prefix hits, and the stream
    equals the uninterrupted big-pool run token-for-token — the pool
    partition staying exact throughout."""
    lm, variables = lm_setup
    A, flood = _prompts()
    want = _reference_stream(lm, variables, A, flood, dtype=dtype)
    tier = CacheTierConfig(
        spill_pages_per_tick=16, readmit_pages_per_tick=16
    )
    bat = _mk(lm, variables, 12, tier=tier, dtype=dtype)
    kinds0 = dict(global_flight_recorder().kind_counts())
    bat.submit(A, STEPS)
    bat.run()
    for p in flood:
        bat.submit(p, STEPS)
    bat.run()
    st = bat.stats()
    assert st["tier_spilled"] > 0, "flood never spilled"
    hits0 = st["prefix_hits"]
    rid = bat.submit(A, STEPS)
    got = bat.run()[rid]
    np.testing.assert_array_equal(got, want)
    st = bat.stats()
    assert st["tier_readmitted"] >= 1
    assert st["prefix_hits"] - hits0 >= st["tier_readmitted"]
    # Pool partition exact with the tier attached (pages_free counts
    # evictable cached pages — the gauges partition instead).
    alloc = st["pool_pages"] - 1
    assert st["pages_in_use"] + (st["pages_free"] - st["pages_cached"]) \
        + st["pages_cached"] == alloc
    kinds = global_flight_recorder().kind_counts()
    assert kinds.get("kv_spill", 0) > kinds0.get("kv_spill", 0)
    assert kinds.get("kv_readmit", 0) > kinds0.get("kv_readmit", 0)
    bat.close()


def test_spill_budget_respected_and_drops_counted(lm_setup):
    """A spill budget of 1/tick bounds tier work: no tick spills more
    than one page, and evictions past the budget count dropped."""
    lm, variables = lm_setup
    A, flood = _prompts(n=6)
    tier = CacheTierConfig(
        spill_pages_per_tick=1, readmit_pages_per_tick=4,
        # Neutralize the proactive sweep (need = cached - alloc <= 0),
        # so every spill is a demand capture at eviction — the budget
        # path under test.
        spill_watermark=1.0, spill_low_watermark=1.0,
    )
    bat = _mk(lm, variables, 12, tier=tier)
    bat.submit(A, STEPS)
    bat.run()
    last = bat.stats()["tier_spilled"]
    for p in flood:
        bat.submit(p, STEPS)
        while bat.tick() or bat.stats()["queued"]:
            s = bat.stats()["tier_spilled"]
            assert s - last <= 1, "tick spilled past the budget"
            last = s
    st = bat.stats()
    assert st["tier_spilled"] >= 1
    assert st["tier_dropped"] >= 1, (
        "evictions past a 1-page budget must count dropped"
    )
    bat.close()


def test_live_pages_never_spill(lm_setup):
    """Only rc=0 LRU pages ever reach the tier (the invariant that
    keeps lossy cold codecs away from live decode state): while a
    request holds its prompt pages, their keys stay out of the host
    tier even under the most aggressive watermark."""
    lm, variables = lm_setup
    tier = CacheTierConfig(
        spill_watermark=0.0, spill_low_watermark=0.0,
        spill_pages_per_tick=64,
    )
    bat = _mk(lm, variables, 16, tier=tier, slots=1)
    rng = np.random.RandomState(3)
    A = rng.randint(0, VOCAB, size=2 * PAGE + 2).astype(np.int32)
    bat.submit(A, 24)
    for _ in range(3):
        bat.tick()
    # Mid-request: prompt pages are rc>0 and registered; the sweep ran
    # every tick at watermark 0, yet none of A's keys may be host-side.
    assert bat.stats()["active"] == 1
    for j in range(2):
        key = Pager.prefix_key(A, (j + 1) * PAGE)
        assert not bat._tier.contains(key)
    bat.run()
    # Retired: the pages are rc=0 LRU now — the sweep may take them.
    bat.tick()
    assert bat.stats()["tier_spilled"] >= 1
    bat.close()


def test_prefix_cached_reads_the_hierarchy(lm_setup):
    lm, variables = lm_setup
    A, flood = _prompts()
    tier = CacheTierConfig(
        spill_pages_per_tick=16, readmit_pages_per_tick=16
    )
    bat = _mk(lm, variables, 12, tier=tier)
    assert bat.prefix_cached(A) == 0
    bat.submit(A, STEPS)
    bat.run()
    assert bat.prefix_cached(A) == 2  # HBM-resident
    for p in flood:
        bat.submit(p, STEPS)
    bat.run()
    # Evicted from HBM but host-resident: still servable.
    assert bat.stats()["tier_spilled"] > 0
    assert bat.prefix_cached(A) == 2
    bat.close()


def test_cold_codec_stream_agreement(lm_setup):
    """Warm capacity 0 demotes every spill through the lossy int8
    page codec; the readmitted stream's top-1 agreement vs the
    uncompressed reference holds the >= 0.95 bar (the int4 pools'
    bar)."""
    lm, variables = lm_setup
    A, flood = _prompts()
    want = _reference_stream(lm, variables, A, flood)
    tier = CacheTierConfig(
        host_capacity_pages=64, warm_capacity_pages=0,
        cold_codec="int8", spill_pages_per_tick=16,
        readmit_pages_per_tick=16,
    )
    bat = _mk(lm, variables, 12, tier=tier)
    got = _evict_then_rereference(bat, A, flood)
    assert bat.stats()["tier_readmitted"] >= 1
    n = min(len(got), len(want))
    assert n > 0
    agreement = float((got[:n] == want[:n]).sum()) / n
    assert agreement >= 0.95, agreement
    bat.close()


# -- composition -------------------------------------------------------------


@pytest.mark.slow
def test_tp2_spill_readmit_bit_identical(lm_setup, sim_mesh):
    """tp=2 head sharding composes: spill assembles per-shard host
    pieces (fetch_head_shards), readmit places per-shard slices
    (KVHandoffPlan) — streams stay bit-identical to the uninterrupted
    tp=2 run."""
    lm, variables = lm_setup
    mesh = sim_mesh(2)
    A, flood = _prompts()
    kw = dict(mesh=mesh, parallel=ParallelConfig(tp=2))
    want = _reference_stream(lm, variables, A, flood, **kw)
    tier = CacheTierConfig(
        spill_pages_per_tick=16, readmit_pages_per_tick=16
    )
    bat = _mk(lm, variables, 12, tier=tier, **kw)
    got = _evict_then_rereference(bat, A, flood)
    np.testing.assert_array_equal(got, want)
    assert bat.stats()["tier_readmitted"] >= 1
    bat.close()


@pytest.mark.slow
def test_speculative_spill_readmit_bit_identical(lm_setup):
    """Speculative mode composes (self-draft, perfect acceptance):
    the readmitted prefix feeds the same draft+verify tick and the
    stream equals the uninterrupted speculative run."""
    lm, variables = lm_setup
    A, flood = _prompts()
    kw = dict(
        draft_lm=lm, draft_variables=variables,
        speculative=SpeculativeConfig(draft_k=3),
    )
    want = _reference_stream(lm, variables, A, flood, **kw)
    tier = CacheTierConfig(
        spill_pages_per_tick=16, readmit_pages_per_tick=16
    )
    bat = _mk(lm, variables, 12, tier=tier, **kw)
    got = _evict_then_rereference(bat, A, flood)
    np.testing.assert_array_equal(got, want)
    assert bat.stats()["tier_readmitted"] >= 1
    bat.close()


def test_wire_codec_roundtrip_and_crc_on_compressed():
    """MSG_KV_PAGES with a wire codec: lz roundtrips bit-exact, lossy
    codecs keep int tensors (prompt) exact, and the crc verifies the
    COMPRESSED payload — a flipped wire bit raises before any decode."""
    from adapt_tpu.runtime.disagg import KVHandoff
    from adapt_tpu.comm.framing import frame_parts, parse_frame

    rng = np.random.RandomState(3)

    def block():  # a native pool's pages: fused K|V rows
        return rng.rand(3, 2, PAGE, 8).astype(np.float32)

    h = KVHandoff(
        req_id=7,
        prompt=rng.randint(0, VOCAB, size=3 * PAGE + 3).astype(np.int32),
        page_size=PAGE, n_pages=3, quantized=False,
        blocks=[block() for _ in range(2)],
    )
    got = unpack_handoff(loopback(pack_handoff(h, wire_codec="lz")))
    np.testing.assert_array_equal(got.prompt, h.prompt)
    assert len(got.blocks) == len(h.blocks) == 2
    for ours, theirs in zip(h.blocks, got.blocks):
        np.testing.assert_array_equal(ours, theirs)
    lossy = unpack_handoff(loopback(pack_handoff(h, wire_codec="int8")))
    np.testing.assert_array_equal(lossy.prompt, h.prompt)  # int: exact
    assert np.allclose(lossy.blocks[0], h.blocks[0], atol=0.02)
    # crc runs on the compressed payload: flip a late (payload) byte.
    msg = pack_handoff(h, wire_codec="lz")
    wire = bytearray(b"".join(frame_parts(msg)))
    wire[-5] ^= 0xFF
    with pytest.raises((HandoffError, ConnectionError)):
        unpack_handoff(parse_frame(memoryview(wire)[8:]))


def test_disagg_wire_codec_and_raw_bytes_counter(lm_setup):
    """DisaggServer + tier-enabled decode + lz wire codec: streams
    stay bit-identical to the collocated path, and the wire records
    BOTH post-codec (handoff_bytes) and raw (handoff_bytes_raw)
    bytes."""
    lm, variables = lm_setup
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, VOCAB, size=37).astype(np.int32)
    ref = _mk(lm, variables, 64, slots=2)
    rid = ref.submit(prompt, 10)
    want = ref.run()[rid]
    ref.close()
    tier = CacheTierConfig(wire_codec="lz")
    decode = _mk(lm, variables, 64, tier=tier, slots=2)
    worker = PrefillWorker(
        lm, variables, page_size=PAGE, prefill_chunk=2 * PAGE
    )
    srv = DisaggServer(
        decode, worker,
        DisaggConfig(prompt_threshold=2 * PAGE,
                     busy_prompt_threshold=2 * PAGE),
    )
    assert srv.wire_codec == "lz"  # inherited from the tier config
    c0 = global_metrics().snapshot()["counters"]
    sid = srv.submit(prompt, 10)
    got = srv.run()[sid]
    np.testing.assert_array_equal(got, want)
    assert srv.disaggregated == 1
    c1 = global_metrics().snapshot()["counters"]
    wire = c1.get("disagg.handoff_bytes", 0) - c0.get(
        "disagg.handoff_bytes", 0
    )
    raw = c1.get("disagg.handoff_bytes_raw", 0) - c0.get(
        "disagg.handoff_bytes_raw", 0
    )
    assert wire > 0 and raw > 0
    srv.close()
    decode.close()
