"""Disaggregated prefill/decode serving (ISSUE 9): a PrefillWorker
streams KV pages over the comm tier to a decode ContinuousBatcher,
landing them through the paged prefix cache.

Pinned contracts:

- **Wire**: pack/loopback/unpack round-trips bit-exactly with ZERO
  codec-layer payload copies on the send path and receive arrays
  VIEWING the wire buffer (the PR-1 zero-copy framing contract,
  measured via ``codec.copy_stats()``); corrupt or truncated frames
  raise ``HandoffError`` — and through the server, fail the request
  CLEANLY (empty result, ``request_failed`` event, serving continues).
- **Bit-exactness**: greedy streams through the disaggregated path
  equal the collocated path token-for-token (native and int8 pools,
  tp=1 and tp=2 decode side, speculative mode), and handed-off pool
  pages hold byte-identical K/V to an in-place chunked prefill with
  the same chunk schedule.
- **Hot path**: after handoff admissions, steady decode ticks stay at
  zero h2d transfers with a frozen compile footprint.
- **Policy**: the placement decision follows ``config.DisaggConfig``
  (length threshold, occupancy tightening, role-tagged-lease
  liveness) and every fallback is collocated.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.comm import codec
from adapt_tpu.comm.framing import frame_parts, parse_frame
from adapt_tpu.config import DisaggConfig, ParallelConfig, SpeculativeConfig
from adapt_tpu.control.registry import WorkerRegistry
from adapt_tpu.models.transformer_lm import transformer_lm
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.disagg import (
    DisaggServer,
    HandoffError,
    KVHandoff,
    PrefillWorker,
    loopback,
    pack_handoff,
    unpack_handoff,
)
from adapt_tpu.runtime.paged import Pager
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.tracing import global_flight_recorder

VOCAB = 61
PAGE = 8


@pytest.fixture(scope="module")
def lm_setup():
    # Small on purpose (2 blocks, dim 32): disaggregation is a
    # scheduling/placement property, and every batcher + worker pair
    # compiles its own programs — tier-1 wall time is the budget.
    lm = transformer_lm(VOCAB, 32, 2, 2, 64, max_len=96,
                        name="disagg_lm")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def _mk_pair(lm, variables, dtype="native", mesh=None, tp=1, spec=None,
             draft=None):
    kw = dict(
        slots=2, chunk=4, kv_layout="paged", page_size=PAGE,
        kv_cache_dtype=dtype,
    )
    if mesh is not None:
        kw.update(mesh=mesh, parallel=ParallelConfig(tp=tp))
    if spec is not None:
        dlm, dvars = draft
        kw.update(draft_lm=dlm, draft_variables=dvars, speculative=spec)
    decode = ContinuousBatcher(lm, variables, **kw)
    worker = PrefillWorker(
        lm, variables, page_size=PAGE, prefill_chunk=2 * PAGE,
        kv_cache_dtype=dtype,
    )
    srv = DisaggServer(
        decode, worker,
        DisaggConfig(prompt_threshold=2 * PAGE,
                     busy_prompt_threshold=2 * PAGE),
    )
    return decode, worker, srv


def _rand_handoff(rng, quantized=False, blocks=2, n=3, kvh=2, hd=4):
    def block():
        # A block's pool pages: fused K|V rows (2 * hd lanes), beside
        # them the K and the V scale planes when quantized.
        if quantized:
            return (
                rng.randint(-127, 127, size=(n, kvh, PAGE, 2 * hd)).astype(
                    np.int8
                ),
                rng.rand(n, kvh, PAGE, 1).astype(np.float32),
                rng.rand(n, kvh, PAGE, 1).astype(np.float32),
            )
        return rng.rand(n, kvh, PAGE, 2 * hd).astype(np.float32)

    return KVHandoff(
        req_id=7,
        prompt=rng.randint(0, VOCAB, size=n * PAGE + 3).astype(np.int32),
        page_size=PAGE,
        n_pages=n,
        quantized=quantized,
        blocks=[block() for _ in range(blocks)],
    )


@pytest.mark.parametrize("quantized", [False, True])
def test_handoff_wire_roundtrip_zero_copy(quantized):
    """pack -> gather -> parse -> unpack is bit-exact; the send path
    makes ZERO codec-layer payload copies (scatter-write parts), and
    every received tensor VIEWS the wire buffer (zero-copy receive)."""
    rng = np.random.RandomState(3)
    h = _rand_handoff(rng, quantized=quantized)
    codec.reset_copy_stats()
    msg = pack_handoff(h)
    assert codec.copy_stats()["calls"] == 0  # scatter parts, no joins
    wire = bytearray(b"".join(frame_parts(msg)))
    got = unpack_handoff(parse_frame(memoryview(wire)[8:]))
    assert codec.copy_stats()["calls"] == 0  # unpack slices, never joins
    assert got.n_pages == h.n_pages and got.quantized == quantized
    np.testing.assert_array_equal(got.prompt, h.prompt)
    wire_arr = np.frombuffer(wire, np.uint8)
    assert jax.tree.structure(got.blocks) == jax.tree.structure(h.blocks)
    for a, b in zip(jax.tree.leaves(h.blocks), jax.tree.leaves(got.blocks)):
        np.testing.assert_array_equal(a, b)
        assert np.shares_memory(b, wire_arr), (
            "received tensor does not view the wire buffer"
        )


def test_corrupt_and_truncated_handoff_raise():
    rng = np.random.RandomState(4)
    h = _rand_handoff(rng)
    msg = pack_handoff(h)
    wire = bytearray(b"".join(frame_parts(msg)))
    # Truncation: drop the payload tail — frame lengths stop tiling.
    with pytest.raises((HandoffError, ConnectionError)):
        unpack_handoff(parse_frame(memoryview(wire)[8:-17]))
    # Corruption: scribble over the page annex (JSON) region.
    wire2 = bytearray(wire)
    wire2[30:40] = b"\xff" * 10
    with pytest.raises((HandoffError, ConnectionError)):
        unpack_handoff(parse_frame(memoryview(wire2)[8:]))


def test_pager_adopt_cached():
    p = Pager(6, 2, 4)  # 5 allocatable pages
    keys = [b"k0", b"k1", b"k2"]
    got = p.adopt_cached(keys)
    assert [i for i, _ in got] == [0, 1, 2]
    st = p.stats()
    assert st.cached == 3 and st.in_use == 0
    # Dedupe: resident keys are skipped, only the new one adopts.
    got2 = p.adopt_cached([b"k1", b"k3"])
    assert [i for i, _ in got2] == [1]
    # Pool pressure: 1 page left free, 4 cached (evictable) -> a
    # 6-new-key adoption cannot fit all-or-nothing.
    assert p.adopt_cached([f"n{i}".encode() for i in range(6)]) == []
    # An admission's prefix probe shares an adopted page (rc 0 -> 1).
    page = dict(got)[0]
    assert p.lookup_share(0, b"k0") == page
    assert p.stats().in_use == 1


def test_disagg_stream_bit_identical_and_hot_path(lm_setup):
    """The core pin: greedy streams through the disaggregated path
    equal the collocated path token-for-token; the handoff lands as
    prefix-cache hits; steady decode ticks afterwards stay at zero
    h2d with no sentinel events."""
    lm, variables = lm_setup
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in (37, 29, 50)]
    steps = [12, 9, 10]
    ref_bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE,
    )
    rids = [ref_bat.submit(p, s) for p, s in zip(prompts, steps)]
    refs = ref_bat.run()
    decode, worker, srv = _mk_pair(lm, variables)
    sids = [srv.submit(p, s) for p, s in zip(prompts, steps)]
    outs = srv.run()
    for rid, sid, p in zip(rids, sids, prompts):
        np.testing.assert_array_equal(
            refs[rid], outs[sid], err_msg=f"prompt len {len(p)}"
        )
    assert srv.disaggregated == 3 and srv.collocated == 0
    assert worker.handoffs == 3
    st = decode.stats()
    assert st["prefix_hits"] >= sum((len(p) - 1) // PAGE for p in prompts)
    # Steady-state hot path survives: occupy a slot, then tick with no
    # admissions — zero staging transfers, no new compiled variants.
    sid = srv.submit(prompts[0][:5], 30)  # short -> collocated; stays
    srv.tick()  # live across the steady window below (retirement is
    # allowed its own O(1) staging — the pin here is the TICKS)
    h2d0 = decode.stats()["h2d_transfers"]
    for _ in range(3):
        srv.tick()
    assert decode.stats()["h2d_transfers"] == h2d0
    assert decode._sentinel.sample(write_gauges=False) == 0
    srv.run()


def test_handoff_pages_equal_inplace_chunked_prefill(lm_setup):
    """Satellite pin: pages packed/framed/unpacked into a FRESH pool
    hold byte-identical K/V to an in-place chunked prefill with the
    same chunk schedule — so attention outputs over them are identical
    too (the stream test above covers the end-to-end claim)."""
    lm, variables = lm_setup
    rng = np.random.RandomState(5)
    # m*PAGE is a multiple of the chunk (2 pages), so the worker's
    # chunk passes coincide exactly with the collocated ones.
    prompt = rng.randint(0, VOCAB, size=4 * PAGE + 3).astype(np.int32)
    colo = ContinuousBatcher(
        lm, variables, slots=1, chunk=4, kv_layout="paged",
        page_size=PAGE, prefill_chunk=2 * PAGE,
    )
    colo.submit(prompt, 2)
    colo.run()
    decode, worker, srv = _mk_pair(lm, variables)
    sid = srv.submit(prompt, 2)
    srv.run()
    m = (len(prompt) - 1) // PAGE
    key = Pager.prefix_key(prompt, m * PAGE)
    for bat in (colo, decode):
        assert bat._pager._by_key.get(key) is not None
    # A native block's pool is ONE plane, K|V fused on the lanes.
    for b, (cpool, dpool) in enumerate(zip(colo._caches, decode._caches)):
        for j in range(m):
            pkey = Pager.prefix_key(prompt, (j + 1) * PAGE)
            cpage = colo._pager._by_key[pkey]
            dpage = decode._pager._by_key[pkey]
            np.testing.assert_array_equal(
                np.asarray(cpool[cpage]),
                np.asarray(dpool[dpage]),
                err_msg=f"block {b} page {j}",
            )


def test_corrupt_wire_fails_request_cleanly(lm_setup, monkeypatch):
    """A corrupted handoff frame fails ONLY that request (empty
    result, request_failed + finish events — result() never wedges);
    the next request serves normally."""
    lm, variables = lm_setup
    decode, worker, srv = _mk_pair(lm, variables)
    import adapt_tpu.runtime.disagg as disagg_mod

    real_loopback = disagg_mod.loopback

    def corrupting(msg):
        wire = bytearray(b"".join(frame_parts(msg)))
        wire[len(wire) // 2] ^= 0xFF  # flip a payload byte mid-frame
        try:
            return parse_frame(memoryview(wire)[8:])
        except ConnectionError as e:
            raise HandoffError(str(e)) from e

    monkeypatch.setattr(disagg_mod, "loopback", corrupting)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, VOCAB, size=40).astype(np.int32)
    rec0 = global_flight_recorder().kind_counts().get("request_failed", 0)
    sid = srv.submit(prompt, 8)
    out = srv.result(sid, max_ticks=200)
    assert out.shape == (0,)
    counts = global_flight_recorder().kind_counts()
    assert counts.get("request_failed", 0) == rec0 + 1
    assert srv.failed == 1
    # Un-corrupt the wire: serving continues, streams stay exact —
    # and streaming callbacks see the SERVER id (the one submit
    # returned and cancel()/result() accept), not the decode rid.
    monkeypatch.setattr(disagg_mod, "loopback", real_loopback)
    cb_ids = []
    sid2 = srv.submit(
        prompt, 8, on_token=lambda rid, tok, idx: cb_ids.append(rid)
    )
    out2 = srv.result(sid2, max_ticks=400)
    assert set(cb_ids) == {sid2} and len(cb_ids) == len(out2)
    ref = ContinuousBatcher(
        lm, variables, slots=1, chunk=4, kv_layout="paged",
        page_size=PAGE,
    )
    rid = ref.submit(prompt, 8)
    np.testing.assert_array_equal(ref.run()[rid], out2)


def test_placement_policy_and_role_lease(lm_setup):
    """Threshold + occupancy knobs route requests; a dead role-tagged
    prefill lease falls back to collocated; the lease is invisible to
    untagged membership queries with a role filter."""
    lm, variables = lm_setup
    reg = WorkerRegistry(default_ttl_s=5.0)
    decode = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE,
    )
    worker = PrefillWorker(lm, variables, page_size=PAGE)
    srv = DisaggServer(
        decode, worker,
        DisaggConfig(prompt_threshold=48, busy_prompt_threshold=17,
                     busy_occupancy=0.5),
        registry=reg,
    )
    assert reg.alive(role="prefill") == ["prefill:prefill0"]
    assert reg.alive(role="decode") == []
    assert reg.role("prefill:prefill0") == "prefill"
    # Idle decode tier: only the long threshold disaggregates.
    assert not srv._placement(30)
    assert srv._placement(60)
    assert not srv._placement(PAGE)  # no full page to hand off
    # Busy decode tier: the tightened threshold applies.
    decode.slots[0].req = object()  # occupancy 0.5 >= busy_occupancy
    assert srv._placement(30)
    decode.slots[0].req = None
    # Dead lease: the policy stops routing to the prefill tier.
    reg.deregister("prefill:prefill0")
    assert not srv._placement(60)
    # And the registry-level role filter keeps the pools disjoint the
    # other way: an untagged worker never shows up under the role, and
    # the dispatcher-side untagged query never sees a tagged lease.
    reg.register("w0")
    assert reg.alive(role="prefill") == []
    assert "w0" in reg.alive()
    # The next tick's keepalive resurrects an EXPIRED lease (the tier
    # is self-evidently alive — it is ticking)...
    srv.tick()
    assert reg.alive(role="prefill") == ["prefill:prefill0"]
    assert reg.alive_untagged() == ["w0"]
    # ...but close() is the drain switch: the lease stays gone.
    srv.close()
    srv.tick()
    assert reg.alive(role="prefill") == []
    assert not srv._placement(60)


def test_prefill_stall_metric(lm_setup):
    """continuous.prefill_stall_s records decode-tick delay only when
    a decoding request was actually waiting behind in-tick prefill."""
    lm, variables = lm_setup
    reg = global_metrics()
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE,
    )

    def stall_count():
        h = reg.snapshot()["histograms"].get(
            "continuous.prefill_stall_s", {}
        )
        return h.get("count", 0)

    rng = np.random.RandomState(2)
    bat.submit(rng.randint(0, VOCAB, size=6).astype(np.int32), 12)
    c0 = stall_count()
    bat.tick()  # admission into an EMPTY batch: nobody waited
    assert stall_count() == c0
    bat.tick()
    c1 = stall_count()
    bat.submit(rng.randint(0, VOCAB, size=40).astype(np.int32), 4)
    bat.tick()  # long admission while slot 0 decodes: a stall sample
    assert stall_count() == c1 + 1
    bat.tick()  # steady tick, no prefill work: no sample
    assert stall_count() == c1 + 1
    bat.run()


def test_prefill_cancel_before_handoff(lm_setup):
    """A cancel landing while the request is still in the prefill tier
    drops it with an empty result and balanced lifecycle events."""
    lm, variables = lm_setup
    decode, worker, srv = _mk_pair(lm, variables)
    rng = np.random.RandomState(6)
    sid = srv.submit(rng.randint(0, VOCAB, size=40).astype(np.int32), 8)
    assert worker.pending() == 1
    assert srv.cancel(sid)
    assert worker.pending() == 0
    assert srv.result(sid, max_ticks=5).shape == (0,)
    assert not srv.cancel(sid)  # already resolved


@pytest.mark.slow
@pytest.mark.parametrize(
    "dtype,tp", [("int8", 1), ("native", 2), ("int8", 2)]
)
def test_disagg_bit_identity_matrix(lm_setup, sim_mesh, dtype, tp):
    """int8 pools and tp-sharded decode pools: the handoff (scales
    travel with their values; per-shard slices land with no gather)
    stays bit-identical to the collocated path."""
    from jax.sharding import Mesh

    lm, variables = lm_setup
    mesh = sim_mesh(tp) if tp > 1 else None
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in (37, 29, 50)]
    steps = [10, 8, 9]
    kw = dict(slots=2, chunk=4, kv_layout="paged", page_size=PAGE,
              kv_cache_dtype=dtype)
    if mesh is not None:
        kw.update(mesh=mesh, parallel=ParallelConfig(tp=tp))
    ref = ContinuousBatcher(lm, variables, **kw)
    rids = [ref.submit(p, s) for p, s in zip(prompts, steps)]
    refs = ref.run()
    decode, worker, srv = _mk_pair(
        lm, variables, dtype=dtype, mesh=mesh, tp=tp
    )
    sids = [srv.submit(p, s) for p, s in zip(prompts, steps)]
    outs = srv.run()
    for rid, sid in zip(rids, sids):
        np.testing.assert_array_equal(refs[rid], outs[sid])
    assert srv.disaggregated == len(prompts)
    # Per-device bytes stay logical/tp after adoption (the handoff
    # placed per-shard slices, never replicated pages).
    st = decode.stats()
    assert st["cache_bytes_per_device"] * tp == st["cache_bytes"]


@pytest.mark.slow
def test_disagg_speculative_compose(lm_setup):
    """Speculative decode batcher behind the disaggregated path:
    handed-off requests admit through the prefix cache, the draft
    prefills decode-side as always, greedy streams stay lossless."""
    lm, variables = lm_setup
    draft = transformer_lm(VOCAB, 16, 1, 1, 32, max_len=96,
                           name="disagg_draft")
    dvars = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in (37, 26)]
    steps = [10, 8]
    spec = SpeculativeConfig(draft_k=3)
    ref = ContinuousBatcher(
        lm, variables, slots=2, chunk=4, kv_layout="paged",
        page_size=PAGE, draft_lm=draft, draft_variables=dvars,
        speculative=spec,
    )
    rids = [ref.submit(p, s) for p, s in zip(prompts, steps)]
    refs = ref.run()
    decode, worker, srv = _mk_pair(
        lm, variables, spec=spec, draft=(draft, dvars)
    )
    sids = [srv.submit(p, s) for p, s in zip(prompts, steps)]
    outs = srv.run()
    for rid, sid in zip(rids, sids):
        np.testing.assert_array_equal(refs[rid], outs[sid])
    assert srv.disaggregated == len(prompts)
