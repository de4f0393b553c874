"""Elastic mesh recovery (ISSUE 8): survive a chip loss in the TP
request tier with live KV resharding.

The contract under test, end to end:

- **detection** — ``DeviceHealthMonitor.kill`` (the simulated-kill
  injection point) revokes the device's membership lease; the batcher
  consumes the ``leave`` event at its next tick (or raises
  ``DeviceLostError`` under ``auto_reshard=False``);
- **re-lowering** — the mesh rebuilds from survivors (tp=4 -> tp=2),
  the program families re-lower with exactly ONE new variant each (no
  phantom variants, no sentinel recompile events), per-device KV bytes
  land at logical/2, and the steady-state tick goes back to staging
  zero host arrays;
- **live migration** — surviving in-flight greedy requests finish
  BIT-IDENTICAL to an uninterrupted tp=4 run (both KV layouts,
  speculative mode, int8 pools included);
- **replay** — non-migratable requests replay from the journal to
  identical tokens, re-entering through the paged prefix cache
  (``paged.prefix_hits`` increments) instead of a full re-prefill;
- **observability** — ``device_lost`` / ``mesh_reshard`` /
  ``kv_migrated`` / ``replayed_from_journal`` flight events with
  ``kind_counts()`` visibility, the ``recovery.wall_s`` histogram and
  ``recovery.{migrated,replayed,dropped}_total`` counters;
- **combined fault** (slow) — a device kill concurrent with a cancel
  storm and live /metrics.json + /debug/events scrapes: the lifecycle
  books balance, no gauge goes negative, every scrape parses.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.config import (
    ParallelConfig,
    RecoveryConfig,
    SLOSpec,
    SpeculativeConfig,
)
from adapt_tpu.control.journal import DispatcherJournal
from adapt_tpu.control.registry import DeviceHealthMonitor
from adapt_tpu.models.transformer_lm import generate, transformer_lm
from adapt_tpu.runtime.continuous import ContinuousBatcher, DeviceLostError
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import global_compile_sentinel
from adapt_tpu.utils.tracing import global_flight_recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def lm_setup():
    # GQA with kv_heads divisible by tp=4 AND tp=2 — the divisor-shrink
    # class elastic recovery serves.
    lm = transformer_lm(37, 32, 2, 8, 64, max_len=48, kv_heads=4,
                        name="rec_target")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def draft_setup():
    draft = transformer_lm(37, 16, 1, 1, 32, max_len=48, name="rec_draft")
    variables = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    return draft, variables


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


def _tp4(lm, variables, sim_mesh, health=None, **kw):
    return ContinuousBatcher(
        lm, variables, mesh=sim_mesh(4), parallel=ParallelConfig(tp=4),
        health=health, **kw,
    )


def _mesh_devices(bat):
    return list(bat._mesh.devices.flat)


PROMPTS = [
    np.asarray(p, np.int32)
    for p in ([1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11, 12], [13, 14, 15, 16, 17])
]
STEPS = [20, 14, 10]


def _run_workload(bat, kill_device=None, monitor=None):
    """Staggered admits; optionally kill one mesh device after the
    third request's first tick (every request slot-bound and
    mid-stream); run to drain."""
    ids = [bat.submit(PROMPTS[0], STEPS[0]), bat.submit(PROMPTS[1], STEPS[1])]
    bat.tick()
    bat.tick()
    ids.append(bat.submit(PROMPTS[2], STEPS[2]))
    bat.tick()  # admit the third: all three decoding at kill time
    if kill_device is not None:
        monitor.kill(kill_device)
    out = bat.run()
    return [out[r] for r in ids]


@pytest.mark.parametrize("page_size", [128, 8])
def test_kill_midstream_bit_identical(lm_setup, sim_mesh, page_size):
    """THE acceptance pin: kill one device of the tp=4 mesh mid-stream;
    every surviving in-flight greedy request finishes bit-identical to
    the uninterrupted tp=4 run AND to solo generate(), inside one
    page and across pages; per-device KV bytes land at logical/2 on the shrunk
    mesh."""
    lm, variables = lm_setup
    kw = dict(slots=3, chunk=2, page_size=page_size)
    base_bat = _tp4(lm, variables, sim_mesh, **kw)
    base = _run_workload(base_bat)
    base_bat.close()
    mon = DeviceHealthMonitor()
    bat = _tp4(lm, variables, sim_mesh, health=mon, **kw)
    got = _run_workload(bat, kill_device=_mesh_devices(bat)[3], monitor=mon)
    st = bat.stats()
    assert st["tp"] == 2
    assert st["recoveries"] == 1
    assert st["recovery_migrated"] == 3  # all three were decoding
    assert st["recovery_replayed"] == 0
    assert st["recovery_dropped"] == 0
    assert st["last_recovery_wall_s"] > 0.0
    assert st["cache_bytes_per_device"] * 2 == st["cache_bytes"]
    # The embedding tables (rows of 32) were re-placed as the engine
    # holds them, a whole lane tile a row; it hands out the model's.
    assert st["embed_row_pad"] == 96
    held, own = (
        {t.shape[-1] for t in jax.tree.leaves(v["embed"])}
        for v in (bat._served, bat.variables)
    )
    assert (held, own) == ({128}, {32})
    for i in range(3):
        np.testing.assert_array_equal(
            got[i], base[i], err_msg=f"req {i}: killed != uninterrupted"
        )
        np.testing.assert_array_equal(
            got[i], _solo(lm, variables, PROMPTS[i], STEPS[i]),
            err_msg=f"req {i}: killed != solo generate()",
        )
    bat.close()


@pytest.mark.parametrize("page_size", [128, 8])
def test_kill_speculative_int8(lm_setup, draft_setup, sim_mesh, page_size):
    """Recovery composes with the full stack: speculative mode + int8
    pools. The killed run stays lossless vs solo
    generate(kv_cache_dtype='int8') at both page sizes, the draft state
    re-replicates, and both quantized pytree members land at
    logical/2 per device."""
    lm, variables = lm_setup
    draft, dvars = draft_setup
    kw = dict(slots=2, kv_cache_dtype="int8", draft_lm=draft,
              draft_variables=dvars,
              speculative=SpeculativeConfig(draft_k=3),
              page_size=page_size)
    mon = DeviceHealthMonitor()
    bat = _tp4(lm, variables, sim_mesh, health=mon, **kw)
    r1 = bat.submit(PROMPTS[0], 9)
    r2 = bat.submit(PROMPTS[1], 7)
    bat.tick()
    mon.kill(_mesh_devices(bat)[2])
    out = bat.run()
    st = bat.stats()
    assert st["tp"] == 2 and st["recoveries"] == 1
    assert st["cache_bytes_per_device"] * 2 == st["cache_bytes"]
    # Both pytree members (int8 values AND f32 scales) head-shard to
    # exactly half per device after the reshard.
    for member in jax.tree.leaves(bat._caches):
        from adapt_tpu.utils.profiling import device_local_nbytes

        assert device_local_nbytes(member) * 2 == member.nbytes
    for r, (p, s) in ((r1, (PROMPTS[0], 9)), (r2, (PROMPTS[1], 7))):
        np.testing.assert_array_equal(
            out[r],
            _solo(lm, variables, p, s, kv_cache_dtype="int8"),
        )
    bat.close()


def test_replay_policy_journal_roundtrip(lm_setup, sim_mesh, tmp_path):
    """policy='replay': every in-flight request re-queues from its
    JOURNALED record (payload + sampling-knob meta) instead of
    migrating — identical final tokens, ``replayed_from_journal``
    flight events with source='journal', and done marks leave the
    journal with no pending entries once drained."""
    lm, variables = lm_setup
    journal = DispatcherJournal(str(tmp_path / "wal"))
    mon = DeviceHealthMonitor()
    rec = global_flight_recorder()
    before = rec.kind_counts().get("replayed_from_journal", 0)
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=3, chunk=2,
        recovery=RecoveryConfig(policy="replay"), journal=journal,
    )
    got = _run_workload(bat, kill_device=_mesh_devices(bat)[1], monitor=mon)
    st = bat.stats()
    assert st["tp"] == 2
    assert st["recovery_replayed"] == 3 and st["recovery_migrated"] == 0
    for i in range(3):
        np.testing.assert_array_equal(
            got[i], _solo(lm, variables, PROMPTS[i], STEPS[i]),
            err_msg=f"replayed req {i}",
        )
    events = [
        e for e in rec.events("replayed_from_journal")
        if e["data"].get("source") == "journal"
    ]
    assert rec.kind_counts()["replayed_from_journal"] - before == 3
    assert len(events) >= 3
    # Every request finished -> done-marked: nothing pending on disk.
    _, pending, _ = journal.load()
    assert pending == {}
    bat.close()
    journal.close()


def test_replay_streams_exactly_once(lm_setup, sim_mesh):
    """A replayed request's on_token transcript has no duplicated
    prefix: indices delivered pre-kill are suppressed on the re-run
    (which regenerates them identically), later ones arrive once each
    — and the request's TTFT is not re-observed in its second life."""
    lm, variables = lm_setup
    reg = global_metrics()
    ttft0 = reg.snapshot()["histograms"].get("continuous.ttft_s", {}).get(
        "count", 0
    )
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        recovery=RecoveryConfig(policy="replay"),
    )
    stream: list[tuple[int, int]] = []
    r = bat.submit(
        PROMPTS[0], STEPS[0],
        on_token=lambda rid, tok, idx: stream.append((idx, int(tok))),
    )
    bat.tick()
    bat.tick()  # several tokens delivered pre-kill
    assert len(stream) >= 2
    mon.kill(_mesh_devices(bat)[2])
    out = bat.run()
    assert bat.stats()["recovery_replayed"] == 1
    assert [i for i, _ in stream] == list(range(len(out[r]))), (
        "duplicated or missing stream indices across the replay"
    )
    np.testing.assert_array_equal([t for _, t in stream], out[r])
    ttft1 = reg.snapshot()["histograms"]["continuous.ttft_s"]["count"]
    assert ttft1 - ttft0 == 1, "replay re-observed TTFT"
    bat.close()


def test_replay_reenters_prefix_cache(lm_setup, sim_mesh):
    """The replay-from-prefix-cache satellite: a replayed paged request
    whose prompt spans full pages re-admits through the content-
    addressed prefix cache (``paged.prefix_hits`` increments; its
    pages were registered at the original admission and survive the
    reshard with their contents), instead of paying a full
    re-prefill."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        kv_layout="paged", page_size=8,
        recovery=RecoveryConfig(policy="replay"),
    )
    prompt = np.arange(1, 20, dtype=np.int32)  # 19 tokens: 2 full pages
    r = bat.submit(prompt, 16)
    bat.tick()
    bat.tick()
    hits0 = bat.stats()["prefix_hits"]
    mon.kill(_mesh_devices(bat)[0])  # device 0 dies; mesh rebuilds [1, 2]
    out = bat.run()
    st = bat.stats()
    assert st["recovery_replayed"] == 1
    assert st["prefix_hits"] > hits0, (
        "replayed request did not re-enter through the prefix cache"
    )
    np.testing.assert_array_equal(
        out[r], _solo(lm, variables, prompt, 16)
    )
    bat.close()


def test_dead_at_construction_detected(lm_setup, sim_mesh):
    """A device already dead on the shared monitor when the batcher is
    constructed delivers NO future 'leave' event (its lease is gone,
    and track() refuses to resurrect it) — the constructor must seed
    the loss from ``dead_ids()`` or every tick dispatches onto the
    dead chip undetected."""
    lm, variables = lm_setup
    mesh = sim_mesh(4)
    mon = DeviceHealthMonitor()
    dead = list(mesh.devices.flat)[3]
    mon.kill(dead)  # dies BEFORE the batcher exists
    bat = ContinuousBatcher(
        lm, variables, mesh=mesh, parallel=ParallelConfig(tp=4),
        health=mon, slots=2, chunk=2,
    )
    assert bat.device_lost_pending(), (
        "pre-existing dead device not detected at construction"
    )
    r = bat.submit(PROMPTS[0], STEPS[0])
    out = bat.run()
    st = bat.stats()
    assert st["tp"] == 2 and st["recoveries"] == 1
    np.testing.assert_array_equal(
        out[r], _solo(lm, variables, PROMPTS[0], STEPS[0])
    )
    bat.close()


def test_queued_cancel_of_replayed_request_keeps_delivered_stream(
    lm_setup, sim_mesh
):
    """A cancel landing while a recovery-replayed request waits for
    re-admission resolves result() with the tokens the client already
    received in its first life — not the empty array a never-admitted
    queued request gets (the stream and result() must never
    disagree)."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        recovery=RecoveryConfig(policy="replay"),
    )
    stream: list[int] = []
    r = bat.submit(
        PROMPTS[0], STEPS[0],
        on_token=lambda rid, tok, idx: stream.append(int(tok)),
    )
    bat.tick()
    bat.tick()
    assert len(stream) >= 2  # tokens delivered pre-kill
    mon.kill(_mesh_devices(bat)[2])
    bat.recover()  # replay re-queues the request; no tick yet
    assert bat.cancel(r)
    out = bat.run()
    np.testing.assert_array_equal(
        out[r], np.asarray(stream, np.int32),
        err_msg="queued cancel of a replayed request lost its "
                "delivered stream",
    )
    # Serve one more request on the shrunk mesh: the batcher survives
    # a recovery whose only in-flight request was cancelled away — and
    # the re-lowered program families compile HERE, consuming the
    # recovery's expected-compile allowances instead of leaking them
    # onto the shared class-level sentinel watches (where they would
    # absorb another batcher's real phantom-variant event).
    r2 = bat.submit(PROMPTS[1], STEPS[1])
    out2 = bat.run()
    np.testing.assert_array_equal(
        out2[r2], _solo(lm, variables, PROMPTS[1], STEPS[1])
    )
    bat.close()


def test_replay_first_new_token_itl_spans_recovery(lm_setup, sim_mesh):
    """The first post-regeneration token's ITL gap measures from the
    last token the client RECEIVED pre-kill — so a replay-policy
    recovery stall is judged against the ITL budget exactly like a
    migrated request's is, not hidden behind the regenerated prefix's
    fresh commit stamps."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        recovery=RecoveryConfig(policy="replay"),
    )
    r = bat.submit(
        PROMPTS[0], STEPS[0], slo=SLOSpec(itl_budget_s=5.0, tenant="rec")
    )
    bat.tick()
    bat.tick()
    mon.kill(_mesh_devices(bat)[1])
    bat.recover()
    req = next(q for q in bat._queue if q.req_id == r)
    assert req.t_last_delivered > 0.0, (
        "replay did not carry the pre-kill delivery stamp"
    )
    # Simulate a recovery stall far past the budget: with the gap
    # measured from the carried stamp this is an ITL miss; measured
    # from the regenerated prefix's last commit it would pass.
    req.t_last_delivered -= 100.0
    bat.run()
    assert bat.stats()["slo_itl_missed"] >= 1, (
        "kill-to-recovery stall never registered as an ITL violation"
    )
    bat.close()


@pytest.mark.parametrize("quant", ["native", "int8"])
def test_post_reshard_invariants(lm_setup, sim_mesh, quant):
    """Satellite 3: after tp=4 -> tp=2 the hot-path invariants
    re-establish — per-device KV bytes == logical/2 for BOTH pytree
    members of paged pools (native and int8), ZERO h2d per steady
    tick, and the compile sentinel sees exactly ONE re-lowered
    step-chunk variant with zero recompile EVENTS (the re-arm makes
    re-lowering expected, not phantom)."""
    from adapt_tpu.utils.profiling import device_local_nbytes

    lm, variables = lm_setup
    sentinel = global_compile_sentinel()
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        kv_layout="paged", page_size=8, kv_cache_dtype=quant,
    )
    r1 = bat.submit(PROMPTS[0], 30)
    bat.tick()
    bat.tick()
    variants0 = sentinel.compiles("continuous.step_chunk")
    events0 = sentinel.events
    mon.kill(_mesh_devices(bat)[3])
    bat.tick()  # recovers + decodes on the shrunk mesh
    # Exactly one re-lowered decode variant; the sentinel fired NO
    # unexpected-recompile event for it (warmup re-armed).
    assert sentinel.compiles("continuous.step_chunk") - variants0 == 1
    assert sentinel.events == events0
    st = bat.stats()
    assert st["tp"] == 2
    assert st["cache_bytes_per_device"] * 2 == st["cache_bytes"]
    for member in jax.tree.leaves(bat._caches):
        assert device_local_nbytes(member) * 2 == member.nbytes
    bat.tick()  # settle: first post-recovery tick re-uploads the table
    h0 = bat.stats()["h2d_transfers"]
    for _ in range(3):
        bat.tick()
    assert bat.stats()["h2d_transfers"] == h0, (
        "steady-state tick staged host arrays after the reshard"
    )
    # Churn on the shrunk mesh adds no further variants.
    variants1 = sentinel.compiles("continuous.step_chunk")
    bat.run()
    r2 = bat.submit(PROMPTS[2], 4)
    out = bat.run()
    assert set(out) == {r2} or r1 in out
    assert sentinel.compiles("continuous.step_chunk") == variants1
    assert sentinel.events == events0
    bat.close()


def test_flight_events_and_recovery_metrics(lm_setup, sim_mesh):
    """Satellite 1: the full lifecycle is visible — device_lost /
    mesh_reshard / kv_migrated flight events land in kind_counts(),
    recovery.wall_s records a histogram sample and the
    recovery.*_total counters move."""
    lm, variables = lm_setup
    rec = global_flight_recorder()
    reg = global_metrics()
    k0 = rec.kind_counts()
    snap0 = reg.snapshot()
    mon = DeviceHealthMonitor()
    bat = _tp4(lm, variables, sim_mesh, health=mon, slots=2, chunk=2)
    bat.submit(PROMPTS[0], 12)
    bat.tick()
    mon.kill(_mesh_devices(bat)[3])
    bat.run()
    k1 = rec.kind_counts()
    assert k1.get("device_lost", 0) - k0.get("device_lost", 0) == 1
    assert k1.get("mesh_reshard", 0) - k0.get("mesh_reshard", 0) == 1
    assert k1.get("kv_migrated", 0) - k0.get("kv_migrated", 0) == 1
    ev = rec.events("mesh_reshard")[-1]["data"]
    assert ev["old_tp"] == 4 and ev["new_tp"] == 2
    assert ev["moved_bytes"] > 0 and ev["host_staged_bytes"] > 0
    snap1 = reg.snapshot()
    c0 = snap0["counters"].get("recovery.migrated_total", 0.0)
    assert snap1["counters"]["recovery.migrated_total"] - c0 == 1.0
    h = snap1["histograms"]["recovery.wall_s"]
    assert h["count"] >= 1 and h["max"] > 0.0
    bat.close()


def test_auto_reshard_off_raises_then_manual_recover(lm_setup, sim_mesh):
    """auto_reshard=False: dispatches after a loss raise
    DeviceLostError (nothing runs on the broken layout) until
    recover() is called explicitly — then the stream completes
    identically."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        recovery=RecoveryConfig(auto_reshard=False),
    )
    r = bat.submit(PROMPTS[0], 12)
    bat.tick()
    mon.kill(_mesh_devices(bat)[2])
    assert bat.device_lost_pending()
    with pytest.raises(DeviceLostError, match="auto_reshard"):
        bat.tick()
    with pytest.raises(DeviceLostError):
        bat.tick()  # still broken: every dispatch raises
    bat.recover()
    out = bat.run()
    np.testing.assert_array_equal(
        out[r], _solo(lm, variables, PROMPTS[0], 12)
    )
    assert bat.stats()["tp"] == 2
    bat.close()


def test_min_tp_floor_refuses_recovery(lm_setup, sim_mesh):
    """RecoveryConfig.min_tp: survivors below the floor raise instead
    of silently serving from a remnant that cannot hold the model."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        recovery=RecoveryConfig(min_tp=2),
    )
    bat.submit(PROMPTS[0], 8)
    bat.tick()
    devs = _mesh_devices(bat)
    for d in devs[1:]:
        mon.kill(d)  # one survivor -> tp=1 < min_tp=2
    with pytest.raises(DeviceLostError, match="min_tp"):
        bat.tick()
    bat.close()


def test_triple_kill_single_device_fallback(lm_setup, sim_mesh):
    """Losing 3 of 4 chips degrades to the single-device path (the
    degenerate-mesh discipline): the stream still finishes identical
    to solo generate(), and staging lands on the SURVIVING device."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(lm, variables, sim_mesh, health=mon, slots=2, chunk=2)
    r = bat.submit(PROMPTS[1], 12)
    bat.tick()
    devs = _mesh_devices(bat)
    for d in (devs[0], devs[2], devs[3]):
        mon.kill(d)
    out = bat.run()
    st = bat.stats()
    assert st["tp"] == 1 and st["recoveries"] == 1
    np.testing.assert_array_equal(
        out[r], _solo(lm, variables, PROMPTS[1], 12)
    )
    # Post-recovery traffic works end to end on the remnant.
    r2 = bat.submit(PROMPTS[0], 5)
    out = bat.run()
    np.testing.assert_array_equal(
        out[r2], _solo(lm, variables, PROMPTS[0], 5)
    )
    # Losing the LAST remnant device must raise — the degraded batcher
    # (mesh=None but still device-backed) cannot report healthy and
    # dispatch onto a dead chip.
    bat.submit(PROMPTS[2], 4)
    mon.kill(devs[1])
    assert bat.device_lost_pending()
    with pytest.raises(DeviceLostError, match="every device"):
        bat.tick()
    bat.close()


def test_mid_chunked_prefill_replays(lm_setup, sim_mesh):
    """A slot mid-chunked-prefill at kill time has emitted nothing —
    it REPLAYS (policy='migrate' notwithstanding) and still produces
    the exact stream."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = _tp4(
        lm, variables, sim_mesh, health=mon, slots=2, chunk=2,
        kv_layout="paged", page_size=8, prefill_chunk=8,
    )
    long_prompt = np.arange(1, 30, dtype=np.int32)  # 29 toks: 4 chunks
    r = bat.submit(long_prompt, 6)
    bat.tick()  # first prefill chunk only — nothing emitted yet
    assert bat.slots[0].pf_done >= 0
    mon.kill(_mesh_devices(bat)[1])
    out = bat.run()
    st = bat.stats()
    assert st["recovery_replayed"] == 1 and st["recovery_migrated"] == 0
    np.testing.assert_array_equal(
        out[r], _solo(lm, variables, long_prompt, 6)
    )
    bat.close()


def test_health_monitor_membership_semantics():
    """The monitor IS membership: tracked devices own registry leases,
    kill revokes exactly one, watchers see the leave, and re-tracking
    a dead device does not resurrect it."""
    mon = DeviceHealthMonitor()
    devs = jax.devices()[:4]
    mon.track(devs)
    alive = set(mon.registry.alive())
    assert {DeviceHealthMonitor.device_key(d) for d in devs} <= alive
    events = []
    mon.watch(lambda ev, key: events.append((ev, key)))
    key = mon.kill(devs[2])
    assert key == f"device:{devs[2].id}"
    assert ("leave", key) in events
    assert mon.is_dead(devs[2]) and not mon.is_dead(devs[0])
    assert mon.alive_devices(devs) == [devs[0], devs[1], devs[3]]
    mon.kill(devs[2])  # idempotent: no second leave
    assert [e for e in events if e == ("leave", key)] == [("leave", key)]
    mon.track(devs)  # dead device must not rejoin
    assert key not in set(mon.registry.alive())
    # A leave arriving from the REGISTRY side — lease expiry is the
    # production loss signal; explicit deregister exercises the same
    # watcher edge — folds into the dead set exactly like kill(), so
    # recover()'s dead_ids() view always agrees with the queued event.
    mon.registry.deregister(DeviceHealthMonitor.device_key(devs[1]))
    assert mon.is_dead(devs[1])
    assert mon.alive_devices(devs) == [devs[0], devs[3]]


@pytest.mark.slow
def test_combined_fault_kill_during_cancel_storm(lm_setup, sim_mesh):
    """Satellite 4: a device kill mid-stream CONCURRENT with a cancel
    storm while /metrics.json and /debug/events scrape continuously.
    The admit/finish books balance (every admitted request finishes,
    cancelled or not), no gauge or counter goes negative, every
    scrape parses, and exactly one reshard happened."""
    from adapt_tpu.utils.exporter import serve_metrics

    lm, variables = lm_setup
    rec = global_flight_recorder()
    server = serve_metrics(port=0)
    port = server.server_address[1]
    stop = threading.Event()
    scrapes: list[dict] = []
    scrape_errors: list[Exception] = []

    def scraper():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics.json", timeout=10
                ) as r:
                    scrapes.append(json.loads(r.read()))
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/events", timeout=10
                ) as r:
                    json.loads(r.read())
            except Exception as e:  # noqa: BLE001 — assert after join
                scrape_errors.append(e)
                return

    mon = DeviceHealthMonitor()
    bat = _tp4(lm, variables, sim_mesh, health=mon, slots=3, chunk=2)
    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    rng = np.random.RandomState(5)
    admits0 = rec.kind_counts().get("admit", 0)
    finishes0 = rec.kind_counts().get("finish", 0)
    try:
        ids = []
        cancelled = set()
        killed = False
        for wave in range(6):
            for _ in range(3):
                p = rng.randint(0, 37, size=rng.randint(2, 10)).astype(
                    np.int32
                )
                ids.append(bat.submit(p, int(rng.randint(4, 16))))
            bat.tick()
            # Storm: cancel ~half of everything in flight each wave.
            for r in ids:
                if r not in cancelled and rng.rand() < 0.5:
                    if bat.cancel(r):
                        cancelled.add(r)
            if wave == 2 and not killed:
                mon.kill(_mesh_devices(bat)[3])  # mid-storm kill
                killed = True
            bat.tick()
        bat.run()
    finally:
        stop.set()
        t.join(timeout=30)
        server.shutdown()
        server.server_close()
    assert not scrape_errors, scrape_errors
    assert scrapes, "scraper never completed a scrape"
    assert cancelled, "storm cancelled nothing"
    st = bat.stats()
    assert st["tp"] == 2 and st["recoveries"] == 1
    assert st["active"] == 0 and st["queued"] == 0
    counts = rec.kind_counts()
    admits = counts.get("admit", 0) - admits0
    finishes = counts.get("finish", 0) - finishes0
    # Every ADMITTED request produced exactly one finish edge — except
    # replayed ones, which admit twice for their single finish. The
    # books balance modulo the recorded replays; queued-cancels
    # consumed before admission appear in neither column.
    replays = st["recovery_replayed"]
    assert admits == finishes + replays, (admits, finishes, replays)
    assert counts.get("mesh_reshard", 0) >= 1
    for snap in [scrapes[-1], global_metrics().snapshot()]:
        for name, v in snap["gauges"].items():
            assert v >= 0.0, f"negative gauge {name}={v}"
        for name, v in snap["counters"].items():
            assert v >= 0.0, f"negative counter {name}={v}"
    bat.close()
