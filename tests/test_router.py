"""Fleet router: prefix-affinity placement, resharded handoff wire,
lease-driven membership, autoscaling.

What is verified here:

- the head-tiled wire: ``pack_handoff(head_ranges=...)`` frames each
  KV leaf as one contiguous slice per destination shard and
  ``unpack_handoff`` regroups them bit-exactly (sender-side reshard —
  never a global gather);
- ``FleetRouter`` placement streams bit-identical to a single-replica
  reference (routing is a placement property, never a numerics one);
- the cross-replica disagg path: a prefill tier feeding a tp=4 decode
  replica over the real wire, 4 head tiles per leaf, landing through
  ``adopt_prefill_pages`` as an ordinary prefix hit;
- the kill-one-of-3 acceptance: deregister one replica's lease
  mid-load, the router re-places its work within the recovery budget,
  streams stay bit-identical and token delivery exactly-once;
- synchronous shed through the replicas' admission books, the
  autoscaler's up/down edges, ``FederatedStore``'s capacity-book
  max-age evict, the ``/fleet/placements`` endpoint and the
  ``fleet_top`` rendering that consumes it.
"""

import importlib.util
import json
import pathlib
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.comm.framing import frame_parts, parse_frame
from adapt_tpu.config import (
    CapacityConfig,
    DisaggConfig,
    ParallelConfig,
    RouterConfig,
    SchedulerConfig,
    ServeConfig,
)
from adapt_tpu.control.registry import WorkerRegistry
from adapt_tpu.models.transformer_lm import transformer_lm
from adapt_tpu.parallel.sharding import head_tiles
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.disagg import (
    HandoffError,
    KVHandoff,
    PrefillWorker,
    pack_handoff,
    unpack_handoff,
)
from adapt_tpu.runtime.router import FleetAutoscaler, FleetRouter
from adapt_tpu.runtime.scheduler import QueueFullError
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.telemetry import FederatedStore
from adapt_tpu.utils.tracing import global_flight_recorder

VOCAB = 31
PAGE = 8


@pytest.fixture
def clean_slate():
    global_metrics().reset()
    global_flight_recorder().clear()
    yield
    global_metrics().reset()
    global_flight_recorder().clear()


@pytest.fixture(scope="module")
def lm_setup():
    # heads=4 so a tp=4 decode replica is buildable (and kv head
    # tiling by 4 engages on the wire); small everywhere else —
    # every batcher compiles its own programs and tier-1 wall time
    # is the budget.
    lm = transformer_lm(VOCAB, 32, 2, 4, 64, max_len=96,
                        name="router_lm")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def _mk_replica(lm, variables, mesh=None, tp=1, scheduler=None):
    kw = dict(
        slots=2, chunk=PAGE, kv_layout="paged", page_size=PAGE,
        capacity=CapacityConfig(refresh_s=0.0), scheduler=scheduler,
    )
    if mesh is not None:
        kw.update(mesh=mesh, parallel=ParallelConfig(tp=tp))
    return ContinuousBatcher(lm, variables, **kw)


# -- config ------------------------------------------------------------------


def test_router_config_validation():
    assert ServeConfig().router.policy == "affinity"
    with pytest.raises(ValueError, match="policy"):
        RouterConfig(policy="round_robin")
    with pytest.raises(ValueError, match="max_replicas"):
        RouterConfig(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="scale_up_queue_frac"):
        RouterConfig(scale_up_queue_frac=1.5)
    with pytest.raises(ValueError, match="book_max_age_s"):
        RouterConfig(book_max_age_s=0.0)


# -- the resharded wire ------------------------------------------------------


def test_head_tiles():
    assert head_tiles(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert head_tiles(4, 1) == [(0, 4)]
    with pytest.raises(ValueError):
        head_tiles(3, 2)  # heads must tile evenly
    with pytest.raises(ValueError):
        head_tiles(4, 0)


def _rand_handoff(rng, quantized=False, blocks=2, n=3, kvh=4, hd=4):
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
def test_ranged_handoff_wire_roundtrip(quantized):
    """Sender-side reshard on the wire: with ``head_ranges`` every KV
    leaf ships as one contiguous frame PER destination tile (the annex
    records the tiling, frame count grows to 1 + leaves * R), and the
    receive side regroups tiles host-side bit-exactly — the resharded
    wire and today's whole-leaf wire decode to the same handoff."""
    rng = np.random.RandomState(3)
    h = _rand_handoff(rng, quantized=quantized, kvh=4)
    ranges = head_tiles(4, 2)
    msg = pack_handoff(h, head_ranges=ranges)
    meta = json.loads(msg.page_annex.decode())
    assert meta["head_ranges"] == [[0, 2], [2, 4]]
    leaves = 2 * (3 if quantized else 1)  # blocks * planes of a pool
    assert len(meta["frame_lens"]) == 1 + leaves * 2
    wire = bytearray(b"".join(frame_parts(msg)))
    got = unpack_handoff(parse_frame(memoryview(wire)[8:]))
    assert got.n_pages == h.n_pages and got.quantized == quantized
    np.testing.assert_array_equal(got.prompt, h.prompt)
    assert jax.tree.structure(got.blocks) == jax.tree.structure(h.blocks)
    for a, b in zip(jax.tree.leaves(h.blocks), jax.tree.leaves(got.blocks)):
        np.testing.assert_array_equal(a, b)


def test_ranged_handoff_bad_tiling_raises():
    rng = np.random.RandomState(4)
    h = _rand_handoff(rng, kvh=4)
    with pytest.raises(HandoffError, match="head_ranges"):
        pack_handoff(h, head_ranges=[(0, 2)])  # leaves heads 2..4 behind
    with pytest.raises(HandoffError, match="head_ranges"):
        pack_handoff(h, head_ranges=[(0, 3), (2, 4)])  # overlap


# -- placement ---------------------------------------------------------------


def test_router_placement_bit_identical(clean_slate, lm_setup):
    """Two replicas behind the router: every stream is bit-identical
    to a single-replica reference (placement is a scheduling decision,
    not a numerics one), the decision ring explains each landing and
    the router's books balance."""
    lm, variables = lm_setup
    reg = WorkerRegistry()
    router = FleetRouter(
        {"r0": _mk_replica(lm, variables),
         "r1": _mk_replica(lm, variables)},
        registry=reg,
    )
    rng = np.random.RandomState(0)
    toks = {}
    prompts, sids = [], []
    for i in range(6):
        p = rng.randint(1, VOCAB, size=12 + (i % 3) * 8).astype(np.int32)
        sid = router.submit(
            p, steps=6,
            on_token=lambda s, t, i: toks.setdefault(s, []).append(i),
        )
        prompts.append(p)
        sids.append(sid)
    out = router.run()
    assert set(out) == set(sids)
    # both replicas hold live leases carrying their capacity books
    # (checked BEFORE the reference compiles — leases only heartbeat
    # while the router ticks)
    for name in ("r0", "r1"):
        meta = reg.alive_meta()[f"decode:{name}"]
        assert meta["capacity"]["kind"] == "decode"
    ref = _mk_replica(lm, variables)
    rids = [ref.submit(p, steps=6) for p in prompts]
    rout = ref.run()
    for sid, rid in zip(sids, rids):
        np.testing.assert_array_equal(out[sid], rout[rid])
    # exactly-once, in-order token delivery
    for sid in sids:
        assert toks[sid] == list(range(len(out[sid])))
    st = router.stats()
    assert st["placed"] == 6 and st["shed"] == 0
    assert st["replicas_live"] == 2
    pl = router.placements()
    assert len(pl["decisions"]) == 6
    assert all(d["kind"] == "placed" for d in pl["decisions"])
    router.close()


def test_router_prefill_reshard_tp4(clean_slate, lm_setup, sim_mesh):
    """The cross-replica disagg path: a (host) prefill tier streams KV
    to a tp=4 decode replica over the real wire, each leaf resharded
    sender-side into 4 head tiles (never a global gather), landing
    through the prefix cache — bit-identical to collocated prefill."""
    lm, variables = lm_setup
    mesh = sim_mesh(4)
    pf = PrefillWorker(
        lm, variables, page_size=PAGE, prefill_chunk=2 * PAGE
    )
    router = FleetRouter(
        {"d0": _mk_replica(lm, variables, mesh=mesh, tp=4)},
        prefill=pf,
        disagg=DisaggConfig(
            prompt_threshold=2 * PAGE, busy_prompt_threshold=2 * PAGE
        ),
    )
    rng = np.random.RandomState(7)
    prompts = [
        rng.randint(1, VOCAB, size=n).astype(np.int32)
        for n in (37, 29, 50)
    ]
    sids = [router.submit(p, steps=6) for p in prompts]
    out = router.run()
    assert set(out) == set(sids)
    evs = [e["data"] for e in global_flight_recorder().events("kv_handoff")]
    assert len(evs) == 3  # every prompt crossed the wire
    assert all(e["tiles"] == 4 and e["adopted"] for e in evs)
    ref = _mk_replica(lm, variables, mesh=mesh, tp=4)
    rids = [ref.submit(p, steps=6) for p in prompts]
    rout = ref.run()
    for sid, rid in zip(sids, rids):
        np.testing.assert_array_equal(out[sid], rout[rid])
    router.close()


def test_router_kill_one_of_three_midload(clean_slate, lm_setup):
    """The acceptance kill: deregister one of three replicas' leases
    mid-load. The router re-places every stranded request on the leave
    edge within the recovery budget, the re-placed (greedy) streams
    finish bit-identical to an undisturbed reference, and each client
    callback saw every token index exactly once."""
    lm, variables = lm_setup
    reg = WorkerRegistry()
    router = FleetRouter(
        {f"r{i}": _mk_replica(lm, variables) for i in range(3)},
        registry=reg,
        config=RouterConfig(recovery_budget_s=2.0),
    )
    rng = np.random.RandomState(1)
    toks = {}
    prompts, sids = [], []
    for _ in range(9):
        p = rng.randint(1, VOCAB, size=12).astype(np.int32)
        sid = router.submit(
            p, steps=6,
            on_token=lambda s, t, i: toks.setdefault(s, []).append(i),
        )
        prompts.append(p)
        sids.append(sid)
    for _ in range(2):  # let the fleet start decoding
        router.tick()
    victim = max(
        router._replicas.values(), key=lambda r: len(r.sids)
    )
    assert victim.sids  # the kill must strand real work
    reg.deregister(victim.lease_key, victim.lease_token)
    out = router.run()
    assert set(out) == set(sids)
    ref = _mk_replica(lm, variables)
    rids = [ref.submit(p, steps=6) for p in prompts]
    rout = ref.run()
    for sid, rid in zip(sids, rids):
        np.testing.assert_array_equal(out[sid], rout[rid])
    # exactly-once delivery across the re-placement (re-placed
    # requests replay their prefix on the survivor; the watermark
    # suppresses the duplicates)
    for sid in sids:
        assert toks[sid] == list(range(len(out[sid])))
    assert router.replaced > 0
    leaves = [
        e["data"]
        for e in global_flight_recorder().events("replica_leave")
    ]
    assert len(leaves) == 1
    assert leaves[0]["reason"] == "lost"
    assert leaves[0]["moved"] == router.replaced
    assert leaves[0]["wall_s"] < 2.0  # the recovery budget
    assert router.stats()["replicas_live"] == 2
    router.close()


def test_router_sheds_synchronously(clean_slate, lm_setup):
    """Overload sheds at submit through the replicas' own admission
    books: once every live replica's queue is at bound the router
    raises QueueFullError BEFORE any work is queued, books the shed
    and records the decision."""
    lm, variables = lm_setup
    router = FleetRouter({
        "r0": _mk_replica(
            lm, variables,
            scheduler=SchedulerConfig(
                max_queue_depth=2, preempt=False, degrade=False
            ),
        ),
    })
    rng = np.random.RandomState(2)
    accepted, sheds = [], 0
    for _ in range(6):
        p = rng.randint(1, VOCAB, size=8).astype(np.int32)
        try:
            accepted.append(router.submit(p, steps=4))
        except QueueFullError:
            sheds += 1
    assert len(accepted) == 2 and sheds == 4
    assert router.shed == 4
    kinds = [d["kind"] for d in router.placements()["decisions"]]
    assert kinds.count("shed") == 4
    c = global_metrics().snapshot()["counters"]
    assert c["router.shed_total"] == 4
    out = router.run()
    assert set(out) == set(accepted)
    router.close()


def test_autoscaler_up_on_pressure_down_on_drain(clean_slate, lm_setup):
    """Queue pressure above the threshold (held past the dwell) spawns
    a replica BEFORE attainment breaks; a drained fleet retires idle
    replicas back to the floor. Both edges land in the flight stream."""
    lm, variables = lm_setup
    cfg = RouterConfig(
        min_replicas=1, max_replicas=2, scale_up_queue_frac=0.5,
        autoscale_dwell_s=0.0, scale_down_idle_s=0.05,
    )
    sched = SchedulerConfig(
        max_queue_depth=4, preempt=False, degrade=False
    )
    router = FleetRouter(
        {"r0": _mk_replica(lm, variables, scheduler=sched)},
        config=cfg,
    )
    spawned = []

    def spawn(i):
        spawned.append(i)
        return f"auto{i}", _mk_replica(lm, variables, scheduler=sched)

    scaler = FleetAutoscaler(router, spawn, cfg)
    rng = np.random.RandomState(5)
    sids = [
        router.submit(rng.randint(1, VOCAB, size=8).astype(np.int32), 4)
        for _ in range(4)
    ]
    # 2 slots active, 2+ queued of bound 4 -> pressure >= 0.5; dwell
    # is zero so the second tick's autoscale pass fires the spawn.
    for _ in range(3):
        router.tick()
        if scaler.scale_ups:
            break
    assert scaler.scale_ups == 1 and spawned == [1]
    ups = [e["data"] for e in global_flight_recorder().events("scale_up")]
    assert ups and ups[0]["replica"] == "auto1" and ups[0]["fleet"] == 2
    assert router.stats()["replicas_live"] == 2
    out = router.run()
    assert set(out) == set(sids)
    # drained: the spare replica sits idle past the bound and retires
    deadline = time.monotonic() + 5.0
    while not scaler.scale_downs and time.monotonic() < deadline:
        time.sleep(0.02)
        router.tick()
    assert scaler.scale_downs == 1
    downs = [
        e["data"] for e in global_flight_recorder().events("scale_down")
    ]
    assert downs and downs[0]["fleet"] == 1
    assert router.stats()["replicas_live"] == 1
    router.close()


# -- capacity-plane satellites ----------------------------------------------


def test_federated_store_evicts_dead_lease_books():
    """A killed replica's book ages in the fleet view (placement must
    see "stale", not "gone") but past ``capacity_max_age_s`` it evicts
    for good — a replica dead for minutes is not a placement candidate
    and must not scroll a fleet view forever."""
    from adapt_tpu.runtime.capacity import stage_book

    store = FederatedStore()
    reg = WorkerRegistry()
    store.attach_registry(reg)
    token = reg.register(
        "cap-w0", meta={"capacity": stage_book(1, backlog=0)}, ttl_s=60
    )
    assert "lease:cap-w0" in store.capacity_snapshot()["replicas"]
    reg.deregister("cap-w0", token)
    # default retention: the book stays, age growing
    assert "lease:cap-w0" in store.capacity_snapshot()["replicas"]
    store.capacity_max_age_s = 0.01
    time.sleep(0.03)
    assert "lease:cap-w0" not in store.capacity_snapshot()["replicas"]
    # and it stays gone: the retention map itself dropped the entry
    store.capacity_max_age_s = 60.0
    assert "lease:cap-w0" not in store.capacity_snapshot()["replicas"]


def test_fleet_placements_endpoint(clean_slate):
    """``GET /fleet/placements`` serves the router's decision ring
    when a provider is wired, and 404s (never an empty fabrication)
    when the process runs no router."""
    from adapt_tpu.utils.exporter import serve_metrics

    ring = {"v": 1, "router": "router0",
            "decisions": [{"kind": "placed", "replica": "r0"}]}
    srv = serve_metrics(
        port=0, store=FederatedStore(), placements_provider=lambda: ring
    )
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet/placements", timeout=10
        ) as r:
            got = json.loads(r.read().decode())
        assert got == ring
    finally:
        srv.shutdown()
    srv2 = serve_metrics(port=0, store=FederatedStore())
    try:
        port = srv2.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet/placements", timeout=10
            )
        assert ei.value.code == 404
    finally:
        srv2.shutdown()


def _load_fleet_top():
    path = (
        pathlib.Path(__file__).resolve().parent.parent
        / "scripts" / "fleet_top.py"
    )
    spec = importlib.util.spec_from_file_location("fleet_top", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_top_route_column_and_sort():
    ft = _load_fleet_top()
    caps = {"replicas": {
        "lease:decode:r0": {
            "role": "decode", "via": "lease", "age_s": 0.1,
            "book": {
                "health": "ok",
                "headroom": {"slots_free": 1, "slots_total": 2,
                             "queue_frac": 0.1},
                "forecast": {"bias": 1.0, "queue_wait_s": 0.2,
                             "tick_gap_s": 0.0, "samples": 4,
                             "calibration": 0.9, "walls": {"8": 0.01}},
                "sketch": {"entries": [{"h": 1}, {"h": 2}]},
            },
        },
        "lease:decode:r1": {
            "role": "decode", "via": "lease", "age_s": 0.4,
            "book": {
                "health": "degraded",
                "headroom": {"slots_free": 0, "slots_total": 2,
                             "queue_frac": 0.9},
                "forecast": {"bias": 1.0, "queue_wait_s": 0.01,
                             "tick_gap_s": 0.0, "samples": 2,
                             "calibration": 0.8, "walls": {"8": 0.01}},
                "sketch": {"entries": []},
            },
        },
    }}
    placements = {"decisions": [
        {"kind": "placed", "replica": "r0",
         "why": {"affinity_tokens": 96, "forecast_s": 0.02}},
        {"kind": "placed", "replica": "r0",
         "why": {"affinity_tokens": 96, "forecast_s": 0.02}},
        {"kind": "placed", "replica": "r1",
         "why": {"affinity_tokens": 0, "forecast_s": 0.011}},
    ]}
    route, n = ft._route_col("lease:decode:r0", placements)
    assert route == "2x aff:96" and n == 2
    route, _ = ft._route_col("lease:decode:r1", placements)
    assert route == "1x fc:0.011"
    route, _ = ft._route_col("lease:decode:r9", placements)
    assert route == "-"
    rows = ft._rows(caps, {}, placements, sort="key")
    assert [r[0] for r in rows] == ["lease:decode:r0", "lease:decode:r1"]
    assert rows[0][-1] == "2x aff:96"
    # health sort: degraded r1 outranks ok r0
    rows = ft._rows(caps, {}, placements, sort="health")
    assert rows[0][0] == "lease:decode:r1"
    # forecast sort: slowest estimate first (r0's 0.21 > r1's 0.02)
    rows = ft._rows(caps, {}, placements, sort="forecast")
    assert rows[0][0] == "lease:decode:r0"
    # affinity sort: hottest sketch first
    rows = ft._rows(caps, {}, placements, sort="affinity")
    assert rows[0][0] == "lease:decode:r0"
