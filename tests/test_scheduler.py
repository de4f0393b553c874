"""Multi-tenant overload control (ISSUE 10): quotas, weighted fair
queueing, bounded admission, decode-slot preemption, closed-loop
degradation.

Five layers, one file:

- ``AdmissionQueue`` unit semantics — FIFO degradation without a
  config, strict priority classes, deficit-round-robin weight shares,
  burst caps and the global depth bound, cancel removal, recovery's
  clear/extend rebuild;
- bounded ``submit()`` — synchronous ``QueueFullError``, the
  ``request_rejected`` flight event + ``scheduler.rejected_total``,
  ``stats()["queued"]`` never exceeding the bound, and no wedged
  ``result()`` (a rejected request has no id to wait on);
- decode-slot preemption — the acceptance pin: a preempted request's
  final stream is BIT-IDENTICAL to an unpreempted run of the same
  request (inside one page and across pages), ``on_token`` delivery
  stays exactly-once across the preemption, a re-admission with full
  prompt pages re-enters through the prefix cache, and the victim is the lowest-priority slot;
- the degradation ladder — escalation under backlog walks
  draft_k -> evict-cached -> reject-best-effort (events, counters,
  gauge), de-escalation restores on drain;
- preemption/rejection x disaggregation — a handoff landing into a
  full admission queue fails ONLY its request while both pools'
  page partitions stay exact (no leaked rc), and a preempted
  disagg-admitted request replays through its adopted pages.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.config import (
    DisaggConfig,
    SchedulerConfig,
    SLOSpec,
    SpeculativeConfig,
    TenantQuota,
)
from adapt_tpu.models.transformer_lm import lm_tiny
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.scheduler import AdmissionQueue, QueueFullError
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.tracing import global_flight_recorder


@pytest.fixture
def clean_slate():
    import gc

    gc.collect()
    global_metrics().reset()
    global_flight_recorder().clear()
    yield
    global_metrics().reset()
    global_flight_recorder().clear()


class _Req:
    """Duck-typed request for queue unit tests (the queue only reads
    ``.slo``, ``.req_id``, ``.t_submit``/``.t_requeued``)."""

    def __init__(self, req_id, tenant=None, priority=0, ttft=None):
        self.req_id = req_id
        self.slo = (
            SLOSpec(tenant=tenant, priority=priority, ttft_budget_s=ttft)
            if tenant is not None
            else None
        )
        self.t_submit = float(req_id)
        self.t_requeued = 0.0


@pytest.fixture
def batcher_factory():
    made = []

    def make(draft=False, scheduler=None, **kw):
        lm = lm_tiny(vocab=29, max_len=64)
        variables = lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        if draft:
            kw.update(draft_lm=lm, draft_variables=variables)
        bat = ContinuousBatcher(
            lm, variables, chunk=4, scheduler=scheduler, **kw
        )
        made.append(bat)
        return bat

    yield make
    for b in made:
        b.close()


# -- AdmissionQueue unit semantics ------------------------------------------


def test_queue_without_config_is_strict_fifo_and_bounded():
    q = AdmissionQueue()  # no config: priority/tenant inert
    reqs = [
        _Req(0, "b", priority=5),
        _Req(1, "a", priority=0),
        _Req(2),  # no SLO at all
        _Req(3, "a", priority=99),
    ]
    for r in reqs:
        q.append(r)
    assert len(q) == 4
    assert q.preempt_candidate() is None  # FIFO mode never nominates
    assert [q.popleft().req_id for _ in range(4)] == [0, 1, 2, 3]
    with pytest.raises(IndexError):
        q.popleft()


def test_queue_priority_classes_strictly_order():
    q = AdmissionQueue(SchedulerConfig())
    q.append(_Req(0, "t", priority=0))
    q.append(_Req(1, "t", priority=-1))  # best-effort
    q.append(_Req(2, "t", priority=7))
    q.append(_Req(3, "t", priority=0))
    assert [q.popleft().req_id for _ in range(4)] == [2, 0, 3, 1]


def test_queue_drr_weight_shares():
    cfg = SchedulerConfig(
        quotas={"a": TenantQuota(weight=3.0), "b": TenantQuota(weight=1.0)}
    )
    q = AdmissionQueue(cfg)
    for i in range(8):
        q.append(_Req(i, "a"))
    for i in range(8, 16):
        q.append(_Req(i, "b"))
    first8 = [q.popleft() for _ in range(8)]
    from adapt_tpu.runtime.scheduler import request_tenant

    tenants = [request_tenant(r) for r in first8]
    # Weight 3:1 -> a drains 3 per round, b 1: 6 a's in the first 8.
    assert tenants.count("a") == 6 and tenants.count("b") == 2
    # Within each tenant: FIFO.
    assert [r.req_id for r in first8 if r.slo.tenant == "a"] == list(
        range(6)
    )


def test_queue_bounds_burst_and_depth_and_shed():
    cfg = SchedulerConfig(
        max_queue_depth=4, quotas={"f": TenantQuota(burst=2)}
    )
    q = AdmissionQueue(cfg)
    q.append(_Req(0, "f"))
    q.append(_Req(1, "f"))
    with pytest.raises(QueueFullError):  # tenant burst cap
        q.append(_Req(2, "f"))
    q.append(_Req(3, "g"))
    q.append(_Req(4, "g"))
    with pytest.raises(QueueFullError):  # global depth bound
        q.append(_Req(5, "g"))
    # appendleft (replay/preemption re-insert) bypasses the bound.
    q.popleft()
    q.appendleft(_Req(6, "f"))
    assert len(q) == 4
    # Best-effort shed (degradation rung 4): priority < 0 rejected.
    q2 = AdmissionQueue(SchedulerConfig())
    q2.shed_best_effort = True
    with pytest.raises(QueueFullError):
        q2.append(_Req(0, "x", priority=-1))
    q2.append(_Req(1, "x", priority=0))  # ordinary class unaffected


def test_queue_front_reinsert_restores_head_of_line():
    """A pool-pressure put-back (appendleft of the request just
    popped) must restore the tenant's service turn — ring front +
    DRR unit refunded — or other tenants' smaller requests jump the
    large one every round and it starves."""
    cfg = SchedulerConfig(
        quotas={"a": TenantQuota(weight=1.0), "b": TenantQuota(weight=1.0)}
    )
    q = AdmissionQueue(cfg)
    q.append(_Req(0, "a"))
    q.append(_Req(1, "b"))
    q.append(_Req(2, "a"))
    r = q.popleft()
    assert r.req_id == 0
    q.appendleft(r)  # alloc failed: put it back
    assert q.popleft().req_id == 0  # head-of-line, not b's turn
    assert q.popleft().req_id == 1  # then the round proceeds


def test_queue_remove_id_depths_and_rebuild():
    cfg = SchedulerConfig(quotas={"a": TenantQuota(weight=2.0)})
    q = AdmissionQueue(cfg)
    for i in range(3):
        q.append(_Req(i, "a"))
    q.append(_Req(3, "b"))
    assert q.depths() == {"a": 3, "b": 1}
    got = q.remove_id(1)
    assert got.req_id == 1 and len(q) == 3
    assert q.remove_id(99) is None
    assert q.depths()["a"] == 2
    # recover()'s rebuild path: clear + extend preserves given order
    # per tenant and the membership iteration sees everything.
    held = list(q)
    q.clear()
    assert len(q) == 0 and q.depths() == {"a": 0, "b": 0}
    q.extend(held)
    assert sorted(r.req_id for r in q) == [0, 2, 3]


def test_queue_cache_aware_picks_hottest_prefix_in_window():
    """``cache_aware=True`` with an installed probe: the pop takes the
    hottest/longest radix-resident prefix among the first
    ``cache_aware_window`` candidates of the selected tenant queue —
    entries past the window cannot jump, equal scores keep strict
    arrival order (a cold queue degrades to byte-exact FIFO), and a
    probe that explodes must never break admission."""
    cfg = SchedulerConfig(cache_aware=True, cache_aware_window=3)
    q = AdmissionQueue(cfg)
    score = {0: (0, 0), 1: (2, 5), 2: (2, 9), 3: (0, 0), 4: (9, 9)}
    q.prefix_probe = lambda r: score[r.req_id]
    for i in range(5):
        q.append(_Req(i, "t"))
    # Window scans 0..2: req 2 (same depth as 1, hotter) wins; req 4's
    # top score sits OUTSIDE the window and cannot jump yet.
    assert q.popleft().req_id == 2
    assert q.popleft().req_id == 1  # window scans 0,1,3: 1 wins
    assert q.popleft().req_id == 4  # 4 slid into the window
    # 0 vs 3 tie at (0, 0): strictly-greater wins only -> FIFO.
    assert [q.popleft().req_id, q.popleft().req_id] == [0, 3]
    # A broken probe degrades to FIFO instead of raising out of pop.
    q2 = AdmissionQueue(cfg)
    q2.prefix_probe = lambda r: 1 // 0
    for i in range(3):
        q2.append(_Req(i, "t"))
    assert [q2.popleft().req_id for _ in range(3)] == [0, 1, 2]
    # cache_aware off: an installed probe is inert.
    q3 = AdmissionQueue(SchedulerConfig())
    q3.prefix_probe = lambda r: -r.req_id
    for i in range(3):
        q3.append(_Req(i, "t"))
    assert [q3.popleft().req_id for _ in range(3)] == [0, 1, 2]


def test_queue_cache_aware_defers_to_front_reinserts():
    """A pool-pressure put-back (``appendleft``) must get the next pop
    VERBATIM: the cache-aware scan is suppressed while a front
    re-insert waits, else a hotter newcomer starves a request the
    batcher already promised to retry."""
    cfg = SchedulerConfig(cache_aware=True, cache_aware_window=8)
    q = AdmissionQueue(cfg)
    score = {0: 0, 1: 7, 2: 1}
    q.prefix_probe = lambda r: score[r.req_id]
    for i in range(3):
        q.append(_Req(i, "t"))
    r = q.popleft()
    assert r.req_id == 1  # hottest jumped the queue
    q.appendleft(r)  # alloc failed: put it back
    score[2] = 99  # a now-hotter rival must NOT displace the put-back
    assert q.popleft().req_id == 1
    assert [q.popleft().req_id, q.popleft().req_id] == [2, 0]


@pytest.mark.parametrize("aware", [True, False])
def test_cache_aware_admission_prefers_resident_prefix(
    clean_slate, batcher_factory, aware
):
    """End-to-end: with ``cache_aware`` on a paged batcher, a queued
    request whose prefix is radix-RESIDENT admits before an
    earlier-arrived cold peer of the same priority (suffix-only
    prefill starts sooner while the pages are still hot); with it off
    the identical traffic stays strict FIFO."""
    rng = np.random.RandomState(31)
    warm = rng.randint(0, 29, size=17).astype(np.int32)  # 2 full pages
    cold = rng.randint(0, 29, size=17).astype(np.int32)
    warm_again = np.concatenate(
        [warm, rng.randint(0, 29, size=5).astype(np.int32)]
    )
    bat = batcher_factory(
        page_size=8, slots=1,
        scheduler=SchedulerConfig(cache_aware=aware),
    )
    bat.submit(warm, 3)
    bat.run()  # retire: warm's full pages stay radix-resident (rc=0)
    first: list[int] = []

    def cb(rid, tok, idx):
        if rid not in first:
            first.append(rid)

    b = bat.submit(cold, 3, on_token=cb)  # arrives first, cold
    c = bat.submit(warm_again, 3, on_token=cb)  # arrives second, warm
    bat.run()
    assert first == ([c, b] if aware else [b, c])


# -- bounded submit ----------------------------------------------------------


def test_submit_rejects_synchronously_and_books_it(
    clean_slate, batcher_factory
):
    bat = batcher_factory(
        slots=1,
        scheduler=SchedulerConfig(
            max_queue_depth=2, preempt=False, degrade=False
        ),
    )
    rng = np.random.RandomState(0)
    accepted = []
    rejections = 0
    for _ in range(6):
        try:
            accepted.append(bat.submit(rng.randint(0, 29, 4), 4))
        except QueueFullError:
            rejections += 1
    # slot admission happens at tick, so at most max_queue_depth
    # requests sit queued; everything past the bound rejected.
    assert rejections == 4
    assert bat.stats()["queued"] <= 2
    assert bat.stats()["rejected"] == 4
    ev = global_flight_recorder().kind_counts()
    assert ev.get("request_rejected") == 4
    c = global_metrics().snapshot()["counters"]
    assert c["scheduler.rejected_total"] == 4
    assert c["scheduler.admitted_total"] == len(accepted)
    # The accepted requests all finish — nothing wedges.
    out = bat.run()
    assert sorted(out) == sorted(accepted)


# -- decode-slot preemption --------------------------------------------------


@pytest.mark.parametrize("page_size", [128, 8])
def test_preemption_bit_identical_and_exactly_once(
    clean_slate, batcher_factory, page_size
):
    """The acceptance pin: a preempted request's final stream is
    bit-identical to an unpreempted run of the same request, inside
    one page and across pages, with on_token delivery exactly-once across the
    preemption (stream_skip suppresses the regenerated prefix)."""
    p_low = np.arange(10, dtype=np.int32) % 29
    p_hi = (np.arange(7, dtype=np.int32) * 3) % 29
    # Reference: each request alone on an unpreempted batcher.
    ref = batcher_factory(page_size=page_size, slots=1)
    r_low = ref.submit(p_low, 20)
    ref_low = ref.run()[r_low]
    r_hi = ref.submit(p_hi, 10)
    ref_hi = ref.run()[r_hi]

    bat = batcher_factory(
        page_size=page_size,
        slots=1,
        scheduler=SchedulerConfig(
            preempt=True, preempt_ttft_fraction=0.5, degrade=False
        ),
    )
    delivered: dict[int, list] = {}

    def cb(rid, tok, idx):
        delivered.setdefault(rid, []).append((idx, tok))

    low = bat.submit(
        p_low, 20, slo=SLOSpec(tenant="free", priority=0), on_token=cb
    )
    bat.tick()
    bat.tick()  # low decodes a few chunks first
    tokens_before = len(delivered.get(low, []))
    assert tokens_before > 0
    hi = bat.submit(
        p_hi,
        10,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="gold", priority=10),
        on_token=cb,
    )
    out = bat.run()
    # The preemption fired (tiny TTFT budget: the first tick after the
    # high-priority submit is already past its headroom).
    ev = global_flight_recorder().events("preempted")
    assert len(ev) == 1
    assert ev[0]["data"]["request"] == low
    assert ev[0]["data"]["for_request"] == hi
    assert bat.stats()["preempted"] == 1
    assert global_metrics().snapshot()["counters"][
        "scheduler.preempted_total"
    ] == 1
    # Bit-identity for BOTH parties.
    assert np.array_equal(out[hi], ref_hi)
    assert np.array_equal(out[low], ref_low)
    # Exactly-once delivery: indices 0..n-1 each exactly once, tokens
    # matching the final stream (the regenerated prefix re-ran for
    # state only).
    idxs = [i for i, _ in delivered[low]]
    assert idxs == list(range(len(ref_low)))
    assert [t for _, t in delivered[low]] == list(ref_low)
    if page_size == 8:
        # The victim re-admitted THROUGH the prefix cache: its prompt
        # pages dropped into the LRU at preemption and were shared
        # back on re-admission.
        assert bat.stats()["prefix_hits"] > 0


def test_preemption_fires_on_page_starvation_with_a_free_slot(
    clean_slate, batcher_factory
):
    """A free SLOT is not enough: paged admission is all-or-nothing,
    so a high-priority head whose reservation the pool cannot cover
    (even after evicting every cold page) must still preempt — the
    lower-priority decode's pages are what it is waiting for."""
    bat = batcher_factory(
        page_size=8,
        slots=2,
        pool_pages=10,  # 9 allocatable: low takes 6, gold needs 5
        scheduler=SchedulerConfig(
            preempt=True, preempt_ttft_fraction=0.5, degrade=False
        ),
    )
    rng = np.random.RandomState(7)
    low = bat.submit(
        rng.randint(0, 29, 8), 40,
        slo=SLOSpec(tenant="free", priority=0),
    )
    bat.tick()  # low decoding, 6/9 pages held; one slot FREE
    assert sum(1 for s in bat.slots if s.req is None) == 1
    hi = bat.submit(
        rng.randint(0, 29, 24), 16,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="gold", priority=10),
    )
    out = bat.run()
    ev = global_flight_recorder().events("preempted")
    assert [e["data"]["request"] for e in ev] == [low]
    assert len(out[hi]) == 16 and len(out[low]) == 40


def test_preemption_picks_lowest_priority_victim_and_spares_equals(
    clean_slate, batcher_factory
):
    bat = batcher_factory(
        slots=2,
        scheduler=SchedulerConfig(
            preempt=True, preempt_ttft_fraction=0.5, degrade=False
        ),
    )
    rng = np.random.RandomState(3)
    mid = bat.submit(
        rng.randint(0, 29, 6), 24, slo=SLOSpec(tenant="m", priority=5)
    )
    low = bat.submit(
        rng.randint(0, 29, 6), 24, slo=SLOSpec(tenant="l", priority=1)
    )
    bat.tick()  # both admitted and decoding
    hi = bat.submit(
        rng.randint(0, 29, 4),
        4,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="g", priority=9),
    )
    bat.run()
    ev = global_flight_recorder().events("preempted")
    assert [e["data"]["request"] for e in ev] == [low]
    # An equal-or-higher class is never preempted: with only
    # priority-9 slots active, a second priority-9 request waits.
    bat2 = batcher_factory(
        slots=1,
        scheduler=SchedulerConfig(
            preempt=True, preempt_ttft_fraction=0.5, degrade=False
        ),
    )
    a = bat2.submit(
        rng.randint(0, 29, 6), 12,
        slo=SLOSpec(tenant="g", priority=9),
    )
    bat2.tick()
    b = bat2.submit(
        rng.randint(0, 29, 6), 4,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="g", priority=9),
    )
    out = bat2.run()
    assert not global_flight_recorder().events("preempted")[len(ev):]
    assert len(out[a]) == 12 and len(out[b]) == 4


# -- closed-loop degradation -------------------------------------------------


def test_degradation_ladder_escalates_and_recovers(
    clean_slate, batcher_factory
):
    """Backlog pressure walks the ladder one rung per dwell (draft_k
    shrink -> busy threshold (no disagg attached: no-op rung) ->
    evict cached -> reject best-effort), then de-escalates as the
    queue drains."""
    cfg = SchedulerConfig(
        max_queue_depth=8,
        degrade=True,
        degrade_dwell_s=0.0,
        degrade_occupancy=0.0,  # any occupancy counts as saturated
        degrade_queue_high=0.25,
        degrade_queue_low=0.05,
        preempt=False,
    )
    bat = batcher_factory(
        page_size=8, draft=True, slots=2,
        speculative=SpeculativeConfig(draft_k=4), scheduler=cfg,
    )
    rng = np.random.RandomState(0)
    # Seed a cold cached page: one paged request whose prompt fills a
    # full page, retired before the flood.
    warm = bat.submit(rng.randint(0, 29, 9), 2)
    bat.run()
    assert bat.stats()["pages_cached"] > 0
    # Long-running flood: slots stay occupied and the queue stays
    # above the high watermark across the escalation ticks.
    for _ in range(6):
        bat.submit(rng.randint(0, 29, 4), 30)
    for _ in range(4):
        bat.tick()
    st = bat.stats()
    assert st["degradation_level"] == 4
    assert bat._spec_k_eff == 2  # draft_k 4 -> 4 // 2
    assert bat._queue.shed_best_effort
    assert bat.stats()["pages_cached"] == 0  # cold pages evicted
    with pytest.raises(QueueFullError):
        bat.submit(
            rng.randint(0, 29, 4), 2,
            slo=SLOSpec(tenant="be", priority=-1),
        )
    g = global_metrics().snapshot()
    assert g["counters"]["scheduler.degraded_total"] == 4
    assert g["gauges"]["scheduler.degradation_level"] == 4.0
    ups = [
        e["data"]["step"]
        for e in global_flight_recorder().events("degradation_step")
        if e["data"]["direction"] == "up"
    ]
    assert ups == [
        "draft_k", "busy_threshold", "evict_cached",
        "reject_best_effort",
    ]
    # Drain, then idle ticks de-escalate back to level 0 and restore
    # the configured draft_k.
    bat.run()
    for _ in range(6):
        bat.tick()
    assert bat.stats()["degradation_level"] == 0
    assert bat._spec_k_eff == 4
    assert not bat._queue.shed_best_effort
    assert warm == 0  # the warm request's id (sanity: nothing renumbered)


@pytest.mark.parametrize(
    "sample_kw",
    [
        {},
        # temperature > 0 routes through the speculative-SAMPLING
        # verify (accept/reject + residual resample), but top_k=1
        # shapes the target to a point mass on its argmax — so the
        # committed stream must STILL equal the greedy reference
        # bit-for-bit whatever the ladder does to draft_k mid-serve.
        {"temperature": 0.7, "top_k": 1},
    ],
    ids=["greedy", "sampled_topk1"],
)
def test_shrunk_draft_k_streams_stay_lossless(
    clean_slate, batcher_factory, sample_kw
):
    """set_draft_k mid-serve: the narrowed rounds still commit the
    target's exact stream (losslessness is the target's property, not
    the draft depth's) — in greedy mode AND in sampling mode."""
    p = np.arange(8, dtype=np.int32) % 29
    ref = batcher_factory(slots=1)
    rr = ref.submit(p, 16)
    expect = ref.run()[rr]
    bat = batcher_factory(
        draft=True, slots=1, speculative=SpeculativeConfig(draft_k=4)
    )
    kw = dict(sample_kw)
    if kw:
        kw["rng"] = jax.random.PRNGKey(5)
    r = bat.submit(p, 16, **kw)
    bat.tick()
    bat.set_draft_k(1)  # shrink mid-request
    bat.tick()
    bat.set_draft_k(4)  # and restore
    out = bat.run()
    assert np.array_equal(out[r], expect)


# -- preemption / rejection x disaggregation ---------------------------------


def _build_disagg(scheduler=None, slots=2):
    from adapt_tpu.runtime.disagg import DisaggServer, PrefillWorker

    lm = lm_tiny(vocab=29, max_len=96)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    decode = ContinuousBatcher(
        lm, variables, slots=slots, chunk=4, kv_layout="paged",
        page_size=8, scheduler=scheduler,
    )
    worker = PrefillWorker(
        lm, variables, page_size=8, prefill_chunk=16
    )
    srv = DisaggServer(
        decode, worker,
        DisaggConfig(prompt_threshold=24, busy_prompt_threshold=16),
    )
    return srv, decode, worker


def _assert_partition(pager):
    st = pager.stats()
    assert st.in_use + st.free == pager.num_allocatable
    assert all(rc > 0 for rc in pager._rc.values())


def test_disagg_landing_into_full_queue_fails_only_that_request(
    clean_slate,
):
    """A KV handoff whose decode admission is REJECTED (queue filled
    while the prefill ran) frees the prefill-side pages, leaves the
    adopted decode-side pages rc=0 in the prefix LRU, fails only its
    request, and both pools' page partitions stay exact."""
    srv, decode, worker = _build_disagg(
        scheduler=SchedulerConfig(
            max_queue_depth=2, preempt=False, degrade=False
        ),
        slots=1,
    )
    rng = np.random.RandomState(0)
    long_prompt = rng.randint(0, 29, 40).astype(np.int32)
    sid = srv.submit(long_prompt, 4)  # routed to the prefill tier
    assert srv.disaggregated == 1
    # Fill the decode queue to the bound and pin the one slot with a
    # long decode, so the queue is STILL full when the handoff lands.
    slow = srv.submit(rng.randint(0, 29, 4), 30)
    fillers = [srv.submit(rng.randint(0, 29, 4), 2)]
    srv.tick()  # admits `slow` into the slot; prefill pass 1 runs
    fillers.append(srv.submit(rng.randint(0, 29, 4), 2))
    with pytest.raises(QueueFullError):
        srv.submit(rng.randint(0, 29, 4), 2)  # bound holds for submits
    out = srv.run()
    # The handoff landed (pages adopted) but admission rejected: the
    # request failed cleanly — empty result, not a wedge — and the
    # fillers finished.
    assert out[sid].shape == (0,)
    assert len(out[slow]) == 30
    assert all(len(out[f]) == 2 for f in fillers)
    assert srv.failed == 1
    kinds = global_flight_recorder().kind_counts()
    assert kinds.get("request_failed", 0) == 1
    assert kinds.get("request_rejected", 0) >= 1
    # No leaked rc on either pool; partitions exact. The adopted pages
    # sit rc=0 in the decode LRU (land-then-LRU: evictable capacity,
    # or a free prefix hit for a retry).
    _assert_partition(decode._pager)
    _assert_partition(worker._pager)
    assert worker._pager.stats().in_use == 0
    assert decode._pager.stats().cached > 0
    # A resubmit of the same prompt prefix-hits the adopted pages.
    hits0 = decode._pager.prefix_hits
    sid2 = srv.submit(long_prompt, 4)
    out2 = srv.result(sid2)
    assert len(out2) == 4
    assert decode._pager.prefix_hits > hits0
    decode.close()


@pytest.mark.slow  # two full disagg stacks; the landing-rejection
# test above carries the tier-1 partition pin
def test_preempted_disagg_request_replays_through_adopted_pages(
    clean_slate,
):
    """A disagg-admitted request preempted mid-decode re-queues and
    re-admits through the prefix cache (its prompt pages — adopted at
    landing — went rc=0 into the LRU at preemption); the partition
    stays exact and the stream is bit-identical to an unpreempted
    run."""
    srv, decode, worker = _build_disagg(slots=1)
    rng = np.random.RandomState(1)
    long_prompt = rng.randint(0, 29, 40).astype(np.int32)
    ref_sid = srv.submit(long_prompt, 12)
    expect = srv.result(ref_sid)  # unpreempted reference, same server

    srv2, decode2, worker2 = _build_disagg(
        scheduler=SchedulerConfig(
            preempt=True, preempt_ttft_fraction=0.5, degrade=False
        ),
        slots=1,
    )
    victim = srv2.submit(
        long_prompt, 12, slo=SLOSpec(tenant="free", priority=0)
    )
    # Drive until the disagg request is decoding in its slot.
    for _ in range(40):
        srv2.tick()
        if any(s.req is not None for s in decode2.slots):
            break
    assert any(s.req is not None for s in decode2.slots)
    hi = srv2.submit(
        np.arange(4, dtype=np.int32) % 29,
        4,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="gold", priority=10),
    )
    out_hi = srv2.result(hi)
    out_victim = srv2.result(victim)
    assert len(out_hi) == 4
    assert np.array_equal(out_victim, expect)
    assert global_flight_recorder().events("preempted")
    _assert_partition(decode2._pager)
    _assert_partition(worker2._pager)
    decode.close()
    decode2.close()


# -- observability ----------------------------------------------------------


def test_scheduler_gauges_and_flight_kinds(clean_slate, batcher_factory):
    bat = batcher_factory(
        slots=1,
        scheduler=SchedulerConfig(
            max_queue_depth=3,
            quotas={"free": TenantQuota(burst=2)},
            preempt=True,
            preempt_ttft_fraction=0.5,
            degrade=True,
            degrade_dwell_s=0.0,
            degrade_occupancy=0.0,
            degrade_queue_high=0.3,
        ),
    )
    rng = np.random.RandomState(0)
    low = bat.submit(
        rng.randint(0, 29, 6), 16,
        slo=SLOSpec(tenant="free", priority=0),
    )
    bat.tick()
    for _ in range(2):
        bat.submit(
            rng.randint(0, 29, 4), 2,
            slo=SLOSpec(tenant="free", priority=0),
        )
    with pytest.raises(QueueFullError):  # burst cap
        bat.submit(
            rng.randint(0, 29, 4), 2,
            slo=SLOSpec(tenant="free", priority=0),
        )
    bat.submit(
        rng.randint(0, 29, 4), 2,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="gold", priority=5),
    )
    bat.tick()  # preempts low; also degrades (queue high)
    g = global_metrics().snapshot()["gauges"]
    assert "scheduler.queue_depth.free" in g
    assert "scheduler.queue_depth.gold" in g
    bat.run()
    kinds = global_flight_recorder().kind_counts()
    # The satellite contract: every traffic-control lifecycle edge is
    # kind_counts()-visible.
    assert kinds.get("request_rejected", 0) >= 1
    assert kinds.get("preempted", 0) >= 1
    assert kinds.get("degradation_step", 0) >= 1
    c = global_metrics().snapshot()["counters"]
    assert c["scheduler.rejected_total"] >= 1
    assert c["scheduler.preempted_total"] >= 1
    assert c["scheduler.degraded_total"] >= 1
    assert low == 0  # sanity
