"""The batcher's tick order — dispatch tick *t*, commit tick *t−1*
while *t* runs on device — must be INVISIBLE in outputs. Greedy
streams stay bit-identical to the same batcher driven ``tick();
drain()`` (every tick committed before the next is dispatched) and to
``generate()``, including speculative + int8 + tp=2 composed; cancels,
preemption and a kill-mid-stream recovery all land exactly-once with
balanced lifecycle books while the in-flight tick drains at the
pipeline boundary; and the hot-path invariants hold (0 h2d per steady
tick, the two-program compile footprint)."""

from __future__ import annotations

import contextlib
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.config import (
    ParallelConfig,
    SchedulerConfig,
    SLOSpec,
    SpeculativeConfig,
)
from adapt_tpu.control.registry import DeviceHealthMonitor
from adapt_tpu.models.transformer_lm import (
    BlockSpec,
    generate,
    transformer_lm,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import global_compile_sentinel
from adapt_tpu.utils.tracing import global_flight_recorder
from conftest import drained


@pytest.fixture(scope="module")
def lm_setup():
    # kv_heads divisible by tp=2 AND tp=4: the same model serves the
    # single-device identity tests, the tp=2 composed test, and the
    # tp=4 -> tp=2 recovery drain test.
    lm = transformer_lm(37, 32, 2, 8, 64, max_len=64, kv_heads=4,
                        name="async_target")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture(scope="module")
def draft_setup():
    draft = transformer_lm(37, 16, 1, 1, 32, max_len=64,
                           name="async_draft")
    variables = draft.graph.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )
    return draft, variables


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


#: The two ways a test drives one batcher: as it is, and with every
#: tick committed before the next is dispatched.
ORDERS = ("drained", "overlapped")


def _batcher(order, *args, **kw):
    bat = ContinuousBatcher(*args, **kw)
    return drained(bat) if order == "drained" else bat


RNG = np.random.RandomState(11)
PROMPTS = [RNG.randint(0, 37, size=n).astype(np.int32)
           for n in (3, 9, 5, 12, 7)]
STEPS = [20, 4, 8, 3, 6]


def _staggered(bat, cancel_idx=None):
    """Staggered admits + optional mid-flight cancel; returns
    ({idx: tokens}, cancelled_idx_len_ok)."""
    ids = {}
    for i in range(2):
        ids[bat.submit(PROMPTS[i], STEPS[i])] = i
    bat.tick()
    bat.tick()
    for i in range(2, len(PROMPTS)):
        ids[bat.submit(PROMPTS[i], STEPS[i])] = i
    if cancel_idx is not None:
        bat.tick()
        rid = next(r for r, i in ids.items() if i == cancel_idx)
        assert bat.cancel(rid)
    out = bat.run()
    return {ids[r]: out[r] for r in ids}


def _two_group_lm(window=8):
    """K-EXAONE's cache shape at toy widths: window layers and a full
    one, so two cache groups."""
    def spec(window):
        return BlockSpec(dim=32, heads=4, kv_heads=2, head_dim=8,
                         mlp_dim=64, window=window)

    lm = transformer_lm(
        37, blocks=[spec(window), spec(None)], max_len=64, pos="none",
        name="async_two_groups",
    )
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.mark.parametrize("model", ["one_group", "two_groups"])
def test_a_tick_leaves_its_results_in_flight(lm_setup, model):
    """Whatever the model (one cache group or several), a ``tick()``
    leaves what it dispatched for the next call, and ``drain()`` lands
    it."""
    lm, variables = lm_setup if model == "one_group" else _two_group_lm()
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2, page_size=8)
    rid = bat.submit(PROMPTS[0], 6)
    bat.tick()
    bat.tick()
    assert bat.stats()["inflight"]
    assert bat.drain() == 1 and not bat.stats()["inflight"]
    assert len(bat.run()[rid]) == 6
    bat.close()


@pytest.mark.parametrize(
    "page_size",
    [
        # Tier-1 budget: small pages carry the identity pin (requests
        # span pages, decode crosses boundaries, the prefix cache has
        # full pages to share); one page a request re-proves the same
        # invariant and rides tier 2 (the composed spec×int8×tp
        # variant below is slow-marked for the same reason).
        pytest.param(128, marks=pytest.mark.slow),
        8,
    ],
)
def test_async_bit_identical_staggered(lm_setup, page_size):
    """THE identity pin: the same staggered workload (admits,
    retirements, mid-stream EOS-by-steps) drained after every tick and
    overlapped yields bit-identical streams at both page sizes, each
    equal to solo generate(); books balance and the pipeline drains
    empty."""
    lm, variables = lm_setup
    kw = dict(slots=3, chunk=2, page_size=page_size)
    outs = {}
    for order in ORDERS:
        bat = _batcher(order, lm, variables, **kw)
        outs[order] = _staggered(bat)
        st = bat.stats()
        assert st["active"] == 0 and st["queued"] == 0
        assert not st["inflight"]  # run() drained the pipeline
        assert st["admitted"] == st["completed"] == len(PROMPTS)
        bat.close()
    for i in range(len(PROMPTS)):
        np.testing.assert_array_equal(
            outs["overlapped"][i], outs["drained"][i],
            err_msg=f"req {i}: overlapped != drained",
        )
        np.testing.assert_array_equal(
            outs["overlapped"][i],
            _solo(lm, variables, PROMPTS[i], STEPS[i]),
            err_msg=f"req {i}: overlapped != generate",
        )


@pytest.mark.parametrize(
    "window, page, chunk, prefill_chunk",
    [
        # A window over two pages: grants and releases at every edge.
        (8, 4, 3, 8),
        # A chunk LONGER than window + page: a row that ended by step
        # count inside the tick in flight is dispatched once more with
        # its window wholly past its last position (Pager.hold with
        # hi < lo: releases, grants nothing).
        (2, 2, 6, 4),
    ],
)
def test_async_bit_identical_two_cache_groups(
    window, page, chunk, prefill_chunk
):
    """The identity pin under CACHE GROUPS: a window group's pages are
    granted and recycled from the position each row has been DISPATCHED
    to, so the overlapped order serves a two-group model the streams
    a drain after every tick does, each equal to solo generate(): staggered
    admits, more requests than slots, whole-prompt and chunked prefill,
    retirement by step count mid-chunk and by EOS, every request
    decoding past the window and across page edges. Afterwards no
    group holds a page and nothing is in flight."""
    lm, variables = _two_group_lm(window)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 37, size=n).astype(np.int32)
               for n in (3, 13, 6, 21, 9, 4)]
    steps = [21, 11, 24, 12, 26, 17]  # none a whole number of chunks + 1
    solo = [_solo(lm, variables, p, n) for p, n in zip(prompts, steps)]
    # Requests 2 and 4 end by EOS: the token their own stream reaches
    # last for the first time.
    eos = {}
    for i in (2, 4):
        at = max(list(solo[i]).index(t) for t in set(solo[i]))
        eos[i] = int(solo[i][at])
        solo[i] = solo[i][: at + 1]
        assert len(prompts[i]) + at > window + page
    kw = dict(slots=2, chunk=chunk, page_size=page,
              prefill_chunk=prefill_chunk, prompt_buckets=(8, 16, 32))
    outs, past_end = {}, {}
    for order in ORDERS:
        bat = _batcher(order, lm, variables, **kw)
        assert [g.window for g in bat._groups] == [None, window]
        snap = global_metrics().snapshot(window=True)
        ids = {}

        def submit(i):
            ids[bat.submit(prompts[i], steps[i], eos_id=eos.get(i))] = i

        bound = bat.stats()["pool_pages.window"] - 1
        hold, emptied = bat._pagers[1].hold, []
        bat._pagers[1].hold = lambda slot, lo, hi: (
            emptied.append(hi < lo), hold(slot, lo, hi)
        )
        for i in range(2):
            submit(i)
        bat.tick()
        bat.tick()
        for i in range(2, 4):
            submit(i)
        for _ in range(5):
            bat.tick()
            assert bat.stats()["pages_in_use.window"] <= bound
        for i in range(4, len(prompts)):
            submit(i)
        out = bat.run()
        outs[order] = {ids[r]: np.asarray(out[r]) for r in ids}
        st = bat.stats()
        assert not st["inflight"]
        assert st["active"] == 0 and st["queued"] == 0
        assert st["admitted"] == st["completed"] == len(prompts)
        assert st["pages_in_use.full"] == st["pages_in_use.window"] == 0
        c = global_metrics().snapshot(since=snap)["counters"]
        past_end[order] = c.get("runtime.rows_past_end", 0)
        if order == "overlapped":
            assert c["runtime.ticks_overlapped"] > c[
                "runtime.ticks_synchronous"
            ]
            assert any(emptied) == (chunk > window + page)
        bat.close()
    assert past_end["drained"] == 0 < past_end["overlapped"]
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            outs["overlapped"][i], outs["drained"][i],
            err_msg=f"req {i}: overlapped != drained",
        )
        np.testing.assert_array_equal(
            outs["overlapped"][i], solo[i],
            err_msg=f"req {i}: overlapped != generate",
        )


def test_async_cancel_mid_flight(lm_setup):
    """A cancel landing while the victim's tick is IN FLIGHT: the
    partial stream is a prefix of solo, on_token stays exactly-once
    and contiguous (no token from the dropped in-flight column leaks),
    and the lifecycle books balance."""
    lm, variables = lm_setup
    got: list[tuple[int, int, int]] = []
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2)
    r0 = bat.submit(
        PROMPTS[0], STEPS[0],
        on_token=lambda rid, tok, idx: got.append((rid, tok, idx)),
    )
    r1 = bat.submit(PROMPTS[1], STEPS[1])
    bat.tick()
    bat.tick()  # r0's decode results now ride the one-tick lag
    assert bat.cancel(r0)
    out = bat.run()
    solo = _solo(lm, variables, PROMPTS[0], STEPS[0])
    assert 0 < len(out[r0]) < STEPS[0]
    np.testing.assert_array_equal(out[r0], solo[: len(out[r0])])
    np.testing.assert_array_equal(
        out[r1], _solo(lm, variables, PROMPTS[1], STEPS[1])
    )
    # Exactly-once, contiguous, and consistent with the final result.
    assert [i for (_, _, i) in got] == list(range(len(out[r0])))
    np.testing.assert_array_equal(
        np.asarray([t for (_, t, _) in got], np.int32), out[r0]
    )
    st = bat.stats()
    assert st["admitted"] == st["completed"] == 2
    assert st["active"] == 0 and not st["inflight"]
    bat.close()


def test_async_zero_h2d_and_compile_footprint(lm_setup):
    """The hot-path invariants of the tick loop: steady-state
    ticks stage ZERO host arrays, the step-chunk program holds
    ONE compiled variant across churn, and drain() is idempotent."""
    lm, variables = lm_setup
    sentinel = global_compile_sentinel()
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2)
    before = sentinel.compiles("continuous.step_chunk")
    r1 = bat.submit(np.asarray([1, 2, 3], np.int32), 30)
    bat.tick()
    bat.tick()
    assert sentinel.compiles("continuous.step_chunk") - before == 1
    h0 = bat.stats()["h2d_transfers"]
    for _ in range(4):
        bat.tick()  # pure steady state, one tick always in flight
    assert bat.stats()["h2d_transfers"] == h0
    assert bat.stats()["inflight"]
    entries = sentinel.compiles("continuous.step_chunk")
    # Churn: retire, re-admit — no variant may be added, and the
    # drained pipeline stays drained (idempotent boundary).
    r2 = bat.submit(np.asarray([5, 6], np.int32), 3)
    out = bat.run()
    assert not bat.stats()["inflight"]
    assert bat.drain() == 0
    r3 = bat.submit(np.asarray([9, 9, 9, 9], np.int32), 5)
    out.update(bat.run())
    assert set(out) == {r1, r2, r3}
    assert sentinel.compiles("continuous.step_chunk") == entries
    bat.close()


@pytest.mark.parametrize("page_size", [8])
def test_async_spec_int8_tp2_bit_identical(
    lm_setup, draft_setup, sim_mesh, page_size
):
    """The composed pin: speculative + int8 KV + tp=2, drained after
    every tick vs overlapped — streams bit-identical to each other and
    to solo generate(kv_cache_dtype='int8'); exactly ONE verify variant
    compiles per batcher (two-program footprint)."""
    _async_spec_int8_tp2(lm_setup, draft_setup, sim_mesh, page_size)


@pytest.mark.slow
@pytest.mark.parametrize("page_size", [128])
def test_async_spec_int8_tp2_bit_identical_slow(
    lm_setup, draft_setup, sim_mesh, page_size
):
    """Second page size of the composed pin (slow: tier-1 carries the
    small-page variant; one page a request re-pays the GSPMD compiles
    for the same claim)."""
    _async_spec_int8_tp2(lm_setup, draft_setup, sim_mesh, page_size)


def _async_spec_int8_tp2(lm_setup, draft_setup, sim_mesh, page_size):
    lm, variables = lm_setup
    draft, dvars = draft_setup
    sentinel = global_compile_sentinel()
    kw = dict(slots=2, kv_cache_dtype="int8", draft_lm=draft,
              draft_variables=dvars,
              speculative=SpeculativeConfig(draft_k=3),
              page_size=page_size)
    prompts, steps = PROMPTS[:3], [7, 9, 5]
    outs = {}
    for order in ORDERS:
        bat = _batcher(
            order, lm, variables, mesh=sim_mesh(2),
            parallel=ParallelConfig(tp=2), **kw,
        )
        before = sentinel.compiles("continuous.spec_verify")
        ids = {bat.submit(p, s): i
               for i, (p, s) in enumerate(zip(prompts, steps))}
        out = bat.run()
        assert sentinel.compiles("continuous.spec_verify") - before == 1
        assert 0.0 <= bat.stats()["spec_acceptance"] <= 1.0
        outs[order] = {ids[r]: out[r] for r in ids}
        bat.close()
    for i in range(3):
        np.testing.assert_array_equal(
            outs["overlapped"][i], outs["drained"][i],
            err_msg=f"req {i}: overlapped != drained",
        )
        np.testing.assert_array_equal(
            outs["overlapped"][i],
            _solo(lm, variables, prompts[i], steps[i],
                  kv_cache_dtype="int8"),
            err_msg=f"req {i}: overlapped != solo int8",
        )


def test_async_preemption_exactly_once(lm_setup):
    """Decode-slot preemption under the one-tick lag: the victim's
    in-flight column is dropped (binding identity), the replayed
    stream stays bit-identical to an unpreempted run, and on_token
    delivery is exactly-once across the preemption."""
    lm, variables = lm_setup
    global_metrics().reset()
    global_flight_recorder().clear()
    p_low, p_hi = PROMPTS[1], PROMPTS[2]
    ref = ContinuousBatcher(
        lm, variables, slots=1, chunk=2, kv_layout="paged", page_size=8
    )
    r = ref.submit(p_low, 20)
    ref_low = ref.run()[r]
    r = ref.submit(p_hi, 10)
    ref_hi = ref.run()[r]
    ref.close()

    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=2, kv_layout="paged", page_size=8,
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
    bat.tick()
    bat.tick()  # committed tokens exist AND a tick is in flight
    assert len(delivered.get(low, [])) > 0
    hi = bat.submit(
        p_hi, 10,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="gold", priority=10),
        on_token=cb,
    )
    out = bat.run()
    assert bat.stats()["preempted"] == 1
    assert np.array_equal(out[hi], ref_hi)
    assert np.array_equal(out[low], ref_low)
    for rid, ref_stream in ((low, ref_low), (hi, ref_hi)):
        idxs = [i for i, _ in delivered[rid]]
        assert idxs == list(range(len(ref_stream))), (
            f"req {rid}: duplicated or dropped on_token indices"
        )
        np.testing.assert_array_equal(
            np.asarray([t for _, t in delivered[rid]], np.int32),
            ref_stream,
        )
    st = bat.stats()
    assert st["admitted"] == st["completed"] + st["preempted"] == 3
    assert not st["inflight"]
    bat.close()


def test_async_kill_midstream_recovery_drains_pipeline(
    lm_setup, sim_mesh
):
    """A device kill with a tick IN FLIGHT: recover() drains it at the
    pipeline boundary (its tokens commit, on the old layout) before
    the mesh shrinks tp=4 -> tp=2; surviving requests finish
    bit-identical to solo generate(), on_token stays exactly-once, and
    the books balance with the pipeline empty."""
    lm, variables = lm_setup
    mon = DeviceHealthMonitor()
    bat = ContinuousBatcher(
        lm, variables, mesh=sim_mesh(4), parallel=ParallelConfig(tp=4),
        health=mon, slots=3, chunk=2, kv_layout="paged", page_size=8,
    )
    delivered: dict[int, list] = {}

    def cb(rid, tok, idx):
        delivered.setdefault(rid, []).append((idx, tok))

    steps = [20, 14, 10]
    ids = [
        bat.submit(PROMPTS[i], steps[i], on_token=cb) for i in range(2)
    ]
    bat.tick()
    bat.tick()
    ids.append(bat.submit(PROMPTS[2], steps[2], on_token=cb))
    bat.tick()  # all three slot-bound; one tick in flight
    assert bat.stats()["inflight"]
    mon.kill(list(bat._mesh.devices.flat)[3])
    out = bat.run()
    st = bat.stats()
    assert st["tp"] == 2
    assert st["recoveries"] == 1
    assert st["active"] == 0 and not st["inflight"]
    assert st["admitted"] == 3
    assert st["completed"] + st["recovery_dropped"] == 3
    for i, rid in enumerate(ids):
        solo = _solo(lm, variables, PROMPTS[i], steps[i])
        np.testing.assert_array_equal(
            out[rid], solo, err_msg=f"req {i}: killed != solo"
        )
        idxs = [j for j, _ in delivered[rid]]
        assert idxs == list(range(len(solo))), (
            f"req {i}: duplicated or dropped on_token across recovery"
        )
        np.testing.assert_array_equal(
            np.asarray([t for _, t in delivered[rid]], np.int32), solo
        )
    bat.close()


# -- the benchmark's own driving pattern ----------------------------------

#: (configuration file, traffic laid over the rehearsal): the two
#: serving blocks the GPT-2 cells run (chunk 1 with chunked prefill,
#: and a scanned chunk of 8), at their files' rehearsal widths, with
#: lengths short enough for the CPU. `doc`-like: two and three
#: 256-wide chunk passes, short answers; `batchgen`-like: whole-prompt
#: prefill, answers of a few chunks, some ending mid-chunk.
BENCH_PATTERNS = {
    "gpt2-xl": dict(
        loop="closed", cycle=8,
        prompt=dict(dist="uniform", min=300, max=600),
        output=dict(dist="uniform", min=3, max=12),
    ),
    "cerebras-gpt-1.3b": dict(
        loop="closed", cycle=8,
        prompt=dict(dist="uniform", min=64, max=250),
        output=dict(dist="uniform", min=9, max=45),
    ),
}


def _drive_like_the_benchmark(config_name, order):
    """What ``chipbench/lm_engine.py`` does to a batcher, with its own
    ``Driver`` and ``warm_up``, counted in requests and never timed:
    the correctness sample's three requests and their ``logprobs``,
    warm-up (cancel after the first token, tick to ``active == 0``),
    a closed loop refilled from the callback's bookkeeping, a pause
    with the last tick still in flight, an arrival after the pause.
    Returns per submission index (tokens, logprobs), the batcher's
    final stats and the window's counters."""
    from chipbench import builders, lm_engine
    from chipbench import traffic as tg

    root = Path(__file__).parents[1]
    config = json.loads(
        (root / f"chipbench/configs/{config_name}.json").read_text()
    )
    model = {**config["model"], **config["rehearse"]["model"]}
    serving = {
        **config["serving"], **config["rehearse"]["serving"], "slots": 4,
    }
    traffic = BENCH_PATTERNS[config_name]
    lm, variables, shape = builders.gpt2(model, config["dtype"], seed=5)
    pairs = tg.templates(traffic, shape["max_len"])
    srv = _batcher(
        order, lm, variables,
        slots=serving["slots"], chunk=serving["chunk"],
        kv_layout=serving["kv_layout"], page_size=serving["page_size"],
        pool_pages=lm_engine.pool_pages(serving, pairs, shape["max_len"]),
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    snap = global_metrics().snapshot(window=True)
    drv = lm_engine.Driver(srv, shape["vocab"], 5, contextlib.nullcontext)
    # The correctness sample: two whole-prompt prefills and a chunked one.
    sample = [
        drv.submit(tg.Request(n, lm_engine.SAMPLE_STEPS), 0.0)
        for n in lm_engine._sample_prompts(
            serving["prefill_chunk"], shape["max_len"]
        )
    ]
    drv.run_until(lambda: all(r not in drv.live for r in sample))
    lps = {r: srv.logprobs(r) for r in sample}  # right after the callback
    assert lm_engine.warm_up(drv, pairs) == len(set(pairs))
    assert srv.stats()["active"] == 0
    # Closed loop, a caller a slot: every caller sends two more.
    drv.stream = tg.template_stream(pairs, 5)
    for k, req in enumerate(tg.standing_population(pairs, serving["slots"])):
        drv.submit(req, 0.0, client=k)
    drv.run_until(lambda: len(drv.finished) >= 3 + 3 * serving["slots"])
    # The callers stop; the loop ticks only while it holds a live
    # request, like chat's open loop, so the last tick stays in flight.
    drv.stream = None
    drv.run_until(lambda: not drv.live)
    paused = srv.stats()
    assert paused["active"] == 0
    assert paused["inflight"] == (order == "overlapped")
    # The next arrival lands the stale tick with its own first one.
    late = drv.submit(tg.Request(*pairs[0]), 0.0)
    drv.run_until(lambda: not drv.live)
    for info in drv.finished:
        if info["rid"] not in lps:
            lps[info["rid"]] = srv.logprobs(info["rid"])
    stats = srv.stats()
    counters = global_metrics().snapshot(since=snap)["counters"]
    srv.close()
    cancelled = [r for r, info in drv.reqs.items() if info not in drv.finished]
    assert late in lps and drv.failed == 0
    for info in drv.finished:  # no token lost, duplicated or reordered
        assert info["emitted"] == info["out_len"] == len(info["tokens"])
        assert len(lps[info["rid"]]) == info["out_len"]
    streams = {
        rid: (info["tokens"], lps.get(rid)) for rid, info in drv.reqs.items()
        if rid not in cancelled
    }
    return streams, stats, counters, len(cancelled)


@pytest.mark.parametrize("config_name", sorted(BENCH_PATTERNS))
def test_the_benchmarks_driving_pattern_is_bit_identical_overlapped(
    config_name,
):
    """The paths the benchmark drives and no test had run overlapped
    (manual ``tick()`` with callbacks, refill between ticks, cancel
    then tick to empty, blocking first-token reads in the dispatch
    half, ``logprobs`` after the last callback, a pause with a tick in
    flight): the batcher serves them in the overlapped order, token
    for token and logprob for logprob what the same batcher serves
    drained after every tick. The three counters say how often the
    order engaged and what it wasted."""
    sync, st1, c1, n1 = _drive_like_the_benchmark(config_name, "drained")
    over, st2, c2, n2 = _drive_like_the_benchmark(config_name, "overlapped")
    # Submission k is the same request under both orders (the driver
    # draws ids and lengths by submission index).
    assert sync.keys() == over.keys() and n1 == n2
    for rid in sync:
        assert over[rid][0] == sync[rid][0], f"request {rid}: tokens"
        np.testing.assert_array_equal(
            over[rid][1], sync[rid][1], err_msg=f"request {rid}: logprobs"
        )
    assert st2["completed"] == st1["completed"]
    assert st2["active"] == st2["queued"] == 0
    # Drained: every commit lands before the next dispatch, and no
    # row is decoded for a request already finished.
    assert c1.get("runtime.ticks_overlapped", 0) == 0
    assert c1.get("runtime.rows_past_end", 0) == 0
    assert c1["runtime.ticks_synchronous"] == c1["continuous.ticks"]
    # Overlapped: nearly every commit had a dispatch behind it (the
    # ones before a pause or an idle tick had not), and the waste is
    # at most one tick's row a request that left (finished or cancelled).
    assert c2["runtime.ticks_overlapped"] > 0.8 * c2["continuous.ticks"]
    assert (
        c2["runtime.ticks_overlapped"] + c2.get("runtime.ticks_synchronous", 0)
        <= c2["continuous.ticks"]
    )
    assert 0 < c2["runtime.rows_past_end"] <= st2["completed"]
