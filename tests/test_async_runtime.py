"""Pipelined tick runtime (``config.RuntimeConfig``): the depth-2 loop
— dispatch tick *t*, commit tick *t−1* while *t* runs on device — must
be INVISIBLE in outputs. Greedy streams stay bit-identical to the
synchronous ``pipeline_depth=1`` loop on both KV layouts, including
speculative + int8 + tp=2 composed; cancels, preemption and a
kill-mid-stream recovery all land exactly-once with balanced lifecycle
books while the in-flight tick drains at the pipeline boundary; and
the hot-path invariants (0 h2d per steady tick, the two-program
compile footprint) survive the overlapped loop."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.config import (
    ParallelConfig,
    RuntimeConfig,
    SchedulerConfig,
    ServeConfig,
    SLOSpec,
    SpeculativeConfig,
)
from adapt_tpu.control.registry import DeviceHealthMonitor
from adapt_tpu.models.transformer_lm import generate, transformer_lm
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import global_compile_sentinel
from adapt_tpu.utils.tracing import global_flight_recorder


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


def _depth(n):
    return RuntimeConfig(pipeline_depth=n)


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


def test_runtime_config_validation():
    """Depths outside {1, 2} fail eagerly, by name; the ServeConfig
    default is the synchronous loop."""
    assert RuntimeConfig().pipeline_depth == 1
    assert ServeConfig().runtime.pipeline_depth == 1
    for bad in (0, 3, -1):
        with pytest.raises(ValueError, match="pipeline_depth"):
            RuntimeConfig(pipeline_depth=bad)


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
    retirements, mid-stream EOS-by-steps) under depth 1 and depth 2
    yields bit-identical streams at both page sizes, each equal to
    solo generate(); books balance and the pipeline drains empty."""
    lm, variables = lm_setup
    kw = dict(slots=3, chunk=2, page_size=page_size)
    outs = {}
    for depth in (1, 2):
        bat = ContinuousBatcher(
            lm, variables, runtime=_depth(depth), **kw
        )
        outs[depth] = _staggered(bat)
        st = bat.stats()
        assert st["pipeline_depth"] == depth
        assert st["active"] == 0 and st["queued"] == 0
        assert not st["inflight"]  # run() drained the pipeline
        assert st["admitted"] == st["completed"] == len(PROMPTS)
        bat.close()
    for i in range(len(PROMPTS)):
        np.testing.assert_array_equal(
            outs[2][i], outs[1][i], err_msg=f"req {i}: depth2 != depth1"
        )
        np.testing.assert_array_equal(
            outs[2][i], _solo(lm, variables, PROMPTS[i], STEPS[i]),
            err_msg=f"req {i}: depth2 != generate",
        )


def test_async_cancel_mid_flight(lm_setup):
    """A cancel landing while the victim's tick is IN FLIGHT: the
    partial stream is a prefix of solo, on_token stays exactly-once
    and contiguous (no token from the dropped in-flight column leaks),
    and the lifecycle books balance."""
    lm, variables = lm_setup
    got: list[tuple[int, int, int]] = []
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, runtime=_depth(2)
    )
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
    """The hot-path invariants survive the pipelined loop: steady-state
    depth-2 ticks stage ZERO host arrays, the step-chunk program holds
    ONE compiled variant across churn, and drain() is idempotent."""
    lm, variables = lm_setup
    sentinel = global_compile_sentinel()
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, runtime=_depth(2)
    )
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
    """The composed pin: speculative + int8 KV + tp=2, depth 1 vs
    depth 2 — streams bit-identical to each other and to solo
    generate(kv_cache_dtype='int8'); exactly ONE verify variant
    compiles per batcher (two-program footprint under the async
    loop)."""
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
    for depth in (1, 2):
        bat = ContinuousBatcher(
            lm, variables, mesh=sim_mesh(2),
            parallel=ParallelConfig(tp=2), runtime=_depth(depth), **kw,
        )
        before = sentinel.compiles("continuous.spec_verify")
        ids = {bat.submit(p, s): i
               for i, (p, s) in enumerate(zip(prompts, steps))}
        out = bat.run()
        assert sentinel.compiles("continuous.spec_verify") - before == 1
        assert 0.0 <= bat.stats()["spec_acceptance"] <= 1.0
        outs[depth] = {ids[r]: out[r] for r in ids}
        bat.close()
    for i in range(3):
        np.testing.assert_array_equal(
            outs[2][i], outs[1][i], err_msg=f"req {i}: depth2 != depth1"
        )
        np.testing.assert_array_equal(
            outs[2][i],
            _solo(lm, variables, prompts[i], steps[i],
                  kv_cache_dtype="int8"),
            err_msg=f"req {i}: depth2 != solo int8",
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
        runtime=_depth(2),
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
        runtime=_depth(2),
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
