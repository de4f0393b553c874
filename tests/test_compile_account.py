"""The compile account (``utils.profiling.CompileSentinel.account``):
what every program cost to trace, to lower and to compile, by watch
and by stage, as ``jax.monitoring`` reports it. One journey on the
CPU's tiny model, read at each step; every test reads the journey.

The account is the process's (cumulative, and other test modules
compile in this worker too), so every assertion is on the DIFFERENCE
between two readings with nothing but the journey's own step between.
"""

import logging

import jax
import jax._src.monitoring as jax_monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import lm_tiny
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import (
    OTHER,
    CompileSentinel,
    global_compile_sentinel,
    global_engine_obs,
)
from adapt_tpu.utils.tracing import global_flight_recorder, global_tracer

STEP, PREFILL = "continuous.step_chunk", "continuous.prefill"
STAGES = ("trace", "lower", "backend")
COUNTS = ("traces", "lowerings", "variants")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


class _Counter:
    """The shape of the benchmark's ``CompileCounter``: someone else's
    listener on the list ours hangs on."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == BACKEND_EVENT:
            self.count += 1


def _listeners():
    return {
        "scalar": len(jax_monitoring.get_scalar_listeners()),
        "time_span": len(jax_monitoring.get_event_time_span_listeners()),
        "duration": len(jax_monitoring.get_event_duration_listeners()),
        "event": len(jax_monitoring.get_event_listeners()),
    }


def _gauges(prefix="engine.compile."):
    # Read without running the collectors: a scrape is a step of the
    # journey (it lowers the step program), not a way to look.
    reg = global_metrics()
    with reg._lock:
        return {k: v for k, v in reg._gauges.items() if k.startswith(prefix)}


class _Reading:
    """The account, the sentinel's own cache sizes and the gauges at
    one instant."""

    def __init__(self, sent):
        self.account = sent.account()
        self.sizes = sent.counts()
        self.gauges = _gauges()

    def row(self, watch):
        return self.account["programs"].get(
            watch, dict.fromkeys(
                [f"{s}_s" for s in STAGES] + list(COUNTS), 0
            ),
        )


def _delta(after, before, watch):
    a, b = after.row(watch), before.row(watch)
    return {k: a[k] - b[k] for k in b if k != "last"}


@pytest.fixture(scope="module")
def journey():
    lm = lm_tiny(vocab=37, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    sent = global_compile_sentinel()
    tracer, eo = global_tracer(), global_engine_obs()
    was = tracer.enabled, eo.enabled, sent.warmup_samples
    tracer.enabled = eo.enabled = True
    sent.warmup_samples = 2
    counter = _Counter()
    j = {"sent": sent, "listeners0": _listeners()}
    for _ in range(50):
        assert global_compile_sentinel() is sent

    def serve(bat, n_prompt, steps=6):
        rid = bat.submit(np.arange(1, n_prompt + 1, dtype=np.int32), steps)
        return bat.run()[rid]

    try:
        j["start"] = _Reading(sent)
        span0 = tracer._seq
        bat = ContinuousBatcher(
            lm, variables, slots=2, chunk=2, prompt_buckets=(8, 16, 32),
        )
        j["construct_s"] = _gauges("engine.construct_s")
        j["first_tokens"] = serve(bat, 3)
        j["first"] = _Reading(sent)
        j["first_spans"], _ = tracer.spans_since(span0)
        j["counted"] = counter.count
        j["again_tokens"] = serve(bat, 3)
        j["again"] = _Reading(sent)
        before_account = sent.counts()
        sent.account()
        j["account_grew"] = (before_account, sent.counts())
        # Warm past the sentinel's grace, then a bucket nothing has
        # compiled: one more prefill program, after warm-up.
        flight0 = len(global_flight_recorder().events("recompile"))
        records: list[logging.LogRecord] = []
        handler = logging.Handler()
        handler.emit = records.append
        plog = logging.getLogger("adapt_tpu.profiling")
        plog.addHandler(handler)
        try:
            serve(bat, 12)
        finally:
            plog.removeHandler(handler)
        j["bucket"] = _Reading(sent)
        j["recompiles"] = global_flight_recorder().events("recompile")[
            flight0:
        ]
        j["warnings"] = [r.getMessage() for r in records]
        # A second batcher (and its own programs: a jit cache keys on
        # ``self``), then the listeners are counted again.
        bat2 = ContinuousBatcher(
            lm, variables, slots=2, chunk=2, prompt_buckets=(8, 16, 32),
        )
        serve(bat2, 3)
        j["second"] = _Reading(sent)
        j["listeners1"] = _listeners()
        j["counter"] = counter.count
        bat.close()
        bat2.close()
        yield j
    finally:
        jax.monitoring.unregister_event_duration_listener(counter._on)
        tracer.enabled, eo.enabled, sent.warmup_samples = was


@pytest.mark.parametrize("watch", [STEP, PREFILL])
@pytest.mark.parametrize("stage", STAGES)
def test_one_request_books_every_stage_of_the_watch(journey, watch, stage):
    d = _delta(journey["first"], journey["start"], watch)
    assert d[f"{stage}_s"] > 0
    assert d[COUNTS[STAGES.index(stage)]] >= 1


@pytest.mark.parametrize("watch", [STEP, PREFILL])
def test_variants_are_the_sentinels_own_cache_size(journey, watch):
    """One backend event a jit-cache entry: the account's count and the
    sentinel's (``fn._cache_size()``) grow together, and the gauges say
    what the account says."""
    first, start = journey["first"], journey["start"]
    grown = first.sizes[watch] - start.sizes.get(watch, 0)
    assert _delta(first, start, watch)["variants"] == grown >= 1
    row = first.row(watch)
    assert first.gauges[f"engine.compile.variants.{watch}"] == row["variants"]
    assert first.gauges[f"engine.compile.seconds.{watch}"] == pytest.approx(
        row["trace_s"] + row["lower_s"] + row["backend_s"]
    )


@pytest.mark.parametrize(
    "key", ["programs", "trace_s", "lower_s", "backend_s"]
)
def test_totals_are_gauges(journey, key):
    first = journey["first"]
    assert first.gauges[f"engine.compile.{key}"] == pytest.approx(
        first.account["totals"][key]
    )
    assert first.account["totals"][key] > journey["start"].account[
        "totals"][key]


def test_totals_are_the_sum_of_the_rows(journey):
    acc = journey["second"].account
    for stage in STAGES:
        assert acc["totals"][f"{stage}_s"] == pytest.approx(
            sum(r[f"{stage}_s"] for r in acc["programs"].values())
        )
    # Every backend event is a program; no stage nested in this journey.
    assert acc["totals"]["programs"] == sum(
        r["variants"] for r in acc["programs"].values()
    )


def test_programs_no_watch_names_keep_their_names(journey):
    """The host path's eager ``jax.numpy`` calls and the batcher's
    unwatched programs are programs too: ``other``, by name."""
    acc = journey["first"].account
    assert acc["programs"][OTHER]["variants"] >= 1
    assert acc["other"] and len(acc["other"]) <= 17  # 16 and (rest)
    seconds = [
        r["trace_s"] + r["lower_s"] + r["backend_s"]
        for n, r in acc["other"].items() if n != "(rest)"
    ]
    assert seconds == sorted(seconds, reverse=True)
    assert not any(n.startswith("jit(") for n in acc["other"])


def test_the_same_request_again_books_nothing(journey):
    first, again = journey["first"], journey["again"]
    assert again.account == first.account  # counts AND seconds, exactly
    assert again.gauges == first.gauges
    assert np.array_equal(journey["again_tokens"], journey["first_tokens"])


def test_reading_the_account_grows_no_jit_cache(journey):
    before, after = journey["account_grew"]
    assert before == after


def test_a_new_prompt_bucket_is_one_more_prefill_program(journey):
    d = _delta(journey["bucket"], journey["again"], PREFILL)
    assert (d["traces"], d["lowerings"], d["variants"]) == (1, 1, 1)
    assert min(d["trace_s"], d["lower_s"], d["backend_s"]) > 0
    last = journey["bucket"].row(PREFILL)["last"]
    assert (last["trace_s"], last["lower_s"], last["backend_s"]) == (
        pytest.approx(d["trace_s"]), pytest.approx(d["lower_s"]),
        pytest.approx(d["backend_s"]),
    )
    # The decode program saw nothing of it.
    assert not any(_delta(journey["bucket"], journey["again"], STEP).values())


@pytest.mark.parametrize("kind", ["scalar", "time_span", "duration", "event"])
def test_one_listener_of_each_kind_however_many_batchers(journey, kind):
    """Fifty ``global_compile_sentinel()`` calls and two batchers later
    the lists are as long as they were: ours was on each once (the
    import put it there) and is on each once."""
    assert journey["listeners1"][kind] == journey["listeners0"][kind]
    ours = {
        "scalar": (jax_monitoring.get_scalar_listeners, "_on_stage_open"),
        "time_span": (
            jax_monitoring.get_event_time_span_listeners, "_on_stage_close"
        ),
        "duration": (
            jax_monitoring.get_event_duration_listeners, "_on_cache_seconds"
        ),
        "event": (jax_monitoring.get_event_listeners, "_on_cache_event"),
    }[kind]
    mine = getattr(journey["sent"], ours[1])
    assert sum(1 for cb in ours[0]() if cb == mine) == 1


def test_another_listener_on_the_list_still_fires(journey):
    """Two independent listeners on one event agree: the benchmark's
    ``compiles N`` against ``engine.compile.programs``."""
    grew = (
        journey["second"].account["totals"]["programs"]
        - journey["start"].account["totals"]["programs"]
    )
    assert journey["counter"] == grew > 0
    assert journey["counted"] == (
        journey["first"].account["totals"]["programs"]
        - journey["start"].account["totals"]["programs"]
    )


@pytest.mark.parametrize(
    "program,watch,parents",
    [
        ("prefill", PREFILL, ("engine.admit", "engine.prefill")),
        ("_step_chunk", STEP, ("engine.launch",)),
    ],
)
def test_a_compile_span_lies_inside_the_phase_that_caused_it(
    journey, program, watch, parents
):
    spans = journey["first_spans"]
    backend = [
        s for s in spans
        if s.name == "engine.compile.backend"
        and s.attrs["program"] == program
    ]
    assert backend and backend[0].attrs["watch"] == watch
    assert backend[0].attrs["cache"] in ("hit", "miss", "off")
    assert backend[0].attrs["variant"] >= 1
    for b in backend:
        stages = [
            s for s in spans
            if s.name.startswith("engine.compile.")
            and s.attrs["program"] == program and s.tid == b.tid
        ]
        assert {s.name.rsplit(".", 1)[1] for s in stages} == set(STAGES)
        # JAX read the epoch clock and the ring is on the perf clock:
        # the shift is good to well under a phase's own length here.
        slack = 2e-3
        assert any(
            p.name in parents and p.tid == b.tid
            and p.start - slack <= b.start and b.end <= p.end + slack
            for p in spans
        ), (b, [p for p in spans if p.name in parents])


def test_the_recompile_alarm_says_what_it_cost(journey):
    events = [
        e["data"] for e in journey["recompiles"]
        if e["data"]["program"] == PREFILL
    ]
    assert len(events) == 1 and events[0]["new"] == 1
    d = _delta(journey["bucket"], journey["again"], PREFILL)
    for stage in STAGES:
        assert events[0][f"{stage}_s"] == pytest.approx(d[f"{stage}_s"])
    assert events[0]["cache"] in ("hit", "miss", "off")
    line = next(w for w in journey["warnings"] if PREFILL in w)
    assert "unexpected recompile" in line
    for key in ("trace_s=", "lower_s=", "backend_s=", "cache="):
        assert key in line


def test_the_constructor_stamps_its_own_wall(journey):
    assert journey["construct_s"]["engine.construct_s"] > 0


def test_trace_seconds_are_the_outermost_span_not_the_sum():
    """A program that calls jitted library functions fires a trace
    event for each INSIDE its own; the account books the outer span's
    length once."""
    sent = global_compile_sentinel()
    seen: list[tuple[str, float, float]] = []

    def on_span(event, start, end, fun_name="", **_):
        if event == TRACE_EVENT:
            seen.append((fun_name, start, end))

    @jax.jit
    def nest_of_library_calls(x):
        for _ in range(8):
            x = jnp.tanh(jnp.matmul(x, x)) + jnp.where(x > 0, x, 0.0)
        return jnp.linalg.norm(x)

    sent.register("test.nest", nest_of_library_calls)
    before = sent.account()["programs"].get("test.nest", {"trace_s": 0.0})
    x = jnp.ones((4, 4), jnp.float32)  # eager: programs of its own
    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        nest_of_library_calls(x)
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        sent.unregister("test.nest")
    outer = [s for s in seen if s[0] == "nest_of_library_calls"]
    inner = [s for s in seen if s[0] != "nest_of_library_calls"]
    assert len(outer) == 1 and len(inner) >= 8
    _, start, end = outer[0]
    assert all(start <= s and e <= end for _, s, e in inner)  # they nest
    row = sent.account()["programs"]["test.nest"]
    assert row["trace_s"] - before["trace_s"] == end - start
    assert row["traces"] - before.get("traces", 0) == 1
    assert sum(e - s for _, s, e in seen) > end - start


def test_a_cached_trace_and_a_served_lowering_book_no_seconds():
    """Trap (d): asking JAX to lower a program it has lowered at the
    same avals fires a trace event of 0.0 s and no ``lower`` event."""
    sent = global_compile_sentinel()

    @jax.jit
    def lowered_twice(x):
        return x * 2.0 + 1.0

    sent.register("test.twice", lowered_twice)
    try:
        x = jnp.ones((3,), jnp.float32)
        lowered_twice(x)
        first = sent.account()["programs"]["test.twice"]
        lowered_twice.lower(jax.ShapeDtypeStruct((3,), jnp.float32))
        second = sent.account()["programs"]["test.twice"]
    finally:
        sent.unregister("test.twice")
    assert second["variants"] == first["variants"]
    assert second["lowerings"] == first["lowerings"]
    assert second["backend_s"] == first["backend_s"]
    assert second["trace_s"] - first["trace_s"] < 1e-3


def test_the_persistent_cache_is_booked_by_stage_and_lent_to_the_program(
    tmp_path,
):
    """A compile written to the persistent cache is a miss, the same
    program loaded from it a hit with its load seconds; the events
    carry no name, so the totals count them, and the program whose
    backend span was open gets the outcome (``cache=``, its row)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    sent = global_compile_sentinel()
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    was = [getattr(jax.config, k) for k in keys]

    @jax.jit
    def written_then_loaded(x):
        return jnp.sin(x) * 3.0 + 1.0

    sent.register("test.cached", written_then_loaded)
    x = jnp.ones((5,), jnp.float32)
    tracer = global_tracer()
    tracer_was, tracer.enabled = tracer.enabled, True
    span0 = tracer._seq
    try:
        for key, value in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(key, value)
        cc.reset_cache()
        t0 = sent.account()["totals"]
        written_then_loaded(x)
        t1, row1 = sent.account()["totals"], sent.account()["programs"][
            "test.cached"]
        jax.clear_caches()  # the jit cache only: the directory stays
        written_then_loaded(x)
        t2, row2 = sent.account()["totals"], sent.account()["programs"][
            "test.cached"]
    finally:
        for key, value in zip(keys, was):
            jax.config.update(key, value)
        cc.reset_cache()
        tracer.enabled = tracer_was
        sent.unregister("test.cached")
    assert (t1["cache_misses"] - t0["cache_misses"], row1["cache_misses"],
            row1["last"]["cache"]) == (1, 1, "miss")
    assert t1["cache_hits"] == t0["cache_hits"]
    assert (t2["cache_hits"] - t1["cache_hits"], row2["cache_hits"],
            row2["last"]["cache"]) == (1, 1, "hit")
    assert t2["cache_misses"] == t1["cache_misses"]
    assert t2["cache_load_s"] > t1["cache_load_s"] == t0["cache_load_s"]
    assert row2["variants"] == 2 and t2["programs"] - t0["programs"] == 2
    gauges = _gauges()
    for key in ("cache_hits", "cache_misses", "cache_load_s"):
        assert gauges[f"engine.compile.{key}"] == t2[key]
    spans, _ = tracer.spans_since(span0)
    assert [
        s.attrs["cache"] for s in spans
        if s.name == "engine.compile.backend"
        and s.attrs["program"] == "written_then_loaded"
    ] == ["miss", "hit"]


def test_only_the_process_sentinel_listens():
    """A sentinel a test (or a tier) builds for itself keeps the old
    contract and an empty account."""
    mine = CompileSentinel()

    @jax.jit
    def private(x):
        return x - 1

    mine.register("private", private)
    private(jnp.zeros((2,), jnp.float32))
    assert mine.compiles("private") == 1
    acc = mine.account()
    assert acc["programs"] == {} and acc["other"] == {}
    assert acc["totals"]["programs"] == 0


def test_the_other_table_is_bounded():
    """Past its room the smallest name folds into ``(rest)``; nothing
    is lost from the sum."""
    sent = CompileSentinel()
    for i in range(200):
        with sent._lock:
            row = sent._other_row(f"f{i}")
            row["backend_s"] += 1.0 + i
            row["variants"] += 1
    acc = sent.account()
    assert len(sent._other) <= 65
    assert len(acc["other"]) == 17 and "(rest)" in acc["other"]
    assert next(iter(acc["other"])) == "f199"
    assert sum(r["variants"] for r in acc["other"].values()) == 200
    assert sum(r["backend_s"] for r in acc["other"].values()) == sum(
        1.0 + i for i in range(200)
    )
