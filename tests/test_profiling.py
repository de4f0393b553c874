"""Engine-tier observability (``utils.profiling`` + wiring): the
compile sentinel flags post-warmup jit-cache growth as a counted,
recorded event; memory gauges partition the paged pool exactly and
report dense strip bytes; tick-phase histograms are one-branch gated;
``logging.kv`` stays machine-parseable; and the perf-regression gate
fails injected regressions while passing within-tolerance runs."""

import json
import shlex
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import ci_gate
from adapt_tpu.models.transformer_lm import lm_tiny
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils import profiling
from adapt_tpu.utils.exporter import serve_metrics
from adapt_tpu.utils.logging import kv
from adapt_tpu.utils.metrics import MetricsRegistry, global_metrics
from adapt_tpu.utils.profiling import (
    CompileSentinel,
    engine_collector,
    global_compile_sentinel,
    global_engine_obs,
    register_memory_source,
)
from adapt_tpu.utils.tracing import global_flight_recorder


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=37, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture
def isolated_memory_sources():
    """Empty the process memory-source table for one test: jit caches
    hold strong refs to ``self`` (static argnum), so batchers from
    earlier tests stay alive and would otherwise sum into the
    gauges."""
    saved = dict(profiling._MEMORY_SOURCES)
    profiling._MEMORY_SOURCES.clear()
    try:
        yield
    finally:
        profiling._MEMORY_SOURCES.clear()
        profiling._MEMORY_SOURCES.update(saved)


# -- logging.kv quoting -----------------------------------------------------


def test_kv_quotes_unparseable_values():
    line = kv(a="x y", b="k=v", n=5, empty="", q='say "hi"')
    assert line == 'a="x y" b="k=v" n=5 empty="" q="say \\"hi\\""'
    # The quoted form stays splittable by a standard shell-style lexer:
    # exactly one token per field, '=' intact inside values.
    parts = shlex.split(line)
    assert parts == ["a=x y", "b=k=v", "n=5", "empty=", 'q=say "hi"']
    # Backslashes must round-trip too (an unquoted a\b would shlex back
    # to 'ab'), and carriage returns are escaped like newlines.
    assert shlex.split(kv(path="a\\b")) == ["path=a\\b"]
    assert kv(err="40%\rdone") == 'err="40%\\rdone"'


def test_kv_plain_values_unquoted():
    assert kv(slot=3, ratio=0.25, name="worker-1") == (
        "slot=3 ratio=0.25 name=worker-1"
    )


# -- compile sentinel -------------------------------------------------------


def test_sentinel_flags_recompile_only_after_warmup():
    sent = CompileSentinel(warmup_samples=2)

    @jax.jit
    def toy(x):
        return x + 1

    sent.register("toy", toy)
    toy(jnp.zeros((2,), jnp.float32))
    assert sent.sample() == 0  # first sample: baseline read
    toy(jnp.zeros((3,), jnp.float32))  # growth inside warmup
    assert sent.sample() == 0
    assert sent.events == 0

    flight_before = len(global_flight_recorder().events("recompile"))
    counter_before = global_metrics().counter("engine.compile_events")
    toy(jnp.zeros((4,), jnp.float32))  # forced shape change, warmed
    assert sent.sample() == 1
    assert sent.events == 1
    assert (
        global_metrics().counter("engine.compile_events")
        == counter_before + 1
    )
    recompiles = global_flight_recorder().events("recompile")
    assert len(recompiles) == flight_before + 1
    assert recompiles[-1]["data"]["program"] == "toy"
    assert recompiles[-1]["data"]["new"] == 1
    # Gauge tracks the cache size through both expected and unexpected
    # growth.
    snap = global_metrics().snapshot()
    assert snap["gauges"]["engine.compiles.toy"] == 3.0
    assert sent.compiles("toy") == 3
    # Stability: no growth, no event.
    toy(jnp.zeros((4,), jnp.float32))
    assert sent.sample() == 0
    # A custom registry (serve_metrics(registry=...)) sampling AFTER
    # the event still converges to the cumulative counter — detection
    # is sentinel-global, not first-sampler-wins.
    reg2 = MetricsRegistry()
    sent.sample(reg2)
    assert reg2.counter("engine.compile_events") == 1.0
    assert reg2.snapshot()["gauges"]["engine.compiles.toy"] == 3.0


def test_sentinel_idle_scrapes_do_not_burn_warmup():
    """A program registered at startup and sampled while the process is
    idle (exporter scrapes) keeps its full grace window: warmup counts
    ACTIVE samples (size > 0) only, so the first real compiles are
    never flagged."""
    sent = CompileSentinel(warmup_samples=2)

    @jax.jit
    def toy(x):
        return x - 1

    sent.register("toy", toy)
    for _ in range(10):  # idle scrapes: cache size stays 0
        assert sent.sample() == 0
    toy(jnp.zeros((2,), jnp.float32))  # first activity
    toy(jnp.zeros((3,), jnp.float32))
    assert sent.sample() == 0  # first ACTIVE sample: inside warmup
    assert sent.events == 0


def test_sentinel_prunes_watch_when_owner_gone():
    sent = CompileSentinel()
    sent.register("gone", size_fn=lambda: 2)
    sent.register("alive", size_fn=lambda: 1)
    reg = MetricsRegistry()
    sent.sample(reg)
    assert "engine.compiles.gone" in reg.snapshot()["gauges"]
    sent.register("gone", size_fn=lambda: None)  # owner collected
    sent.sample(reg)
    assert sent.watched() == ["alive"]
    # The retired program's gauge is cleared, not served stale forever.
    gauges = reg.snapshot()["gauges"]
    assert "engine.compiles.gone" not in gauges
    assert gauges["engine.compiles.alive"] == 1.0


def test_sentinel_reregister_rearms_warmup():
    sent = CompileSentinel(warmup_samples=1)

    @jax.jit
    def toy(x):
        return x * 2

    sent.register("toy", toy)
    toy(jnp.zeros((2,), jnp.float32))
    sent.sample()
    sent.sample()  # warmed now
    sent.register("toy", toy)  # re-arm (a fresh instance's constructor)
    toy(jnp.zeros((5,), jnp.float32))
    assert sent.sample() == 0  # growth back inside the new warmup
    assert sent.events == 0


def test_sentinel_disarm_revokes_unconsumed_allowance():
    """A granter that retires before its planned re-lowering lands must
    be able to take the allowance back: leftover slack on the shared
    watch would silently absorb another instance's REAL phantom
    variant (the batcher's close() calls disarm with its full grant;
    consumed units are already subtracted, so the clamp at zero strips
    exactly the leftovers)."""
    sent = CompileSentinel(warmup_samples=1)

    @jax.jit
    def toy(x):
        return x + 3

    sent.register("toy", toy)
    toy(jnp.zeros((2,), jnp.float32))
    sent.sample()
    sent.sample()  # warmed
    sent.rearm("toy", expect=2)  # planned re-lowering, never lands
    sent.disarm("toy", expect=2)  # granter retires: full grant back
    toy(jnp.zeros((5,), jnp.float32))  # REAL phantom variant
    assert sent.sample() == 1, "revoked allowance still absorbed growth"
    assert sent.events == 1
    sent.disarm("toy", expect=5)  # over-disarm clamps at zero...
    sent.disarm("missing")  # ...and unknown names are a no-op
    toy(jnp.zeros((7,), jnp.float32))
    assert sent.sample() == 1  # clamp did not go negative


def test_batcher_forced_shape_change_fires_sentinel(lm_setup):
    """Acceptance pin: a forced shape change after warmup increments
    ``engine.compile_events`` and records a flight-recorder event —
    through the real serving path (a late sampled+top_k request
    compiles new decode/staging variants). The same batcher journey
    also pins the one-branch phase gate: no ``engine.phase.*_s``
    samples while ``obs_engine`` is off, one per phase per tick while
    on."""
    lm, variables = lm_setup
    sent = global_compile_sentinel()
    eo = global_engine_obs()
    assert eo.enabled is False  # process default: off
    old_warmup = sent.warmup_samples
    sent.warmup_samples = 3
    try:
        bat = ContinuousBatcher(lm, variables, slots=2, chunk=2)

        def tick():
            # One sample per phase PER TICK is the assertion below: a
            # tick's decode and commit samples land with its drain.
            bat.tick()
            bat.drain()

        prompt = np.asarray([1, 2, 3], np.int32)
        r1 = bat.submit(prompt, 40)

        def phase_count(name):
            return (
                global_metrics().snapshot()["histograms"]
                .get(f"engine.phase.{name}_s", {}).get("count", 0)
            )

        phases = ("admit", "prefill", "decode", "commit", "update")
        before = {n: phase_count(n) for n in phases}
        for _ in range(3):  # gate off: no phase samples recorded
            tick()
        for n, c in before.items():
            assert phase_count(n) == c, n
        eo.enabled = True
        try:
            for _ in range(3):  # past warmup, steady greedy decode
                tick()
            for n, c in before.items():
                assert phase_count(n) >= c + 3, n
        finally:
            eo.enabled = False
        events_before = sent.events
        counter_before = global_metrics().counter("engine.compile_events")
        flight_before = len(global_flight_recorder().events("recompile"))
        # Forced shape change: first sampled top_k request compiles the
        # truncate decode variant (and a new key-bucket staging variant).
        bat.submit(
            prompt, 4, temperature=0.7, top_k=5,
            rng=jax.random.PRNGKey(3),
        )
        tick()
        assert sent.events > events_before
        assert (
            global_metrics().counter("engine.compile_events")
            > counter_before
        )
        new_events = global_flight_recorder().events("recompile")[
            flight_before:
        ]
        assert any(
            e["data"]["program"].startswith("continuous.") for e in new_events
        )
        out = bat.run()  # drain
        assert r1 in out
    finally:
        sent.warmup_samples = old_warmup


# -- memory accounting ------------------------------------------------------


def test_paged_memory_gauges_partition_pool(
    lm_setup, isolated_memory_sources
):
    """Acceptance pin: after N paged admissions,
    ``memory.pages_used + memory.pages_free + memory.pages_cached``
    equals the (allocatable) pool size — mid-flight and after
    retirement — and prefix reuse surfaces in the bridged counters."""
    lm, variables = lm_setup
    pool_pages = 20
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=8,
        pool_pages=pool_pages,
    )
    register_memory_source("continuous", bat)  # table was isolated
    reg = MetricsRegistry()
    reg.register_collector(engine_collector)

    def gauges():
        return reg.snapshot()["gauges"]

    prompt = np.asarray(list(range(1, 18)), np.int32)  # 2 full pages
    bat.submit(prompt, 12)
    bat.tick()  # admitted, mid-flight
    g = gauges()
    assert g["memory.pool_pages"] == float(pool_pages - 1)  # excl. trash
    assert g["memory.pages_used"] > 0
    assert (
        g["memory.pages_used"] + g["memory.pages_free"]
        + g["memory.pages_cached"]
        == g["memory.pool_pages"]
    )
    assert g["memory.pool_bytes"] > 0
    bat.run()
    # Second admission with the SAME prompt: full prompt pages are
    # reused from the retired request's cached pages.
    hist_before = (
        global_metrics().snapshot()["histograms"]
        .get("paged.pages_reused_per_admission", {}).get("count", 0)
    )
    bat.submit(prompt, 6)
    bat.run()
    g = gauges()
    assert (
        g["memory.pages_used"] + g["memory.pages_free"]
        + g["memory.pages_cached"]
        == g["memory.pool_pages"]
    )
    assert g["paged.prefix_hits"] >= 2  # both full prompt pages shared
    assert g["paged.prefix_misses"] >= 1  # the first, cold admission
    snap = global_metrics().snapshot()["histograms"][
        "paged.pages_reused_per_admission"
    ]
    assert snap["count"] >= hist_before + 1
    assert snap["max"] >= 2.0


def test_paged_memory_gauges_partition_with_cache_tier(
    lm_setup, isolated_memory_sources
):
    """Satellite pin (ISSUE 14): with the host tier attached, the HBM
    partition (used + free + cached == pool_pages) stays exact
    MID-FLIGHT while pages spill, and the spilled books are served as
    their own gauges (``memory.pages_spilled`` / ``memory.host_bytes``
    — a copy BELOW the pool, never double-counted in the
    partition)."""
    from adapt_tpu.config import CacheTierConfig

    lm, variables = lm_setup
    pool_pages = 12
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=2, kv_layout="paged", page_size=8,
        pool_pages=pool_pages,
        cache_tier=CacheTierConfig(
            spill_pages_per_tick=16, readmit_pages_per_tick=16
        ),
    )
    register_memory_source("continuous", bat)
    reg = MetricsRegistry()
    reg.register_collector(engine_collector)

    def check_partition():
        g = reg.snapshot()["gauges"]
        assert (
            g["memory.pages_used"] + g["memory.pages_free"]
            + g["memory.pages_cached"]
            == g["memory.pool_pages"]
        )
        return g

    rng = np.random.RandomState(0)
    first = rng.randint(1, 30, size=17).astype(np.int32)
    bat.submit(first, 8)
    bat.tick()  # mid-flight
    check_partition()
    # Flood until the first prompt's registered pages spill, checking
    # the partition at every boundary the books move across.
    for _ in range(4):
        bat.submit(rng.randint(1, 30, size=17).astype(np.int32), 8)
        bat.run()
        check_partition()
    g = check_partition()
    assert g["memory.pages_spilled"] >= 1
    assert g["memory.host_bytes"] > 0
    assert g["memory.pages_spilled"] == float(bat._tier.pages)
    # Readmit on re-reference: partition still exact, spilled gauge
    # tracks the tier (readmitted pages STAY host-resident — MRU).
    bat.submit(first, 4)
    bat.run()
    g = check_partition()
    assert bat.stats()["tier_readmitted"] >= 1
    assert g["memory.pages_spilled"] == float(bat._tier.pages)
    bat.close()


def test_pool_memory_gauges_match_pool_shapes(
    lm_setup, isolated_memory_sources
):
    """Pool bytes must equal the configured pool's shapes exactly:
    layers x (K,V) x (slots x pages_per_slot + trash) x kv_heads x
    page x head_dim x itemsize; a second batcher SUMS, and close()
    retires one from the gauges."""
    lm, variables = lm_setup
    slots, page = 3, 8
    bat = ContinuousBatcher(
        lm, variables, slots=slots, chunk=2, page_size=page
    )
    register_memory_source("continuous", bat)
    block0 = lm.graph.node(lm.block_names[0]).module
    expected = (
        len(lm.block_names)
        * 2
        * (slots * -(-lm.max_len // page) + 1)
        * block0.cache_heads
        * page
        * block0.head_dim
        * jnp.dtype(block0.dtype).itemsize
    )
    ms = bat._memory_stats()
    assert ms["memory.pool_bytes"] == float(expected)
    assert ms["memory.kv_bytes_ratio"] == 1.0
    assert "memory.kv_bytes" not in ms  # went with the dense layout
    reg = MetricsRegistry()
    reg.register_collector(engine_collector)
    assert reg.snapshot()["gauges"]["memory.pool_bytes"] == float(expected)
    # A second batcher SUMS; close() retires it from the gauges even
    # though its jit caches pin the instance alive (GC never fires).
    bat2 = ContinuousBatcher(
        lm, variables, slots=slots, chunk=2, page_size=page
    )
    register_memory_source("continuous", bat2)
    assert (
        reg.snapshot()["gauges"]["memory.pool_bytes"] == 2.0 * expected
    )
    bat2.close()
    assert reg.snapshot()["gauges"]["memory.pool_bytes"] == float(expected)
    # Gauges whose every source retired are REMOVED, not served stale.
    bat.close()
    assert "memory.pool_pages" not in reg.snapshot()["gauges"]


def test_draft_cache_gauge_matches_draft_strips(
    lm_setup, isolated_memory_sources
):
    """The one dense family left: the speculative draft's per-slot
    strips. ``memory.draft_cache_bytes`` is exactly their bytes —
    layers x (K,V) x slots x kv_heads x (max_len + draft_k + 1) x
    head_dim x itemsize, one more position with a tree draft — and a
    batcher without a draft serves no such gauge."""
    from adapt_tpu.config import SpeculativeConfig

    lm, variables = lm_setup
    slots, k = 2, 3
    block0 = lm.graph.node(lm.block_names[0]).module
    for tree_width in (0, 1):
        bat = ContinuousBatcher(
            lm, variables, slots=slots, draft_lm=lm,
            draft_variables=variables,
            speculative=SpeculativeConfig(
                draft_k=k, tree_width=tree_width
            ),
        )
        strip = lm.max_len + k + 1 + (1 if tree_width else 0)
        expected = (
            len(lm.block_names) * 2 * slots * block0.cache_heads
            * strip * block0.head_dim * jnp.dtype(block0.dtype).itemsize
        )
        ms = bat._memory_stats()
        assert ms["memory.draft_cache_bytes"] == float(expected)
        assert bat.stats()["draft_cache_bytes"] == expected
        assert bat._draft_caches[0][0].shape == (
            slots, block0.cache_heads, strip, block0.head_dim
        )
        bat.close()
    plain = ContinuousBatcher(lm, variables, slots=slots)
    assert "memory.draft_cache_bytes" not in plain._memory_stats()
    plain.close()


# -- regression gate --------------------------------------------------------


def test_ci_gate_compare_tolerances():
    base = {
        "tps": {"value": 10.0, "direction": "higher_better",
                "rel_tol": 0.1},
        "overhead": {"value": 0.0, "direction": "lower_better",
                     "abs_tol": 5.0},
    }
    ok = {"tps": {"value": 9.5}, "overhead": {"value": 4.9}}
    assert ci_gate.compare(ok, base) == []
    # Improvements never fail.
    better = {"tps": {"value": 12.0}, "overhead": {"value": -1.0}}
    assert ci_gate.compare(better, base) == []
    # Injected regressions fail, NAMING the metric.
    bad = {"tps": {"value": 8.5}, "overhead": {"value": 5.2}}
    regs = ci_gate.compare(bad, base)
    assert len(regs) == 2
    assert regs[0].startswith("overhead:")  # sorted by metric name
    assert regs[1].startswith("tps:")
    # A driver error record or a missing metric is always a regression.
    assert ci_gate.compare(
        {"tps": {"value": 10.0, "error": "boom"}, "overhead": {"value": 0}},
        base,
    ) != []
    assert any(
        "missing" in r
        for r in ci_gate.compare({"tps": {"value": 10.0}}, base)
    )
    # A crashed driver is keyed by driver name (no metric line was ever
    # printed): the missing-metric regression must surface its error
    # text, not hide the cause.
    regs = ci_gate.compare(
        {
            "tps": {"value": 10.0},
            "some_driver": {"value": 0.0, "error": "timed out after 600s"},
        },
        base,
    )
    assert any("overhead: missing" in r and "timed out" in r for r in regs)


def test_ci_gate_main_exit_codes(tmp_path, capsys):
    baseline = {
        "suite": {},
        "metrics": {
            "m": {"value": 5.0, "direction": "higher_better",
                  "rel_tol": 0.1}
        },
    }
    path = tmp_path / "base.json"
    path.write_text(json.dumps(baseline))
    rc = ci_gate.main(
        ["--baseline", str(path)], records={"m": {"value": 4.8}}
    )
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and report["ok"] is True
    rc = ci_gate.main(
        ["--baseline", str(path)], records={"m": {"value": 3.0}}
    )
    captured = capsys.readouterr()
    report = json.loads(captured.out.strip().splitlines()[-1])
    assert rc == 1 and report["ok"] is False
    assert report["regressions"] and "m:" in report["regressions"][0]
    assert "REGRESSION: m:" in captured.err
    # Re-baselining carries tolerances, takes the measured value.
    out = tmp_path / "new.json"
    rc = ci_gate.main(
        ["--baseline", str(path), "--write-baseline", str(out)],
        records={"m": {"value": 6.5}},
    )
    capsys.readouterr()
    assert rc == 0
    new = json.loads(out.read_text())
    assert new["metrics"]["m"]["value"] == 6.5
    assert new["metrics"]["m"]["rel_tol"] == 0.1


# -- exporter under live ticking --------------------------------------------


def test_exporter_scrape_concurrent_with_ticking_batcher(lm_setup):
    """Scrapes race a live serving loop: metrics mutate during
    serialization, the memory collector walks a pager the ticking
    thread is mutating, and the sentinel samples from both threads —
    every response must stay well-formed."""
    lm, variables = lm_setup
    bat = ContinuousBatcher(lm, variables, slots=2, chunk=2)
    server = serve_metrics(port=0)
    port = server.server_address[1]
    rng = np.random.RandomState(5)
    try:
        with bat:
            ids = [
                bat.submit(
                    rng.randint(1, 37, size=n).astype(np.int32), 40
                )
                for n in (3, 5, 7, 4)
            ]
            for _ in range(10):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10
                ) as r:
                    text = r.read().decode()
                assert "adapt_continuous_ticks_total" in text
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics.json", timeout=10
                ) as r:
                    snap = json.loads(r.read().decode())
                assert "gauges" in snap and "histograms" in snap
            # Engine-tier families are served on the existing exporter.
            assert any(
                g.startswith("engine.compiles.continuous.")
                for g in snap["gauges"]
            )
            assert "memory.pool_bytes" in snap["gauges"]
            for rid in ids:
                bat.result(rid, timeout=120.0)
    finally:
        server.shutdown()
        server.server_close()
