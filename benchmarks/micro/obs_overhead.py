"""Observability overhead on the steady-state decode tick.

The instrumentation contract (ISSUE 2, extended by the engine tier):
request timelines, engine phase timing and tracing must be cheap enough
to leave on. Disabled, the only residue is one branch per site and,
at the tick's phase sites (``EngineObs.region``), one
``jax.profiler.TraceAnnotation`` (~0.4 us with no profiler session,
about ten a tick: always on, so part of the floor and not of the
overhead measured here); enabled, the budget is < 5% added tick wall
time on CPU.

Four configurations over the SAME ContinuousBatcher steady state
(all slots decoding, no admissions, chunked ticks):

- ``off``     — ``obs_timeline=False``, engine obs off, tracer disabled
  (the floor; the always-on compile-sentinel sample per tick and the
  phase sites' profiler annotations are part of this floor by design).
- ``timeline``— default serving config: TTFT/ITL/queue-wait histograms
  + flight-recorder lifecycle events (engine + tracer still off).
  Every request carries an ``SLOSpec``, so this config ALSO pays the
  per-commit SLO evaluation + the per-tick goodput/attainment flush —
  the budget below covers SLO tracking, not just the bare histograms.
- ``engine``  — timeline + ``obs_engine`` per-phase histograms
  (``engine.phase.{tick,admit,prefill,launch,decode,fetch,commit,update}_s``).
- ``trace``   — engine + the span ring (prefill/decode-chunk spans).
- ``federation`` — trace + the telemetry-federation REPORT PATH
  (``utils/telemetry``): a ``TelemetryReporter.collect()`` (windowed
  snapshot delta + reservoir serialization + flight/span drain) folded
  into a ``FederatedStore`` every ``REPORT_EVERY`` ticks — the
  worker-side collect and the parent-side ingest of one report, i.e.
  both halves of the fleet path, timed inside the serving loop.

TWO JSON lines: ``micro_obs_overhead_async_pct`` (fully-enabled
"trace" overhead vs the floor, percent; ``vs_baseline`` = the 5%
budget minus the measured overhead, positive = within budget; the
tick's deferred commit half carries the ``_obs_flush``/SLO arithmetic,
and this row holds that seam to the budget) and
``micro_obs_federation_pct`` (federation config vs the same floor,
same budget — gated via benchmarks/baselines/seed.json). Per-config
per-tick means and the engine-only overhead ride in extras.

A THIRD gated line, ``micro_obs_overhead_capacity_pct``, measures the
capacity/placement-signal plane (``runtime/capacity.CapacityModel``)
on a PAIR of fresh paged batchers: one with
``CapacityConfig(enabled=False)`` (the floor — no model attached, zero
extra work anywhere) and one with ``refresh_s=0.0`` (book + sketch
rebuilt EVERY flush — far more aggressive than the production 0.25 s
cadence, so the measured overhead upper-bounds the real one). Both run
with the default timeline config so the delta isolates the capacity
arm alone. Same < 5% budget.

Timing note (benchmarks/common.py): ticks end in a real host fetch of
the chunk's tokens, so the region is honestly bounded per tick.

Usage: ``python benchmarks/micro/obs_overhead.py [--slots 4]
[--ticks 40] [--trials 5]``
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, int_flag  # noqa: E402

BUDGET_PCT = 5.0
#: Telemetry-report cadence in TICKS for the federation config — far
#: more aggressive than production (reports go out on a seconds-scale
#: wall cadence there), so the measured overhead upper-bounds the
#: real one.
REPORT_EVERY = 4


def main() -> int:
    slots = int_flag(sys.argv, "--slots", 4)
    n_ticks = int_flag(sys.argv, "--ticks", 40)
    trials = int_flag(sys.argv, "--trials", 5)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax
        import numpy as np

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from adapt_tpu.config import SLOSpec
        from adapt_tpu.models.transformer_lm import lm_tiny
        from adapt_tpu.runtime.continuous import ContinuousBatcher
        from adapt_tpu.utils.tracing import global_tracer

        from adapt_tpu.utils.profiling import global_engine_obs

        chunk = 8
        # Requests must OUTLIVE every measured window (warmup + 5
        # configs x trials x n_ticks), or late ticks measure an idle
        # batcher: size max_len from the measurement plan.
        total_ticks = n_ticks * (5 * trials + 1) + 8
        steps = total_ticks * chunk
        lm = lm_tiny(vocab=37, max_len=steps + 16)
        variables = lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        bat = ContinuousBatcher(lm, variables, slots=slots, chunk=chunk)
        rng = np.random.RandomState(0)
        # Generous budgets that never miss: the measured cost is the
        # EVALUATION (two comparisons per commit + the per-tick flush),
        # which is identical met or missed — minus one flight event.
        slo = SLOSpec(ttft_budget_s=3600.0, itl_budget_s=3600.0)
        for _ in range(slots):
            bat.submit(
                rng.randint(0, 37, size=6).astype(np.int32), steps,
                slo=slo,
            )
        bat.tick()  # admission burst + compiles
        bat.tick()

        tracer = global_tracer()
        eobs = global_engine_obs()
        for _ in range(n_ticks):  # warm caches before ANY timed window
            bat.tick()

        from adapt_tpu.utils.telemetry import (
            FederatedStore,
            TelemetryReporter,
        )

        store = FederatedStore()
        reporter = TelemetryReporter("bench", "obs0")

        configs = {  # name -> (obs_timeline, obs_engine, tracer.enabled)
            "off": (False, False, False),
            "timeline": (True, False, False),
            "engine": (True, True, False),
            "trace": (True, True, True),
            "federation": (True, True, True),
        }
        best = {name: float("inf") for name in configs}
        # Round-robin trials + best-of, ROTATING the config order each
        # trial: tick cost grows with sequence position (longer
        # attention window), so a fixed order would hand the
        # first-measured config the cheapest positions every trial.
        names = list(configs)
        n = len(names)
        for t in range(trials):
            for name in names[t % n:] + names[: t % n]:
                timeline, engine, trace = configs[name]
                bat.obs_timeline = timeline
                eobs.enabled = engine
                tracer.enabled = trace
                federate = name == "federation"
                t0 = time.perf_counter()
                for i in range(n_ticks):
                    bat.tick()
                    if federate and i % REPORT_EVERY == 0:
                        # Both halves of the fleet report path inside
                        # the timed region: the worker-side collect
                        # (windowed delta + reservoir serialization)
                        # and the parent-side ingest.
                        store.ingest(reporter.collect())
                best[name] = min(
                    best[name], (time.perf_counter() - t0) / n_ticks
                )
                if federate:
                    # Close the chained snapshot window OUTSIDE the
                    # timed region: an open window's reservoir forks
                    # would tax every OTHER config's observe() calls.
                    reporter.close()
        t_off, t_timeline, t_engine, t_trace = (
            best["off"], best["timeline"], best["engine"], best["trace"]
        )
        t_fed = best["federation"]
        tracer.enabled = False
        eobs.enabled = False
        still_active = bat.stats()["active"]
        if still_active != slots:
            raise RuntimeError(
                f"batcher fell out of steady state mid-measure "
                f"({still_active}/{slots} slots active)"
            )
        overhead_pct = (t_trace / t_off - 1.0) * 100.0
        federation_pct = (t_fed / t_off - 1.0) * 100.0
        emit(
            "micro_obs_overhead_async_pct",
            overhead_pct,
            "% tick wall time (trace+engine+timeline vs off)",
            BUDGET_PCT - overhead_pct,
            budget_pct=BUDGET_PCT,
            tick_off_ms=round(t_off * 1e3, 4),
            tick_timeline_ms=round(t_timeline * 1e3, 4),
            tick_engine_ms=round(t_engine * 1e3, 4),
            tick_trace_ms=round(t_trace * 1e3, 4),
            timeline_only_pct=round((t_timeline / t_off - 1.0) * 100.0, 3),
            engine_pct=round((t_engine / t_off - 1.0) * 100.0, 3),
            slots=slots,
            ticks=n_ticks,
            trials=trials,
            chunk=bat.chunk,
        )
        emit(
            "micro_obs_federation_pct",
            federation_pct,
            "% tick wall time (trace + telemetry report path vs off)",
            BUDGET_PCT - federation_pct,
            budget_pct=BUDGET_PCT,
            tick_federation_ms=round(t_fed * 1e3, 4),
            report_every_ticks=REPORT_EVERY,
            reports_ingested=store.sources()
            .get("bench:obs0:%d" % os.getpid(), {})
            .get("reports", 0),
        )

        bat.close()

        # Capacity-plane arm: a fresh PAGED batcher pair (paged so the
        # book rebuild pays the full bill — headroom from Pager.stats
        # plus the radix affinity sketch). The floor batcher has the
        # plane disabled (no model attached); the hot one rebuilds the
        # book on EVERY flush (refresh_s=0.0, vs 0.25 s in production),
        # so this upper-bounds the steady-state cost. Both keep the
        # default timeline config: the delta is the capacity arm alone.
        from adapt_tpu.config import CapacityConfig

        page = 16
        csteps = (n_ticks * (trials + 1) + 8) * chunk
        pool = slots * ((csteps + 48 + page) // page + 1) + 8
        cbats = {}
        for cname, ccfg in (
            ("off", CapacityConfig(enabled=False)),
            ("on", CapacityConfig(refresh_s=0.0)),
        ):
            cb = ContinuousBatcher(
                lm, variables, slots=slots, chunk=chunk,
                kv_layout="paged", page_size=page, pool_pages=pool,
                capacity=ccfg,
            )
            for _ in range(slots):
                # 3-page prompts so the radix tree (and therefore the
                # sketch rebuild) has real content to walk.
                cb.submit(
                    rng.randint(0, 37, size=3 * page).astype(np.int32),
                    csteps, slo=slo,
                )
            cb.tick()  # admission burst + paged-program compiles
            cb.tick()
            for _ in range(n_ticks):  # warm before any timed window
                cb.tick()
            cbats[cname] = cb
        cbest = {"off": float("inf"), "on": float("inf")}
        for t in range(trials):
            order = ("off", "on") if t % 2 == 0 else ("on", "off")
            for cname in order:
                cb = cbats[cname]
                t0 = time.perf_counter()
                for _ in range(n_ticks):
                    cb.tick()
                cbest[cname] = min(
                    cbest[cname], (time.perf_counter() - t0) / n_ticks
                )
        for cname, cb in cbats.items():
            if cb.stats()["active"] != slots:
                raise RuntimeError(
                    f"capacity-{cname} batcher fell out of steady "
                    "state mid-measure"
                )
        book = cbats["on"].capacity_book() or {}
        for cb in cbats.values():
            cb.close()
        capacity_pct = (cbest["on"] / cbest["off"] - 1.0) * 100.0
        emit(
            "micro_obs_overhead_capacity_pct",
            capacity_pct,
            "% tick wall time (capacity book rebuilt every flush vs "
            "plane disabled, paged batcher)",
            BUDGET_PCT - capacity_pct,
            budget_pct=BUDGET_PCT,
            tick_capacity_off_ms=round(cbest["off"] * 1e3, 4),
            tick_capacity_on_ms=round(cbest["on"] * 1e3, 4),
            refresh_s=0.0,
            sketch_entries=len(
                book.get("sketch", {}).get("entries", ())
            ),
            slots=slots,
            ticks=n_ticks,
            trials=trials,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        emit(
            "micro_obs_overhead_async_pct", 0.0,
            "% tick wall time (trace+engine+timeline vs off)", 0.0,
            error=str(e)[-300:],
        )
        emit(
            "micro_obs_federation_pct", 0.0,
            "% tick wall time (trace + telemetry report path vs off)",
            0.0,
            error=str(e)[-300:],
        )
        emit(
            "micro_obs_overhead_capacity_pct", 0.0,
            "% tick wall time (capacity book rebuilt every flush vs "
            "plane disabled, paged batcher)",
            0.0,
            error=str(e)[-300:],
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
