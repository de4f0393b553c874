"""Capacity-plane smoke: forecast self-calibration + sketch affinity.

Two gated records for the ``runtime/capacity`` signal plane
(``CapacityModel`` — the self-describing replica a router places on):

- ``load_capacity_forecast_within_2x`` — an HONEST train-then-measure
  protocol on the smoke-preset workload shape: one phase of seeded
  open-loop traffic trains the TTFT forecaster (queue-wait EWMA,
  per-bucket prefill walls, tick gap, bias corrector), then the
  calibration window is reset and a SECOND phase (fresh seed) is
  measured — the gate is the fraction of that phase's admissions whose
  realized TTFT landed within 2x of the forecast made at their own
  submit. Cold admissions (forecast 0.0 — nothing learned yet) never
  enter the books, and a measure phase with ZERO scored admissions
  reports 0.0, not the empty-window default of 1.0.
- ``load_capacity_affinity_picks_resident`` — structural: the corpus
  preset's recurring prefixes run against a paged replica, its
  prefix-affinity sketch is exported (``sketch_from_pager`` — hashed
  content keys only), and ``affinity_score`` must rank that replica
  above a COLD replica with free slots for a corpus-prefix prompt,
  from the sketches alone (no prompt round-trip). The sketch must also
  stay bounded (<= sketch_k entries) after adversarial prefix churn
  (a burst of distinct never-repeated prompts).

Usage: ``python benchmarks/load/capacity_smoke.py [--seed 0]``
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, int_flag  # noqa: E402
from benchmarks.load.workload import (  # noqa: E402
    WorkloadSpec,
    build_schedule,
    preset,
    schedule_prefixes,
)

#: Forecast arm: the smoke-preset shape at its under-capacity rate.
RATE_RPS = 8.0
#: Affinity arm page size — 6 full pages per 96-token corpus prefix.
PAGE = 16

_METRICS = (
    ("load_capacity_forecast_within_2x",
     "fraction of measure-phase admissions with realized TTFT within "
     "2x of their submit-time forecast"),
    ("load_capacity_affinity_picks_resident",
     "1.0 = sketch-only affinity ranks the prefix-resident replica "
     "above a cold one AND the sketch stays bounded under churn"),
)


def _emit_errors(err: str) -> None:
    for metric, unit in _METRICS:
        print(
            json.dumps(
                {"metric": metric, "value": 0.0, "unit": unit,
                 "vs_baseline": 0.0, "error": err}
            ),
            flush=True,
        )


def main() -> int:
    seed = int_flag(sys.argv, "--seed", 0)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import numpy as np

        from benchmarks.load.harness import (
            build_batcher,
            drive_phase,
            warmup,
        )

        from adapt_tpu.config import CapacityConfig
        from adapt_tpu.runtime.capacity import (
            affinity_score,
            sketch_from_pager,
        )

        # ---- arm 1: forecast self-calibration (train, reset, measure)
        spec = WorkloadSpec(
            duration_s=2.0,
            rate_rps=RATE_RPS,
            prompt_median=6,
            prompt_max=16,
            steps_median=16,
            steps_sigma=0.4,
            steps_max=48,
            ttft_budget_s=3.0,
            itl_budget_s=2.0,
        )
        bat = build_batcher(
            spec.vocab, spec.prompt_max + spec.steps_max + 8,
            slots=4, chunk=8,
        )
        cap = bat._capacity
        if cap is None:
            raise RuntimeError("capacity plane disabled on the batcher")
        warmup(bat, spec.vocab, spec.steps_max, spec.prompt_max)
        train = drive_phase(bat, build_schedule(spec, seed), spec)
        # Train-then-measure: drop the training verdicts (warmup's
        # compile-scale queue waits poison the early forecasts; the
        # EWMAs and bias they trained SURVIVE the reset) and score
        # only the fresh phase.
        cap.reset_calibration()
        measure = drive_phase(bat, build_schedule(spec, seed + 100), spec)
        # One idle tick so the last admissions' pending (forecast,
        # realized) pairs drain into the calibration window.
        bat.tick()
        scored = len(cap.forecaster._within)
        calibration = cap.calibration() if scored else 0.0
        bat.close()
        emit(
            _METRICS[0][0],
            round(calibration, 4),
            _METRICS[0][1],
            round(calibration - 1.0, 4),
            seed=seed,
            rate_rps=RATE_RPS,
            scored_admissions=scored,
            measure_requests=measure["requests"],
            train_requests=train["requests"],
            forecast=cap.forecaster.snapshot(),
            ttft_p99_s=measure["ttft_s"].get("p99"),
        )

        # ---- arm 2: sketch-only affinity, resident vs cold ----------
        cspec = preset("corpus", duration_s=1.5)
        sketch_k = CapacityConfig().sketch_k
        resident = build_batcher(
            cspec.vocab, cspec.prompt_max + cspec.steps_max + 8,
            slots=2, chunk=4, page_size=PAGE,
        )
        warmup(resident, cspec.vocab, cspec.steps_max, cspec.prompt_max)
        drive_phase(resident, build_schedule(cspec, seed), cspec)
        prefixes = schedule_prefixes(cspec, seed)
        # Probe prompts: each corpus prefix plus a fresh tail — the
        # shapes a router would place. Score the max: the pool is
        # smaller than the corpus working set so LRU evicts SOME
        # prefixes, but a router only needs one hot prefix to rank the
        # resident replica above a cold one.
        probes = [
            np.asarray(tuple(p) + (1, 2, 3), np.int32)
            for p in prefixes
        ]
        resident_sketch = sketch_from_pager(resident._pager, sketch_k)
        score_resident = max(
            affinity_score(resident_sketch, p) for p in probes
        )
        # The cold replica: same shape, zero traffic. Its sketch is
        # what a fresh pager exports — free slots, no affinity.
        cold = build_batcher(
            cspec.vocab, cspec.prompt_max + cspec.steps_max + 8,
            slots=2, chunk=4, page_size=PAGE,
        )
        cold_sketch = sketch_from_pager(cold._pager, sketch_k)
        score_cold = max(
            affinity_score(cold_sketch, p) for p in probes
        )
        cold.close()
        # Adversarial prefix churn: a burst of distinct never-repeated
        # prompts, then the bound check — top-K by construction, but
        # the gate pins it against regression.
        rng = np.random.default_rng(seed + 7)
        for _ in range(64):
            resident.submit(
                rng.integers(1, cspec.vocab, size=3 * PAGE).astype(
                    np.int32
                ),
                2,
            )
        resident.run()
        churned_sketch = sketch_from_pager(resident._pager, sketch_k)
        bounded = len(churned_sketch["entries"]) <= sketch_k
        resident.close()
        ok = (
            score_resident > score_cold
            and score_resident > 0.0
            and bounded
        )
        emit(
            _METRICS[1][0],
            1.0 if ok else 0.0,
            _METRICS[1][1],
            (1.0 if ok else 0.0) - 1.0,
            seed=seed,
            score_resident=round(score_resident, 4),
            score_cold=round(score_cold, 4),
            sketch_entries=len(resident_sketch["entries"]),
            churned_entries=len(churned_sketch["entries"]),
            sketch_k=sketch_k,
            corpus_prefixes=len(prefixes),
        )
    except Exception as e:  # noqa: BLE001 — always JSON lines, rc 0
        _emit_errors(str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
