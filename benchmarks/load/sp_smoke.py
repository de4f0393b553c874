"""Long-context-preset A/B: the SAME seeded heavy-prefill schedule
through an sp-off and an sp-on batcher — the load-harness TTFT gate
for the sequence-parallel prefill path (ROADMAP item 5 / ISSUE 15).

The schedule is a scaled-down instance of the ``long_context`` preset
shape (lognormal prompts dominating the work, short outputs) sized for
CI: the REAL preset's 8k-64k prompts drive manual runs via
``harness.py --preset long_context --sp on|off``; this driver keeps
the same prompt/output shape class at a tiny LM so the gate runs in
seconds. Two gated records:

- ``load_sp_ttft_ratio`` — sp-off p50 TTFT / sp-on p50 TTFT on the
  same seeded schedule. On THIS one-core CI box the virtual ring
  ranks serialize, so the honest pin is NON-REGRESSION (the sp path's
  ring/landing overhead must not damage TTFT); the prefill-wall WIN
  is gated structurally by ``micro_sp_prefill_flops_ratio`` (the
  per-device work split — the number that becomes wall clock the
  moment the ring ranks are real chips). On parallel hardware this
  ratio tracks that split; the gate's floor only catches the sp path
  making TTFT materially worse.
- ``load_sp_prefills`` — STRUCTURAL: long-prompt admissions that
  actually took the sp program in the sp-on arm (must be > 0, exact
  count is schedule-deterministic). An sp arm that silently
  collocates everything measures nothing; the driver also fails
  (error records) when the two arms' per-request token counts
  diverge — the determinism half of the bit-identity contract, whose
  full byte/stream pins live in tests/test_sp_prefill.py and the
  micro driver.

Usage: ``python benchmarks/load/sp_smoke.py [--seed 0]``
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, force_cpu_mesh, int_flag  # noqa: E402
from benchmarks.load.harness import (  # noqa: E402
    build_batcher,
    drive_phase,
    warmup,
)
from benchmarks.load.workload import WorkloadSpec, build_schedule  # noqa: E402

DURATION_S = 2.0
SLOTS = 2
CHUNK = 4
PAGE = 16
SP_THRESHOLD = 64
SP_WIDTH = 2


def main() -> int:
    seed = int_flag(sys.argv, "--seed", 0)
    try:
        force_cpu_mesh(max(2, SP_WIDTH))
        from adapt_tpu.config import PrefillConfig

        # The long_context preset's SHAPE (prefill-dominated heavy
        # tail, short outputs) at CI scale: median 6 pages, tail to 20
        # pages, outputs a handful of tokens.
        spec = WorkloadSpec(
            rate_rps=4.0,
            duration_s=DURATION_S,
            prompt_median=96,
            prompt_sigma=0.7,
            prompt_max=320,
            steps_median=6,
            steps_sigma=0.4,
            steps_max=12,
            ttft_budget_s=10.0,
            itl_budget_s=5.0,
        )
        schedule = build_schedule(spec, seed)
        max_len = spec.prompt_max + spec.steps_max + 8
        arms: dict[str, dict] = {}
        for arm, cfg in (
            ("off", None),
            ("on", PrefillConfig(sp_threshold=SP_THRESHOLD,
                                 sp_width=SP_WIDTH)),
        ):
            bat = build_batcher(
                spec.vocab, max_len, SLOTS, CHUNK, page_size=PAGE, prefill=cfg, prefill_chunk=2 * PAGE,
            )
            warmup(bat, spec.vocab, spec.steps_max, spec.prompt_max)
            report = drive_phase(bat, schedule, spec)
            arms[arm] = {
                "ttft_p50": report["ttft_s"].get("p50"),
                "ttft_p99": report["ttft_s"].get("p99"),
                "sp_prefills": report["sp_prefills"],
                "sp_width": report["sp_width"],
                "token_counts": report["token_counts"],
                "prefill_tokens_s": report["prefill_tokens_s"],
                "wall_s": report["wall_s"],
                "schedule_digest": report["schedule_digest"],
            }
            bat.close()

        off, on = arms["off"], arms["on"]
        violations: list[str] = []
        if not on["sp_prefills"]:
            violations.append(
                "sp-on arm never dispatched the sp program (threshold "
                f"{SP_THRESHOLD}, widths {on['sp_width']})"
            )
        if off["sp_prefills"]:
            violations.append(
                f"sp-off arm reports {off['sp_prefills']} sp prefills"
            )
        if off["token_counts"] != on["token_counts"]:
            violations.append(
                "per-request token counts diverged between arms "
                "(determinism contract broken)"
            )
        if violations:
            for metric in ("load_sp_ttft_ratio", "load_sp_prefills"):
                emit(metric, 0.0, "structural", 0.0,
                     error="; ".join(violations)[:300])
            return 0
        ratio = (
            off["ttft_p50"] / on["ttft_p50"]
            if on["ttft_p50"] else 0.0
        )
        extras = dict(
            seed=seed,
            sp_width=SP_WIDTH,
            sp_threshold=SP_THRESHOLD,
            requests=len(schedule),
            off={k: v for k, v in off.items() if k != "token_counts"},
            on={k: v for k, v in on.items() if k != "token_counts"},
        )
        emit(
            "load_sp_ttft_ratio", ratio,
            "sp-off p50 TTFT / sp-on p50 TTFT (same seeded schedule)",
            0.0, **extras,
        )
        emit(
            "load_sp_prefills", float(on["sp_prefills"]),
            "sp-program admissions in the sp-on arm (structural)",
            0.0, seed=seed,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        for metric in ("load_sp_ttft_ratio", "load_sp_prefills"):
            emit(metric, 0.0, "structural", 0.0, error=str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
