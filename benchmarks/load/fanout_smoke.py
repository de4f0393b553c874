"""Agent-trace A/B: the SAME branching schedule submitted through
``submit_fanout`` (copy-on-write page sharing) vs serially.

The ``agent_trace`` workload preset fans every arrival into 4
identical-prompt branches tied by ``Arrival.group`` — the tool-call /
search exploration shape. The harness's ``--fanout on`` arm groups
each branch set into ONE ``submit_fanout`` call; ``--fanout off``
submits the identical arrivals one by one. Greedy fan-out is
contractually bit-identical to serial submits, so the whole A/B is a
correctness gate with a perf headline on top. Two gated records:

- ``load_fanout_identity_exact`` — 1.0 when the fan-out arm's
  per-request token streams are BIT-IDENTICAL to the serial arm's,
  the fan-out arm actually forked (``cow_forks`` > 0; a zero means
  every branch re-ran its suffix prefill and the arm measured
  nothing), the serial arm recorded none, and both arms drain with
  the pool partition exact and zero leaked page claims. Any violation
  becomes an ``error`` record the gate always fails.
- ``load_fanout_prefill_ratio`` — prompt positions prefilled in-tick,
  serial / fan-out: each CoW fork skips a whole suffix pass, so the
  fan-out arm must prefill strictly fewer positions over the same
  schedule. Deterministic (schedule-derived counts, not wall clock).

Usage: ``python benchmarks/load/fanout_smoke.py [--seed 0]``
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, int_flag  # noqa: E402
from benchmarks.load.harness import (  # noqa: E402
    build_batcher,
    drive_phase,
    warmup,
)
from benchmarks.load.workload import build_schedule, preset  # noqa: E402

DURATION_S = 2.0
SLOTS = 4
CHUNK = 4
PAGE = 16
#: Covers the 4 slots' worst case (ceil(116/16) = 8 pages each) plus
#: prefix-LRU headroom so branch groups admit without pool pressure.
POOL_PAGES = 48

_METRICS = (
    ("load_fanout_identity_exact", "bool"),
    ("load_fanout_prefill_ratio",
     "x (in-tick prefill positions, serial / fan-out)"),
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
    try:
        from adapt_tpu.utils.profiling import global_compile_sentinel

        # Two fresh batchers (one per arm) in one process: the second
        # arm's warmup compiles are legitimate — disarm the alarm (the
        # kv_tiers rationale).
        global_compile_sentinel().warmup_samples = 10**9
        spec = preset("agent_trace", duration_s=DURATION_S)
        schedule = build_schedule(spec, seed)
        max_len = spec.prompt_max + spec.steps_max + 8
        arms: dict[str, dict] = {}
        for arm in ("serial", "fanout"):
            bat = build_batcher(
                spec.vocab, max_len, SLOTS, CHUNK, page_size=PAGE, pool_pages=POOL_PAGES,
            )
            warmup(bat, spec.vocab, spec.steps_max, spec.prompt_max)
            pf0 = bat.stats()["prefill_tokens"]
            report = drive_phase(
                bat, schedule, spec, fanout=arm == "fanout"
            )
            st = bat.stats()
            arms[arm] = {
                "streams": report["token_streams"],
                "prefill_tokens": st["prefill_tokens"] - pf0,
                "cow_forks": st["cow_forks"],
                "pages_in_use": st["pages_in_use"],
                "partition_ok": (
                    st["pages_in_use"] + st["pages_free"]
                    == st["pool_pages"] - 1
                ),
                "fanout_groups": st["fanout_groups"],
                "report": {
                    k: report[k]
                    for k in ("goodput_tokens_s", "ttft_s", "itl_s",
                              "wall_s", "cow_forks", "schedule_digest")
                },
            }
            bat.close()

        errors: list[str] = []
        ser, fan = arms["serial"], arms["fanout"]
        if fan["cow_forks"] == 0:
            errors.append(
                "fan-out arm never forked a page — every branch "
                "re-ran its suffix prefill, the arm measures nothing"
            )
        if ser["cow_forks"] != 0:
            errors.append(
                f"serial arm booked {ser['cow_forks']} cow forks"
            )
        for arm, d in arms.items():
            if not d["partition_ok"]:
                errors.append(f"{arm} arm: pool partition broke")
            if d["pages_in_use"] != 0 or d["fanout_groups"] != 0:
                errors.append(
                    f"{arm} arm leaked page claims at drain "
                    f"({d['pages_in_use']} in use, "
                    f"{d['fanout_groups']} groups)"
                )
        diverged = sum(
            1 for a, b in zip(ser["streams"], fan["streams"]) if a != b
        )
        if diverged:
            errors.append(
                f"{diverged}/{len(schedule)} request streams diverged "
                "between the serial and fan-out arms"
            )
        if fan["prefill_tokens"] >= ser["prefill_tokens"]:
            errors.append(
                f"fan-out arm prefilled {fan['prefill_tokens']} "
                f"positions vs serial {ser['prefill_tokens']} — the "
                "forks saved nothing"
            )
        if errors:
            _emit_errors("; ".join(errors)[-300:])
            return 0

        extras = {
            arm: {k: v for k, v in d.items() if k != "streams"}
            for arm, d in arms.items()
        }
        emit(
            "load_fanout_identity_exact", 1.0, _METRICS[0][1], 0.0,
            seed=seed, requests=len(schedule),
            cow_forks=fan["cow_forks"], arms=extras,
        )
        ratio = ser["prefill_tokens"] / max(fan["prefill_tokens"], 1)
        emit(
            "load_fanout_prefill_ratio",
            round(ratio, 4),
            _METRICS[1][1],
            round(ratio - 1.0, 4),
            seed=seed,
            prefill_serial=ser["prefill_tokens"],
            prefill_fanout=fan["prefill_tokens"],
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        _emit_errors(str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
