"""Automated perf-regression gate over the micro-benchmark suite.

The BENCH_r*.json trajectory was write-only: every round measured, nothing
compared. This driver closes the loop — it runs the micro-benchmark
drivers (each prints one JSON line in the ``benchmarks/common.emit``
contract), collects the records into one BENCH-style report, compares
each gated metric against a checked-in baseline with per-metric
tolerances, and exits nonzero listing every regressed metric (one
``REGRESSION:`` line on stderr per miss).

Baseline format (``benchmarks/baselines/seed.json``)::

    {
      "suite":   {"<driver>": ["--flag", "value", ...], ...},
      "metrics": {
        "<metric>": {
          "value": <baseline value>,
          "direction": "higher_better" | "lower_better",
          "rel_tol": <fraction of |value| allowed as slack, default 0>,
          "abs_tol": <absolute slack, default 0>
        }, ...
      }
    }

``suite`` names drivers under ``benchmarks/micro/`` (sans ``.py``) with
their args, so the baseline and the workload that produced it travel
together; a ``/`` in the name resolves under ``benchmarks/`` instead
(``"load/smoke"`` -> ``benchmarks/load/smoke.py``). A driver may emit
SEVERAL records — one JSON object per stdout line — and each gates
independently (the load smoke emits goodput AND attainment).
Comparison is ONE-SIDED: a metric only fails when it is worse
than ``value`` by more than ``abs_tol + |value| * rel_tol`` in its
direction — improvements never fail the gate (re-baseline with
``--write-baseline`` when they should become the new floor). A driver
error record (the drivers emit ``{"value": 0, "error": ...}`` instead of
crashing) or a missing metric is always a regression: a gate that can't
measure must fail loud, not pass quiet.

Usage::

    python benchmarks/ci_gate.py --baseline benchmarks/baselines/seed.json
    python benchmarks/ci_gate.py --baseline ... --out gate_report.json
    python benchmarks/ci_gate.py --baseline ... --write-baseline new.json

``scripts/tier1.sh --gate`` runs the tier-1 tests then this gate.
``compare()`` and ``main(argv, records=...)`` are importable for unit
tests (inject records, skip the suite run).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Per-driver wall clamp: a hung driver (runaway compile, stuck thread)
#: must fail the gate, not wedge CI.
DRIVER_TIMEOUT_S = 600.0

_DIRECTIONS = ("higher_better", "lower_better")


def run_suite(
    suite: dict[str, list[str]], timeout_s: float = DRIVER_TIMEOUT_S
) -> dict[str, dict]:
    """Run each micro driver; return {metric: record}. Drivers keep the
    always-one-JSON-line contract, so a crash/timeout becomes an error
    record under the driver's name (which compare() then fails)."""
    records: dict[str, dict] = {}
    for name, args in suite.items():
        # "/" in the suite name addresses a driver package outside
        # micro/ ("load/smoke" -> benchmarks/load/smoke.py).
        parts = name.split("/") if "/" in name else ["micro", name]
        path = os.path.join(REPO, "benchmarks", *parts) + ".py"
        cmd = [sys.executable, path, *[str(a) for a in args]]
        recs: list[dict] = []
        err = ""
        try:
            proc = subprocess.run(
                cmd,
                capture_output=True,
                text=True,
                timeout=timeout_s,
                cwd=REPO,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            # Multi-record contract: every parseable '{'-line is one
            # record (the load smoke gates two metrics from one run).
            for ln in proc.stdout.splitlines():
                ln = ln.strip()
                if ln.startswith("{"):
                    try:
                        recs.append(json.loads(ln))
                    except json.JSONDecodeError:
                        continue  # stray '{'-noise; keep scanning
            if not recs:
                err = (proc.stderr or proc.stdout or "").strip()[-300:]
        except subprocess.TimeoutExpired:
            err = f"driver timed out after {timeout_s:.0f}s"
        if not recs:
            recs = [{"metric": name, "value": 0.0, "error": err}]
        for rec in recs:
            records[str(rec.get("metric", name))] = rec
    return records


def compare(
    records: dict[str, dict], baseline_metrics: dict[str, dict]
) -> list[str]:
    """One line per regressed metric (empty = gate passes). ``records``
    maps metric name -> the driver's record (only ``value`` and an
    optional ``error`` are consulted)."""
    regressions: list[str] = []
    for metric in sorted(baseline_metrics):
        spec = baseline_metrics[metric]
        direction = spec.get("direction", "higher_better")
        if direction not in _DIRECTIONS:
            raise ValueError(
                f"{metric}: direction={direction!r}, expected one of "
                f"{_DIRECTIONS}"
            )
        rec = records.get(metric)
        if rec is None:
            # A crashed/hung driver is keyed by its DRIVER name (its
            # metric name was never printed): surface the captured
            # error text instead of a bare "missing".
            errs = "; ".join(
                f"{k}: {r['error']}"
                for k, r in sorted(records.items())
                if r.get("error") and k not in baseline_metrics
            )
            detail = f" (driver errors: {errs})" if errs else (
                " (gated metrics must be measured)"
            )
            regressions.append(
                f"{metric}: missing from the current run{detail}"
            )
            continue
        if rec.get("error"):
            regressions.append(f"{metric}: driver error: {rec['error']}")
            continue
        value = float(rec.get("value", 0.0))
        base = float(spec["value"])
        slack = float(spec.get("abs_tol", 0.0)) + abs(base) * float(
            spec.get("rel_tol", 0.0)
        )
        worse = (base - value) if direction == "higher_better" else (
            value - base
        )
        if worse > slack:
            regressions.append(
                f"{metric}: {value:g} vs baseline {base:g} "
                f"({direction}: worse by {worse:.4g} > tolerance "
                f"{slack:.4g})"
            )
    return regressions


def write_baseline(
    path: str, records: dict[str, dict], old: dict
) -> None:
    """Re-baseline from the current run: measured values replace the old
    ones, per-metric direction/tolerances (and the suite) carry over."""
    metrics = {}
    for metric, spec in old.get("metrics", {}).items():
        rec = records.get(metric)
        new_spec = dict(spec)
        if rec is not None and not rec.get("error"):
            new_spec["value"] = rec.get("value", spec["value"])
        metrics[metric] = new_spec
    out = {
        "description": old.get("description", "perf-regression baseline"),
        "suite": old.get("suite", {}),
        "metrics": metrics,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")


def main(argv: list[str] | None = None,
         records: dict[str, dict] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--baseline",
        default=os.path.join(REPO, "benchmarks", "baselines", "seed.json"),
        help="checked-in baseline JSON (suite + per-metric tolerances)",
    )
    p.add_argument(
        "--out", default=None,
        help="also write the full gate report JSON here",
    )
    p.add_argument(
        "--write-baseline", default=None, metavar="PATH",
        help="write a re-baselined file from this run's values "
        "(tolerances carried over) — the gate still runs",
    )
    args = p.parse_args(argv)

    with open(args.baseline, encoding="utf-8") as f:
        baseline = json.load(f)
    if records is None:
        records = run_suite(baseline.get("suite", {}))
    regressions = compare(records, baseline.get("metrics", {}))
    report = {
        "metric": "ci_gate_regressions",
        "value": float(len(regressions)),
        "unit": "regressed metrics",
        "vs_baseline": 0.0 - len(regressions),
        "ok": not regressions,
        "baseline": args.baseline,
        "regressions": regressions,
        "results": records,
    }
    print(json.dumps(report), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    if args.write_baseline:
        write_baseline(args.write_baseline, records, baseline)
    for line in regressions:
        print(f"REGRESSION: {line}", file=sys.stderr)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
