"""Flash-kernel vs XLA-attention crossover sweep on the real chip.

The ViT-B/16 re-measure after the ragged-sequence fix showed the Pallas
flash kernel LOSING to XLA's fused attention at seq 197 (1,762 vs
3,373 img/s end-to-end): at short sequences the S x S score matrix fits
in VMEM anyway, XLA emits one large batched matmul chain, and the flash
grid (batch*heads tiny programs, each re-DMAing full K/V) pays more in
program overhead than it saves in HBM traffic. The kernel's reason to
exist is long sequences — O(S*D) memory where XLA's materialized S x S
scores blow past VMEM.

This driver measures both paths at several sequence lengths on the real
TPU; together with the end-to-end A/B (``tpu_vit_b16_ab.json``) and the
long-sequence sweep (``attn_longseq.json``) it backs the dispatch in
``adapt_tpu.ops.attention`` (``FLASH_SCORE_BYTES_BUDGET`` +
``FLASH_MIN_SEQ`` guard). Perf-first dispatch, backed by artifacts
rather than folklore — at small shapes a standalone micro-timing is
dispatch-overhead-dominated and the END-TO-END A/B is the authority.

Usage: ``python benchmarks/attn_crossover.py --out chiprun_out/attn_crossover.json``
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
#: (batch, heads, seq, head_dim) — ViT-B/16-like width, seq swept from the
#: ViT shape into long-context territory. Batch shrinks as seq grows to
#: keep the working set sane.
SHAPES = [
    (32, 12, 197, 64),
    (32, 12, 256, 64),
    (16, 12, 512, 64),
    (8, 12, 1024, 64),
    (4, 12, 2048, 64),
    (2, 12, 4096, 64),
    (1, 12, 8192, 64),
]


def _child(out_path: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from adapt_tpu.ops.attention import _flash_impl, attention_reference

    def timed(fn, q, k, v, iters=20, trials=3):
        """Same honest timed region as bench.py: the iteration loop lives
        on-device in a lax.scan with a data-dependent carry, timed around
        a host fetch."""

        def body(c, _):
            o = fn(c, k, v)
            return c * 0.999 + (jnp.mean(o) * 1e-6).astype(c.dtype), ()

        run = jax.jit(lambda q: lax.scan(body, q, None, length=iters)[0])
        np.asarray(run(q))  # compile + warm
        times = []
        for t in range(trials):
            qt = q + (t + 1) * 1e-6
            t0 = time.perf_counter()
            np.asarray(run(qt))
            times.append(time.perf_counter() - t0)
        return statistics.median(times) / iters

    rows = []
    for b, h, s, d in SHAPES:
        key = jax.random.PRNGKey(0)
        q, k, v = (
            jax.random.normal(jax.random.fold_in(key, i), (b, h, s, d), jnp.bfloat16)
            for i in range(3)
        )
        row = {"batch": b, "heads": h, "seq": s, "head_dim": d}
        try:
            row["flash_ms"] = timed(
                lambda q_, k_, v_: _flash_impl(q_, k_, v_), q, k, v
            ) * 1e3
        except Exception as e:  # noqa: BLE001
            row["flash_error"] = str(e)[-200:]
        try:
            row["xla_ms"] = timed(attention_reference, q, k, v) * 1e3
        except Exception as e:  # noqa: BLE001
            row["xla_error"] = str(e)[-200:]
        if "flash_ms" in row and "xla_ms" in row:
            row["flash_speedup"] = round(row["xla_ms"] / row["flash_ms"], 3)
        rows.append(row)
        print(json.dumps(row), flush=True)

    artifact = {
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "rows": rows,
        "methodology": "on-device lax.scan (20 iters, data-dependent carry), "
        "median of 3 trials, timed around host fetch; bf16; "
        "_flash_impl called directly (bypasses the dispatch heuristic "
        "this sweep calibrates)",
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("--child", action="store_true")
    args = p.parse_args()
    if args.child:
        _child(args.out)
        return 0
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--out", args.out,
             "--child"],
            capture_output=True, text=True, timeout=1800, cwd=REPO,
        )
        sys.stdout.write(proc.stdout)
        if proc.returncode != 0:
            sys.stderr.write((proc.stderr or "")[-500:])
    except subprocess.TimeoutExpired:
        print(json.dumps({"error": "attn crossover sweep timed out"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
