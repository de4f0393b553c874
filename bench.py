"""Headline benchmark: ResNet-50 inference images/sec on one TPU chip.

Reference metric (BASELINE.json): "images/sec/chip (ResNet-50, bs=32)".
The reference never published numbers (BASELINE.md); the baseline constant
here is a single NVIDIA A100's framework-level ResNet-50 fp16 inference
throughput at bs=32 (~3000 images/sec, XLA/TF-class stacks — TensorRT INT8
figures are far higher but not framework-comparable).

One process, one chip, no fallback: when JAX finds no TPU this exits
non-zero and prints no row — a CPU number under this metric's name is
worse than no number. Run it through the chip tool
(``python bench.py``); the compile cache lives where
``adapt_tpu.utils.compile_cache`` says.

Measurement: the timed region is ONE jitted program that runs ITERS
forward passes in a ``lax.scan``, each iteration's input carrying a data
dependency on the previous iteration's logits (a loop-invariant body
could be hoisted by XLA), so per-call dispatch is amortized away and the
number is the device's. Wall clock is taken around a host fetch of the
scalar result.

MFU is reported alongside: images/sec x ~8.2 GFLOP/image (ResNet-50 fwd,
multiply+add counted separately) over the chip's peak bf16 FLOP/s, looked
up by ``device_kind`` in ``utils.profiling.ROOFLINE_PEAKS``; a kind that
is not in the table is an error, not a default.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

A100_IMAGES_PER_SEC = 3000.0  # single-A100 fp16 bs32, framework-level
RESNET50_FLOPS_PER_IMAGE = 8.2e9  # fwd pass @224x224, mul+add as 2
BATCH = 32


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--stem", choices=("conv7", "s2d"), default="conv7")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--trials", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from adapt_tpu.models.resnet import resnet50
    from adapt_tpu.utils.compile_cache import ensure_compile_cache
    from adapt_tpu.utils.profiling import ROOFLINE_PEAKS
    from benchmarks.common import measure_scan_throughput

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"bench: no TPU — JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); this benchmark only reports from a chip",
            file=sys.stderr,
        )
        return 2
    peaks = ROOFLINE_PEAKS.get(dev.device_kind.lower())
    if peaks is None:
        print(
            f"bench: no published peak for device kind "
            f"{dev.device_kind!r} in utils.profiling.ROOFLINE_PEAKS; add "
            "its row (with its source) before reporting MFU",
            file=sys.stderr,
        )
        return 2
    cache_dir = ensure_compile_cache()

    graph = resnet50(num_classes=1000, dtype=jnp.bfloat16, stem=args.stem)
    x0 = jax.random.normal(
        jax.random.PRNGKey(0), (args.batch, 224, 224, 3), jnp.float32
    )
    images_per_sec, times = measure_scan_throughput(
        graph, x0, args.iters, args.trials
    )
    print(
        json.dumps(
            {
                # The headline metric name is the bs=32 contract;
                # off-headline sweep rows are labeled by their actual
                # batch (vs_baseline still divides by the bs=32 A100
                # constant).
                "metric": f"resnet50_bs{args.batch}_images_per_sec_per_chip"
                + ("" if args.stem == "conv7" else f"_{args.stem}"),
                "value": round(images_per_sec, 2),
                "unit": "images/sec",
                "vs_baseline": round(images_per_sec / A100_IMAGES_PER_SEC, 4),
                "baseline": (
                    "single A100 fp16 bs=32 ~3000 img/s (framework-level)"
                ),
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "device_count": len(devices),
                "batch": args.batch,
                "iters": args.iters,
                "trials": args.trials,
                "trial_seconds": [round(t, 4) for t in times],
                "mfu": round(
                    images_per_sec * RESNET50_FLOPS_PER_IMAGE / peaks[0], 4
                ),
                "compile_cache_dir": cache_dir,
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
