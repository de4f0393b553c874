"""Single-chip TPU throughput for the non-headline model families.

BASELINE.md configs reference ResNet-50 (headline, repo-root ``bench.py``)
plus ViT-B/16 and EfficientNet-B4; this driver measures those two on the
real chip with the same timed region as ``bench.py``
(``benchmarks.common.measure_scan_throughput``: on-device ``lax.scan``
with a data-dependent carry, timed around a host fetch). The parent
imports no JAX (the child owns the chip) and runs the measurement in a
subprocess under a hard timeout; one JSON line, non-zero exit when the
child failed.

Usage: ``python benchmarks/tpu_models.py --model vit_b16``
       ``python benchmarks/tpu_models.py --model efficientnet_b4``

vs_baseline compares against a single A100's framework-level fp16
throughput for the same model/batch (~1600 img/s ViT-B/16 bs=32,
~400 img/s EfficientNet-B4 bs=16 — same XLA/TF-class framing as
bench.py's ResNet-50 constant).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (  # noqa: E402  (imports no JAX)
    int_flag,
    run_child_json,
    str_flag,
)

TPU_V5E_PEAK_FLOPS = 197e12  # bf16
#: v5e HBM bandwidth — the MBU denominator (the same 819 GB/s the
#: decode-MBU model in benchmarks/README.md uses). The serving tier's
#: roofline gauges (`adapt_tpu.utils.profiling.ROOFLINE_PEAKS`) mirror
#: this pair; keep them in sync.
TPU_V5E_PEAK_HBM_BYTES_S = 8.19e11

#: model -> (batch, fwd FLOPs/image (mul+add as 2, matching bench.py's
#: ResNet convention of 8.2e9 = 2 x 4.1 GMACs), A100 img/s baseline);
#: input h/w come from the model registry.
#:
#: ViT-B/16: the widely-quoted "17.6 GFLOPs" is the MAC count (paper
#: convention). Derivation at S=197, d=768, mlp=3072, 12 layers:
#: per layer QKV 197*768*2304 = 348.6M + scores+AV 2*12*197*197*64 =
#: 59.6M + out 197*768*768 = 116.2M + MLP 2*197*768*3072 = 929.7M
#: ~= 1.454 GMACs; x12 + patch embed 196*768*768 ~= 17.57 GMACs
#: -> 35.2e9 FLOPs at mul+add-as-2. (Rounds 1-3 used 17.6e9 here and
#: under-reported ViT MFU 2x — the "0.293 MFU" in r03 artifacts is
#: really 0.59, in line with ResNet's 0.575 batch-sweep peak.)
#: EfficientNet-B4: 8.8e9 = 2 x 4.4 GMACs (the paper's "4.2B FLOPs"
#: is likewise a MAC count) — already on the right convention.
MODELS = {
    "vit_b16": (32, 35.2e9, 1600.0),
    "efficientnet_b4": (16, 8.8e9, 400.0),
}


def _child(
    model: str, batch: int, iters: int, trials: int, attn: str | None,
    resident: str | None,
) -> None:
    import jax
    import jax.numpy as jnp

    from adapt_tpu.models import MODEL_REGISTRY
    from benchmarks.common import measure_scan_throughput

    _, flops, a100 = MODELS[model]
    factory, (h, w, c) = MODEL_REGISTRY[model]
    kwargs = {"attn_prefer": attn} if attn else {}
    graph = factory(num_classes=1000, dtype=jnp.bfloat16, **kwargs)
    x0 = jax.random.normal(
        jax.random.PRNGKey(0), (batch, h, w, c), jnp.float32
    )
    images_per_sec, times = measure_scan_throughput(
        graph, x0, iters, trials,
        param_dtype="bfloat16" if resident == "bf16" else None,
    )
    record = {
        "metric": f"{model}_bs{batch}_images_per_sec_per_chip"
        + (f"_attn_{attn}" if attn else "")
        + (f"_res_{resident}" if resident else ""),
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / a100, 4),
        "baseline": f"single A100 fp16 ~{a100:.0f} img/s (framework-level)",
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "batch": batch,
        "iters": iters,
        "trials": trials,
        "trial_seconds": [round(t, 4) for t in times],
    }
    if record["platform"] != "cpu":
        record["mfu"] = round(images_per_sec * flops / TPU_V5E_PEAK_FLOPS, 4)
    print(json.dumps(record), flush=True)


def main() -> int:
    model = (
        sys.argv[sys.argv.index("--model") + 1]
        if "--model" in sys.argv
        else "vit_b16"
    )
    if model not in MODELS:
        print(json.dumps({"metric": f"{model}_images_per_sec_per_chip",
                          "value": 0.0, "unit": "images/sec",
                          "vs_baseline": 0.0,
                          "error": f"unknown model; have {sorted(MODELS)}"}))
        return 0
    default_batch = MODELS[model][0]
    batch = int_flag(sys.argv, "--batch", default_batch)
    iters = int_flag(sys.argv, "--iters", 50)
    trials = int_flag(sys.argv, "--trials", 5)
    # End-to-end attention A/B knob (vit only): force "pallas" or "xla";
    # default "" follows ops.attention's measured dispatch.
    attn = str_flag(sys.argv, "--attn", "", choices=("", "pallas", "xla"))
    # bf16-RESIDENT weights (vs flax's default f32 residency + per-use
    # cast): halves the weight bytes each iteration streams.
    resident = str_flag(sys.argv, "--resident", "", choices=("", "bf16"))
    if attn and model != "vit_b16":
        print(json.dumps({"metric": f"{model}_bs{batch}_images_per_sec_per_chip"
                                    f"_attn_{attn}",
                          "value": 0.0, "unit": "images/sec",
                          "vs_baseline": 0.0,
                          "error": "--attn applies only to vit_b16 "
                                   "(the other models have no attention)"}))
        return 0
    if "--child" in sys.argv:
        _child(model, batch, iters, trials, attn or None, resident or None)
        return 0

    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--model", model, "--batch", str(batch),
           "--iters", str(iters), "--trials", str(trials)]
    if attn:
        cmd += ["--attn", attn]
    if resident:
        cmd += ["--resident", resident]
    return run_child_json(
        cmd,
        # Same suffixes the child uses on success, so a failed A/B run
        # emits its error row under the A/B metric, never the baseline's.
        metric=f"{model}_bs{batch}_images_per_sec_per_chip"
        + (f"_attn_{attn}" if attn else "")
        + (f"_res_{resident}" if resident else ""),
        unit="images/sec",
        timeout_s=900,
    )


if __name__ == "__main__":
    sys.exit(main())
