"""Partitioned-pipeline throughput drivers: BASELINE.md configs 2-4.

  --config resnet50-3stage    ResNet-50 cut at conv3_block1/conv4_block1
                              into 3 stages (config 2)
  --config resnet152-8stage   ResNet-152, 8 balanced stages, int8
                              activation quantization at every hop
                              (config 3, the zfpy-style codec)
  --config effnetb4-dag       EfficientNet-B4, 8 balanced stages through
                              the multi-branch DAG (config 4)

Runs on the virtual CPU mesh (one device per stage): a correctness and
control-flow run — its req/s are CPU walls, never device numbers (the
four-chip stage-tier number is ROADMAP B0's to take). vs_baseline is
streamed pipeline req/s over single-device req/s on the same backend —
the A/B the reference runs by hand (``test/test.py`` vs
``test/local_infer.py``). NOTE: virtual CPU devices share one host's
cores, so unlike real per-stage chips there is no extra compute to win;
~1.0 is the ceiling and the number reads as "throughput retained while
paying all stage-boundary costs" (values >1 mean the pipeline's
cross-device overlap beats single-program XLA parallelism on this host).

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, ".")  # repo root

from benchmarks.common import distinct_inputs, emit, force_cpu_mesh  # noqa: E402

REQUESTS = 12
BATCH = 1


def build(config: str):
    import jax.numpy as jnp

    from adapt_tpu.graph.partition import balanced_cuts

    if config == "resnet50-3stage":
        from adapt_tpu.models.resnet import resnet50

        graph = resnet50(num_classes=1000, dtype=jnp.float32)
        cuts = ["conv3_block1_out", "conv4_block1_out"]
        hop = None
    elif config == "resnet152-8stage":
        from adapt_tpu.models.resnet import resnet152

        graph = resnet152(num_classes=1000, dtype=jnp.float32)
        cuts = balanced_cuts(graph, 8)
        hop = _int8_hop()
    elif config == "effnetb4-dag":
        from adapt_tpu.models.efficientnet import efficientnet_b4

        graph = efficientnet_b4(num_classes=1000, dtype=jnp.float32)
        cuts = balanced_cuts(graph, 8)
        hop = None
    else:
        raise SystemExit(f"unknown --config {config!r}")
    return graph, cuts, hop


def _int8_hop():
    """Int8 quantization round-trip on every activation hop — what the
    reference pays with zfp+lz4 on every socket hop (``src/dispatcher.py:
    92-98``), expressed through the framework's own codec routing."""
    from adapt_tpu.config import CodecConfig
    from adapt_tpu.runtime.pipeline import codec_hop_transform

    return codec_hop_transform(CodecConfig(name="int8"))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="resnet50-3stage")
    parser.add_argument("--requests", type=int, default=REQUESTS)
    parser.add_argument("--batch", type=int, default=BATCH)
    args = parser.parse_args()

    graph, cuts, hop = build(args.config)
    n_stages = len(cuts) + 1
    force_cpu_mesh(n_stages)
    import jax
    import numpy as np

    from adapt_tpu.graph.partition import partition
    from adapt_tpu.runtime.pipeline import LocalPipeline

    hw = 380 if args.config == "effnetb4-dag" else 224
    x0 = jax.numpy.ones((args.batch, hw, hw, 3), jax.numpy.float32)
    variables = jax.jit(graph.init)(jax.random.PRNGKey(0), x0)
    plan = partition(graph, cuts)
    pipe = LocalPipeline(
        plan, variables, devices=jax.devices()[:n_stages], hop_transform=hop
    )
    pipe.warmup(x0)
    xs = distinct_inputs(jax.random.PRNGKey(7), x0.shape, args.requests)

    outputs, dt = pipe.throughput(xs)
    assert len(outputs) == args.requests
    np.asarray(outputs[-1])
    pipeline_req_s = args.requests / dt

    # Single-device denominator (reference test/local_infer.py semantics).
    full = jax.jit(graph.apply)
    dev0 = jax.devices()[0]
    v0 = jax.device_put(variables, dev0)
    np.asarray(full(v0, jax.device_put(xs[0], dev0)))
    t0 = time.perf_counter()
    for x in xs:
        y = full(v0, jax.device_put(x, dev0))
    np.asarray(y)
    single_req_s = args.requests / (time.perf_counter() - t0)

    emit(
        f"{args.config}_pipeline_req_per_s",
        pipeline_req_s,
        "req/s",
        pipeline_req_s / single_req_s,
    )


if __name__ == "__main__":
    main()
