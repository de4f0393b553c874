"""Overlap vs serial SPMD pipeline schedule on a virtual CPU mesh.

The overlap schedule (``parallel/pipeline_spmd.py``) issues each rank's
``collective_permute`` hop inside the same scan step as the NEXT
microbatch's compute, with no data dependency between the two — on TPU,
XLA turns that into an async collective-permute start/done pair running
concurrently with compute, hiding hop latency (each tick costs
max(compute, hop) instead of compute + hop; "On Optimizing the
Communication of Model Parallelism", PAPERS.md).

What CPU can and cannot validate: the CPU backend runs collectives
synchronously, so the wall-clock ratio here only tracks the schedule's
extra ticks (T = M + (P−1)(hop_buffers) vs M + P − 1) — the latency win
is the TPU run's to show. What CPU DOES settle: both schedules produce
BIT-IDENTICAL outputs on the same inputs (also pinned by
``tests/test_parallel.py``), so flipping ``PipelineConfig.schedule`` on
the chip is a pure perf knob.

One JSON line: value = serial/overlap wall-clock ratio (CPU; ≈1 or
slightly below is expected here), extra fields carry tick counts and the
bitwise-equality verdict.

Usage: ``python benchmarks/micro/hop_overlap.py [--ranks 4] [--micro 8]
[--dim 128] [--hop-buffers 2]``
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, force_cpu_mesh, int_flag  # noqa: E402


def main() -> int:
    ranks = int_flag(sys.argv, "--ranks", 4)
    num_micro = int_flag(sys.argv, "--micro", 8)
    dim = int_flag(sys.argv, "--dim", 128)
    hop_buffers = int_flag(sys.argv, "--hop-buffers", 2)
    try:
        force_cpu_mesh(max(ranks, 2))
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh

        from adapt_tpu.parallel.pipeline_spmd import (
            spmd_pipeline,
            stack_stage_params,
        )

        mesh = Mesh(np.array(jax.devices()[:ranks]), ("pp",))
        key = jax.random.PRNGKey(0)
        blocks = [
            jax.random.normal(jax.random.fold_in(key, i), (dim, dim))
            / np.sqrt(dim)
            for i in range(ranks)
        ]
        stacked = stack_stage_params(blocks)
        xs = jax.random.normal(
            jax.random.fold_in(key, 99), (num_micro, 16, dim)
        )

        def block_fn(p, x):
            return jnp.tanh(x @ p)

        def run(schedule):
            fn = jax.jit(
                lambda s, x: spmd_pipeline(
                    block_fn, s, x, mesh, schedule=schedule,
                    hop_buffers=hop_buffers,
                )
            )
            y = np.asarray(fn(stacked, xs))  # compile + warm
            t0 = time.perf_counter()
            trials = 10
            for i in range(trials):
                # a distinct input per trial
                y = np.asarray(fn(stacked, xs + i * 1e-6))
            return y, (time.perf_counter() - t0) / trials

        y_serial, t_serial = run("serial")
        y_overlap, t_overlap = run("overlap")
        bit_identical = bool(
            np.array_equal(y_serial, y_overlap)
        )
        emit(
            "micro_hop_overlap_speedup",
            t_serial / t_overlap,
            "serial/overlap wall ratio",
            t_serial / t_overlap,
            bit_identical=bit_identical,
            ranks=ranks,
            microbatches=num_micro,
            hop_buffers=hop_buffers,
            ticks_serial=num_micro + ranks - 1,
            ticks_overlap=num_micro + (ranks - 1) * hop_buffers,
            t_serial_ms=round(t_serial * 1e3, 3),
            t_overlap_ms=round(t_overlap * 1e3, 3),
            platform=jax.devices()[0].platform,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        emit("micro_hop_overlap_speedup", 0.0, "serial/overlap wall ratio",
             0.0, error=str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
