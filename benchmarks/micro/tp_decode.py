"""Tensor-parallel serving on the sim mesh: per-device KV bytes + tick
wall time at tp = {1, 2, 4}.

The capacity claim TP serving makes is STRUCTURAL: the batcher's KV
pools shard on their head axis over the
mesh's ``tp`` axis, so each device holds exactly ``logical / tp`` bytes
— a model whose KV residency busts one chip's HBM fits a tp-group, and
like the other micro drivers that counter transfers to the TPU run
directly however noisy the CPU wall clock is. This driver builds the
same GQA model's batcher at tp=1/2/4 on the virtual CPU mesh
(``--xla_force_host_platform_device_count``), runs identical steady
traffic through each, and reports:

- ``tp{n}_kv_bytes_per_device`` — from ``stats()`` (and the
  ``memory.kv_bytes_per_device`` gauge path): MUST equal logical/n;
- ``tp{n}_tick_ms`` — decode tick wall time (honest but CPU-noisy: the
  sim mesh pays real collectives with none of the ICI overlap, so this
  is a schedule-sanity number, not the TPU win);
- ``tp{n}_h2d_per_tick`` — the PR-1 fused-staging contract under a
  mesh: 0 per steady-state tick;
- per-config compile growth across churn (admit/retire/re-admit): the
  two-program steady state must hold under GSPMD.

Structural violations (per-device bytes != logical/tp, h2d > 0, compile
growth) turn into an ``error`` record so ``benchmarks/ci_gate.py``
fails loud. The headline ``value`` is the tp1/tp4 per-device-bytes
ratio — exactly 4.0 when sharding lands (the gated metric in
``benchmarks/baselines/seed.json``).

Usage: ``python benchmarks/micro/tp_decode.py [--slots 4] [--ticks 8]``
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, force_cpu_mesh, int_flag  # noqa: E402

#: Devices the sim mesh needs (tp=4 is the largest config);
#: ``force_cpu_mesh`` provisions them (appending/upgrading the XLA flag
#: without clobbering inherited flags) and fails loudly if a too-small
#: backend was already initialized.
_NDEV = 4


def _measure(bat, slots: int, n_ticks: int, steps: int):
    """Fill every slot, settle, measure N steady-state ticks. Returns
    (tick_ms, h2d_per_tick, tokens)."""
    import numpy as np

    rng = np.random.RandomState(0)
    for _ in range(slots):
        bat.submit(rng.randint(0, 61, size=6).astype(np.int32), steps)
    bat.tick()  # admissions
    bat.tick()  # settle
    h2d0 = bat.stats()["h2d_transfers"]
    tok0 = sum(len(s.tokens) for s in bat.slots if s.req is not None)
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        bat.tick()
    wall = time.perf_counter() - t0
    tok1 = sum(len(s.tokens) for s in bat.slots if s.req is not None)
    h2d = (bat.stats()["h2d_transfers"] - h2d0) / n_ticks
    return wall * 1e3 / n_ticks, h2d, tok1 - tok0


def main() -> int:
    slots = int_flag(sys.argv, "--slots", 4)
    n_ticks = int_flag(sys.argv, "--ticks", 8)
    try:
        force_cpu_mesh(_NDEV)
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh

        from adapt_tpu.config import ParallelConfig
        from adapt_tpu.models.transformer_lm import transformer_lm
        from adapt_tpu.runtime.continuous import ContinuousBatcher
        from adapt_tpu.utils.profiling import global_compile_sentinel

        # GQA target whose kv_heads divide every tp config — the shape
        # class head-sharded serving exists for.
        lm = transformer_lm(61, 64, 2, 8, 128, max_len=128, kv_heads=4)
        variables = lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        sentinel = global_compile_sentinel()
        # This driver deliberately provokes legitimate compiles (three
        # batcher instances, a churn probe with fresh key-bucket and
        # retirement shapes) and asserts the deltas it cares about
        # EXPLICITLY via sentinel.compiles(). Disarm the recompile
        # ALARM for the whole run: with the default 8-sample warmup the
        # churn admissions land post-warmup and every honest run would
        # log "unexpected recompile" WARNINGs and bump
        # engine.compile_events — false positives for anyone alerting
        # on the PR4 telemetry.
        sentinel.warmup_samples = 10**9
        steps = n_ticks * 8 + 32  # outlive the measured window
        errors: list[str] = []
        extras: dict = {}
        kv_pd: dict[int, int] = {}
        for tp in (1, 2, 4):
            mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
            bat = ContinuousBatcher(
                lm, variables, slots=slots, chunk=8, mesh=mesh,
                parallel=ParallelConfig(tp=tp),
            )
            tick_ms, h2d, tokens = _measure(bat, slots, n_ticks, steps)
            st = bat.stats()
            kv_pd[tp] = st["cache_bytes_per_device"]
            extras[f"tp{tp}_kv_bytes_per_device"] = kv_pd[tp]
            extras[f"tp{tp}_tick_ms"] = round(tick_ms, 3)
            extras[f"tp{tp}_h2d_per_tick"] = h2d
            extras[f"tp{tp}_toks_per_tick"] = round(
                tokens / n_ticks, 2
            )
            if st["cache_bytes_per_device"] * tp != st["cache_bytes"]:
                errors.append(
                    f"tp{tp}: per-device bytes "
                    f"{st['cache_bytes_per_device']} * {tp} != logical "
                    f"{st['cache_bytes']}"
                )
            if h2d != 0:
                errors.append(f"tp{tp}: steady tick staged {h2d} h2d")
            # Churn must not grow the decode program: the two-program
            # steady state holds under GSPMD partitioning too.
            entries = sentinel.compiles("continuous.step_chunk")
            bat.submit(np.arange(1, 6, dtype=np.int32), 4)
            bat.run()
            grew = sentinel.compiles("continuous.step_chunk") - entries
            if grew:
                errors.append(f"tp{tp}: churn compiled {grew} variants")
            bat.close()
        extras["kv_bytes_logical"] = int(
            kv_pd[1]
        )  # tp=1 per-device == logical by construction
        ratio = kv_pd[1] / kv_pd[4]
        if errors:
            emit(
                "micro_tp_decode_kv_per_device_ratio", 0.0, "x", 0.0,
                error="; ".join(errors)[-300:], **extras,
            )
            return 0
        emit(
            "micro_tp_decode_kv_per_device_ratio",
            round(ratio, 4),
            "x",
            round(ratio - 1.0, 4),
            slots=slots,
            ticks=n_ticks,
            **extras,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        emit("micro_tp_decode_kv_per_device_ratio", 0.0, "x", 0.0,
             error=str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
