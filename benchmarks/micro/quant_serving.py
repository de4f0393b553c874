"""Quantized KV serving across the dtype/mode grid: pool bytes ratio,
tick wall, h2d/tick, churn compiles.

The capacity claim int8 KV makes is STRUCTURAL, like tp_decode's: the
batcher's pools become ``(int8 values, f32 scales)`` pairs, so
resident cache bytes drop to ``(hd + 4) / (hd * native_itemsize)`` of
the native pool (0.3125 at f32/hd=16) whatever the traffic, and the
counter-based hot-path contracts must survive the composition. This
driver runs the full native/int8/int4 x plain/spec grid (one small model,
identical traffic; int4 packs two nibbles per int8 lane for
``(hd/2 + 4) / (hd * 4)`` = 0.1875 at f32/hd=16, gated as a second
record ``micro_quant_int4_kv_bytes_ratio`` <= 0.2) and reports per
config:

- ``<cfg>_kv_bytes`` — ``stats()["cache_bytes"]`` (scale planes
  INCLUDED — the honest number the memory.pool_bytes gauge serves);
- ``<cfg>_tick_ms`` — decode tick wall (CPU-noisy; the interpreter-mode
  attention oracle is the schedule-sanity number, not the TPU win);
- ``<cfg>_h2d_per_tick`` — the fused-staging contract under
  quantization: 0 per steady-state tick;
- per-config compile growth across churn (admit/retire/re-admit): the
  two-program steady state must hold over quantized caches.

Structural violations (h2d > 0, compile growth, int8 not actually
smaller, int8/native ratio off the analytic value) become ``error``
records the gate always fails. The headline ``value`` is the WORST
(largest) int8/native cache-bytes ratio across modes —
gated ``<= 0.55`` in ``benchmarks/baselines/seed.json`` (analytic:
0.3125 at f32/hd=16). A bf16-native model's ratio would be 0.625 and
fail the gate by design — the scale-plane overhead is relatively
larger there, so the baseline must be consciously re-measured, not
silently absorbed, if this driver's model ever goes bf16.

Usage: ``python benchmarks/micro/quant_serving.py [--ticks 4]``
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, int_flag  # noqa: E402


def _measure(bat, slots: int, n_ticks: int, steps: int):
    """Fill every slot, settle, measure N steady-state ticks."""
    import numpy as np

    rng = np.random.RandomState(0)
    for _ in range(slots):
        bat.submit(rng.randint(0, 61, size=6).astype(np.int32), steps)
    bat.tick()  # admissions
    bat.tick()  # settle
    h2d0 = bat.stats()["h2d_transfers"]
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        bat.tick()
    wall = time.perf_counter() - t0
    h2d = (bat.stats()["h2d_transfers"] - h2d0) / n_ticks
    return wall * 1e3 / n_ticks, h2d


def main() -> int:
    n_ticks = int_flag(sys.argv, "--ticks", 4)
    slots = 2
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from adapt_tpu.config import SpeculativeConfig
        from adapt_tpu.models.transformer_lm import transformer_lm
        from adapt_tpu.runtime.continuous import ContinuousBatcher
        from adapt_tpu.utils.profiling import global_compile_sentinel

        # Requests must OUTLIVE the measured window (a retirement
        # inside it is a legitimate +1 h2d row-clear, not a violation):
        # admission + settle + n_ticks measured ticks at chunk=8.
        steps = 8 * (n_ticks + 2) + 8
        lm = transformer_lm(61, 32, 2, 2, 64, max_len=steps + 16)
        variables = lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        sentinel = global_compile_sentinel()
        # This driver provokes legitimate compiles (8 batcher
        # instances, churn probes); assert the deltas explicitly,
        # disarm the alarm (the tp_decode rationale).
        sentinel.warmup_samples = 10**9
        errors: list[str] = []
        extras: dict = {}
        kv_bytes: dict[tuple, int] = {}
        for dtype in ("native", "int8", "int4"):
            for spec in (False, True):
                tag = f"paged_{dtype}{'_spec' if spec else ''}"
                kw: dict = dict(
                    kv_cache_dtype=dtype, chunk=8, page_size=16
                )
                prog = "continuous.step_chunk"
                if spec:
                    # Self-draft: perfect acceptance, no second
                    # model's compile bill — the quantization
                    # composition is what's measured here.
                    kw.update(
                        draft_lm=lm, draft_variables=variables,
                        speculative=SpeculativeConfig(draft_k=3),
                    )
                    prog = "continuous.spec_verify"
                bat = ContinuousBatcher(
                    lm, variables, slots=slots, **kw
                )
                tick_ms, h2d = _measure(bat, slots, n_ticks, steps)
                st = bat.stats()
                kv_bytes[(dtype, spec)] = st["cache_bytes"]
                extras[f"{tag}_kv_bytes"] = st["cache_bytes"]
                extras[f"{tag}_tick_ms"] = round(tick_ms, 3)
                extras[f"{tag}_h2d_per_tick"] = h2d
                if h2d != 0:
                    errors.append(f"{tag}: steady tick staged {h2d}")
                entries = sentinel.compiles(prog)
                bat.submit(np.arange(1, 6, dtype=np.int32), 4)
                bat.run()
                grew = sentinel.compiles(prog) - entries
                if grew:
                    errors.append(
                        f"{tag}: churn compiled {grew} variants"
                    )
                bat.close()
        ratios = []
        ratios4 = []
        for spec in (False, True):
            n = kv_bytes[("native", spec)]
            q = kv_bytes[("int8", spec)]
            q4 = kv_bytes[("int4", spec)]
            ratios.append(q / n)
            ratios4.append(q4 / n)
            if q >= n:
                errors.append(
                    f"{'spec' if spec else 'plain'}: int8 cache "
                    f"{q} not smaller than native {n}"
                )
            if q4 >= q:
                errors.append(
                    f"{'spec' if spec else 'plain'}: int4 cache "
                    f"{q4} not smaller than int8 {q}"
                )
        ratio = max(ratios)
        ratio4 = max(ratios4)
        extras["kv_bytes_ratio_min"] = round(min(ratios), 4)
        extras["int4_kv_bytes_ratio_min"] = round(min(ratios4), 4)
        if errors:
            emit(
                "micro_quant_kv_bytes_ratio", 1.0, "x", 0.0,
                error="; ".join(errors)[-300:], **extras,
            )
            emit(
                "micro_quant_int4_kv_bytes_ratio", 1.0, "x", 0.0,
                error="; ".join(errors)[-300:],
            )
            return 0
        emit(
            "micro_quant_kv_bytes_ratio",
            round(ratio, 4),
            "x",
            round(0.5 - ratio, 4),
            ticks=n_ticks,
            slots=slots,
            **extras,
        )
        # Second gated record: the int4 grid's worst per-slot KV bytes
        # ratio vs native (analytic (hd/2 + 4) / (hd * 4) = 0.1875 at
        # f32/hd=16; the ISSUE-12 capacity pin is <= 0.2).
        emit(
            "micro_quant_int4_kv_bytes_ratio",
            round(ratio4, 4),
            "x",
            round(0.2 - ratio4, 4),
            ticks=n_ticks,
            slots=slots,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        emit("micro_quant_kv_bytes_ratio", 1.0, "x", 0.0,
             error=str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
