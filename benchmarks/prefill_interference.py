"""Prefill interference: what a long admission does to running decodes.

The latency story behind chunked prefill (Sarathi-style): with
whole-prompt prefill, a running request's next tick stalls for the full
prompt forward when a long request admits; with ``prefill_chunk``, the
admission spreads over page-aligned chunk passes and the running
request keeps emitting between them. This driver measures PER-TICK
latency of a steady decode stream while long prompts arrive, for both
modes, and reports the p99 tick latency ratio (chunked / whole) — the
number that should drop well below 1 as prompt length grows.

Method: one long-running greedy request decodes through a paged
batcher; every ``gap`` ticks a long-prompt request is submitted. Tick
wall-times are recorded around ``bat.tick()`` (each tick = admission +
prefill work + one decode chunk). Same traffic, same model, two
batchers — only ``prefill_chunk`` differs.

One JSON line (the chunked mode's p99 tick seconds; ``vs_baseline`` =
whole-prompt p99 / chunked p99, >1 means chunking wins); a JSONL row
appends to ``results/r04/prefill_interference.json``. ``--cpu`` runs
the small validation model (dispatch overhead dominates there — the
TPU row is the evidence, same caveat as continuous_serve).

Usage: ``python benchmarks/prefill_interference.py [--long 1536]
[--chunk 256] [--cpu]``
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (  # noqa: E402  (imports no JAX)
    int_flag,
    out_path,
    run_child_json,
)

VOCAB, DIM, DEPTH, HEADS, MLP = 50257, 768, 12, 12, 3072
OUT = out_path("prefill_interference.json")


def _run_mode(ContinuousBatcher, np, lm, variables, long_len, n_long,
              gap, prefill_chunk, page):
    rng = np.random.RandomState(0)
    steady = rng.randint(0, lm.vocab, size=8).astype(np.int32)
    longs = [
        rng.randint(0, lm.vocab, size=long_len).astype(np.int32)
        for _ in range(n_long)
    ]
    bat = ContinuousBatcher(
        lm, variables, slots=4, chunk=4, kv_layout="paged",
        page_size=page, prefill_chunk=prefill_chunk,
    )
    # Warm every compiled piece (long-prefill variants + decode chunk)
    # untimed — with a DEDICATED prompt: warming with a timed prompt
    # would register its pages in the prefix cache and turn the timed
    # admission into a near-free hit.
    warm_p = rng.randint(0, lm.vocab, size=long_len).astype(np.int32)
    warm = bat.submit(warm_p, 2)
    bat.run()
    bat.submit(steady, 4000)
    bat.tick()
    ticks = []
    li = 0
    t_all0 = time.perf_counter()
    for i in range(n_long * gap + 24):
        if i % gap == 0 and li < n_long:
            bat.submit(longs[li], 8)
            li += 1
        t0 = time.perf_counter()
        bat.tick()
        ticks.append(time.perf_counter() - t0)
    total_s = time.perf_counter() - t_all0
    del warm
    ticks = sorted(ticks)
    p99 = ticks[min(len(ticks) - 1, int(0.99 * len(ticks)))]
    p50 = ticks[len(ticks) // 2]
    return {"p99_tick_s": p99, "p50_tick_s": p50, "total_s": total_s}


def _child(long_len: int, chunk: int, small: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.transformer_lm import transformer_lm
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    page = 128
    if small:
        page = 16
        lm = transformer_lm(512, 128, 4, 4, 512, max_len=4096)
    else:
        lm = transformer_lm(
            VOCAB, DIM, DEPTH, HEADS, MLP, max_len=4096,
            dtype=jnp.bfloat16,
        )
    variables = jax.jit(lm.graph.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )
    if not small:
        variables = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x,
            variables,
        )
    n_long, gap = 4, 12
    whole = _run_mode(ContinuousBatcher, np, lm, variables, long_len,
                      n_long, gap, None, page)
    chunked = _run_mode(ContinuousBatcher, np, lm, variables, long_len,
                        n_long, gap, chunk, page)
    print(
        json.dumps(
            {
                "metric": "prefill_interference_p99_tick_s",
                "value": round(chunked["p99_tick_s"], 5),
                "unit": "s",
                "vs_baseline": round(
                    whole["p99_tick_s"] / max(chunked["p99_tick_s"], 1e-9),
                    3,
                ),
                "baseline": "whole-prompt prefill p99 tick "
                f"({whole['p99_tick_s']:.5f}s; p50 "
                f"{whole['p50_tick_s']:.5f}s vs chunked p50 "
                f"{chunked['p50_tick_s']:.5f}s) — >1 means chunked "
                "prefill shields running decodes from long admissions",
                "platform": jax.devices()[0].platform,
                "long_prompt": long_len,
                "prefill_chunk": chunk,
                "whole": whole,
                "chunked": chunked,
            }
        ),
        flush=True,
    )


def main() -> int:
    long_len = int_flag(sys.argv, "--long", 1536)
    chunk = int_flag(sys.argv, "--chunk", 256)
    cpu = "--cpu" in sys.argv
    if "--child" in sys.argv:
        _child(long_len, chunk, cpu)
        return 0
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--long", str(long_len), "--chunk", str(chunk)]
    if cpu:
        cmd.append("--cpu")
    return run_child_json(
        cmd,
        metric="prefill_interference_p99_tick_s",
        unit="s",
        timeout_s=2400,
        env=env,
        allow_cpu=cpu,
        out_path=OUT,
    )


if __name__ == "__main__":
    sys.exit(main())
