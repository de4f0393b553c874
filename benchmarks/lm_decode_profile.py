"""Capture a jax.profiler trace of the cached decode loop on the chip.

VERDICT r4 #4 workflow: the short-context decode row sits at MBU 0.43
(0.32 at 2k) against the benchmark's own HBM ceiling, and the gap cannot
be attributed without a trace — layout? cache copies in the scan carry?
the LM-head matmul? per-step sampling? This driver runs the exact
``lm_decode.py`` workload under ``jax.profiler.trace`` and commits the
trace directory beside the round's artifacts (the r03 committed-trace
precedent, ``results/r03/trace/``).

The traced region is ONE warm ``generate()`` call (prefill + steps-token
scan): compile happens before tracing starts, so the trace is pure
execution — per-op time in the scan body is then readable in
tensorboard/xprof, and the biggest op's share of step time IS the gap
accounting.

Prints one JSON line: value = traced decode tokens/sec (sanity vs the
lm_decode row), plus the trace path.

Usage: ``python benchmarks/lm_decode_profile.py [--batch 8] [--steps 128]
[--prompt 64] [--maxlen 256] [--kv native|int8] [--out DIR]``
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
    str_flag,
)

VOCAB, DIM, DEPTH, HEADS, MLP = 50257, 768, 12, 12, 3072


def _child(
    batch: int, steps: int, prompt_len: int, max_len: int, kv: str, out: str
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.transformer_lm import generate, transformer_lm

    lm = transformer_lm(
        VOCAB, DIM, DEPTH, HEADS, MLP, max_len=max_len, dtype=jnp.bfloat16
    )
    key = jax.random.PRNGKey(0)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, VOCAB)
    variables = jax.jit(lm.graph.init)(jax.random.PRNGKey(1), prompt)
    variables = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        variables,
    )
    kv_dtype = "int8" if kv == "int8" else "native"

    def run(p):
        return np.asarray(
            generate(lm, variables, p, steps, kv_cache_dtype=kv_dtype)
        )

    run(prompt)  # compile + warm OUTSIDE the trace
    os.makedirs(out, exist_ok=True)
    with jax.profiler.trace(out):
        t0 = time.perf_counter()
        run((prompt + 1) % VOCAB)  # distinct input
        dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"lm_decode_profile_bs{batch}_tokens_per_sec",
                "value": round(batch * steps / dt, 2),
                "unit": "tokens/sec",
                "vs_baseline": 1.0,
                "baseline": "sanity check vs the lm_decode row; the "
                "deliverable is the trace",
                "platform": jax.devices()[0].platform,
                "trace_dir": out,
                "config": f"prompt{prompt_len} steps{steps} "
                f"max_len{max_len} kv={kv_dtype}",
                "traced_s": round(dt, 4),
            }
        ),
        flush=True,
    )


def main() -> int:
    batch = int_flag(sys.argv, "--batch", 8)
    steps = int_flag(sys.argv, "--steps", 128)
    prompt_len = int_flag(sys.argv, "--prompt", 64)
    max_len = int_flag(sys.argv, "--maxlen", 256)
    kv = str_flag(sys.argv, "--kv", "native", choices=("native", "int8"))
    out = str_flag(sys.argv, "--out", out_path("trace_decode"))
    if "--child" in sys.argv:
        _child(batch, steps, prompt_len, max_len, kv, out)
        return 0
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--batch", str(batch), "--steps", str(steps),
           "--prompt", str(prompt_len), "--maxlen", str(max_len),
           "--kv", kv, "--out", out]
    return run_child_json(
        cmd,
        metric=f"lm_decode_profile_bs{batch}_tokens_per_sec",
        unit="tokens/sec",
        timeout_s=1500,
    )


if __name__ == "__main__":
    sys.exit(main())
