"""Continuous batching vs batch-synchronous serving.

The workload that motivates continuous batching: requests with VARIED
decode lengths. Batch-synchronous serving (``generate()`` on a full
batch) runs every row to the longest request's end — short requests
occupy dead slots (the convoy effect). The ContinuousBatcher admits the
next request the moment a slot frees.

Measured: total emitted tokens / wall seconds for N requests with decode
lengths drawn round-robin from a short/long mix, served (a) through
``ContinuousBatcher(slots=B)`` and (b) as ceil(N/B) batch-synchronous
``generate()`` rounds padded to each round's longest request (tokens
counted = requested tokens only, both sides). ``vs_baseline`` =
continuous/batch-synchronous tokens-per-sec (>1 means the slot recycling
beats the convoy).

Artifact: results/<round>/continuous_serve.json. Runs on the real chip by
default; ``--cpu`` validates the schedule on the host backend (and is
what CI-grade environments can run). Honest caveat on the CPU number:
with the tiny validation model a decode step is microseconds of real
compute, so per-chunk dispatch overhead dominates and batch-synchronous
fused scans still win (measured 0.83x at chunk=16; 0.42x at chunk=8) —
the convoy-effect thesis is for serving-scale models where a step is
real milliseconds, which only the TPU run can settle.

Usage: ``python benchmarks/continuous_serve.py [--slots 8]
[--requests 32] [--cpu]``
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import int_flag, out_path  # noqa: E402  (no JAX)

VOCAB, DIM, DEPTH, HEADS, MLP = 50257, 768, 12, 12, 3072
PROMPT_LEN, MAX_LEN = 32, 256


def metric_name(slots: int) -> str:
    """ONE metric-name builder for parent and child (the parent's
    error-row metric on child failure must equal the child's success
    metric — same rule as lm_decode.metric_suffix). The ``_paged``
    suffix stays: rows without it in older artifacts are the dense
    per-slot layout, which left in PR 29."""
    return f"continuous_serve_slots{slots}_paged_tokens_per_sec"
STEP_MIX = (16, 96, 32, 128)  # short/long interleave — the convoy case
OUT = out_path("continuous_serve.json")


def _child(slots: int, n_requests: int, small: bool, chunk: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.transformer_lm import generate, transformer_lm
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    if small:  # CPU schedule validation: shrink the model, keep the mix
        lm = transformer_lm(512, 128, 4, 4, 512, max_len=MAX_LEN)
    else:
        lm = transformer_lm(
            VOCAB, DIM, DEPTH, HEADS, MLP, max_len=MAX_LEN,
            dtype=jnp.bfloat16,
        )
    key = jax.random.PRNGKey(0)
    vocab = lm.vocab
    prompts = [
        np.asarray(
            jax.random.randint(
                jax.random.fold_in(key, i), (PROMPT_LEN,), 0, vocab
            )
        )
        for i in range(n_requests)
    ]
    steps = [STEP_MIX[i % len(STEP_MIX)] for i in range(n_requests)]
    variables = jax.jit(lm.graph.init)(
        jax.random.PRNGKey(1), jnp.asarray(prompts[0])[None]
    )
    total_tokens = sum(steps)

    # -- continuous ------------------------------------------------------
    # The page-pool cache + scalar-prefetch kernels at a worst-case
    # pool (capacity sizing is a separate knob): at this workload's
    # geometry (max_len 256, page 128) every request needs its full 2
    # pages.
    bat = ContinuousBatcher(
        lm, variables, slots=slots, chunk=chunk, page_size=128
    )
    cache_bytes = bat.stats()["cache_bytes"]
    # Warm the compiled pieces (bucket prefill + step) out of the timed
    # region, mirroring generate()'s warmup below.
    bat.submit(prompts[0], 2)
    bat.run()  # drains the warmup request; timed run starts empty
    prefill_tokens0 = bat.stats()["prefill_tokens"]
    t0 = time.perf_counter()
    for p, s in zip(prompts, steps):
        bat.submit(p, s)
    done = bat.run()
    cont_s = time.perf_counter() - t0
    assert len(done) == n_requests
    # Prefill/decode split: the headline tokens/sec blends decode
    # tokens over a wall that includes prefill work — these two fields
    # separate the rates (exactly the ratio disaggregated serving
    # changes; see docs/SERVING.md "Disaggregated prefill/decode").
    prefill_tokens = bat.stats()["prefill_tokens"] - prefill_tokens0

    # -- batch-synchronous rounds ---------------------------------------
    batch0 = jnp.stack([jnp.asarray(p) for p in prompts[:slots]])
    np.asarray(generate(lm, variables, batch0, 2))  # warm
    t0 = time.perf_counter()
    for lo in range(0, n_requests, slots):
        round_idxs = list(range(lo, min(lo + slots, n_requests)))
        batch = jnp.stack([jnp.asarray(prompts[i]) for i in round_idxs])
        np.asarray(
            generate(
                lm, variables, batch, max(steps[i] for i in round_idxs)
            )
        )
    sync_s = time.perf_counter() - t0

    cont_tps = total_tokens / cont_s
    sync_tps = total_tokens / sync_s
    print(
        json.dumps(
            {
                "metric": metric_name(slots),
                "value": round(cont_tps, 2),
                "unit": "tokens/sec",
                "vs_baseline": round(cont_tps / sync_tps, 4),
                "baseline": "batch-synchronous generate() rounds on the "
                f"same workload ({sync_tps:.1f} tok/s useful tokens; "
                "rounds pad to their longest request)",
                "platform": jax.devices()[0].platform,
                "requests": n_requests,
                "slots": slots,
                "chunk": chunk,
                "kv_layout": "paged",
                "cache_bytes": cache_bytes,
                "step_mix": list(STEP_MIX),
                "continuous_s": round(cont_s, 3),
                "batch_sync_s": round(sync_s, 3),
                "prefill_tokens_per_sec": round(
                    prefill_tokens / cont_s, 2
                ),
                "decode_tokens_per_sec": round(
                    total_tokens / cont_s, 2
                ),
            }
        ),
        flush=True,
    )


def main() -> int:
    slots = int_flag(sys.argv, "--slots", 8)
    n_requests = int_flag(sys.argv, "--requests", 32)
    chunk = int_flag(sys.argv, "--chunk", 8)
    cpu = "--cpu" in sys.argv
    if "--child" in sys.argv:
        _child(slots, n_requests, cpu, chunk)
        return 0
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    metric = metric_name(slots)
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--slots", str(slots), "--requests", str(n_requests),
           "--chunk", str(chunk)]
    if cpu:
        cmd.append("--cpu")
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=2400, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        record = None
        for ln in proc.stdout.splitlines():
            if ln.strip().startswith("{"):
                try:
                    record = json.loads(ln)
                    break
                except json.JSONDecodeError:
                    continue
        if proc.returncode != 0 or record is None:
            record = {"metric": metric, "value": 0.0, "unit": "tokens/sec",
                      "vs_baseline": 0.0,
                      "error": (proc.stderr or proc.stdout or "")[-300:]}
        elif not cpu and record.get("platform") == "cpu":
            record = {"metric": metric, "value": 0.0, "unit": "tokens/sec",
                      "vs_baseline": 0.0,
                      "error": "TPU run fell back to the CPU backend"}
    except subprocess.TimeoutExpired:
        record = {"metric": metric, "value": 0.0, "unit": "tokens/sec",
                  "vs_baseline": 0.0, "error": "child timed out"}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    # Append (JSONL, one row per run) like speculative_decode.py: a
    # failed attempt must land BESIDE earlier measurements, never
    # clobber them.
    mode = "a" if os.path.exists(OUT) else "w"
    with open(OUT, mode) as f:
        json.dump(record, f)
        f.write("\n")
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
