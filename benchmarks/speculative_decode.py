"""Speculative decoding mechanism bounds on the real chip.

With UNTRAINED weights a draft's acceptance rate is meaningless (it is a
property of trained model pairs), so this driver brackets the MECHANISM
instead of claiming an end-task speedup:

- ``--draft self``: the target drafts for itself — acceptance 1.0 by
  construction, the upper bound: every round emits draft_k+1 tokens for
  one big-model weight stream (plus the draft cost, here equal to the
  target's). The interesting number is tokens/sec vs plain generate().
- ``--draft tiny``: an independent 2-layer draft — acceptance ~0 on
  random weights, the lower bound: one token per round plus pure
  overhead. How much slower than generate() this is = the price of
  mis-speculation.

A trained pair lands between the bounds in proportion to its acceptance.
vs_baseline = speculative/vanilla tokens-per-sec. Artifact:
results/r04/speculative_decode.json (appended per run).

CPU caveat: with the tiny ``--cpu`` validation model, timings are
dominated by XLA-CPU loop/dispatch overheads and can exaggerate (or
invert) ratios — this repo has measured such inversions before
(benchmarks/README "Attention dispatch" caveat). The CPU rows validate
losslessness and the schedule; the TPU rows are the perf evidence.

Usage: ``python benchmarks/speculative_decode.py [--draft self|tiny]
[--k 4] [--steps 128] [--cpu]``
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import int_flag, out_path, str_flag  # noqa: E402  (no JAX)

VOCAB, DIM, DEPTH, HEADS, MLP = 50257, 768, 12, 12, 3072
PROMPT_LEN, MAX_LEN = 32, 256
OUT = out_path("speculative_decode.json")


def _child(draft_kind: str, k: int, steps: int, small: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.speculative import speculative_generate
    from adapt_tpu.models.transformer_lm import generate, transformer_lm

    if small:
        lm = transformer_lm(512, 128, 4, 4, 512, max_len=MAX_LEN)
    else:
        lm = transformer_lm(
            VOCAB, DIM, DEPTH, HEADS, MLP, max_len=MAX_LEN,
            dtype=jnp.bfloat16,
        )
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (1, PROMPT_LEN), 0, lm.vocab
    )
    variables = jax.jit(lm.graph.init)(jax.random.PRNGKey(1), prompt)
    if draft_kind == "self":
        draft, dvars = lm, variables
    else:
        draft = transformer_lm(
            lm.vocab, 256, 2, 4, 1024, max_len=MAX_LEN, name="draft",
            dtype=jnp.bfloat16 if not small else jnp.float32,
        )
        dvars = jax.jit(draft.graph.init)(jax.random.PRNGKey(2), prompt)

    def timed(fn):
        fn(prompt)  # warm/compile
        t0 = time.perf_counter()
        out = fn((prompt + 1) % lm.vocab)
        return out, time.perf_counter() - t0

    van_out, van_s = timed(
        lambda p: np.asarray(generate(lm, variables, p, steps))
    )
    (spec_out, stats), spec_s = timed(
        lambda p: speculative_generate(
            lm, variables, p, steps, draft, dvars, draft_k=k,
            return_stats=True,
        )
    )
    # Losslessness holds exactly when the chunked verify and the
    # sequential decode produce bitwise-equal logits; XLA may reorder
    # bf16 reductions between the two shapes, so near-tie argmaxes can
    # flip on hardware. Report the count instead of crashing the
    # measurement — 0 is the expectation, nonzero is itself a finding.
    token_mismatches = int((van_out != spec_out).sum())
    van_tps = steps / van_s
    spec_tps = steps / spec_s
    print(
        json.dumps(
            {
                "metric": f"speculative_{draft_kind}_k{k}_tokens_per_sec",
                "value": round(spec_tps, 2),
                "unit": "tokens/sec",
                "vs_baseline": round(spec_tps / van_tps, 4),
                "baseline": f"plain generate() ({van_tps:.1f} tok/s); "
                "self-draft = acceptance-1.0 upper bound, tiny-draft = "
                "acceptance-0 overhead lower bound",
                "platform": jax.devices()[0].platform,
                "draft": draft_kind,
                "draft_k": k,
                "steps": steps,
                "acceptance": round(stats["acceptance"], 4),
                "rounds": stats["rounds"],
                "token_mismatches_vs_generate": token_mismatches,
            }
        ),
        flush=True,
    )


def main() -> int:
    draft_kind = str_flag(sys.argv, "--draft", "self", choices=("self", "tiny"))
    k = int_flag(sys.argv, "--k", 4)
    steps = int_flag(sys.argv, "--steps", 128)
    cpu = "--cpu" in sys.argv
    if "--child" in sys.argv:
        _child(draft_kind, k, steps, cpu)
        return 0
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    metric = f"speculative_{draft_kind}_k{k}_tokens_per_sec"
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--draft", draft_kind, "--k", str(k), "--steps", str(steps)]
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
    mode = "a" if os.path.exists(OUT) else "w"
    with open(OUT, mode) as f:
        json.dump(record, f)
        f.write("\n")
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
