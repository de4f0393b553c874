"""Decode-MBU gap accounting by ablation (VERDICT r4 #4).

``lm_decode.py`` reports MBU against a THEORETICAL ceiling (all param +
cache bytes at the 819 GB/s spec sheet number). This driver decomposes
the gap with four measured scans at the same GPT-2-small widths, each a
``lax.scan`` whose carry forces every step to re-stream its weights:

  stream    vector@matrix over EVERY weight matrix, nothing else — the
            measured ACHIEVABLE streaming bandwidth of this chip for
            decode-shaped (skinny) matmuls. spec/stream is the part of
            the "gap" that is the spec sheet, not the program.
  mlp       the 12 blocks' MLP matmuls only (fc + proj per block)
  attn      qkv/out projections + cached attention over a max_len cache
  head      final (b,1,d) @ (d,V) logits projection only

Accounting: if step_time(full) ~= step_time(mlp) + step_time(attn) +
step_time(head) (each measured alone), the loop is bandwidth-additive
and the gap vs the stream row is per-op efficiency; a large
super-additive residual means scheduling/fusion overhead between
components. Every variant reports its own bytes and achieved GB/s, so
the artifact directly names where the 0.43 went.

One JSON line; vs_baseline = full-model achieved GB/s / stream-test
achieved GB/s (how close the real decode loop gets to what the chip
demonstrably sustains).

Usage: ``python benchmarks/lm_decode_ablate.py [--batch 8] [--steps 64]
[--maxlen 256] [--trials 3]``
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import int_flag, run_child_json  # noqa: E402

VOCAB, DIM, DEPTH, HEADS, MLP = 50257, 768, 12, 12, 3072
TPU_V5E_HBM_BYTES_PER_S = 819e9


def _child(batch: int, steps: int, max_len: int, trials: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    key = jax.random.PRNGKey(0)
    hd = DIM // HEADS

    def mk(*shape):
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.normal(sub, shape, jnp.bfloat16) * 0.02

    blocks = [
        {
            "qkv": mk(DIM, 3 * DIM),
            "out": mk(DIM, DIM),
            "fc": mk(DIM, MLP),
            "proj": mk(MLP, DIM),
        }
        for _ in range(DEPTH)
    ]
    w_head = mk(DIM, VOCAB)
    w_embed = mk(VOCAB, DIM)
    caches = [
        (mk(batch, HEADS, max_len, hd), mk(batch, HEADS, max_len, hd))
        for _ in range(DEPTH)
    ]

    def bytes_of(tree):
        return sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
        )

    # -- variants: each a (carry x) -> (carry', token-ish scalar) step ----
    def step_mlp(x):
        for b in blocks:
            h = jax.nn.gelu(x @ b["fc"])
            x = x + h @ b["proj"]
        return x

    def step_attn(x, index):
        for b, (ck, cv) in zip(blocks, caches):
            qkv = x @ b["qkv"]  # (B, 1, 3D)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(batch, 1, HEADS, hd).transpose(0, 2, 1, 3)
            # bf16 operands + f32 accumulation via preferred_element_type:
            # an .astype(f32) on the loop-invariant cache would be HOISTED
            # by XLA into a materialized f32 copy, silently doubling the
            # bytes each step streams vs what the row is charged.
            s = jnp.einsum(
                "bhqd,bhkd->bhqk", q, ck,
                preferred_element_type=jnp.float32,
            ) / np.sqrt(hd)
            mask = jnp.arange(max_len) <= index
            s = jnp.where(mask[None, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
            o = jnp.einsum(
                "bhqk,bhkd->bhqd", p, cv,
                preferred_element_type=jnp.float32,
            ).astype(jnp.bfloat16)
            o = o.transpose(0, 2, 1, 3).reshape(batch, 1, DIM)
            x = x + o @ b["out"]
        return x

    def _logits(x):
        # bf16 matmul, f32 accumulate — same convert-hoisting hazard as
        # the cache above (w_head is 77 MB; an f32 copy would be 154).
        return jax.lax.dot_general(
            x, w_head, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (B, 1, V) f32

    def step_head(x):
        # Consume EVERY logits column (a [..., :DIM] slice would let
        # XLA rewrite slice(dot) into a dot over 1.5% of w_head and
        # fake a 60x-faster head).
        m = _logits(x).max(axis=-1, keepdims=True)  # (B, 1, 1)
        return x * jnp.bfloat16(0.5) + m.astype(jnp.bfloat16) * 1e-9

    def step_full(x, index):
        x = step_attn(x, index)
        x = step_mlp(x)
        tok = jnp.argmax(_logits(x), axis=-1)  # (B, 1)
        # Re-embed the argmax: the real loop's token->embedding data
        # dependency, defeating cross-step pipelining XLA couldn't do
        # for the real model either.
        return x * 0.5 + w_embed[tok[:, 0]][:, None, :].astype(jnp.bfloat16)

    def step_stream(v):
        # v: (DIM,) carry. One skinny matmul per weight matrix: the
        # chip streams every byte, compute is negligible, and the carry
        # dependency defeats hoisting.
        acc = jnp.zeros((), jnp.float32)
        for b in blocks:
            for w in b.values():
                acc = acc + (v @ w.reshape(DIM, -1).astype(jnp.bfloat16))[
                    0
                ].astype(jnp.float32)
        acc = acc + (v @ w_head)[0].astype(jnp.float32)
        acc = acc + (v @ w_embed.T.reshape(DIM, -1))[0].astype(jnp.float32)
        return v * jnp.bfloat16(0.999) + acc.astype(jnp.bfloat16) * 1e-9

    x0 = mk(batch, 1, DIM)
    v0 = mk(DIM)

    # Each variant is jitted as a function of its INITIAL carry so
    # trials can perturb the input (as lm_decode.py's timed() does).
    variants = {}
    variants["stream"] = (
        lambda init: lax.scan(
            lambda c, _: (step_stream(c), ()), init, None, length=steps
        )[0],
        v0,
        bytes_of((blocks, w_head, w_embed)),
    )
    variants["mlp"] = (
        lambda init: lax.scan(
            lambda c, _: (step_mlp(c), ()), init, None, length=steps
        )[0],
        x0,
        bytes_of([(b["fc"], b["proj"]) for b in blocks]),
    )
    variants["attn"] = (
        lambda init: lax.scan(
            lambda c, i: (step_attn(c, i), ()),
            init,
            jnp.arange(steps),
        )[0],
        x0,
        bytes_of([(b["qkv"], b["out"]) for b in blocks])
        + bytes_of(caches),
    )
    variants["head"] = (
        lambda init: lax.scan(
            lambda c, _: (step_head(c), ()), init, None, length=steps
        )[0],
        x0,
        bytes_of(w_head),
    )
    variants["full"] = (
        lambda init: lax.scan(
            lambda c, i: (step_full(c, i), ()), init, jnp.arange(steps)
        )[0],
        x0,
        # w_embed is read one GATHERED row per batch element per step,
        # not wholesale — charging the full 77 MB table would overstate
        # the achieved bandwidth ~20%.
        bytes_of((blocks, w_head))
        + bytes_of(caches)
        + batch * DIM * 2,
    )

    rows = {}
    for name, (fn, init, nbytes) in variants.items():
        jfn = jax.jit(fn)
        np.asarray(jfn(init))  # compile + warm
        times = []
        for t in range(trials):
            perturbed = init + jnp.bfloat16(1e-6 * (t + 1))
            t0 = time.perf_counter()
            np.asarray(jfn(perturbed))
            times.append(time.perf_counter() - t0)
        per_step = statistics.median(times) / steps
        rows[name] = {
            "ms_per_step": round(per_step * 1e3, 4),
            "bytes_per_step": nbytes,
            "achieved_gb_s": round(nbytes / per_step / 1e9, 1),
            "mbu_vs_spec": round(
                (nbytes / per_step) / TPU_V5E_HBM_BYTES_PER_S, 4
            ),
        }

    parts = sum(rows[k]["ms_per_step"] for k in ("mlp", "attn", "head"))
    rows["additivity"] = {
        "parts_ms": round(parts, 4),
        "full_ms": rows["full"]["ms_per_step"],
        # >0: scheduling/fusion overhead beyond the parts; <0: fusion
        # across components actually HELPS the full program.
        "residual_ms": round(rows["full"]["ms_per_step"] - parts, 4),
    }
    print(
        json.dumps(
            {
                "metric": f"lm_decode_ablate_bs{batch}_full_vs_stream",
                "value": rows["full"]["achieved_gb_s"],
                "unit": "GB/s",
                "vs_baseline": round(
                    rows["full"]["achieved_gb_s"]
                    / max(rows["stream"]["achieved_gb_s"], 1e-9),
                    4,
                ),
                "baseline": "the stream variant's measured achievable "
                f"bandwidth ({rows['stream']['achieved_gb_s']} GB/s; "
                "spec sheet 819)",
                "platform": jax.devices()[0].platform,
                "batch": batch,
                "steps": steps,
                "max_len": max_len,
                "rows": rows,
            }
        ),
        flush=True,
    )


def main() -> int:
    batch = int_flag(sys.argv, "--batch", 8)
    steps = int_flag(sys.argv, "--steps", 64)
    max_len = int_flag(sys.argv, "--maxlen", 256)
    trials = int_flag(sys.argv, "--trials", 3)
    if "--child" in sys.argv:
        _child(batch, steps, max_len, trials)
        return 0
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--batch", str(batch), "--steps", str(steps),
           "--maxlen", str(max_len), "--trials", str(trials)]
    return run_child_json(
        cmd,
        metric=f"lm_decode_ablate_bs{batch}_full_vs_stream",
        unit="GB/s",
        timeout_s=1800,
    )


if __name__ == "__main__":
    sys.exit(main())
