"""Decoder LM generation throughput on the real chip.

Beyond the reference's CNN configs (BASELINE.md): tokens/sec for the
KV-cache ``generate()`` loop of ``models/transformer_lm`` at a
GPT-2-small-ish width. ``vs_baseline`` is the model-bandwidth-utilization
(MBU): measured decode steps/sec divided by the bandwidth-bound ceiling
(HBM bytes/sec over bf16 param bytes — each decode step must stream every
weight once), the standard honesty metric for decode throughput.

Parent imports no JAX (the child owns the chip), child runs under a hard
timeout, exactly one JSON line. The decode loop lives on-device (scan),
timed around a host fetch, with a distinct prompt per trial.

``--kv int8`` runs the same measurement with the quantized KV cache
(``kv_cache_dtype="int8"``), the A/B that settles whether the cache
bandwidth claim (~2x fewer cache bytes than the native bf16 cache)
survives XLA's fusion of the dequant — measure at a long context
(``--prompt 1024 --maxlen 2048``) where cache traffic rivals weight
traffic, or the weights term hides the difference. The MBU denominator
counts weight bytes + per-step mean cache bytes actually resident, so
vs_baseline stays honest across cache dtypes.

``--decode-attn pallas`` swaps the per-step attention for the streaming
Pallas decode kernel (``ops/decode_attention``), which dequantizes int8
caches in VMEM — the A/B that decides ``decode_kernel_wins``'s measured
dispatch rule.

Usage: ``python benchmarks/lm_decode.py [--batch 8] [--steps 128]
[--prompt 64] [--maxlen 256] [--kv native|int8]
[--decode-attn auto|xla|pallas]``
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (  # noqa: E402  (imports no JAX)
    int_flag,
    run_child_json,
    str_flag,
)

VOCAB, DIM, DEPTH, HEADS, MLP = 50257, 768, 12, 12, 3072
TPU_V5E_HBM_BYTES_PER_S = 819e9


def metric_suffix(kv: str, decode_attn: str, moe: int, window: int) -> str:
    """ONE metric-name builder for parent and child: the parent's
    error-row metric (on child failure) must equal the child's
    success-row metric or A/B rows fork across keys."""
    s = "_kv_int8" if kv == "int8" else ""
    if decode_attn != "auto":
        s += f"_attn_{decode_attn}"
    if moe > 0:
        s += f"_moe{moe}"
    if window > 0:
        s += f"_win{window}"
    return s


def _child(
    batch: int, steps: int, trials: int, prompt_len: int, max_len: int,
    kv: str, decode_attn: str, moe: int, window: int,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.transformer_lm import generate, transformer_lm

    # --moe E swaps every block's MLP for a dropless top-2 mixture of E
    # experts (models/moe.MoEDecoderMlp). Single chip = the dense-EP
    # degenerate case: every step streams ALL expert weights, so
    # param_bytes (and the MBU ceiling) below scale with E
    # automatically — the honest single-chip MoE number; the E/ep
    # division shows up only on a real ep mesh.
    # --window W bands attention Mistral-style: decode masks (and with
    # the Pallas decode path, compute-SKIPS) everything behind the
    # window — the A/B against the full-attention row shows what the
    # serving path buys at long context.
    lm = transformer_lm(
        VOCAB, DIM, DEPTH, HEADS, MLP, max_len=max_len,
        dtype=jnp.bfloat16,
        moe_experts=moe if moe > 0 else None,
        moe_top_k=2 if moe > 0 else 1,
        window=window if window > 0 else None,
    )
    key = jax.random.PRNGKey(0)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, VOCAB)
    variables = jax.jit(lm.graph.init)(jax.random.PRNGKey(1), prompt)
    # Serving weights are bf16-resident (decode is bandwidth-bound; f32
    # residency would double the bytes every step streams). param_bytes
    # below counts actual itemsize, so the MBU denominator follows.
    variables = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if x.dtype == jnp.float32
        else x,
        variables,
    )

    def timed(fn, *args, trials=trials):
        np.asarray(fn(*args))  # compile + warm
        times = []
        for t in range(trials):
            p = (args[0] + t + 1) % VOCAB  # distinct prompt per trial
            t0 = time.perf_counter()
            np.asarray(fn(p, *args[1:]))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    kv_dtype = "int8" if kv == "int8" else "native"
    attn = None if decode_attn == "auto" else decode_attn
    if attn == "pallas":
        # Ask the op itself (ONE source of truth for eligibility — a
        # re-encoded literal here drifted once already): decode_attention
        # silently serves the oracle when the cache length is not
        # kernel-eligible, and an A/B row labeled `_attn_pallas` that
        # actually measured XLA would corrupt the measured dispatch rule.
        from adapt_tpu.ops.decode_attention import (
            _supported,
            default_block_k,
        )

        q8 = kv_dtype == "int8"
        if not _supported(max_len, default_block_k(max_len, q8), q8):
            raise SystemExit(
                f"--decode-attn pallas: maxlen {max_len} with "
                f"kv={kv_dtype} is not kernel-eligible (native needs "
                "%256==0, int8 %1024==0): the kernel would fall back "
                "to XLA and the artifact label would lie"
            )
    cached_s = timed(
        lambda p: generate(
            lm, variables, p, steps, kv_cache_dtype=kv_dtype,
            decode_attn=attn,
        ),
        prompt,
    )
    cached_tok_s = batch * steps / cached_s

    # Bandwidth-bound ceiling: every decode step streams all params once
    # PLUS the K+V cache entries. Counting actual itemsize keeps the
    # weight term honest whatever the residency above is set to; the
    # cache term follows the cache dtype (bf16 native here; int8 stores
    # 1 byte/elem + one f32 scale per vector), evaluated at the padded
    # cache length the decode attention actually streams every step.
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(variables)
    )
    head_dim = DIM // HEADS
    vec_bytes = (
        head_dim * 1 + 4 if kv_dtype == "int8" else head_dim * 2
    )  # per K or V vector
    # Sliding window: the IDEAL per-step cache traffic is the window,
    # not the buffer — the ceiling must reflect it or the windowed
    # pallas row (whose kernel really does skip dead blocks) reports an
    # inflated MBU while the XLA row (which streams the whole buffer)
    # hides its overhead. One window-bounded ceiling keeps both honest:
    # the kernel approaches it, the einsum path shows the gap.
    eff_len = min(max_len, window) if window > 0 else max_len
    cache_bytes = 2 * DEPTH * batch * HEADS * eff_len * vec_bytes
    ceiling_steps_s = TPU_V5E_HBM_BYTES_PER_S / (param_bytes + cache_bytes)
    mbu = (cached_tok_s / batch) / ceiling_steps_s

    suffix = metric_suffix(kv_dtype, decode_attn, moe, window)
    print(
        json.dumps(
            {
                "metric": f"lm_decode_bs{batch}_tokens_per_sec{suffix}",
                "value": round(cached_tok_s, 2),
                "unit": "tokens/sec",
                "vs_baseline": round(mbu, 4),
                "baseline": "vs_baseline is MBU: measured decode steps/s "
                f"over the HBM-bandwidth ceiling ({ceiling_steps_s:.0f} "
                "steps/s for these param+cache bytes at 819 GB/s)",
                "platform": jax.devices()[0].platform,
                "device": str(jax.devices()[0]),
                "config": f"vocab{VOCAB} d{DIM} L{DEPTH} h{HEADS} "
                f"prompt{prompt_len} steps{steps} max_len{max_len} bf16 "
                f"kv={kv_dtype}"
                + (f" moe{moe}top2" if moe > 0 else "")
                + (f" window{window}" if window > 0 else ""),
                "param_bytes": param_bytes,
                "kv_cache_bytes": cache_bytes,
                "cached_s_per_trial": round(cached_s, 4),
            }
        ),
        flush=True,
    )


def main() -> int:
    batch = int_flag(sys.argv, "--batch", 8)
    steps = int_flag(sys.argv, "--steps", 128)
    trials = int_flag(sys.argv, "--trials", 3)
    prompt_len = int_flag(sys.argv, "--prompt", 64)
    max_len = int_flag(sys.argv, "--maxlen", 256)
    kv = str_flag(sys.argv, "--kv", "native", choices=("native", "int8"))
    decode_attn = str_flag(
        sys.argv, "--decode-attn", "auto", choices=("auto", "xla", "pallas")
    )
    moe = int_flag(sys.argv, "--moe", 0)
    window = int_flag(sys.argv, "--window", 0)
    if "--child" in sys.argv:
        _child(batch, steps, trials, prompt_len, max_len, kv, decode_attn,
               moe, window)
        return 0
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--batch", str(batch), "--steps", str(steps),
           "--trials", str(trials), "--prompt", str(prompt_len),
           "--maxlen", str(max_len), "--kv", kv,
           "--decode-attn", decode_attn, "--moe", str(moe),
           "--window", str(window)]
    suffix = metric_suffix(kv, decode_attn, moe, window)
    return run_child_json(
        cmd,
        metric=f"lm_decode_bs{batch}_tokens_per_sec{suffix}",
        unit="tokens/sec",
        timeout_s=1500,
    )


if __name__ == "__main__":
    sys.exit(main())
