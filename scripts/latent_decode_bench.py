#!/usr/bin/env python3
"""The latent DECODE kernel alone at ``xing4_longgen8k``'s shape: held
to the gather oracle over contexts that span ONE to EIGHT grid steps,
then timed.

    chiprun -- python3 scripts/latent_decode_bench.py [--seed N] [--out F]

One process, one chip. The cell's pool of one layer (7,937 pages of 128
positions, 576 values a position, bfloat16: 1.17 GB), 128 slots of 62
pages, 32 heads. The engine's correctness sample stops at 333 positions,
inside the first of a slot's grid steps (8 pages = 1,024 positions), and
the cell's timed traffic runs to 7,929: this is the comparison of the
steps past the first ON THE CHIP (tier 1 holds them in interpret mode,
``tests/test_mla_mhc.py``). Contexts are drawn like the traffic's (64 to
7,929), with rows forced onto a step's first and last position, the last
position a slot may hold and a dead row. Prints max|err| against
``latent_attention_reference`` by the number of steps a row's context
spans, then ms a call over six calls in one program and the
share of ``xing4_yardstick.latent_decode_cost``'s floor. Refuses to run
without a TPU; ``JAX_PLATFORMS=cpu ... --rehearse`` walks it small and
interpreted (its time means nothing).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

ROW, VALUES, PAGE = 576, 512, 128
SM_SCALE = 0.14468


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="8 slots of 24 pages, interpreted on the CPU")
    a = ap.parse_args()
    SLOTS, HEADS, PAGES_A_SLOT = (8, 4, 24) if a.rehearse else (128, 32, 62)
    # the cell's 6 layers in one program, as a decode step holds them
    LAYERS, ITERS = (2, 1) if a.rehearse else (6, 20)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.ops import latent_attention as la
    from chipbench import yardstick
    from chipbench.xing4_yardstick import latent_decode_cost

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        raise SystemExit("needs a TPU (or --rehearse under JAX_PLATFORMS=cpu)")
    print("device", dev.device_kind, flush=True)
    rng = np.random.default_rng(a.seed)
    pages = la.latent_pages_per_step(PAGES_A_SLOT, PAGE, ROW, 2)
    step = pages * PAGE
    last = PAGES_A_SLOT * PAGE - 1
    index = rng.integers(64, last - 6, SLOTS)
    index[:8] = [40, step - 1, step, 3 * step - 1, 3 * step,
                 last // step * step, last, -1]
    index = np.minimum(index, last)
    table = np.zeros((SLOTS, PAGES_A_SLOT), np.int32)
    free = iter(rng.permutation(SLOTS * PAGES_A_SLOT) + 1)
    for i, idx in enumerate(index):
        live = idx // PAGE + 1 if idx >= 0 else 0
        table[i, :live] = [next(free) for _ in range(live)]
    key = jax.random.PRNGKey(a.seed)
    pool = jax.random.normal(
        key, (SLOTS * PAGES_A_SLOT + 1, ROW, PAGE), jnp.bfloat16
    )
    pool = pool.at[0].set(1e4)  # the trash page: read by nobody
    # A layer's own queries: equal calls would be merged into one.
    qs = jax.random.normal(
        jax.random.fold_in(key, 1), (LAYERS, SLOTS, HEADS, ROW),
        jnp.bfloat16,
    )
    q = qs[0]
    table, idx = jnp.asarray(table), jnp.asarray(index, jnp.int32)

    got = np.asarray(la.latent_paged_attention(
        q, pool, table, idx, sm_scale=SM_SCALE, v_width=VALUES,
        prefer="pallas",
    ), np.float32)
    want = np.asarray(jax.jit(
        la.latent_attention_reference, static_argnums=(4, 5)
    )(q, pool, table, idx, SM_SCALE, VALUES), np.float32)
    err = np.abs(got - want).max(axis=(1, 2))
    spans = np.where(index >= 0, index // step + 1, 0)
    result = {"pages_per_step": pages, "seed": a.seed, "by_steps": {}}
    for n in sorted(set(spans.tolist())):
        rows = spans == n
        worst = float(err[rows].max())
        if n == 0:  # a dead row reads nothing and gets zeros
            worst = float(np.abs(got[rows]).max())
        result["by_steps"][n] = {"rows": int(rows.sum()), "max_err": worst}
        print(f"contexts over {n} step(s): {rows.sum():3d} rows, "
              f"max|err| {worst:.5f}", flush=True)
    scale = float(np.abs(want[spans > 0]).max())
    result["max_err"] = float(err[spans > 0].max())
    result["max_abs_output"] = scale
    print(f"max|err| {result['max_err']:.5f} of outputs to {scale:.3f} "
          f"(bfloat16 operands, float32 accumulation on both sides)")

    @jax.jit
    def layers(qs, pool, table, idx):
        out = 0.0
        for q in qs:
            out = out + la.latent_paged_attention(
                q, pool, table, idx, sm_scale=SM_SCALE, v_width=VALUES,
                prefer="pallas",
            )
        return out

    layers(qs, pool, table, idx).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = layers(qs, pool, table, idx)
    out.block_until_ready()
    ms = (time.perf_counter() - t0) / ITERS / LAYERS * 1e3
    context = int((index[index >= 0] + 1).sum())
    flops, nbytes = latent_decode_cost(
        context, int((index >= 0).sum()), HEADS, ROW, VALUES, 2
    )
    kind = "TPU v5e" if a.rehearse else dev.device_kind
    floor_ms = yardstick.floor_seconds(flops, nbytes, kind) * 1e3
    result.update(ms_a_call=ms, floor_ms=floor_ms, context_tokens=context)
    print(f"{ms:.3f} ms a call (host clock around {ITERS} programs of "
          f"{LAYERS} calls), floor {floor_ms:.3f} ms = "
          f"{100 * floor_ms / ms:.1f}% ; {context} cached positions read")
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
