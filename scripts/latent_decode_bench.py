#!/usr/bin/env python3
"""The latent DECODE kernel alone at ``xing4_longgen8k``'s shape: held
to the gather oracle over contexts that span ONE to EIGHT iterations of
its walk, then timed.

    chiprun -- python3 scripts/latent_decode_bench.py [--seed N]
        [--contexts uniform,cell] [--pages 8,4] [--root DIR] [--out F]

One process, one chip. The cell's pool of one layer (7,937 pages of 128
positions, 576 values a position, bfloat16: 1.17 GB), 128 slots of 62
pages, 32 heads. The engine's correctness sample stops at 333 positions,
inside the first iteration of a slot's walk (8 pages = 1,024
positions), and the cell's timed traffic runs to 7,929: this is the
comparison of the iterations past the first ON THE CHIP (tier 1 holds
them in interpret mode, ``tests/test_mla_mhc.py``).

``--contexts`` names the draws, each measured in turn: ``uniform`` is
64 to 7,929 like one request's life, ``cell`` is the population that
stands in the cell's window (a request is in flight for as long as its
output is, so outputs are drawn in proportion to their length, at an
age uniform in it: mean ~2,950), the draw to compare with the cell's
own ``kernel.latent_decode_roofline``. Either has rows forced onto an
iteration's first and last position, the last position a slot may hold
and a dead row. Every page no slot owns, the trash page among them, is
NaN: a dead page that reaches a product shows in the result.

Prints what the draw asks of the walk (live and dead pages, and steps
of ``pages`` pages live and dead: the parent's grid took them all, the
walk takes the live ones), max|err| against
``latent_attention_reference`` by the number of iterations a row's
context spans, then ms a call over six calls in one program and the
share of ``xing4_yardstick.latent_decode_cost``'s floor. ``--pages``
times ``_latent_impl`` at other pages an iteration than the entry point
derives; ``--root`` imports ``adapt_tpu`` from another checkout (a ``git
archive`` of the parent in an ignored directory) and times its kernel
with this file's operands. Refuses to run without a TPU;
``JAX_PLATFORMS=cpu ... --rehearse`` walks it small and interpreted
(its time means nothing).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

ROW, VALUES, PAGE = 576, 512, 128
SM_SCALE = 0.14468
#: the cell's traffic (chipbench/traffic/longgen8k.json)
PROMPT, OUTPUT = (64, 256), (2048, 7936)


def draw_contexts(rng, how: str, slots: int, last: int):
    """Each slot's newest position under the draw ``how``."""
    if how == "uniform":
        return rng.integers(64, last - 6, slots)
    if how != "cell":
        raise SystemExit(f"--contexts {how}: expected uniform or cell")
    outs = rng.integers(OUTPUT[0], OUTPUT[1] + 1, 64 * slots)
    outs = rng.choice(outs, slots, p=outs / outs.sum())
    prompts = rng.integers(PROMPT[0], PROMPT[1] + 1, slots)
    return prompts + (rng.random(slots) * outs).astype(int)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--contexts", default="uniform,cell")
    ap.add_argument("--pages", default="",
                    help="pages an iteration to time besides the derived")
    ap.add_argument("--root", default=str(HERE))
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="8 slots of 24 pages, interpreted on the CPU")
    a = ap.parse_args()
    SLOTS, HEADS, PAGES_A_SLOT = (8, 4, 24) if a.rehearse else (128, 32, 62)
    # the cell's 6 layers in one program, as a decode step holds them
    LAYERS, ITERS = (2, 1) if a.rehearse else (6, 20)

    root = os.path.abspath(a.root)
    sys.path.insert(0, root)
    sys.path.insert(1, str(HERE))  # chipbench's yardstick
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import yardstick
    from chipbench.xing4_yardstick import latent_decode_cost

    la = importlib.import_module("adapt_tpu.ops.latent_attention")
    assert os.path.abspath(la.__file__).startswith(root), la.__file__
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        raise SystemExit("needs a TPU (or --rehearse under JAX_PLATFORMS=cpu)")
    print(json.dumps({"root": root, "device_kind": dev.device_kind}),
          flush=True)
    kind = "TPU v5e" if a.rehearse else dev.device_kind
    derived = la.latent_pages_per_step(PAGES_A_SLOT, PAGE, ROW, 2)
    sweep = [derived] + [int(p) for p in a.pages.split(",") if p]
    step = derived * PAGE
    last = PAGES_A_SLOT * PAGE - 1
    key = jax.random.PRNGKey(a.seed)
    # A layer's own queries: equal calls would be merged into one.
    qs = jax.random.normal(
        jax.random.fold_in(key, 1), (LAYERS, SLOTS, HEADS, ROW),
        jnp.bfloat16,
    )
    reference = jax.jit(la.latent_attention_reference, static_argnums=(4, 5))

    for how in a.contexts.split(","):
        rng = np.random.default_rng([a.seed, int(how == "cell")])
        index = draw_contexts(rng, how, SLOTS, last)
        index[:8] = [40, step - 1, step, 3 * step - 1, 3 * step,
                     last // step * step, last, -1]
        index = np.minimum(index, last)
        live = np.where(index >= 0, index // PAGE + 1, 0)
        table = np.zeros((SLOTS, PAGES_A_SLOT), np.int32)
        owned = rng.permutation(SLOTS * PAGES_A_SLOT)[:live.sum()] + 1
        at = np.concatenate([[0], np.cumsum(live)])
        for i, n in enumerate(live):
            table[i, :n] = owned[at[i]:at[i + 1]]
        pool = jax.random.normal(
            key, (SLOTS * PAGES_A_SLOT + 1, ROW, PAGE), jnp.bfloat16
        )
        unowned = np.ones(pool.shape[0], bool)
        unowned[owned] = False
        # the oracle gathers whole windows (0 x NaN is NaN): it reads
        # the pool as drawn, the kernel the one with NaN where no slot owns
        clean = pool
        pool = jnp.where(jnp.asarray(unowned)[:, None, None], jnp.nan, pool)
        spans = -(-live // derived)
        steps_a_slot = -(-PAGES_A_SLOT // derived)
        result = {
            "root": root, "contexts": how, "seed": a.seed,
            "mean_context": float((index[index >= 0] + 1).mean()),
            "live_pages": int(live.sum()),
            "dead_pages": int(SLOTS * PAGES_A_SLOT - live.sum()),
            "pages_past_the_newest_in_live_steps": int(
                (spans * derived - live).sum()
            ),
            "live_steps": int(spans.sum()),
            "dead_steps": int(SLOTS * steps_a_slot - spans.sum()),
            "by_iterations": {},
        }
        print(f"-- contexts {how}: mean {result['mean_context']:.0f}, "
              f"{result['live_pages']} live / {result['dead_pages']} dead "
              f"pages of the table; in steps of {derived} pages "
              f"{result['live_steps']} live / {result['dead_steps']} dead, "
              f"{result['pages_past_the_newest_in_live_steps']} pages of "
              "the live steps past their slot's newest position",
              flush=True)
        table, idx = jnp.asarray(table), jnp.asarray(index, jnp.int32)
        want = np.asarray(
            reference(qs[0], clean, table, idx, SM_SCALE, VALUES), np.float32
        )
        del clean
        context = int((index[index >= 0] + 1).sum())
        flops, nbytes = latent_decode_cost(
            context, int((index >= 0).sum()), HEADS, ROW, VALUES, 2
        )
        floor_ms = yardstick.floor_seconds(flops, nbytes, kind) * 1e3
        result.update(floor_ms=floor_ms, context_tokens=context)

        for pages in sweep:
            got = np.asarray(la._latent_impl(
                qs[0], pool, table, idx, SM_SCALE, VALUES, pages
            ), np.float32)
            err = np.abs(got - want).max(axis=(1, 2))
            for n in sorted(set(spans.tolist())) if pages == derived else ():
                rows = spans == n
                worst = float(err[rows].max())
                if n == 0:  # a dead row reads nothing and gets zeros
                    worst = float(np.abs(got[rows]).max())
                result["by_iterations"][n] = {
                    "rows": int(rows.sum()), "max_err": worst,
                }
                print(f"contexts over {n} iteration(s): {rows.sum():3d} "
                      f"rows, max|err| {worst:.5f}", flush=True)
            scale = float(np.abs(want[spans > 0]).max())
            worst = float(err[spans > 0].max())
            print(f"{pages} pages an iteration: max|err| {worst:.5f} of "
                  f"outputs to {scale:.3f} (bfloat16 operands, float32 "
                  "accumulation on both sides)")
            if not worst < 0.05 * scale:  # NaN too
                raise SystemExit("the kernel left its oracle")

            @jax.jit
            def layers(qs, pool, table, idx):
                out = 0.0
                for q in qs:
                    out = out + la._latent_impl(
                        q, pool, table, idx, SM_SCALE, VALUES, pages
                    )
                return out

            layers(qs, pool, table, idx).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(ITERS):
                out = layers(qs, pool, table, idx)
            out.block_until_ready()
            ms = (time.perf_counter() - t0) / ITERS / LAYERS * 1e3
            print(f"{pages} pages an iteration: {ms:.3f} ms a call (host "
                  f"clock around {ITERS} programs of {LAYERS} calls), "
                  f"floor {floor_ms:.3f} ms = {100 * floor_ms / ms:.1f}% ; "
                  f"{context} cached positions read", flush=True)
            result.setdefault("ms_a_call", {})[pages] = ms
            if pages == derived:
                result.update(max_err=worst, max_abs_output=scale)
        if a.out:
            Path(a.out).parent.mkdir(parents=True, exist_ok=True)
            with open(a.out, "a") as f:
                f.write(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
