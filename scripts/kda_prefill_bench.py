#!/usr/bin/env python3
"""The delta rule's chunked prefill alone at the published widths of
the two configurations that run it (64 heads of a 128 x 128 state; a
decay a key channel: ``solar-open2-250b``; a decay a head:
``gigachat3.5-432b-a28b``): held to the position-by-position recurrence
on the chip, then timed.

    chiprun -- python3 scripts/kda_prefill_bench.py [--seed N]
        [--lengths 256,2048] [--blocks 16,32] [--root DIR]

One process, one chip, one row (the whole-prompt program's and a chunk
pass's shape). For each decay and length: max|err| of ``o`` and of the
state left against ``kda_recurrent`` from a NON-zero carried state,
then ms a call (the median of ``--calls`` calls of one program, each
ended by ``block_until_ready``) at each ``--blocks`` width of the
blocked inverse's diagonal blocks (``kda._SOLVE_BLOCK``, set before the
trace). ``--root`` imports ``adapt_tpu`` from another checkout (a ``git
archive`` of the parent in an ignored directory) and times ITS chunked
forms with this file's operands; a checkout whose module has no
``_SOLVE_BLOCK`` is timed once, as it is. Refuses to run without a TPU;
``JAX_PLATFORMS=cpu ... --rehearse`` walks it at small widths (its time
means nothing).
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lengths", default="256,2048")
    ap.add_argument("--blocks", default="16,32")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--root", default=str(HERE))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(args.root).resolve()))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models import kda

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({platform}): nothing to measure")
        return 1
    heads, d = (4, 16) if args.rehearse else (64, 128)
    blocks = (
        [int(b) for b in args.blocks.split(",")]
        if hasattr(kda, "_SOLVE_BLOCK") else [None]
    )
    print(f"device {jax.devices()[0].device_kind}; root {args.root}; "
          f"{heads} heads of {d} x {d}")

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    for decay, fn in (("channel", "kda_chunked"), ("head", "kda_chunked_head")):
        for s in (int(n) for n in args.lengths.split(",")):
            ks = jax.random.split(jax.random.PRNGKey(args.seed % 2**31), 6)
            q = (unit(jax.random.normal(ks[0], (s, heads, d))) * d ** -0.5)
            k = unit(jax.random.normal(ks[1], (s, heads, d)))
            v = jax.random.normal(ks[2], (s, heads, d))
            q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
            g = -jax.random.uniform(
                ks[3], (s, heads, d), minval=1e-4, maxval=0.16
            )
            if decay == "head":
                g = g[..., 0]
            beta = jax.random.uniform(ks[4], (s, heads), maxval=2.0)
            state = jax.random.normal(ks[5], (heads, d, d))
            wide = g if decay == "channel" else jnp.broadcast_to(
                g[..., None], (s, heads, d)
            )
            want_o, want_s = jax.jit(kda.kda_recurrent)(
                q, k, v, wide, beta, state
            )
            for block in blocks:
                if block is not None:
                    kda._SOLVE_BLOCK = block
                jax.clear_caches()
                run = jax.jit(getattr(kda, fn))
                t0 = time.perf_counter()
                got_o, got_s = jax.block_until_ready(
                    run(q, k, v, g, beta, state)
                )
                first = time.perf_counter() - t0
                times = []
                for _ in range(args.calls):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(q, k, v, g, beta, state))
                    times.append(time.perf_counter() - t0)
                print(
                    f"{decay} s={s} block={block}: "
                    f"{statistics.median(times) * 1e3:.3f} ms a call "
                    f"(min {min(times) * 1e3:.3f}, first {first:.2f} s); "
                    f"max|err| o {np.abs(got_o - want_o).max():.2e} "
                    f"state {np.abs(got_s - want_s).max():.2e}",
                    flush=True,
                )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
