#!/usr/bin/env python3
"""Time the paged DECODE kernel alone, at the benchmark cells' shapes.

    chiprun -- python3 scripts/paged_decode_bench.py [--root DIR]
        [--shapes batchgen,doc,chat] [--splits 1,2,auto] [--heads 16,4,1]
        [--dead] [--trace]

One process, one chip. For each shape it builds the cell's pool, a page
table and live lengths like the cell's traffic, checks the kernel
against ``paged_attention_reference``, then times ``--layers`` kernel
calls inside one jitted program (the way a decode step holds them) and
prints, per call: milliseconds, grid steps, microseconds a step, and
the share of the bytes floor ``chipbench/yardstick.py`` counts. With
``--trace`` one more pass runs under the profiler and the device's
operations are listed by the name the benchmark's readers look for.

``--root`` imports ``adapt_tpu`` from another checkout (a ``git
archive`` of the parent in an ignored directory), so both sides of an
A/B are timed by the same code on the same chip: a checkout whose pool
is one fused K|V plane a block (``fuse_kv``, PR 30 on) gets that, an
older one its two planes. ``--heads`` times
``_paged_impl`` at heads-a-step other than the derived one (this
tree's kernel only): it is how the derivation was checked, not an
option of the program. ``--dead`` times every row dead: what the grid
costs when it moves nothing. Refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> (slots, kv heads, head_dim, pages a slot, pool pages, live
#: slots, (shortest, longest) live context): the three cells'
#: deployments (PERF.md section 4) and contexts like their traffic.
SHAPES = {
    "batchgen": (24, 16, 128, 7, 169, 24, (100, 860)),
    "doc": (8, 25, 64, 7, 57, 8, (650, 830)),
    "chat": (32, 25, 64, 3, 97, 22, (40, 350)),
}
PAGE = 128


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--shapes", default="batchgen,doc,chat")
    ap.add_argument("--splits", default="1,auto")
    ap.add_argument("--heads", default="")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dead", action="store_true")
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()

    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    sys.path.insert(1, HERE)  # chipbench's yardstick and trace reader
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("paged_decode_bench: no TPU; a CPU time is not a device time")
        return 2
    from chipbench import xtrace, yardstick

    pa = importlib.import_module("adapt_tpu.ops.paged_attention")
    dec = importlib.import_module("adapt_tpu.ops.decode_attention")
    assert os.path.abspath(pa.__file__).startswith(root), pa.__file__
    fused = hasattr(pa, "fuse_kv")
    dev = jax.devices()[0]
    kind = dev.device_kind
    print(json.dumps({
        "root": root, "device_kind": kind,
        "num_cores": getattr(dev, "num_cores", None),
        "derives_heads": hasattr(pa, "decode_heads_per_step"),
        "fused_plane": fused,
    }))
    rng = np.random.RandomState(args.seed)
    for name in args.shapes.split(","):
        b, kvh, hd, pps, npages, live_slots, (lo, hi) = SHAPES[name]
        key = jax.random.PRNGKey(args.seed)
        # Four planes of the same bytes on both sides: a block's pool
        # is planes (2i, 2i + 1) side by side on the lanes, or the two.
        planes = [
            jax.random.normal(
                jax.random.fold_in(key, i), (npages, kvh, PAGE, hd),
                jnp.bfloat16,
            )
            for i in range(4)
        ]
        if fused:
            pools = [
                (pa.fuse_kv(planes[i], planes[(i + 1) % 4]),)
                for i in range(4)
            ]
        else:
            pools = [(planes[i], planes[(i + 1) % 4]) for i in range(4)]
        q = jax.random.normal(
            jax.random.fold_in(key, 9), (b, kvh, 1, hd), jnp.bfloat16
        )
        ctx = np.full(b, 0, np.int64)
        ctx[:live_slots] = rng.randint(lo, hi + 1, size=live_slots)
        if args.dead:
            ctx[:] = 0
        index = jnp.asarray(ctx - 1, jnp.int32)  # newest live position
        table = np.zeros((b, pps), np.int32)  # dead entries: trash page
        free = iter(1 + rng.permutation(npages - 1))
        for s in range(b):
            for j in range(-(-int(ctx[s]) // PAGE)):
                table[s, j] = next(free)
        table = jnp.asarray(table)
        nbytes = yardstick.paged_decode_bytes(
            int(ctx.sum()), int((ctx > 0).sum()), kvh, kvh, hd, 2
        )
        floor = yardstick.floor_seconds(0, nbytes, kind)
        ref = None
        if not args.dead:
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(pa.paged_attention_reference(
                    q, *pools[0], table, index
                ).astype(jnp.float32))

        variants = [("auto" if s == "auto" else int(s), None)
                    for s in args.splits.split(",")]
        if args.heads:
            variants += [
                (1, int(h)) for h in args.heads.split(",")
                if kvh % int(h) == 0
            ]
        for split, heads in variants:
            s_val = None if split == "auto" else split
            resolved = dec.resolve_decode_split(pps, s_val)

            if heads is None:
                def call(pool, q=q, s_val=s_val):
                    return pa.paged_attention(
                        q, *pool, table, index, prefer="pallas", split=s_val
                    )
            else:
                def call(pool, q=q, heads=heads):
                    return pa._paged_impl(
                        q, *pool, None, None, table, index, None,
                        heads=heads, split=1,
                    )

            @jax.jit
            def program(pools):
                # Each call's query hangs on the one before, as a
                # layer's does: nothing is merged or reordered.
                acc = jnp.zeros(q.shape, jnp.float32)
                for i in range(args.layers):
                    out = call(
                        pools[i % 4], q=q + (1e-3 * acc).astype(q.dtype),
                    )
                    acc += out
                return acc

            t0 = time.perf_counter()
            program(pools).block_until_ready()
            compile_s = time.perf_counter() - t0
            err = None
            if ref is not None:
                got = np.asarray(call(pools[0]).astype(jnp.float32))
                live = np.asarray(ctx) > 0
                err = float(np.abs(got - ref)[live].max())
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = program(pools)
            out.block_until_ready()
            per_call = (time.perf_counter() - t0) / args.iters / args.layers
            h = heads
            if h is None and hasattr(pa, "decode_heads_per_step"):
                h = pa.decode_heads_per_step(
                    kvh, PAGE, 2 * hd if fused else hd, 2, False, 8, hd
                )
            h = h or 1
            per_row = resolved * -(-pps // resolved)
            steps = b * (kvh // h) * per_row
            line = {
                "shape": name, "split": split, "resolved_split": resolved,
                "heads_per_step": h, "ms_per_call": per_call * 1e3,
                "grid_steps": steps, "us_per_step": per_call * 1e6 / steps,
                "block_kb": 2 * h * PAGE * hd * 2 / 1024,
                "floor_us": floor * 1e6,
                "floor_share_pct": 100 * floor / per_call,
                "max_abs_err": err, "compile_s": compile_s,
            }
            if args.trace:
                tdir = os.path.join(
                    HERE, "chiprun_out", "paged_decode_bench",
                    f"{os.path.basename(root)}.{name}.{split}.{h}",
                )
                with jax.profiler.trace(tdir):
                    for _ in range(3):
                        out = program(pools)
                    out.block_until_ready()
                tr = xtrace.load(xtrace.find_xplane(tdir))
                ops = xtrace.op_seconds(tr.devices[0])
                n = 3 * args.layers
                line["trace_ms_per_call"] = {
                    k: v * 1e3 / n
                    for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:5]
                }
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
