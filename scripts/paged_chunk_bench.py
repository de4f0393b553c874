#!/usr/bin/env python3
"""Time the paged CHUNK-prefill kernel alone, at the benchmark cells' shapes.

    chiprun -- python3 scripts/paged_chunk_bench.py [--root DIR]
        [--shapes doc,kexaone,kexaone_full,falconh1] [--heads 1,5]
        [--body lanes,sublanes] [--trace]

One process, one chip. For each shape and each of its chunk passes
(``pos0`` and the power-of-two page list the batcher would hand it) it
builds a block's fused pool, checks the kernel against
``paged_chunk_attention_reference``, then times ``--layers`` kernel
calls inside one jitted program (the way a prefill pass holds them) and
prints, per call: milliseconds, grid steps, microseconds a step, and
the share of the floor ``chipbench/yardstick.paged_chunk_cost`` counts.
With ``--trace`` one more pass runs under the profiler and the device's
operations are listed by the name the benchmark's readers look for.

``--root`` imports ``adapt_tpu`` from another checkout (a ``git
archive`` of the parent in an ignored directory), so both sides of an
A/B are timed by the same code on the same chip. ``--heads`` and
``--body`` time ``_chunk_impl`` at heads-a-step and step bodies other
than the ones the dispatcher derives (this tree's kernel only):
``--body sublanes`` is the body the kernel had before PR 42 and
quantized pools keep (``_attend_fused``, a query row's state on a
sublane), so ``--heads 1 --body sublanes`` is the old kernel but for
its dead steps' fetch, ``--body sublanes`` alone the fold alone and
``--heads 1`` the body alone: it is how each was priced, not an option
of the program. Refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import itertools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> (kv heads, query heads a KV head, head_dim, window, the
#: chunk passes' first positions): ``gpt2xl_doc``'s three passes of a
#: 641-768-token document, and the chunk shapes K-EXAONE (four of five
#: layers under a 128-position window, one full) and Falcon-H1 compile
#: (PERF.md section 4).
SHAPES = {
    "doc": (25, 1, 64, None, (0, 256, 512)),
    "kexaone": (8, 8, 128, 128, (0, 256, 512)),
    "kexaone_full": (8, 8, 128, None, (0, 256, 512)),
    "falconh1": (4, 5, 128, None, (0, 256, 512)),
}
PAGE = 128
CHUNK = 256
NPAGES = 65


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--shapes", default="doc,kexaone,kexaone_full,falconh1")
    ap.add_argument("--heads", default="")
    ap.add_argument("--body", default="")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()

    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    sys.path.insert(1, HERE)  # chipbench's yardstick and trace reader
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("paged_chunk_bench: no TPU; a CPU time is not a device time")
        return 2
    from chipbench import xtrace, yardstick

    pa = importlib.import_module("adapt_tpu.ops.paged_attention")
    assert os.path.abspath(pa.__file__).startswith(root), pa.__file__
    derives = hasattr(pa, "chunk_heads_per_step")
    kind = jax.devices()[0].device_kind
    print(json.dumps({
        "root": root, "device_kind": kind, "derives_heads": derives,
    }))
    rng = np.random.RandomState(args.seed)
    for name in args.shapes.split(","):
        kvh, g, hd, window, passes = SHAPES[name]
        key = jax.random.PRNGKey(args.seed)
        pools = [
            pa.fuse_kv(*(
                jax.random.normal(
                    jax.random.fold_in(key, 2 * i + j),
                    (NPAGES, kvh, PAGE, hd), jnp.bfloat16,
                )
                for j in range(2)
            ))
            for i in range(4)
        ]
        q = jax.random.normal(
            jax.random.fold_in(key, 9), (1, kvh, g * CHUNK, hd), jnp.bfloat16
        )
        derived = 1
        if derives:
            derived = pa.chunk_heads_per_step(
                kvh, g * CHUNK, PAGE, 2 * hd, 2, False, hd, 2
            )
        variants = [(None, None)]
        if args.heads or args.body:
            variants = [
                (int(h) if h else derived, b or "lanes")
                for h, b in itertools.product(
                    args.heads.split(","), args.body.split(",")
                )
                if not h or kvh % int(h) == 0
            ]
        for pos0 in passes:
            live = -(-(pos0 + CHUNK) // PAGE)
            n = 1 << (live - 1).bit_length()  # the batcher pads to 2^k
            pages = np.zeros(n, np.int32)  # the tail: the trash page
            pages[:live] = 1 + rng.permutation(NPAGES - 1)[:live]
            pages = jnp.asarray(pages)
            flops, nbytes = yardstick.paged_chunk_cost(
                pos0, CHUNK, kvh * g, kvh, hd, 2
            )
            floor = yardstick.floor_seconds(flops, nbytes, kind)
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(pa.paged_chunk_attention_reference(
                    q, pools[0], pages, pos0, CHUNK, window
                ).astype(jnp.float32))
            for heads, body in variants:
                if heads is None:
                    def call(pool, q, pages=pages, pos0=pos0):
                        return pa.paged_chunk_attention(
                            q, pool, pages, pos0, CHUNK, prefer="pallas",
                            window=window,
                        )
                else:
                    def call(pool, q, pages=pages, pos0=pos0, heads=heads,
                             body=body):
                        return pa._chunk_impl(
                            q, pool, None, None, pages,
                            jnp.asarray(pos0, jnp.int32), chunk=CHUNK,
                            window=window, heads=heads,
                            lanes=body == "lanes",
                        )

                @jax.jit
                def program(pools, call=call):
                    # Each call's query hangs on the one before, as a
                    # layer's does: nothing is merged or reordered.
                    acc = jnp.zeros(q.shape, jnp.float32)
                    for i in range(args.layers):
                        out = call(
                            pools[i % 4], q + (1e-3 * acc).astype(q.dtype)
                        )
                        acc += out
                    return acc

                t0 = time.perf_counter()
                try:
                    program(pools).block_until_ready()
                    got = np.asarray(call(pools[0], q).astype(jnp.float32))
                except Exception as e:  # noqa: BLE001 — Mosaic's refusal
                    print(json.dumps({
                        "shape": name, "pos0": pos0, "heads_per_step": heads,
                        "body": body, "refused": str(e)[:300],
                    }), flush=True)
                    continue
                compile_s = time.perf_counter() - t0
                err = float(np.abs(got - ref).max())
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = program(pools)
                out.block_until_ready()
                per_call = (
                    (time.perf_counter() - t0) / args.iters / args.layers
                )
                h = heads or derived
                steps = (kvh // h) * n
                line = {
                    "shape": name, "pos0": pos0, "pages": n,
                    "heads_per_step": h,
                    "body": body or ("lanes" if derives else "sublanes"),
                    "ms_per_call": per_call * 1e3, "grid_steps": steps,
                    "us_per_step": per_call * 1e6 / steps,
                    "floor_us": floor * 1e6,
                    # the yardstick counts the whole causal window: it
                    # is no floor for a layer that attends 128 of it
                    "floor_share_pct": (
                        None if window else 100 * floor / per_call
                    ),
                    "max_abs_err": err, "compile_s": compile_s,
                }
                if args.trace:
                    tdir = os.path.join(
                        HERE, "chiprun_out", "paged_chunk_bench",
                        f"{os.path.basename(root)}.{name}.{pos0}.{h}."
                        f"{line['body']}",
                    )
                    with jax.profiler.trace(tdir):
                        for _ in range(3):
                            out = program(pools)
                        out.block_until_ready()
                    tr = xtrace.load(xtrace.find_xplane(tdir))
                    ops = xtrace.op_seconds(tr.devices[0])
                    line["trace_ms_per_call"] = {
                        k: v * 1e3 / (3 * args.layers)
                        for k, v in sorted(
                            ops.items(), key=lambda kv: -kv[1]
                        )[:5]
                    }
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
