#!/usr/bin/env python3
"""The paged DECODE kernel alone, at the benchmark cells' shapes: held
to the gather oracle, then timed.

    chiprun -- python3 scripts/paged_decode_bench.py [--root DIR]
        [--shapes batchgen,doc,chat,kexaone_full,kexaone_window,falconh1,
        solaropen2] [--contexts uniform,cell] [--pages 1,2,4]
        [--splits 1,2] [--heads 16,4,1] [--dead] [--trace] [--out F]

One process, one chip. For each shape it builds the cell's pool (four
planes of a layer's bytes, so a call's pages are not the last call's),
a page table and live lengths under each ``--contexts`` draw, checks
the kernel against ``paged_attention_reference``, then times
``layers`` kernel calls inside one jitted program (the way a decode
step holds them) and prints, per call: milliseconds, grid steps, and
the share of the bytes floor ``chipbench/yardstick.py`` counts.

``--contexts`` names the draws, each measured in turn: ``uniform`` is a
context anywhere in one request's life; ``cell`` is the population that
STANDS in the cell's window (a request is in flight for as long as its
output is, so outputs are drawn in proportion to their length, at an
age uniform in it), the draw to compare with the cell's own
``kernel.paged_decode_*_roofline``. A window group (``kexaone_window``)
gets its ``valid_from`` and a table that names only the pages the
window holds. The first rows are forced onto a page's first and last
position, one position, the table's last and a dead row; ``chat``
keeps the ~2 live slots of 32 its server has. Every page no slot owns,
the trash page among them, is NaN in the pool the kernel reads: a dead
page that reaches a product shows in the result.

Printed with each draw: what it asks of the kernel (live and dead
pages of the table, and the steps the page axis took on the grid, live
and dead, where the walk takes a row a step and an iteration a live
``pages``). ``--pages`` times ``_paged_impl`` at other pages an
iteration than the entry point derives, ``--heads`` at other heads a
step (this tree's kernel only): how the derivations were checked, not
options of the program. ``--root`` imports ``adapt_tpu`` from another
checkout (a ``git archive`` of the parent in an ignored directory) and
times its kernel with this file's operands. ``--dead`` times every row
dead: what the grid costs when it moves nothing. With ``--trace`` one
more pass runs under the profiler and the device's operations are
listed by the name the benchmark's readers look for. Refuses to run
without a TPU; ``JAX_PLATFORMS=cpu ... --rehearse`` walks it small and
interpreted (its time means nothing).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> slots, KV heads, query heads a KV head, head_dim, pages a
#: slot, live slots, the traffic's (prompt, output) ranges, the layer's
#: window, the calls a decode step holds: the cells' deployments
#: (PERF.md section 4) and contexts like their traffic
#: (chipbench/traffic/*.json).
SHAPES = {
    "batchgen": (24, 16, 1, 128, 7, 24, (64, 256), (256, 768), None, 24),
    "doc": (8, 25, 1, 64, 7, 8, (641, 768), (16, 64), None, 48),
    "chat": (32, 25, 1, 64, 3, 2, (32, 256), (32, 256), None, 48),
    "kexaone_full": (128, 8, 8, 128, 16, 128, (64, 256), (512, 1792),
                     None, 4),
    "kexaone_window": (128, 8, 8, 128, 15, 128, (64, 256), (512, 1792),
                       128, 4),
    "falconh1": (128, 4, 5, 128, 16, 128, (64, 256), (512, 1792), None, 4),
    "solaropen2": (256, 8, 8, 128, 15, 256, (64, 256), (512, 1792),
                   None, 1),
}
PAGE = 128


def draw_contexts(rng, how, n, prompt, output, most):
    """``n`` live rows' contexts (positions held) under the draw."""
    import numpy as np

    prompts = rng.integers(prompt[0], prompt[1] + 1, n)
    if how == "uniform":
        outs = rng.integers(output[0], output[1] + 1, n)
    elif how == "cell":
        outs = rng.integers(output[0], output[1] + 1, 64 * n)
        outs = rng.choice(outs, n, p=outs / outs.sum())
    else:
        raise SystemExit(f"--contexts {how}: expected uniform or cell")
    return np.minimum(prompts + (rng.random(n) * outs).astype(int) + 1, most)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--contexts", default="cell")
    ap.add_argument("--splits", default="1")
    ap.add_argument("--heads", default="")
    ap.add_argument("--pages", default="",
                    help="pages an iteration to time besides the derived")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dead", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="8 slots, 2 calls, interpreted on the CPU")
    args = ap.parse_args()

    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    sys.path.insert(1, HERE)  # chipbench's yardstick and trace reader
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu" and not args.rehearse:
        print("paged_decode_bench: no TPU; a CPU time is not a device time")
        return 2
    from chipbench import xtrace, yardstick

    pa = importlib.import_module("adapt_tpu.ops.paged_attention")
    assert os.path.abspath(pa.__file__).startswith(root), pa.__file__
    walks = hasattr(pa, "decode_pages_per_step")
    dev = jax.devices()[0]
    kind = "TPU v5e" if args.rehearse else dev.device_kind
    print(json.dumps({
        "root": root, "device_kind": dev.device_kind, "walks": walks,
    }), flush=True)
    for name in args.shapes.split(","):
        (b, kvh, g, hd, pps, live_slots, prompt, output, window,
         layers) = SHAPES[name]
        if args.rehearse:
            b, live_slots, layers, args.iters = 8, min(live_slots, 7), 2, 1
        key = jax.random.PRNGKey(args.seed)
        held = pps if window is None else 3  # pages of a slot in the pool
        npages = b * held + 1
        q = jax.random.normal(
            jax.random.fold_in(key, 9), (b, kvh, g, hd), jnp.bfloat16
        )
        geometry = (PAGE, 2 * hd, 2, False, g + (-g) % 8, hd)
        heads_derived = pa.decode_heads_per_step(kvh, *geometry)
        pages_derived = (
            pa.decode_pages_per_step(pps, heads_derived, *geometry)
            if walks else None
        )
        for how in args.contexts.split(","):
            rng = np.random.default_rng([args.seed, int(how == "cell")])
            ctx = np.zeros(b, np.int64)
            ctx[:live_slots] = draw_contexts(
                rng, how, live_slots, prompt, output, pps * PAGE
            )
            if live_slots > 8:
                ctx[:6] = [1, PAGE, PAGE + 1, pps * PAGE, 0, 3 * PAGE]
            if args.dead:
                ctx[:] = 0
            index = ctx - 1  # newest live position; -1: a dead row
            vf = None
            first = np.zeros(b, np.int64)
            if window is not None:
                vf = np.maximum(ctx - window, 0)
                first = vf // PAGE
            live = np.where(ctx > 0, index // PAGE - first + 1, 0)
            table = np.zeros((b, pps), np.int32)  # unowned: the trash page
            owned = 1 + rng.permutation(npages - 1)
            at = np.concatenate([[0], np.cumsum(live)])
            for s in range(b):
                table[s, first[s]:first[s] + live[s]] = (
                    owned[at[s]:at[s + 1]]
                )
            unowned = np.ones(npages, bool)
            unowned[owned[:at[-1]]] = False
            nan = jnp.asarray(unowned)[:, None, None, None]

            def plane(i):
                return pa.fuse_kv(*(
                    jax.random.normal(
                        jax.random.fold_in(key, 2 * i + j),
                        (npages, kvh, PAGE, hd), jnp.bfloat16,
                    ) for j in range(2)
                ))

            # Up to four planes of a layer's bytes, so a call's pages
            # are not the last call's. The oracle gathers whole windows
            # (0 x NaN is NaN): it reads the first plane as drawn, the
            # kernel every plane with NaN where no slot owns.
            clean = plane(0)
            pools = [
                jnp.where(nan, jnp.nan, clean if i == 0 else plane(i))
                for i in range(min(4, layers))
            ]
            table_d = jnp.asarray(table)
            index_d = jnp.asarray(index, jnp.int32)
            vf_d = None if vf is None else jnp.asarray(vf, jnp.int32)
            seen = ctx if window is None else np.minimum(ctx, window)
            nbytes = yardstick.paged_decode_bytes(
                int(seen.sum()), int((ctx > 0).sum()), kvh * g, kvh, hd, 2
            )
            floor = yardstick.floor_seconds(0, nbytes, kind)
            moved = int(live.sum()) * kvh * PAGE * 2 * hd * 2
            asks = {
                "shape": name, "contexts": how, "seed": args.seed,
                "mean_context": float(ctx[ctx > 0].mean()) if live.any()
                else 0.0,
                "live_rows": int((ctx > 0).sum()), "dead_rows":
                int((ctx <= 0).sum()),
                "live_pages": int(live.sum()),
                "dead_pages": int(b * pps - live.sum()),
                "page_axis_steps": b * pps,
                "floor_us": floor * 1e6,
                "whole_pages_floor_us":
                yardstick.floor_seconds(0, moved, kind) * 1e6,
            }
            print(json.dumps(asks), flush=True)
            ref = None
            if live.any():
                with jax.default_matmul_precision("highest"):
                    ref = np.asarray(pa.paged_attention_reference(
                        q, clean, table_d, index_d, vf_d
                    ).astype(jnp.float32))
            del clean

            variants = [("derived", int(s), None, None)
                        for s in args.splits.split(",")]
            if walks:
                # (pages that fit the budget the derivation works in)
                variants += [
                    ("pages", 1, None, int(p))
                    for p in args.pages.split(",")
                    if p and int(p) <= pps and pa.decode_step_vmem_bytes(
                        heads_derived, *geometry, int(p)
                    ) <= pa.DECODE_STEP_VMEM_BUDGET
                ]
            variants += [
                ("heads", 1, int(h), pages_derived)
                for h in args.heads.split(",") if h and kvh % int(h) == 0
            ]
            for what, split, heads, pages in variants:
                if what == "derived":
                    def call(pool, q, split=split):
                        return pa.paged_attention(
                            q, pool, table_d, index_d, vf_d,
                            prefer="pallas", split=split,
                        )
                else:
                    kw = {"pages": pages} if walks else {}

                    def call(pool, q, heads=heads or heads_derived, kw=kw):
                        return pa._paged_impl(
                            q, pool, None, None, table_d, index_d, vf_d,
                            heads=heads, split=1, **kw,
                        )

                @jax.jit
                def program(pools, q):
                    # Each call's query hangs on the one before, as a
                    # layer's does: nothing is merged or reordered.
                    acc = jnp.zeros(q.shape, jnp.float32)
                    for i in range(layers):
                        out = call(
                            pools[i % len(pools)],
                            q + (1e-3 * acc).astype(q.dtype),
                        )
                        acc += out
                    return acc

                t0 = time.perf_counter()
                program(pools, q).block_until_ready()
                compile_s = time.perf_counter() - t0
                err = scale = dead_out = None
                got = np.asarray(call(pools[0], q).astype(jnp.float32))
                if ref is not None:
                    rows = ctx > 0
                    err = float(np.abs(got - ref)[rows].max())
                    scale = float(np.abs(ref[rows]).max())
                if (ctx <= 0).any():  # a dead row reads nothing: zeros
                    dead_out = float(np.abs(got[ctx <= 0]).max())
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = program(pools, q)
                out.block_until_ready()
                per_call = (time.perf_counter() - t0) / args.iters / layers
                h = heads or heads_derived
                p = pages if what != "derived" else pages_derived
                rows_grid = b * (kvh // h)
                line = {
                    "shape": name, "contexts": how, "variant": what,
                    "split": split, "heads_per_step": h,
                    "pages_per_step": p if split == 1 else None,
                    "grid_steps": rows_grid if walks and split == 1
                    else rows_grid * split * -(-pps // split),
                    "ms_per_call": per_call * 1e3,
                    "floor_share_pct": 100 * floor / per_call,
                    "whole_pages_share_pct":
                    asks["whole_pages_floor_us"] / per_call / 1e4,
                    "block_kb": h * PAGE * 2 * hd * 2 / 1024,
                    "max_abs_err": err, "max_abs_output": scale,
                    "dead_rows_max_abs": dead_out, "compile_s": compile_s,
                }
                if args.trace:
                    tdir = os.path.join(
                        HERE, "chiprun_out", "paged_decode_bench",
                        f"{os.path.basename(root)}.{name}.{what}.{h}.{p}",
                    )
                    with jax.profiler.trace(tdir):
                        for _ in range(3):
                            out = program(pools, q)
                        out.block_until_ready()
                    tr = xtrace.load(xtrace.find_xplane(tdir))
                    ops = xtrace.op_seconds(tr.devices[0])
                    n = 3 * layers
                    line["trace_ms_per_call"] = {
                        k: v * 1e3 / n for k, v in
                        sorted(ops.items(), key=lambda kv: -kv[1])[:5]
                    }
                print(json.dumps(line), flush=True)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".",
                                exist_ok=True)
                    with open(args.out, "a") as f:
                        f.write(json.dumps({**asks, **line, "root": root})
                                + "\n")
                bad = err is not None and not err < 0.05 * scale  # NaN too
                if bad or (dead_out is not None and dead_out != 0.0):
                    raise SystemExit("the kernel left its oracle")
    return 0


if __name__ == "__main__":
    sys.exit(main())
