#!/usr/bin/env python3
"""The selecting latent decode alone at ``dsv32_longgen32k``'s shape
(128 heads, 32 slots, rows of 576, index keys of 128, 64 index heads,
top-2,048, pages of 128), in the TWO forms a selected read can take,
at contexts of 4k / 12k / 32k:

    chiprun -- python3 scripts/sparse_latent_bench.py [--seed N]
        [--contexts 4096,12288,32768] [--out F]

``minor``  the shipped form (``ops/sparse_latent_attention``): both
           planes keep a page's positions on the minor axis, ONE kernel
           scores the live index pages, bisects the threshold and walks
           the live latent pages under the mask. Timed whole, and with
           ``top_k`` over the context (no bisection), beside the dense
           latent kernel (``_latent_impl``: the walk with no mask), so
           the score pass and the bisection read as differences.
``rows``   the alternative: a positions-major plane ``(pages * 128,
           640)`` (a row of 576 padded to whole lanes), ``lax.top_k``
           over the scores for the LIST of positions, a gather of the
           listed rows and a plain attention over them. Timed in its
           parts; its score pass is taken as ``minor``'s.

Every slot stands at the same context; every page no slot owns is NaN.
The shipped kernel is held to ``sparse_latent_reference`` first
(max|err|). ms a call by the host's clock around ten calls, bytes a
call by ``deepseek_v32_yardstick.sparse_latent_cost`` (the
token-granular floor) and by what each form moves. Refuses to run
without a TPU; ``JAX_PLATFORMS=cpu ... --rehearse`` walks it small and
interpreted (its time means nothing).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

ROW, VALUES, PAGE, LANES = 576, 512, 128, 640
SM_SCALE = 0.13523
ITERS = 10


def timed(f, *args):
    import jax

    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / ITERS * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--contexts", default="4096,12288,32768")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from adapt_tpu.ops import latent_attention as la
    from adapt_tpu.ops import sparse_latent_attention as sp
    from chipbench import deepseek_v32_yardstick as ys
    from chipbench import yardstick

    if a.rehearse:
        slots, heads, j, d, top_k, dtype = 2, 4, 4, 128, 256, jnp.float32
        contexts = [300, 700]
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("needs a TPU (or --rehearse under JAX_PLATFORMS=cpu)")
        slots, heads, j, d, top_k, dtype = 32, 128, 64, 128, 2048, jnp.bfloat16
        contexts = [int(c) for c in a.contexts.split(",")]
    kind = jax.devices()[0].device_kind
    pps = -(-max(contexts) // PAGE)
    n_pages = slots * pps + 1
    ks = jax.random.split(jax.random.PRNGKey(a.seed), 8)
    table = (
        jax.random.permutation(ks[0], n_pages - 1).reshape(slots, pps) + 1
    ).astype(jnp.int32)
    owned = jnp.zeros((n_pages,), bool).at[table.reshape(-1)].set(True)

    def plane(key, width):
        x = jax.random.normal(key, (n_pages, width, PAGE), dtype)
        return jnp.where(owned[:, None, None], x, jnp.nan)

    pool, ipool = plane(ks[1], ROW), plane(ks[2], d)
    # the positions-major plane of the same rows, padded to whole lanes
    major = jnp.pad(
        la.pages_to_rows(pool).reshape(-1, ROW), ((0, 0), (0, LANES - ROW))
    )
    q = jax.random.normal(ks[3], (slots, heads, ROW), dtype)
    q_i = jax.random.normal(ks[4], (slots, j, d), dtype)
    w = jax.random.normal(ks[5], (slots, j), jnp.float32) * (j * d) ** -0.5
    pages = la.latent_pages_per_step(pps, PAGE, ROW, pool.dtype.itemsize)

    # Every array is an OPERAND of the timed programs: one closed over
    # would be compiled into them as a constant, gigabytes a program.
    def minor(k):
        return jax.jit(lambda idx, q, q_i, w, pool, ipool, table: (
            sp._sparse_latent_impl(
                q, q_i, w, pool, ipool, table, idx, sm_scale=SM_SCALE,
                v_width=VALUES, top_k=k, pages=pages,
            )
        ))

    held = (q, q_i, w, pool, ipool, table)
    dense = jax.jit(lambda idx, q, pool, table: la._latent_impl(
        q, pool, table, idx, sm_scale=SM_SCALE, v_width=VALUES, pages=pages,
    ))
    want = jax.jit(lambda idx, q, q_i, w, pool, ipool, table: (
        sp.sparse_latent_reference(
            q, q_i, w, pool, ipool, table, idx, SM_SCALE, VALUES, top_k
        )
    ))
    q_major = jnp.pad(q, ((0, 0), (0, 0), (0, LANES - ROW)))

    @jax.jit
    def rows_read(listed, n, q_major, major):
        # listed (slots, top_k) flat rows of `major`; the first n valid
        rows = jnp.take(major, listed, axis=0)  # (slots, k, LANES)
        s = jnp.einsum(
            "bhw,bkw->bhk", q_major, rows, preferred_element_type=jnp.float32
        ) * SM_SCALE
        s = jnp.where(jnp.arange(listed.shape[1]) < n, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhk,bkv->bhv", p.astype(rows.dtype), rows[..., :VALUES],
            preferred_element_type=jnp.float32,
        ).astype(q_major.dtype)

    gather_only = jax.jit(
        lambda listed, major: jnp.take(major, listed, axis=0)
    )
    take_top = jax.jit(lambda s: jax.lax.top_k(s, min(top_k, s.shape[1]))[1])

    result = dict(device=kind, slots=slots, heads=heads, top_k=top_k,
                  pages_per_step=pages, contexts={})
    peak = 1.0 if a.rehearse else yardstick.peaks(kind)[1]
    whole, no_bisect = minor(top_k), minor(1 << 30)
    for ctx in contexts:
        idx = jnp.full((slots,), ctx - 1, jnp.int32)
        got = whole(idx, *held)
        err = float(jnp.abs(
            got.astype(jnp.float32) - want(idx, *held).astype(jnp.float32)
        ).max())
        n = min(ctx, top_k)
        pos = jnp.sort(jax.vmap(
            lambda k: jax.random.permutation(k, ctx)[:top_k]
        )(jax.random.split(ks[6], slots)), axis=1) % ctx
        listed = jnp.take_along_axis(table, pos // PAGE, axis=1) * PAGE + (
            pos % PAGE
        )
        scores = jax.random.normal(ks[7], (slots, ctx), jnp.float32)
        ms = {
            "minor.whole": timed(whole, idx, *held),
            "minor.no_bisect": timed(no_bisect, idx, *held),
            "dense_walk": timed(dense, idx, q, pool, table),
            "rows.top_k": timed(take_top, scores),
            "rows.gather": timed(gather_only, listed, major),
            "rows.read": timed(
                rows_read, listed, jnp.int32(n), q_major, major
            ),
        }
        ms["rows.whole"] = (
            ms["minor.no_bisect"] - ms["dense_walk"] + ms["rows.top_k"]
            + ms["rows.read"]
        )
        floor = ys.sparse_latent_cost([ctx] * slots, 1, top_k, d, ROW, 2)
        moved = {
            "floor": floor,
            "minor": slots * ctx * (d + ROW) * 2,
            "rows": slots * (ctx * d * 2 + n * LANES * 2 * 3),
        }
        print(
            f"context {ctx}: max|err| vs reference {err:.2e}; ms a call "
            + "  ".join(f"{k} {v:.3f}" for k, v in ms.items())
            + f"; bytes a call floor {floor:.3e} (= "
            f"{1e3 * floor / peak:.3f} ms at the HBM peak) minor "
            f"{moved['minor']:.3e} rows {moved['rows']:.3e}", flush=True,
        )
        result["contexts"][ctx] = dict(max_err=err, ms=ms, bytes=moved)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
