"""The expert layer's grouped product alone, on the chip, at one
configuration's shapes: ms a call and share of the bytes floor for
each way of computing it, and the device operations a traced call
shows (the names a trace reducer would have to find).

    chiprun -- python3 scripts/expert_product_bench.py [--rows 128]

Candidates: ``shipped`` (``models/moe.expert_product`` as it
dispatches here), ``ragged`` (three ``jax.lax.ragged_dot`` over the
sorted assignments), ``gmm <rows>x<k>x<n>`` (the Pallas grouped matmul
JAX ships, ``megablox.gmm``, at each of ``--tilings``), ``dense``
(every held expert meets every token, a mask picks: the masked-dense
form).
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=128)  # tokens a step
    ap.add_argument("--dim", type=int, default=6144)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--tilings", default="128x512x512,128x1024x1024,"
                    "128x2048x1024,128x1024x2048,256x2048x1024")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.moe import (
        ExpertSpec, RoutedExperts, expert_product, grouped_matmul,
    )

    kind = jax.devices()[0].device_kind
    print("device", jax.devices()[0].platform, kind, flush=True)
    n, d, h, e, k = a.rows, a.dim, a.hidden, a.held, a.top_k
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    w_gate = jax.random.normal(ks[0], (e, d, h), bf) * d ** -0.5
    w_up = jax.random.normal(ks[1], (e, d, h), bf) * d ** -0.5
    w_down = jax.random.normal(ks[2], (e, h, d), bf) * h ** -0.5
    tokens = jax.random.normal(ks[3], (n, d), bf)
    # n * k assignments over all experts; those on [0, e) are held.
    rng = np.random.default_rng(0)
    idx = np.stack([rng.choice(a.experts, k, replace=False) for _ in range(n)])
    eid = np.where(idx < e, idx, e).reshape(-1)
    order = np.argsort(eid, kind="stable")
    sizes = jnp.asarray(np.bincount(eid, minlength=e + 1)[:e], jnp.int32)
    xs = tokens[jnp.asarray(order // k)]
    m = int(sizes.sum())
    hit = int((np.asarray(sizes) > 0).sum())
    nbytes = hit * 3 * d * h * 2 + m * 2 * d * 2
    flops = m * 2 * 3 * d * h
    floor = max(nbytes / 819e9, flops / 197e12)
    print(f"rows {n} assignments held {m} experts hit {hit} of {e}; "
          f"floor {floor * 1e3:.3f} ms (bytes {nbytes / 1e9:.3f} GB)", flush=True)

    def dense(x, wg, wu, wd, sz):
        del sz
        pick = jnp.asarray(
            (idx[:, :, None] == np.arange(e)).any(1), bf
        )  # (n, e)
        g = jnp.einsum("nd,edh->enh", x, wg)
        u = jnp.einsum("nd,edh->enh", x, wu)
        y = jnp.einsum("enh,ehd->end", jax.nn.silu(g) * u, wd)
        return jnp.einsum("end,ne->nd", y, pick)

    def gmm_with(tiles):
        def f(x, wg, wu, wd, sz):
            g = grouped_matmul(x, wg, sz, "pallas", tiles)
            u = grouped_matmul(x, wu, sz, "pallas", tiles)
            return grouped_matmul(jax.nn.silu(g) * u, wd, sz, "pallas", tiles)
        return jax.jit(f)

    cands = {
        "ragged": (functools.partial(expert_product, prefer="xla"), xs),
        "shipped": (expert_product, xs),
        "dense": (jax.jit(dense), tokens),
    }
    for t in a.tilings.split(","):
        tiles = tuple(int(v) for v in t.split("x"))
        cands[f"gmm {t}"] = (gmm_with(tiles), xs)
    for name, (fn, x) in cands.items():
        try:
            out = jax.block_until_ready(fn(x, w_gate, w_up, w_down, sizes))
            t0 = time.perf_counter()
            for _ in range(a.iters):
                out = fn(x, w_gate, w_up, w_down, sizes)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / a.iters * 1e3
            print(f"{name}: {ms:.3f} ms a call, {100 * floor * 1e3 / ms:.1f}% "
                  f"of the floor", flush=True)
        except Exception as err:  # noqa: BLE001 — say which and go on
            print(f"{name}: failed: {type(err).__name__}: {str(err)[:300]}",
                  flush=True)
    # The whole layer (route, sort, gather, product, scatter-add, shared).
    spec = ExpertSpec(a.experts, h, k, score="sigmoid", normalize=True,
                      scale=2.5, select_bias=True, shared_dim=h, held=(0, e))
    layer = RoutedExperts(spec, dtype=bf)
    x3 = tokens[:, None, :]
    shapes = jax.eval_shape(layer.init, ks[4], x3)
    params = jax.tree.map(
        lambda s: jax.random.normal(ks[5], s.shape, bf) * 0.02, shapes
    )
    apply = jax.jit(layer.apply)
    out = jax.block_until_ready(apply(params, x3))
    t0 = time.perf_counter()
    for _ in range(a.iters):
        out = apply(params, x3)
    jax.block_until_ready(out)
    print(f"whole layer: {(time.perf_counter() - t0) / a.iters * 1e3:.3f} "
          "ms a call", flush=True)
    # What a trace calls these operations.
    from chipbench import xtrace

    tdir = str(ROOT / ".chipbench_trace" / "expert_product_bench")
    jax.profiler.start_trace(tdir)
    for _ in range(5):
        out = apply(params, x3)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(tdir)
    dev = xtrace.load(path).devices[0]
    for name, sec in xtrace.top(xtrace.op_seconds(dev), 14):
        print(f"  op {name}: {sec / 5 * 1e3:.3f} ms a call", flush=True)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    seen = set()
                    for ev in line.events:
                        short = ev.name[:160]
                        if short not in seen and len(seen) < 40:
                            seen.add(short)
                            print("  raw:", short, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
