"""Flash-split decode + tree-draft verify micro bench: the ISSUE-12
kernel-push structural grid.

Two claims ride this driver:

1. **Flash-split decode is invariant-preserving.** The split kernels
   (``ops/decode_attention._decode_split_kernel`` + the paged/verify
   wrappers) change only the SCHEDULE of the KV stream — so a batcher
   running them (``KernelConfig(attn_impl="pallas", decode_split=s)``)
   must keep every hot-path contract: greedy streams BIT-IDENTICAL
   across split in {1, 2, 4} and vs the XLA oracle, 0 h2d per steady
   tick, and 0 compile growth across churn. The grid runs split x
   dtype (native/int8/int4) through the Pallas
   INTERPRETER on CPU — wall numbers are schedule-sanity only (the
   interpreter is orders of magnitude off hardware; the TPU win is the
   parallel split fan-out the partials + rescale combine buy), but the
   counters and the bit-identity are the same code path hardware runs.

2. **Tree drafts raise accepted tokens per verify pass beyond the
   chain ceiling.** At draft_k = 4 the chain's perfect-draft ceiling is
   5.0 committed tokens per target weight stream (``spec_tick``'s gated
   headline). ``SpeculativeConfig(tree_width=1)`` adds the draft's
   top-1 leaf for the post-chain position (harvested from logits the
   draft scan already computes — equal draft FLOPs per committed
   token) and the perfect-draft arm commits ``draft_k + 2`` = 6.0 per
   pass, gated ``> 5.0`` as ``micro_decode_split_tree_tokens_per_pass``.

Emits TWO gated records (one JSON line each):

- ``micro_decode_split_h2d_per_tick`` — worst h2d/steady-tick across
  the whole split grid (contract: exactly 0; any bit-identity or
  compile-growth violation becomes an ``error`` record the gate always
  fails);
- ``micro_decode_split_tree_tokens_per_pass`` — perfect-draft
  committed tokens per verify pass with tree_width=1.

Per-config tick walls and compile counts ride as extras.
``engine.mbu`` gating on the decode program stays PENDING the first
real TPU row (ROADMAP A0/A1): on CPU there is no honest
peak to divide by (``utils/profiling.roofline_peaks``).

Usage: ``python benchmarks/micro/decode_split.py [--ticks 3]``
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, int_flag  # noqa: E402


def main() -> int:
    n_ticks = int_flag(sys.argv, "--ticks", 3)
    slots = 2
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from adapt_tpu.config import KernelConfig, SpeculativeConfig
        from adapt_tpu.models.transformer_lm import transformer_lm
        from adapt_tpu.runtime.continuous import ContinuousBatcher
        from adapt_tpu.utils.profiling import global_compile_sentinel

        sentinel = global_compile_sentinel()
        sentinel.warmup_samples = 10**9  # this driver compiles a lot

        errors: list[str] = []
        extras: dict = {}

        # -- 1) split grid ---------------------------------------------
        # 128-token pages: the block the kernels support. Requests
        # outlive the measured window.
        steps = 2 * (n_ticks + 2) + 2
        chunk = 2
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 41, size=5).astype(np.int32)
                   for _ in range(slots)]

        def run_grid(dtype, split):
            lm = transformer_lm(41, 32, 2, 2, 64, max_len=256)
            variables = lm.graph.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
            )
            kw: dict = dict(
                kv_cache_dtype=dtype, chunk=chunk, page_size=128,
                pool_pages=slots * 3 + 1,
            )
            kern = (
                None if split == "xla"
                else KernelConfig(attn_impl="pallas", decode_split=split)
            )
            bat = ContinuousBatcher(
                lm, variables, slots=slots, kernel=kern, **kw
            )
            ids = [bat.submit(p, steps) for p in prompts]
            bat.tick()
            bat.tick()
            h2d0 = bat.stats()["h2d_transfers"]
            t0 = time.perf_counter()
            for _ in range(n_ticks):
                bat.tick()
            wall = (time.perf_counter() - t0) * 1e3 / n_ticks
            h2d = (bat.stats()["h2d_transfers"] - h2d0) / n_ticks
            entries = sentinel.compiles("continuous.step_chunk")
            out = bat.run()
            grew = sentinel.compiles("continuous.step_chunk") - entries
            bat.close()
            return out, h2d, wall, grew

        worst_h2d = 0.0
        for dtype in ("native", "int8", "int4"):
            base = None
            for split in ("xla", 1, 2, 4):
                tag = f"paged_{dtype}_s{split}"
                out, h2d, wall, grew = run_grid(dtype, split)
                extras[f"{tag}_tick_ms"] = round(wall, 3)
                extras[f"{tag}_h2d_per_tick"] = h2d
                worst_h2d = max(worst_h2d, h2d)
                if h2d != 0:
                    errors.append(f"{tag}: steady tick staged {h2d}")
                if grew:
                    errors.append(f"{tag}: churn compiled {grew}")
                if base is None:
                    base = out
                else:
                    for rid in out:
                        if not np.array_equal(out[rid], base[rid]):
                            errors.append(
                                f"{tag}: stream diverged from the "
                                f"{dtype} baseline"
                            )
                            break

        # -- 2) tree-draft acceptance ----------------------------------
        lm = transformer_lm(41, 32, 2, 2, 64, max_len=192)
        variables = lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        per_pass = {}
        for name, w in (("chain", 0), ("tree", 1)):
            bat = ContinuousBatcher(
                lm, variables, slots=slots, draft_lm=lm,
                draft_variables=variables,
                speculative=SpeculativeConfig(draft_k=4, tree_width=w),
            )
            for p in prompts:
                bat.submit(p, 150)
            bat.tick()
            bat.tick()
            e0 = sum(len(s.tokens) for s in bat.slots
                     if s.req is not None)
            rounds = 5
            for _ in range(rounds):
                bat.tick()
            e1 = sum(len(s.tokens) for s in bat.slots
                     if s.req is not None)
            per_pass[name] = (e1 - e0) / (rounds * slots)
            extras[f"{name}_tokens_per_pass"] = round(per_pass[name], 3)
            bat.close()
        if per_pass["tree"] <= per_pass["chain"]:
            errors.append(
                f"tree {per_pass['tree']} did not beat chain "
                f"{per_pass['chain']} on the perfect-draft arm"
            )

        if errors:
            err = "; ".join(errors)[-300:]
            emit("micro_decode_split_h2d_per_tick", 1.0,
                 "transfers/tick", 0.0, error=err, **extras)
            emit("micro_decode_split_tree_tokens_per_pass", 0.0,
                 "tokens/pass", 0.0, error=err)
            return 0
        emit(
            "micro_decode_split_h2d_per_tick",
            worst_h2d,
            "transfers/tick",
            0.0,
            ticks=n_ticks,
            slots=slots,
            **extras,
        )
        emit(
            "micro_decode_split_tree_tokens_per_pass",
            round(per_pass["tree"], 3),
            "tokens/pass",
            round(per_pass["tree"] - 5.0, 3),
            draft_k=4,
            tree_width=1,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        emit("micro_decode_split_h2d_per_tick", 1.0, "transfers/tick",
             0.0, error=str(e)[-300:])
        emit("micro_decode_split_tree_tokens_per_pass", 0.0,
             "tokens/pass", 0.0, error=str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
