"""Sequence-parallel prefill micro-benchmark: byte-equality + the
per-device prefill-wall split (ROADMAP item 5 / ISSUE 15).

Two gated records (``benchmarks/baselines/seed.json``):

- ``micro_sp_prefill_pages_exact`` — STRUCTURAL, exactly 1.0: over the
  native/int8 grid, the sp=2 prefiller's page-major blocks are
  BYTE-EQUAL to the single-device chunked prefill's pages
  (``PrefillWorker``, page-sized chunks), and greedy streams through
  an sp-enabled batcher are BIT-IDENTICAL to the plain batcher on the
  same prompts. Any mismatch becomes an error record the gate always
  fails.
- ``micro_sp_prefill_flops_ratio`` — the prefill-wall split, measured
  structurally: compiled-module ``cost_analysis`` flops of the
  single-device whole-span prefill program divided by the sp=2
  program's PER-DEVICE flops at a 64-page span (~1.95: each ring rank
  computes half the O(S^2) score block plus the ring/psum overhead).
  Gated >= ~1.5 — the "sp=2 at least 1.5x faster than sp=1" pin,
  expressed as the per-chip work ratio because THIS CI box has ONE
  core: its virtual devices serialize, so a wall-clock A/B here
  measures scheduling noise, not the split (the same
  pending-real-hardware discipline as the ``engine.mbu`` gate). The
  wall ratio still rides as an ungated extra so a multi-core or TPU
  run shows up in the record.

Usage: ``python benchmarks/micro/sp_prefill.py [--pages 64]``
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import emit, force_cpu_mesh, int_flag  # noqa: E402

VOCAB = 61
PAGE = 8


def main() -> int:
    pages = int_flag(sys.argv, "--pages", 64)
    try:
        force_cpu_mesh(4)
        import jax
        import jax.numpy as jnp
        import numpy as np

        from adapt_tpu.config import PrefillConfig
        from adapt_tpu.models.transformer_lm import transformer_lm
        from adapt_tpu.parallel.sp_prefill import SPPrefiller, build_sp_mesh
        from adapt_tpu.runtime.continuous import ContinuousBatcher
        from adapt_tpu.runtime.disagg import PrefillWorker
        from adapt_tpu.utils.profiling import global_compile_sentinel

        # The driver builds several batchers/prefillers on purpose —
        # their first compiles are legitimate (tp_decode's rule).
        global_compile_sentinel().warmup_samples = 10**9
        rng = np.random.RandomState(0)

        # -- byte-equality grid (small LM: equality is scale-pinned) --
        lm = transformer_lm(VOCAB, 32, 2, 2, 64, max_len=96,
                            name="spp_lm")
        variables = lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        prompt = rng.randint(1, VOCAB, size=41).astype(np.int32)
        violations: list[str] = []
        for dtype in ("native", "int8"):
            w = PrefillWorker(
                lm, variables, page_size=PAGE, prefill_chunk=PAGE,
                kv_cache_dtype=dtype, name=f"ref-{dtype}",
            )
            w.submit(1, prompt)
            outs = []
            while not outs:
                outs = w.step()
            ref = outs[0].blocks
            pf = SPPrefiller(
                lm, variables, build_sp_mesh(2), PAGE,
                kv_cache_dtype=dtype, name=f"sp-{dtype}",
            )
            _, blocks = pf.prefill(prompt)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(blocks)):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    violations.append(
                        f"{dtype}: sp=2 pages differ from the "
                        "single-device chunked prefill"
                    )
                    break
            pf.close()

        # -- greedy-stream bit-identity through the batcher ------------
        prompts = [rng.randint(1, VOCAB, size=n).astype(np.int32)
                   for n in (41, 7, 33, 25)]

        def run_streams(sp_cfg):
            kw = dict(slots=2, chunk=4, kv_layout="paged",
                      page_size=PAGE, prefill_chunk=2 * PAGE)
            if sp_cfg is not None:
                kw["prefill"] = sp_cfg
            bat = ContinuousBatcher(lm, variables, **kw)
            rids = [bat.submit(p, 8) for p in prompts]
            outs = bat.run()
            st = bat.stats()
            bat.close()
            return [outs[r] for r in rids], st

        ref_streams, _ = run_streams(None)
        sp_streams, sp_st = run_streams(
            PrefillConfig(sp_threshold=24, sp_width=2)
        )
        for i, (a, b) in enumerate(zip(ref_streams, sp_streams)):
            if not np.array_equal(a, b):
                violations.append(f"stream {i} diverged under sp prefill")
        if sp_st.get("sp_prefills", 0) != 3:
            violations.append(
                f"expected 3 sp admissions, saw "
                f"{sp_st.get('sp_prefills')}"
            )

        # -- per-device prefill-wall split (compiled cost analysis) ----
        lm2 = transformer_lm(VOCAB, 64, 2, 4, 128,
                             max_len=pages * PAGE + 8, kv_heads=2,
                             name="spp_lm2")
        vars2 = lm2.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
        span = pages * PAGE
        long_prompt = rng.randint(1, VOCAB, size=span + 1).astype(np.int32)

        def compiled_flops(comp):
            ca = comp.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0] if ca else {}
            return float(ca.get("flops", 0.0))

        # sp=1 arm: the single-device whole-span program (the worker's
        # one-pass chunk body — the exact math the sp program splits).
        w1 = PrefillWorker(lm2, vars2, page_size=PAGE,
                           prefill_chunk=None, pool_pages=pages + 1,
                           name="sp1-arm")
        fn1 = w1._chunk_fn(span, pages)
        f1 = compiled_flops(
            fn1.lower(
                w1.variables, w1._pools,
                jnp.zeros((pages,), jnp.int32),
                jnp.zeros((1, span), jnp.int32),
                jnp.zeros((1,), jnp.int32),
            ).compile()
        )
        # sp=1 wall: run it.
        w1.submit(1, long_prompt)
        t0 = time.perf_counter()
        outs = []
        while not outs:
            outs = w1.step()
        wall_sp1 = time.perf_counter() - t0

        pf2 = SPPrefiller(lm2, vars2, build_sp_mesh(2), PAGE,
                          name="sp2-arm")
        fn2 = pf2._sp_fn(pages)
        f2 = compiled_flops(
            fn2.lower(
                pf2._variables,
                jax.device_put(
                    np.zeros((1, span), np.int32), pf2._repl
                ),
            ).compile()
        )
        t0 = time.perf_counter()
        pf2.prefill(long_prompt)
        wall_sp2 = time.perf_counter() - t0
        pf2.close()
        flops_ratio = f1 / f2 if f2 else 0.0

        if violations:
            for metric in ("micro_sp_prefill_pages_exact",
                           "micro_sp_prefill_flops_ratio"):
                emit(metric, 0.0, "structural", 0.0,
                     error="; ".join(violations)[:300])
            return 0
        emit(
            "micro_sp_prefill_pages_exact", 1.0,
            "1.0 = sp pages byte-equal + greedy streams bit-identical",
            0.0,
            grid="{native,int8} pages x {41,7,33,25}-token streams",
            sp_width=2,
        )
        emit(
            "micro_sp_prefill_flops_ratio", flops_ratio,
            "single-device / per-device sp=2 compiled prefill flops",
            0.0,
            span_tokens=span,
            flops_sp1=f1,
            flops_sp2_per_device=f2,
            # Ungated context: on this 1-core box the virtual devices
            # serialize, so wall_ratio ~<= 1 is EXPECTED; on real
            # parallel hardware it tracks the flops ratio.
            wall_sp1_s=round(wall_sp1, 4),
            wall_sp2_s=round(wall_sp2, 4),
            wall_ratio=round(wall_sp1 / wall_sp2, 4) if wall_sp2 else 0.0,
        )
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        for metric in ("micro_sp_prefill_pages_exact",
                       "micro_sp_prefill_flops_ratio"):
            emit(metric, 0.0, "structural", 0.0, error=str(e)[-300:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
