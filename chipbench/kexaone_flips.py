"""The readings ``k-exaone-236b-a23b``'s ``correct`` block is set from,
on the chip, seed by seed and sparse layer by sparse layer.

    chiprun -- python3 chipbench/kexaone_flips.py --seeds 1,2,3 [--controls] [--out F]

Each seed: the cell's deployment as ``lm_engine.run_cell`` builds it
(ONE batcher a process, that seed's weights swapped in), the
correctness sample through ``lm_engine.correctness_sample`` itself
(its line is what a run of ``kexaone_longgen`` at that seed prints),
then per compared position the served error beside the reference's
gap IN EACH SPARSE LAYER. For a position whose error is over
``--look`` the reference is run once more a sparse layer with that
position's nearest held expert put on the other side of the bar
(``ARCH["flip"]``): the layer whose flipped pass agrees with the
served value is where the served router chose otherwise, at that
layer's gap. ``--controls``: each control of the configuration through
the engine's own function, and the reference with every layer's output
rounded to the next precision below the one served. One JSON line a
seed goes to ``--out`` (and every expert's score at the compared
positions to ``<out>.<seed>.npz``); ``--judge F`` reads such a file back and holds
every seed to the rule as committed (no chip).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "kexaone_longgen"
LOWER = "float8_e4m3fn"  # the next precision below bfloat16


def judge(path: str) -> int:
    """Hold every seed of a ``--out`` file to ``vouched`` and the
    configuration's ``correct`` block as they stand now."""
    import numpy as np

    from chipbench import k_exaone_reference as ref
    from chipbench import lm_engine as eng
    from chipbench import manifest as mf

    manifest = mf.load()
    correct = mf.config_of(manifest, mf.cell(manifest, CELL))["correct"]
    tol = correct["logprob_tol"]
    rows = [json.loads(ln) for ln in open(path)]
    wrong = 0
    for r in rows:
        err, gaps = np.asarray(r["err"]), np.asarray(r["gaps"], np.float32)
        sure = np.asarray(ref.vouched(gaps[:, None, :]))[0]
        least = int(np.ceil(correct["min_vouched"] * err.size))
        worst = float(err[sure].max()) if sure.any() else float("nan")
        c = eng.Compared(
            bool(worst <= tol and sure.sum() >= least), worst, tol,
            int(sure.sum()), err.size, least,
            float(err[~sure].max()) if not sure.all() else 0.0,
        )
        wrong += not c.ok
        line = f"seed {r['seed']}: {c.line()}"
        for name, e in r.get("controls", {}).items():
            if not name.endswith(".ok"):
                line += f"  {name} {np.asarray(e)[sure].max():.4f}"
        print(line)
    print(f"{wrong} of {len(rows)} seeds read WRONG")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--look", type=float, default=0.04)
    ap.add_argument("--out", default="")
    ap.add_argument("--judge", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.judge:
        return judge(a.judge)
    import jax
    import numpy as np

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import k_exaone_reference as ref
    from chipbench import lm_engine as eng
    from chipbench import manifest as mf
    from chipbench import traffic as tg

    manifest = mf.load()
    cell = mf.cell(manifest, CELL)
    config = mf.config_of(manifest, cell)
    traffic = mf.traffic_of(manifest, cell)
    correct = config["correct"]
    model = dict(config["model"])
    serving = {**config["serving"], **traffic.get("serving", {})}
    if a.rehearse:
        model.update(config["rehearse"]["model"])
        serving.update(config["rehearse"]["serving"])
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU (or --rehearse under JAX_PLATFORMS=cpu)")
    else:
        from adapt_tpu.utils.compile_cache import ensure_compile_cache

        print(f"compile cache {ensure_compile_cache()}", flush=True)
    print("device", jax.devices()[0].device_kind, flush=True)
    builder = mf.part_of(config, "builder")
    steps = eng._sample_steps(correct)
    out = open(a.out, "a") if a.out else None
    srv = None
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        if srv is not None:  # the last seed's weights go before these come
            srv.variables = variables = None
            gc.collect()
        lm, variables, shape = builder(model, config["dtype"], seed)
        if srv is None:
            max_total = min(shape["max_len"], serving["prompt_buckets"][-1])
            pairs = tg.templates(traffic, max_total)
            serving["pool_pages"] = eng.pool_pages(
                serving, pairs, shape["max_len"], steps
            )
            srv = ContinuousBatcher(
                lm, variables, slots=serving["slots"], chunk=serving["chunk"],
                kv_layout=serving["kv_layout"],
                page_size=serving["page_size"],
                pool_pages=serving["pool_pages"],
                prefill_chunk=serving["prefill_chunk"],
                prompt_buckets=tuple(serving["prompt_buckets"]),
            )
        else:
            srv.variables = variables
        kept = {}

        def capture(variables, ids, fault=""):
            scores = []
            logp, gaps = ref.logprobs_and_gaps(
                variables, ids, fault, arch={"scores": scores}
            )
            kept.update(ids=ids, logp=np.asarray(logp),
                        gaps=np.asarray(gaps), scores=scores)
            return logp, ref.vouched(gaps)

        def sample(reference, fault=""):
            """-> what the engine compared, and the served logprobs it
            claimed (a request's are handed out once)."""
            drv = eng.Driver(
                srv, shape["vocab"], seed, contextlib.nullcontext
            )
            claimed = []
            hand_out = srv.logprobs

            def logprobs(rid):
                claimed.append(np.asarray(hand_out(rid), np.float32))
                return claimed[-1]

            srv.logprobs = logprobs
            try:
                c = eng.correctness_sample(
                    drv, variables, serving, shape["max_len"], reference,
                    correct, fault,
                )
            finally:
                del srv.logprobs
            return c, claimed

        compared, got = sample(capture)
        print(f"seed {seed}: {compared.line()}", flush=True)
        lens = eng._sample_prompts(
            serving["prefill_chunk"], shape["max_len"], steps
        )
        at = [(row, n - 1 + j) for row, n in enumerate(lens)
              for j in range(steps)]
        rows, cols = (np.asarray(x) for x in zip(*at))

        def sampled(logp):
            return np.asarray(logp)[rows, cols]

        got = np.concatenate(got)
        err = np.abs(got - sampled(kept["logp"]))
        gaps = kept["gaps"][:, rows, cols]  # (layers, 24)
        print("  position: err | gap a sparse layer", flush=True)
        for i in range(err.size):
            print(f"  {i:2d}: {err[i]:.4f} | "
                  + " ".join(f"{g:.4f}" for g in gaps[:, i]), flush=True)
        record = dict(
            seed=seed, line=compared.line(), ok=compared.ok,
            err=err.tolist(), gaps=gaps.tolist(), flips=[],
        )
        if out:  # every expert's score at the compared positions
            np.savez_compressed(
                f"{a.out}.{seed}.npz", err=err,
                scores=np.stack([np.asarray(s)[rows, cols]
                                 for s, _ in kept["scores"]]),
                chosen_by=np.stack([np.asarray(c)[rows, cols]
                                    for _, c in kept["scores"]]),
            )
        for i in np.flatnonzero(err > a.look):
            mask = np.zeros(kept["ids"].shape, bool)
            mask[rows[i], cols[i]] = True

            def flipped(*layers):
                """-> the served error left at position i, and its
                gaps, with these layers' nearest held expert flipped."""
                logp, g = ref.logprobs_and_gaps(
                    variables, kept["ids"],
                    arch={"flip": {layer: mask for layer in layers}},
                )
                g = np.asarray(g)[:, rows[i], cols[i]]
                return float(abs(got[i] - sampled(logp)[i])), g.tolist()

            n = gaps.shape[0]
            tried = {(layer,): flipped(layer) for layer in range(n)}
            if min(e for e, _ in tried.values()) > a.look / 2:
                # No one flip explains it: two, the second on the path
                # the first one set off.
                tried.update({
                    (p, q): flipped(p, q)
                    for p in range(n) for q in range(p + 1, n)
                })
            best = min(tried, key=lambda k: tried[k][0])
            print(f"  position {i}: err {err[i]:.4f}, gaps "
                  + " ".join(f"{g:.4f}" for g in gaps[:, i])
                  + "; err left with the nearest held expert flipped in "
                  + "  ".join(f"{k}: {e:.4f}" for k, (e, _) in tried.items())
                  + f" -> {best}, gaps on that path "
                  + " ".join(f"{g:.4f}" for g in tried[best][1]), flush=True)
            record["flips"].append(dict(
                position=int(i), err=float(err[i]), gaps=gaps[:, i].tolist(),
                tried={",".join(map(str, k)): v for k, v in tried.items()},
                best=list(best),
            ))
        if a.controls:
            low, _ = ref.logprobs_and_gaps(
                variables, kept["ids"], arch={"round_to": LOWER}
            )
            record["controls"] = {
                LOWER: np.abs(sampled(low) - sampled(kept["logp"])).tolist(),
                "served_vs_" + LOWER: np.abs(got - sampled(low)).tolist(),
            }
            for fault in correct["controls"]:
                c, _ = sample(capture, fault)
                print(f"  --fault {fault}: {c.line()}", flush=True)
                record["controls"][fault] = np.abs(
                    got - sampled(kept["logp"])
                ).tolist()
                record["controls"][fault + ".ok"] = c.ok
        if out:
            out.write(json.dumps(record) + "\n")
            out.flush()
        stats = jax.devices()[0].memory_stats() or {}
        print(f"  seed {seed} took {time.perf_counter() - t0:.1f}s; device "
              f"bytes in use {stats.get('bytes_in_use', 0)}", flush=True)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
