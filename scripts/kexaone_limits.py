"""The readings `k-exaone-236b-a23b`'s `correct` block is set from, on
the chip, seed by seed: the correctness sample served exactly as
``lm_engine.correctness_sample`` serves it, then per compared position
the served error beside the reference's gap (how far the last expert
chosen stood from the first left out, in router logits, where either
is held here), the
error of each control, and what the reference itself reads when every
layer's output is rounded to the next precision below the one served.

    chiprun -- python3 scripts/kexaone_limits.py --seeds 1 [--rehearse]

One seed a process on the chip: a second model does not fit beside
what the first one's compiled programs keep alive.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lower", default="float8_e4m3fn")
    ap.add_argument("--quick", action="store_true",
                    help="skip the controls and the lower precision")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import k_exaone_reference as ref
    from chipbench import lm_engine as eng
    from chipbench import manifest as mf
    from chipbench import traffic as tg

    manifest = mf.load()
    cell = mf.cell(manifest, "kexaone_longgen")
    config = mf.config_of(manifest, cell)
    model, serving = dict(config["model"]), dict(config["serving"])
    if a.rehearse:
        model.update(config["rehearse"]["model"])
        serving.update(config["rehearse"]["serving"])
    print("device", jax.devices()[0].device_kind, flush=True)
    lens = eng._sample_prompts(serving["prefill_chunk"], model["positions_served"])
    steps = eng.SAMPLE_STEPS
    for seed in (int(s) for s in a.seeds.split(",")):
        lm, variables, shape = mf.part_of(config, "builder")(
            model, config["dtype"], seed
        )
        srv = ContinuousBatcher(
            lm, variables, slots=serving["slots"], chunk=serving["chunk"],
            kv_layout="paged", page_size=serving["page_size"],
            pool_pages=serving["slots"] * 3 + 1,
            prefill_chunk=serving["prefill_chunk"],
            prompt_buckets=tuple(serving["prompt_buckets"]),
        )
        drv = eng.Driver(srv, shape["vocab"], seed, contextlib.nullcontext)
        rids = [drv.submit(tg.Request(n, steps), time.perf_counter())
                for n in lens]
        drv.run_until(lambda: all(r not in drv.live for r in rids))
        ids = np.zeros((len(rids), max(lens) + steps), np.int32)
        for row, rid in enumerate(rids):
            info = drv.reqs[rid]
            seq = np.concatenate([info["ids"], np.asarray(info["tokens"])])
            ids[row, : len(seq)] = seq
        got = [np.asarray(srv.logprobs(r), np.float32) for r in rids]
        srv.close()
        ids = jnp.asarray(ids)
        at = [slice(n - 1, n - 1 + steps) for n in lens]

        def served_error(want):
            want = np.asarray(want)
            return np.concatenate(
                [np.abs(g - want[row, s]) for row, (g, s) in enumerate(zip(got, at))]
            )

        want, gap = ref.logprobs_and_gap(variables, ids)
        for row, n in enumerate(lens):  # ties before each sample's answers
            g = np.asarray(gap)[row, : n - 1]
            print(f"  prompt of {n}: positions before its answers with a "
                  f"gap under 0.01 / 0.03: {int((g < 0.01).sum())} / "
                  f"{int((g < 0.03).sum())}", flush=True)
        gap = np.concatenate(
            [np.asarray(gap)[row, s] for row, s in enumerate(at)]
        )
        err = served_error(want)
        order = np.argsort(gap)
        print("  in sample order (gap err): " + " ".join(
            f"({gap[i]:.3f} {err[i]:.4f})" for i in range(err.size)
        ), flush=True)
        print(f"seed {seed}: max|err| over all {err.size} positions "
              f"{err.max():.4f}; by gap (gap err): "
              + " ".join(f"({gap[i]:.4f} {err[i]:.4f})" for i in order[:12]),
              flush=True)
        for margin in (0.0, 0.01, 0.02, 0.03, 0.05, 0.08, 0.12, 0.2):
            sure = gap >= margin
            print(f"  margin {margin}: vouched {int(sure.sum())} of "
                  f"{err.size}, worst vouched {err[sure].max():.4f}, worst "
                  f"kept out {err[~sure].max() if (~sure).any() else 0:.4f}",
                  flush=True)
        if a.quick:
            continue
        low, _ = ref.logprobs_and_gap(
            variables, ids, arch={"round_to": a.lower}
        )
        low_err = served_error(low)
        ref_err = np.concatenate([
            np.abs(np.asarray(low) - np.asarray(want))[row, s]
            for row, s in enumerate(at)
        ])
        sure = gap >= ref.MARGIN
        print(f"  reference with layer outputs rounded to {a.lower}: against "
              f"the float32 reference {ref_err[sure].max():.4f} at the "
              f"positions vouched at MARGIN {ref.MARGIN} ({ref_err.max():.4f} "
              f"at all); served against it {low_err[sure].max():.4f}",
              flush=True)
        for fault in ref.CONTROLS:
            w, _ = ref.logprobs_and_gap(variables, ids, fault=fault)
            e = served_error(w)
            print(f"  control {fault}: worst vouched {e[sure].max():.4f}, "
                  f"smallest vouched {e[sure].min():.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
