"""The readings `falcon-h1-34b-instruct`'s `correct` block is set from,
on the chip, seed by seed: the correctness sample served exactly as
``lm_engine.correctness_sample`` serves it (8 slots: the sample is
three requests), then the largest served error against the plain
reference, what the reference itself reads when every block's output
is rounded to the next precision below the one served, and the error
of each control.

    chiprun -- python3 scripts/falcon_h1_limits.py --seed 1 [--rehearse]

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
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lower", default="float8_e4m3fn")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import falcon_h1_reference as ref
    from chipbench import lm_engine as eng
    from chipbench import manifest as mf
    from chipbench import traffic as tg

    manifest = mf.load()
    config = mf.config_of(manifest, mf.cell(manifest, "falconh1_longgen"))
    model, serving = dict(config["model"]), dict(config["serving"])
    if a.rehearse:
        model.update(config["rehearse"]["model"])
    print("device", jax.devices()[0].device_kind, flush=True)
    lens = eng._sample_prompts(
        serving["prefill_chunk"], model["positions_served"]
    )
    steps = eng.SAMPLE_STEPS
    lm, variables, shape = mf.part_of(config, "builder")(
        model, config["dtype"], a.seed
    )
    srv = ContinuousBatcher(
        lm, variables, slots=8, chunk=serving["chunk"], kv_layout="paged",
        page_size=serving["page_size"], pool_pages=8 * 3 + 1,
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    drv = eng.Driver(srv, shape["vocab"], a.seed, contextlib.nullcontext)
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

    def sampled(x):
        x = np.asarray(x)
        return np.concatenate([x[row, s] for row, s in enumerate(at)])

    served = np.concatenate(got)
    want = sampled(ref.next_token_logprobs(variables, ids))
    err = np.abs(served - want)
    print(f"seed {a.seed}: max|err| {err.max():.4f} mean {err.mean():.4f} "
          f"over {err.size} positions; by request "
          + " ".join(f"{err[i * steps:(i + 1) * steps].max():.4f}"
                     for i in range(len(lens))), flush=True)
    low = sampled(ref.next_token_logprobs(
        variables, ids, arch={"round_to": a.lower}
    ))
    print(f"  reference with block outputs rounded to {a.lower}: against the "
          f"float32 reference {np.abs(low - want).max():.4f}; served "
          f"against it {np.abs(served - low).max():.4f}", flush=True)
    for fault in ref.CONTROLS:
        e = np.abs(served - sampled(
            ref.next_token_logprobs(variables, ids, fault=fault)
        ))
        print(f"  control {fault}: {e.max():.4f} (by request "
              + " ".join(f"{e[i * steps:(i + 1) * steps].max():.4f}"
                         for i in range(len(lens))) + ")", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
