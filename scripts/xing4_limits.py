"""The readings ``xing4.0-29b-a4b``'s ``correct`` block is set from, on
the chip, seed by seed.

    chiprun -- python3 scripts/xing4_limits.py --seeds 1,2,3 [--out F]
    python3 scripts/xing4_limits.py --judge F      (no chip)

Each seed: the cell's deployment as ``lm_engine.run_cell`` builds it
(ONE batcher a process, that seed's weights swapped in, as
``chipbench/kexaone_flips.py`` does), the correctness sample through
``lm_engine.correctness_sample`` itself (its line is what a run of
``xing4_longgen8k`` at that seed prints), every control of the
configuration through the same function, and the reference with what the
served model keeps in bfloat16 (sub-layer inputs and outputs, cache rows)
rounded to the next precision below (``float8_e4m3fn``), which must read
over the tolerance. One JSON line a
seed goes to ``--out``: per compared position the served error, its gap
in each sparse layer, and the error under each control. ``--judge``
reads such a file back and holds every seed to the margins and the
``correct`` block as committed (a file kept at fewer ``--steps`` than
the block's ``sample_steps`` is held to the block's shares all the same,
and a control to the gaps of its own faulty reference where the file
kept them). ``--rehearse`` walks it at tiny widths under
``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "xing4_longgen8k"
LOWER = "float8_e4m3fn"  # the next precision below bfloat16


def _correct():
    from chipbench import manifest as mf

    manifest = mf.load(ROOT)
    return mf.config_of(manifest, mf.cell(manifest, CELL))["correct"]


def judge(path: str) -> int:
    import numpy as np

    from chipbench import xing4_reference as ref

    correct = _correct()
    tol = correct["logprob_tol"]
    rows = [json.loads(ln) for ln in open(path)]
    wrong = 0
    worst_all, counts, least_of = [], [], {}
    for r in rows:
        err, gaps = np.asarray(r["err"]), np.asarray(r["gaps"], np.float32)
        sure = np.asarray(ref.vouched(gaps[:, None, :]))[0]
        least = int(np.ceil(correct["min_vouched"] * err.size))
        worst = float(err[sure].max()) if sure.any() else float("nan")
        ok = bool(worst <= tol and sure.sum() >= least)
        wrong += not ok
        worst_all.append(worst)
        counts.append(int(sure.sum()))
        line = (f"seed {r['seed']}: vouched {sure.sum()} of {err.size}, "
                f"largest vouched {worst:.4f}, not vouched "
                f"{err[~sure].max() if not sure.all() else 0.0:.4f} -> "
                f"{'ok' if ok else 'WRONG'}")
        controls = r.get("controls", {})
        for name, e in controls.items():
            if "." in name.removeprefix(LOWER):
                continue  # a control's verdict or gaps, not its errors
            mine = sure  # a faulty reference vouches by its own gaps
            if name + ".gaps" in controls:
                own = np.asarray(controls[name + ".gaps"], np.float32)
                mine = np.asarray(ref.vouched(own[:, None, :]))[0]
            reading = float(np.asarray(e)[mine].max()) if mine.any() else 0.0
            least_of.setdefault(name, []).append(reading)
            line += f"  {name} {reading:.4f}"
        print(line)
    print(f"{wrong} of {len(rows)} seeds read WRONG; largest vouched error "
          f"{min(worst_all):.4f}-{max(worst_all):.4f}, vouched "
          f"{min(counts)}-{max(counts)}; tolerance {tol}; smallest reading "
          "that must be over it: " + ", ".join(
              f"{name} {min(v):.4f}" for name, v in least_of.items()
              if not name.startswith("served_vs_")))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--out", default="")
    ap.add_argument("--judge", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--steps", type=int, default=0,
                    help="serve this many steps a request instead of the "
                    "file's sample_steps (a longer sample holds every "
                    "shorter one: the first steps are the same)")
    ap.add_argument("--controls", default="",
                    help="of the reference's controls, only these")
    a = ap.parse_args()
    if a.judge:
        return judge(a.judge)

    import jax
    import numpy as np

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import lm_engine as eng
    from chipbench import manifest as mf
    from chipbench import traffic as tg
    from chipbench import xing4_reference as ref

    manifest = mf.load(ROOT)
    cell = mf.cell(manifest, CELL)
    config = mf.config_of(manifest, cell)
    traffic = mf.traffic_of(manifest, cell)
    correct = dict(config["correct"])
    if a.steps:
        correct["sample_steps"] = a.steps
    controls = a.controls.split(",") if a.controls else ref.CONTROLS
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
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    out = open(a.out, "a") if a.out else None
    srv = None
    for seed in (int(s) for s in a.seeds.split(",")):
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
            logp, gaps = ref.logprobs_and_gaps(variables, ids, fault)
            kept.update(ids=ids, logp=np.asarray(logp), gaps=np.asarray(gaps))
            return logp, ref.vouched(gaps)

        def sample(fault=""):
            """-> what the engine compared, and the served logprobs it
            claimed (a request's are handed out once)."""
            drv = eng.Driver(srv, shape["vocab"], seed, contextlib.nullcontext)
            claimed = []
            hand_out = srv.logprobs

            def logprobs(rid):
                claimed.append(np.asarray(hand_out(rid), np.float32))
                return claimed[-1]

            srv.logprobs = logprobs
            try:
                c = eng.correctness_sample(
                    drv, variables, serving, shape["max_len"], capture,
                    correct, fault,
                )
            finally:
                del srv.logprobs
            return c, np.concatenate(claimed)

        compared, got = sample()
        print(f"seed {seed}: {compared.line()}", flush=True)
        lens = eng._sample_prompts(
            serving["prefill_chunk"], shape["max_len"], steps
        )
        at = [(row, n - 1 + j) for row, n in enumerate(lens)
              for j in range(steps)]
        rows, cols = (np.asarray(x) for x in zip(*at))

        def sampled(logp):
            return np.asarray(logp)[rows, cols]

        sound = sampled(kept["logp"])
        record = dict(
            seed=seed, steps=steps, line=compared.line(), ok=compared.ok,
            err=np.abs(got - sound).tolist(),
            gaps=kept["gaps"][:, rows, cols].tolist(), controls={},
        )
        ids = kept["ids"]
        low = ref.logprobs_and_gaps(variables, ids, arch={"round_to": LOWER})[0]
        record["controls"][LOWER] = np.abs(sampled(low) - sound).tolist()
        record["controls"]["served_vs_" + LOWER] = np.abs(
            got - sampled(low)
        ).tolist()
        sure = np.asarray(ref.vouched(kept["gaps"]))[rows, cols]
        print(f"  {LOWER}: reference rounded vs float32 "
              f"{np.abs(sampled(low) - sound)[sure].max():.4f}, served vs "
              f"rounded {np.abs(got - sampled(low))[sure].max():.4f} (vouched)",
              flush=True)
        for fault in controls:  # the file's `controls` and one_stream
            c, served = sample(fault)
            print(f"  --fault {fault}: {c.line()}", flush=True)
            record["controls"][fault] = np.abs(
                served - sampled(kept["logp"])
            ).tolist()
            record["controls"][fault + ".ok"] = c.ok
            # the faulty reference vouches by its own gaps
            record["controls"][fault + ".gaps"] = kept["gaps"][
                :, rows, cols
            ].tolist()
        if out:
            out.write(json.dumps(record) + "\n")
            out.flush()
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
