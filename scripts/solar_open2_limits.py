"""The readings ``solar-open2-250b``'s ``correct`` block is set from, on
the chip, seed by seed. What is read comes off the configuration's own
entries: its reference's ``logprobs_and_gaps``, ``vouched``, ``FAULTS``
and ``PRECISION``, its ``correct`` block.

    chiprun -- python3 scripts/solar_open2_limits.py --seeds 1,2,3 [--out F]
    python3 scripts/solar_open2_limits.py --judge F [--lengths 128,512]

``--cell`` reads another cell whose reference has the same entries
(``gigachat35_longgen8k``: ``gigachat3.5-432b-a28b``'s block).

Each seed: the cell's deployment as ``lm_engine.run_cell`` builds it
(ONE batcher a process, that seed's weights swapped in, as
``scripts/xing4_limits.py`` does), the correctness sample served ONCE
through ``lm_engine.correctness_sample`` itself (its line is what a run
of the cell at that seed prints); then, against the same served
logprobs, the reference under every fault it knows (``FAULTS``: a
fault changes the reference alone) and in the precision below the one
the configuration states (``PRECISION``: for Solar-Open2 every KDA
layer's state rounded to bfloat16 after each position, and every
mixer's, expert layer's and layer's output rounded to
``float8_e4m3fn``); ``--readings`` names fewer. One JSON line a seed
goes to ``--out``: per compared position the served error, its gap in
each layer, and the error and gaps under each reading. ``--judge``
reads such a file back and holds every seed to the margins and the
``correct`` block as committed; a sample of ``--steps`` served steps a
request holds every shorter one, and ``--lengths`` judges each.
``--rehearse`` walks it at tiny widths under ``JAX_PLATFORMS=cpu``.
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

CELL = "solaropen2_longgen"


def _parts(cell: str = CELL):
    """The cell's configuration, traffic and reference module."""
    import importlib

    from chipbench import manifest as mf

    manifest = mf.load(ROOT)
    entry = mf.cell(manifest, cell)
    config = mf.config_of(manifest, entry)
    ref = importlib.import_module(config["reference"].split(":")[0])
    return config, mf.traffic_of(manifest, entry), ref


def judge(path: str, lengths: list[int], cell: str = CELL) -> int:
    import numpy as np

    config, _, ref = _parts(cell)
    correct = config["correct"]
    tol = correct["logprob_tol"]
    must = set(correct["controls"])

    def reading(err, gaps, keep):
        err = np.asarray(err)[keep]
        sure = np.asarray(
            ref.vouched(np.asarray(gaps, np.float32)[:, None, keep])
        )[0]
        worst = float(err[sure].max()) if sure.any() else float("nan")
        return worst, int(sure.sum()), (
            float(err[~sure].max()) if not sure.all() else 0.0
        )

    rows = [json.loads(ln) for ln in open(path)]
    for length in lengths or [0]:
        wrong, sound, counts, least_of = 0, [], [], {}
        for r in rows:
            # positions are kept request by request, ``steps`` each
            step = np.arange(len(r["err"])) % r["steps"]
            keep = step < (length or r["steps"])
            worst, n, out = reading(r["err"], r["gaps"], keep)
            least = int(np.ceil(correct["min_vouched"] * keep.sum()))
            ok = bool(worst <= tol and n >= least)
            wrong += not ok
            sound.append(worst)
            counts.append(n)
            line = (f"seed {r['seed']}: vouched {n} of {keep.sum()}, largest "
                    f"vouched {worst:.4f}, not vouched {out:.4f} -> "
                    f"{'ok' if ok else 'WRONG'}")
            for name, c in r["controls"].items():
                w, n_c, _ = reading(c["err"], c["gaps"], keep)
                least_of.setdefault(name, []).append(w)
                reads_wrong = not (w <= tol and n_c >= least)
                line += f"  {name} {w:.4f}" + (
                    " (READS OK)" if name in must and not reads_wrong else ""
                )
            print(line)
        print(f"{length or 'all'} steps: {wrong} of {len(rows)} seeds read "
              f"WRONG; largest vouched error {min(sound):.4f}-"
              f"{max(sound):.4f}, vouched {min(counts)}-{max(counts)}; "
              f"tolerance {tol}; each reading's range: " + ", ".join(
                  f"{name} {min(v):.4f}-{max(v):.4f}"
                  for name, v in least_of.items()))
    return 0


def state_readings(ref, srv, variables, ids, lens, steps) -> dict:
    """Per reading (the reference proper, and with its state kept in
    bfloat16) and KDA layer, the distance of a served slot's state
    from the reference's after the same ``n + steps - 1`` tokens, over
    the reference's norm; the largest of the three requests'. Which
    slot a request had is the nearest of the first eight."""
    import jax.numpy as jnp

    served = [st[0][:8] for st in srv._states]  # (8, H, d_k, d_v) a layer
    out = {}
    for name in ("", "state_bfloat16"):
        worst = [0.0] * len(served)
        for row, n in enumerate(lens):
            want = []
            ref.hidden_states(
                variables, ids[row: row + 1, : n + steps - 1], name,
                arch={"states": want},
            )
            for layer, (got, w) in enumerate(zip(served, want)):
                far = jnp.sqrt(((got - w) ** 2).sum((1, 2, 3))) / (
                    jnp.sqrt((w ** 2).sum())
                )
                worst[layer] = max(worst[layer], float(far.min()))
        out[name or "sound"] = worst
        print(f"  served state against the reference's "
              f"({name or 'sound'}), by KDA layer: "
              + " ".join(f"{w:.5f}" for w in worst), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--out", default="")
    ap.add_argument("--judge", default="")
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--lengths", default="",
                    help="with --judge: hold the first N served steps of "
                    "every request to the rule, for each N listed")
    ap.add_argument("--readings", default="",
                    help="the faults and precisions to read (default: all "
                    "the reference knows)")
    ap.add_argument("--state", action="store_true",
                    help="also hold every KDA layer's served state of the "
                    "sample's three slots to the reference's at the same "
                    "position (every tick drained, steps - 1 a whole number "
                    "of scans: the state then stands where the row ended)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--steps", type=int, default=0,
                    help="serve this many steps a request instead of the "
                    "file's sample_steps")
    a = ap.parse_args()
    if a.judge:
        return judge(
            a.judge, [int(n) for n in a.lengths.split(",") if n], a.cell
        )

    import jax
    import numpy as np

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import lm_engine as eng
    from chipbench import manifest as mf
    from chipbench import traffic as tg

    config, traffic, ref = _parts(a.cell)
    correct = dict(config["correct"])
    if a.steps:
        correct["sample_steps"] = a.steps
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
    if a.state and (steps - 1) % serving["chunk"]:
        raise SystemExit("--state: steps - 1 must be a multiple of the scan")
    readings = a.readings.split(",") if a.readings else [
        *ref.FAULTS, *ref.PRECISION
    ]
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
            if a.state:
                # No scan past a request's end: every tick is committed
                # before the next is dispatched, so a row's state stays
                # where its last served step left it.
                tick = srv.tick
                srv.tick = lambda: tick() + srv.drain()
        else:
            srv.variables = variables
        kept, claimed = {}, []

        def capture(variables, ids, fault=""):
            logp, gaps = ref.logprobs_and_gaps(variables, ids, fault)
            kept.update(ids=ids, logp=np.asarray(logp), gaps=np.asarray(gaps))
            return logp, ref.vouched(gaps)

        hand_out = srv.logprobs

        def logprobs(rid):  # a request's are handed out once
            claimed.append(np.asarray(hand_out(rid), np.float32))
            return claimed[-1]

        srv.logprobs = logprobs
        try:
            compared = eng.correctness_sample(
                eng.Driver(srv, shape["vocab"], seed, contextlib.nullcontext),
                variables, serving, shape["max_len"], capture, correct,
            )
        finally:
            del srv.logprobs
        got = np.concatenate(claimed)
        print(f"seed {seed}: {compared.line()}", flush=True)
        lens = eng._sample_prompts(
            serving["prefill_chunk"], shape["max_len"], steps
        )
        at = [(row, n - 1 + j) for row, n in enumerate(lens)
              for j in range(steps)]
        rows, cols = (np.asarray(x) for x in zip(*at))
        record = dict(
            seed=seed, steps=steps, line=compared.line(), ok=compared.ok,
            err=np.abs(got - kept["logp"][rows, cols]).tolist(),
            gaps=kept["gaps"][:, rows, cols].tolist(), controls={},
        )
        for name in readings:
            logp, gaps = ref.logprobs_and_gaps(variables, kept["ids"], name)
            err = np.abs(got - np.asarray(logp)[rows, cols])
            gaps = np.asarray(gaps)[:, rows, cols]
            sure = np.asarray(ref.vouched(gaps[:, None, :]))[0]
            print(f"  {name}: largest vouched error "
                  f"{err[sure].max() if sure.any() else float('nan'):.4f} "
                  f"(vouched {sure.sum()} of {err.size})", flush=True)
            record["controls"][name] = dict(
                err=err.tolist(), gaps=gaps.tolist(),
                # the reference against itself: what the fault alone moves
                moved=np.abs(
                    np.asarray(logp)[rows, cols] - kept["logp"][rows, cols]
                ).tolist(),
            )
        if a.state:
            record["state"] = state_readings(
                ref, srv, variables, kept["ids"], lens, steps
            )
        if out:
            out.write(json.dumps(record) + "\n")
            out.flush()
    print("state_bytes", srv.stats()["state_bytes"], "peak",
          (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
          flush=True)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
