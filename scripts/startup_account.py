"""The compile account's control: build one cell's deployment, serve one
request, print what every program of the process cost to trace, to
lower and to compile or load (``CompileSentinel.account()``).

    chiprun -- python3 scripts/startup_account.py gpt2xl_chat \
        [--bucket] [--scrape] [--seed N]
    JAX_PLATFORMS=cpu python3 scripts/startup_account.py gpt2xl_chat \
        --rehearse --bucket --scrape      # tiny widths: counts, no times

The deployment is the cell's (``BENCHMARK.json``: the configuration's
builder and serving block, the traffic mix's slots, the benchmark's
pool rule); the first request is one chunked prompt (``prefill_chunk``
+ 45 tokens, two passes), 8 tokens out. Then, each printing only the
account's rows that changed:

``--bucket``  one prompt in a bucket nothing has compiled (40 tokens:
    the whole-prompt program of the smallest bucket). The positive
    control: a program added is a program seen, with its price. The
    account must show exactly one more variant under
    ``continuous.prefill`` with a trace, a lowering and a backend
    compile of its own, and no other watch changed.
``--scrape``  one ``global_metrics().snapshot()`` with the engine's
    collector on, as an exporter's first scrape (and the benchmark's
    snapshot at the window's open) takes it: ``_program_costs`` asks
    for the step program's lowering again, with ``ShapeDtypeStruct``
    stand-ins. Either the account shows a second lowering of
    ``continuous.step_chunk`` with no backend compile, or JAX served it
    from its in-process cache and nothing fired. The line says which.

The line ``compiles N`` is the benchmark's own ``CompileCounter`` (a
second, independent listener on the backend event): it must equal the
account's ``programs``.

``--run`` instead runs the benchmark's own command for the cell in this
process (``chipbench/run.py --workload <cell> --seed N --trace T``, the
window and all) and prints the account after its result line: set-up
by program, as the run paid it.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

STAGES = ("trace_s", "lower_s", "backend_s")
COUNTS = ("traces", "lowerings", "variants", "cache_hits", "cache_misses")


def _line(name: str, row: dict) -> str:
    return (
        f"  {name:<34} variants {row['variants']:>4}  traces "
        f"{row['traces']:>4}  lowerings {row['lowerings']:>4}  trace "
        f"{row['trace_s']:9.3f}s  lower {row['lower_s']:9.3f}s  backend "
        f"{row['backend_s']:9.3f}s  cache hit {row['cache_hits']:>3} miss "
        f"{row['cache_misses']:>3}"
    )


def show(acc: dict, title: str) -> None:
    print(title, flush=True)
    tot = acc["totals"]
    print(
        f"  totals: programs {tot['programs']}  trace {tot['trace_s']:.3f}s"
        f"  lower {tot['lower_s']:.3f}s  backend {tot['backend_s']:.3f}s"
        f"  | cache hits {tot['cache_hits']} misses {tot['cache_misses']}"
        f" load {tot['cache_load_s']:.3f}s saved {tot['cache_saved_s']:.3f}s",
        flush=True,
    )
    print(" by program (a watch, or `other`):", flush=True)
    for name, row in sorted(
        acc["programs"].items(),
        key=lambda kv: -sum(kv[1][s] for s in STAGES),
    ):
        print(_line(name, row), flush=True)
    print(" `other` by function, the largest first:", flush=True)
    for name, row in acc["other"].items():
        print(_line(name, row), flush=True)


def changed(before: dict, after: dict, table: str) -> dict:
    """Rows of ``table`` that differ, as after - before."""
    zero = dict.fromkeys(STAGES + COUNTS, 0)
    out = {}
    for name, row in after[table].items():
        was = before[table].get(name, zero)
        d = {k: row[k] - was[k] for k in zero}
        if any(d[k] for k in COUNTS) or any(abs(d[k]) > 0 for k in STAGES):
            out[name] = d
    return out


def show_changed(before: dict, after: dict, title: str) -> dict:
    watches = changed(before, after, "programs")
    print(title, flush=True)
    if not watches:
        print("  nothing: no listener fired", flush=True)
    for name, d in watches.items():
        print(_line(name, d), flush=True)
    for name, d in changed(before, after, "other").items():
        if name != "(rest)":  # a change of rank moves a name in or out of it
            print(_line("other: " + name, d), flush=True)
    return watches


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bucket", action="store_true")
    ap.add_argument("--scrape", action="store_true")
    ap.add_argument("--run", action="store_true",
                    help="run the benchmark's command, then print the account")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: counts, no device time")
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))

    if args.run:
        from chipbench import run

        rc = run.main(
            ["--workload", args.cell, "--seed", str(args.seed), "--trace",
             str(args.trace)]
            + (["--rehearse"] if args.rehearse else [])
        )
        from adapt_tpu.utils.profiling import global_compile_sentinel

        show(global_compile_sentinel().account(),
             f"account after the benchmark's run of {args.cell}:")
        return rc

    import jax

    from chipbench import lm_engine
    from chipbench import manifest as mf
    from chipbench import traffic as tg

    manifest = mf.load(root)
    cell = mf.cell(manifest, args.cell)
    config = mf.config_of(manifest, cell, root)
    traffic = mf.traffic_of(manifest, cell, root)
    platform = jax.devices()[0].platform
    if args.rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse needs JAX_PLATFORMS=cpu")
    elif platform != "tpu":
        raise SystemExit(f"{args.cell} needs a TPU; JAX found {platform}")
    else:
        from adapt_tpu.utils.compile_cache import ensure_compile_cache

        print(f"compile cache {ensure_compile_cache()}", flush=True)

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from adapt_tpu.utils.metrics import global_metrics
    from adapt_tpu.utils.profiling import (
        engine_collector,
        global_compile_sentinel,
    )

    sent = global_compile_sentinel()
    compiles = lm_engine.CompileCounter()
    model = dict(config["model"])
    serving = {**config["serving"], **traffic.get("serving", {})}
    if args.rehearse:
        model.update(config["rehearse"]["model"])
        serving.update(config["rehearse"]["serving"])
    lm, variables, shape = mf.part_of(config, "builder")(
        model, config["dtype"], args.seed
    )
    max_total = min(shape["max_len"], serving["prompt_buckets"][-1])
    pairs = tg.templates(traffic, max_total)
    serving["pool_pages"] = lm_engine.pool_pages(
        serving, pairs, shape["max_len"]
    )
    srv = ContinuousBatcher(
        lm, variables,
        slots=serving["slots"], chunk=serving["chunk"],
        kv_layout=serving["kv_layout"], page_size=serving["page_size"],
        pool_pages=serving["pool_pages"],
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    served = 0

    def serve(n_prompt: int) -> None:
        nonlocal served
        ids = tg.token_ids(args.seed, served, n_prompt, shape["vocab"])
        served += 1
        rid = srv.submit(ids, 8)
        if len(srv.run()[rid]) != 8:
            raise RuntimeError(f"request {rid} did not finish")

    first = min(serving["prefill_chunk"] + 45, shape["max_len"] - 8)
    serve(first)
    acc = sent.account()
    show(acc, f"account after one request ({first} tokens in, 8 out), "
         f"{args.cell}:")
    agree = "agree" if compiles.count == acc["totals"]["programs"] else "DIFFER"
    print(
        f"compiles {compiles.count} ({compiles.seconds:.1f}s in backend "
        f"compile or cache load) | account: programs "
        f"{acc['totals']['programs']} backend {acc['totals']['backend_s']:.1f}s"
        f" -> {agree}", flush=True,
    )
    if args.bucket:
        before = sent.account()
        serve(40)
        watches = show_changed(
            before, sent.account(),
            "--bucket: one prompt of 40 tokens (the smallest bucket's "
            "whole-prompt program); what changed:",
        )
        d = watches.pop("continuous.prefill", dict.fromkeys(COUNTS, 0))
        watches.pop("other", None)
        ok = (
            (d["traces"], d["lowerings"], d["variants"]) == (1, 1, 1)
            and not watches
        )
        print(
            f"bucket: continuous.prefill +{d['variants']} variant "
            f"(+{d['traces']} trace, +{d['lowerings']} lowering), other "
            f"watches changed: {sorted(watches) or 'none'} -> "
            f"{'ok' if ok else 'NOT the one program expected'}", flush=True,
        )
    if args.scrape:
        before = sent.account()
        global_metrics().register_collector(engine_collector)
        global_metrics().snapshot()
        watches = show_changed(
            before, sent.account(),
            "--scrape: one snapshot with the engine's collector on; what "
            "changed:",
        )
        d = watches.get("continuous.step_chunk", dict.fromkeys(
            STAGES + COUNTS, 0))
        if d["lowerings"] or d["variants"]:
            print(
                f"scrape: continuous.step_chunk +{d['traces']} trace "
                f"({d['trace_s']:.3f}s) +{d['lowerings']} lowering "
                f"({d['lower_s']:.3f}s) +{d['variants']} backend: a process "
                "with an exporter pays the step program's lowering again",
                flush=True,
            )
        else:
            print(
                "scrape: JAX served the step program's lowering from its "
                f"in-process cache: no lowering fired (+{d['traces']} trace "
                f"event of {d['trace_s']:.3f}s, the cached trace's)",
                flush=True,
            )
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
