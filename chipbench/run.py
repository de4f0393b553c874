"""The benchmark's one command:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run. The last line of standard output is the result
object; where set-up went, histograms and lateness go on earlier
lines. Without the chips the cell asks for it exits non-zero and
prints no result. ``--rehearse`` walks cells at tiny widths on the CPU
and prints no device number.
"""

from __future__ import annotations

import time

CLOCK0 = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import manifest as mf  # noqa: E402
from chipbench import xtrace  # noqa: E402


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c in "._-" else "_" for c in name)


def _device_block(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def traced_device(trace) -> tuple[dict, dict]:
    """(``busy_s`` and ``window_s`` of the device block, ``breakdown``)
    of a traced run. The window's two edges and every interval are on
    the profiler's clock (``xtrace.device_window``): ``window_s`` is the
    time between the edges, ``busy_s`` the mean over the devices of the
    time inside them in which an operation ran, so 0 <= ``busy_s`` <=
    ``window_s`` whatever overhangs an edge, and ``idle_gaps`` (device
    0, the same edges) sums to device 0's ``window_s`` less busy time.
    ``device_ops`` is the whole trace's: what ran, beside the window
    (the kernels' rooflines sum theirs over the decode runs the trace
    holds whole: ``decode_runs.py``)."""
    lo, hi = xtrace.device_window(trace)
    busy = [
        sum(e - s for s, e in xtrace.busy_between(d, lo, hi)) / 1e9
        for d in trace.devices
    ]
    dev = trace.devices[0]
    gaps = xtrace.idle_gaps(dev, trace.host, lo, hi)
    return (
        {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) / 1e9},
        {
            "device_ops": xtrace.top(
                {_sanitize(k): v for k, v in xtrace.op_seconds(dev).items()}
            ),
            "idle_gaps": xtrace.top(
                {_sanitize(k): v for k, v in gaps.items()}
            ),
        },
    )


def run_one(manifest, name: str, args, root: Path = ROOT):
    """Run one cell; returns the result object (None in rehearsal)."""
    if not (args.rehearse and args.trace):
        return _run_one(
            manifest, name, args, root, str(root / ".chipbench_trace" / name)
        )
    # A rehearsal's trace gets a directory of its own: two test workers
    # that rehearse one cell would else empty `.chipbench_trace/<cell>`
    # one under the other's reader ("Failed to parse XSpace").
    trace_dir = tempfile.mkdtemp(prefix=f"chipbench_rehearsal_{name}_")
    try:
        return _run_one(manifest, name, args, root, trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _run_one(manifest, name: str, args, root: Path, trace_dir: str):
    import jax

    cell = mf.cell(manifest, name)
    config = mf.config_of(manifest, cell, root)
    traffic = mf.traffic_of(manifest, cell, root)
    # The faults this configuration's reference knows. Refused here,
    # before any weight is drawn, if --fault names another.
    controls = config.get("correct", {}).get("controls", ["drop_block"])
    if args.fault and args.fault not in controls:
        raise SystemExit(
            f"--fault {args.fault}: configuration {cell['config']!r} names "
            f"the controls {controls}"
        )
    devices = jax.devices()
    if args.rehearse:
        if devices[0].platform != "cpu":
            raise SystemExit("--rehearse needs JAX_PLATFORMS=cpu")
    elif devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise SystemExit(
            f"{name} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})"
        )
    else:
        # Where JAX_COMPILATION_CACHE_DIR says, else the program's own
        # fixed place inside the checkout (<checkout>/.jax_cache).
        from adapt_tpu.utils.compile_cache import ensure_compile_cache

        print(f"compile cache {ensure_compile_cache()}", flush=True)
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))  # modules a later PR adds
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    opts = argparse.Namespace(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        trace_dir=trace_dir, rehearse=args.rehearse, clock0=CLOCK0,
        fault=args.fault,
        sweep=[float(r) for r in args.sweep.split(",")] if args.sweep else None,
        annotate=(
            jax.profiler.TraceAnnotation if args.trace
            else contextlib.nullcontext
        ),
    )
    out = mf.part_of(config, "engine")(cell, config, traffic, opts)
    if out is None:  # a sweep prints its own lines and no result
        return None
    device = _device_block(devices, cell["chips"])
    if not args.trace:
        wanted = mf.metrics_of(manifest, name, "end_to_end")
        values = out["e2e"]
        trace = None
    else:
        path = xtrace.find_xplane(trace_dir)
        trace = xtrace.load(path) if path else None
        wanted = mf.metrics_of(manifest, name, "per_layer")
        values = {
            m["name"]: mf.reader_of(manifest, m["name"], root)(
                trace, out["records"], device["kind"]
            )
            for m in wanted
        }
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values.get(m["name"]) is not None
    }
    if args.rehearse:
        print(
            f"rehearsal {name}: correct={out['correct']} attempted="
            f"{out['attempted']} failed={out['failed']} would report "
            f"{sorted(metrics)} (no device was measured)", flush=True,
        )
        return None
    result = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics, "device": device,
    }
    if trace is not None and trace.devices:
        seconds, result["breakdown"] = traced_device(trace)
        device.update(seconds)
    # Every number compared beside its limit, as standard error's last
    # lines too: of a run that is not correct the driver keeps those.
    for line in out.get("compared", []):
        print(line, file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny widths on the CPU, every cell (or --workload); "
        "prints no result and no device number",
    )
    ap.add_argument(
        "--sweep", default="",
        help="open-loop cells: comma-separated rates, one window each in "
        "one process; prints a line per rate and no result",
    )
    ap.add_argument(
        "--fault", default="",
        help="self-test of `correct`: one of the controls the cell's "
        "configuration names under correct.controls (absent: drop_block, "
        "one block left out of the plain reference); a sound comparison "
        "must then answer false",
    )
    ap.add_argument(
        "--root", default=str(ROOT),
        help="directory that holds BENCHMARK.json (tests point this at "
        "a copy with files added)",
    )
    args = ap.parse_args(argv)
    root = Path(args.root)
    manifest = mf.load(root)
    if args.seconds is None:
        args.seconds = 3.0 if args.rehearse else manifest["run_seconds"]
    if args.rehearse:
        names = [args.workload] if args.workload else [
            w["name"] for w in manifest["workloads"]
        ]
        for name in names:
            # Under a fault, the untraced pass only: `correct` is
            # decided in set-up (lm_engine.correctness_sample), before
            # the window and before any trace starts, by the same code
            # in both passes, so a control's traced pass could only
            # repeat the untraced one.
            for trace in ((0,) if args.fault else (0, 1)):
                args.trace = trace
                run_one(manifest, name, args, root)
        return 0
    if not args.workload:
        ap.error("--workload is required")
    result = run_one(manifest, args.workload, args, root)
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
