"""Recovery-to-serve benchmark: kill one stage worker mid-stream.

BASELINE.md config 5 ("ViT encoder split by transformer block,
kill-one-stage fault-injection") and the second headline target:
recovery-to-serve < 2 s after one node kill.

Two configs:

  --config vit-tiny          4-stage ViT-tiny (control-plane floor: stage
                             weights are KB-scale, so the number isolates
                             detection + scheduling latency)
  --config resnet152-8stage  ResNet-152 in 8 balanced stages — the scale
                             BASELINE.md's <2 s budget was written for:
                             a failover re-bind pays a real multi-MB
                             stage-weight device_put, not a toy one

Runs on the virtual CPU mesh: recovery time is a *control-plane + weight
movement* metric, not an MXU metric — but a weight move between real
chips is not a host memcpy, so the real-chip recovery-to-serve number is
still to be taken (ROADMAP A6/B0).

Definition measured: from the moment a worker is killed (crash mode: the
exec loop dies and stops heartbeating — the reference's machine death)
until EVERY request that was in flight at kill time has completed
successfully. Crash detection is EVENT-driven: the dying exec loop
deregisters immediately (the reference evicts on socket error, not
timeout, ``/root/reference/src/dispatcher.py:153-161``); the lease TTL
remains as the backstop for the failure modes with no event (process
SIGKILL'd between instructions, network partition), so detect_s here
measures the event path, with the TTL as its ceiling.

Breakdown per trial (also written to ``--out`` as a JSON artifact):
  detect_s    kill -> membership 'leave' event (crash eviction; TTL
              expiry is the no-event backstop)
  rebind_s    kill -> first stage configure completed on a surviving worker
              after the kill (the weight device_put failover actually paid)
  total_s     kill -> all in-flight requests completed
  control_s   drain time of an identical burst with NO kill (same trial)
  overhead_s  (submit->done with kill) - control_s: what the kill actually
              cost end-to-end. On the CPU mesh total_s is dominated by
              re-running real stage compute on shared host cores; on
              per-stage TPU chips that replay is milliseconds, so
              detect+rebind+overhead is the hardware-transferable number.

Phase attribution (r4 verdict #8: one r04 trial carried overhead_s=2.6
against a <2 s budget with no diagnosis): each trial also records every
configure's (start, duration, worker, stage) after the kill, the
dispatcher counter deltas over the kill burst (redispatched / stale /
deadline strikes — was the overhead a replay storm?), accumulated GC
pause seconds inside the burst (was it the collector?), and the
completion watermarks' largest gap (was it ONE straggler request, e.g. a
second replay after a task deadline?). An outlier trial is then
attributable from the artifact alone instead of deserving a shrug.

Prints one JSON line; vs_baseline = 2.0 / median_total_s (>1 beats the
<2 s target).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")  # repo root

from benchmarks.common import distinct_inputs, emit, force_cpu_mesh  # noqa: E402

TARGET_S = 2.0

CONFIGS = {
    # name: (n_devices, n_stages, burst, trials)
    "vit-tiny": (8, 4, 8, 4),
    # >= 10 trials: the overhead decomposition subtracts a same-trial
    # control burst whose noise on shared CPU cores is ~±0.3 s — enough
    # trials to bound it (r3's 3-trial run even produced one negative
    # overhead).
    "resnet152-8stage": (8, 8, 6, 10),
}


def _build(config: str):
    import jax

    if config == "vit-tiny":
        from adapt_tpu.models.vit import vit_tiny

        graph = vit_tiny()
        x0 = jax.numpy.ones((1, 32, 32, 3), jax.numpy.float32)
        cuts = [f"encoder_block_{i}" for i in range(1, CONFIGS[config][1])]
    else:
        from adapt_tpu.graph.partition import balanced_cuts
        from adapt_tpu.models.resnet import resnet152

        graph = resnet152(num_classes=1000, dtype=jax.numpy.float32)
        x0 = jax.numpy.ones((1, 224, 224, 3), jax.numpy.float32)
        cuts = balanced_cuts(graph, CONFIGS[config][1])
    return graph, x0, cuts


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="vit-tiny", choices=sorted(CONFIGS))
    parser.add_argument("--out", default=None, help="write per-trial JSON here")
    parser.add_argument(
        "--trials", type=int, default=None, help="override the config's trials"
    )
    args = parser.parse_args()
    n_devices, n_stages, burst, trials = CONFIGS[args.config]
    if args.trials is not None:
        trials = args.trials

    force_cpu_mesh(n_devices)
    import jax

    from adapt_tpu.config import FaultConfig, ServeConfig
    from adapt_tpu.control.worker import WorkerState
    from adapt_tpu.graph.partition import partition
    from adapt_tpu.runtime.pipeline import ServingPipeline

    graph, x0, cuts = _build(args.config)
    variables = jax.jit(graph.init)(jax.random.PRNGKey(0), x0)
    plan = partition(graph, cuts)

    # Production-shaped fault config: sub-second failure detection, the
    # task deadline safely above per-request latency (ResNet-152 stages on
    # CPU take real time per request).
    config = ServeConfig(
        max_inflight=burst * 2,
        fault=FaultConfig(
            lease_ttl_s=0.5,
            heartbeat_s=0.1,
            task_deadline_s=30.0,
            watchdog_period_s=0.05,
            startup_wait_s=10.0,
            max_retries=3,
            configure_timeout_s=120.0,
        ),
    )

    trials_out = []
    for trial in range(trials):
        pipe = ServingPipeline(
            plan, variables, devices=jax.devices()[:n_devices], config=config
        ).start()
        try:
            # Breakdown hooks: membership 'leave' times + configure
            # completion times (a configure after the kill = the failover
            # re-bind paying its weight transfer). ALL leaves are
            # recorded with (time, worker): under heavy host load a
            # healthy worker's heartbeat can starve past the TTL and
            # briefly lapse-then-rejoin, and grabbing that first
            # spurious leave instead of the victim's would corrupt
            # detect_s (observed: negative detects).
            events = {"leaves": [], "configures": []}

            def on_member(event, wid, _ev=events):
                if event == "leave":
                    _ev["leaves"].append((time.monotonic(), wid))

            pipe.registry.watch(on_member)
            for w in pipe.workers:
                orig = w.configure

                def timed(
                    *a, _orig=orig, _w=w, _ev=events, **kw
                ):
                    t_start = time.monotonic()
                    r = _orig(*a, **kw)
                    _ev["configures"].append(
                        (t_start, time.monotonic(), _w.worker_id, a[0])
                    )
                    return r

                w.configure = timed

            pipe.warmup(x0)
            # Control burst: identical load, no kill — isolates the cost
            # of the failure from the cost of the compute itself.
            xs_ctrl = distinct_inputs(
                jax.random.PRNGKey(500 + trial), x0.shape, burst
            )
            t_ctrl = time.monotonic()
            for f in [pipe.dispatcher.submit(x) for x in xs_ctrl]:
                f.result(timeout=300.0)
            control_s = time.monotonic() - t_ctrl

            xs = distinct_inputs(
                jax.random.PRNGKey(100 + trial), x0.shape, burst
            )
            # Phase-attribution hooks for THIS burst: GC pauses and
            # dispatcher counters over exactly the kill window.
            import gc

            gc_pause = {"s": 0.0, "t0": None}

            def on_gc(phase, info, _g=gc_pause):
                if phase == "start":
                    _g["t0"] = time.monotonic()
                elif _g["t0"] is not None:
                    _g["s"] += time.monotonic() - _g["t0"]
                    _g["t0"] = None

            gc.callbacks.append(on_gc)
            from adapt_tpu.utils.metrics import global_metrics

            counters_before = dict(
                global_metrics().snapshot()["counters"]
            )
            t_submit = time.monotonic()
            futures = [pipe.dispatcher.submit(x) for x in xs]
            # Pick a victim that is actually involved: busy or has queued
            # tasks, so its in-flight work must be detected and replayed.
            victim = None
            deadline = time.monotonic() + 10.0
            while victim is None and time.monotonic() < deadline:
                for w in pipe.workers:
                    if w.state is WorkerState.BUSY or w.queue_depth > 0:
                        victim = w
                        break
                time.sleep(0.001)  # don't contend with the mesh under test
            if victim is None:  # burst already drained; any configured worker
                victim = next(
                    w
                    for w in pipe.workers
                    if any(w.is_configured(s) for s in range(n_stages))
                )
            t0 = time.monotonic()
            victim.kill("crash")
            # Completion watermarks: result() in submit order gives a
            # non-decreasing drain curve; its largest gap fingers a
            # straggler (a request replayed late) vs uniform slowdown.
            watermarks = []
            for f in futures:
                f.result(timeout=300.0)
                watermarks.append(time.monotonic())
            t_done = time.monotonic()
            gc.callbacks.remove(on_gc)
            counters_after = global_metrics().snapshot()["counters"]
            total = t_done - t0
            detect = next(
                (
                    t - t0
                    for (t, wid) in events["leaves"]
                    if wid == victim.worker_id and t >= t0
                ),
                None,
            )
            post_kill = [
                (start, end, wid, stage)
                for (start, end, wid, stage) in events["configures"]
                if end > t0
            ]
            rebind = (
                (min(end for (_, end, _, _) in post_kill) - t0)
                if post_kill
                else None
            )
            deltas = {
                k: counters_after.get(k, 0) - counters_before.get(k, 0)
                for k in (
                    "dispatcher.redispatched",
                    "dispatcher.stale_results",
                    "dispatcher.tasks_sent",
                    "dispatcher.probes_ok",
                )
            }
            gaps = [
                b - a for a, b in zip(watermarks, watermarks[1:])
            ]
            trials_out.append(
                {
                    "trial": trial,
                    "victim": victim.worker_id,
                    "detect_s": detect,
                    "rebind_s": rebind,
                    "total_s": total,
                    "control_s": control_s,
                    "overhead_s": (t_done - t_submit) - control_s,
                    # -- phase attribution --
                    "post_kill_configures": [
                        {
                            "at_s": round(start - t0, 4),
                            "dur_s": round(end - start, 4),
                            "worker": wid,
                            "stage": stage,
                        }
                        for (start, end, wid, stage) in sorted(post_kill)
                    ],
                    "counter_deltas": deltas,
                    "gc_pause_s": round(gc_pause["s"], 4),
                    "max_completion_gap_s": round(max(gaps), 4)
                    if gaps
                    else 0.0,
                }
            )
        finally:
            pipe.shutdown()

    med = statistics.median(t["total_s"] for t in trials_out)
    artifact = {
        "config": args.config,
        "n_devices": n_devices,
        "n_stages": n_stages,
        "burst": burst,
        "backend": "cpu-virtual-mesh",
        "lease_ttl_s": config.fault.lease_ttl_s,
        "trials": trials_out,
        "median_total_s": med,
        "median_detect_s": statistics.median(
            t["detect_s"] for t in trials_out if t["detect_s"] is not None
        )
        if any(t["detect_s"] is not None for t in trials_out)
        else None,
        "median_overhead_s": statistics.median(
            t["overhead_s"] for t in trials_out
        ),
        "rebinds_observed": sum(
            1 for t in trials_out if t["rebind_s"] is not None
        ),
        "target_s": TARGET_S,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
    emit(
        f"recovery_to_serve_{args.config}_s",
        med,
        "seconds",
        TARGET_S / med if med > 0 else float("inf"),
    )


if __name__ == "__main__":
    main()
