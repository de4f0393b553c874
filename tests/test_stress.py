"""Membership-fuzz stress test: randomized worker kills and joins under
sustained request load.

SURVEY.md §5 names "race detection / sanitizers" as absent from the
reference (manual locking only); our analog is this deterministic-seed
fuzz of membership events against the control plane's invariants:

  1. every submitted request either completes with the correct value or
     fails loudly — none lost, none duplicated (exactly-once);
  2. the pipeline keeps serving as long as >= 1 worker survives;
  3. the dispatcher's in-flight registry drains to empty.

Also exercises the tracing hook (stage_exec spans) under concurrency.
"""

import random
import threading
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from adapt_tpu.config import FaultConfig, ServeConfig
from adapt_tpu.control.worker import StageWorker, WorkerState
from adapt_tpu.graph import INPUT, LayerGraph, partition
from adapt_tpu.runtime import ServingPipeline
from adapt_tpu.utils.tracing import global_tracer


def _graph(width=8, depth=3):
    g = LayerGraph("stress")
    prev = INPUT
    for i in range(depth):
        prev = g.add(f"dense{i}", nn.Dense(width), prev)
    return g


def test_membership_fuzz_exactly_once(rng, devices):
    random.seed(1234)
    g = _graph()
    x0 = jnp.ones((2, 8))
    variables = g.init(rng, x0)
    plan = partition(g, ["dense0", "dense1"])  # 3 stages
    config = ServeConfig(
        max_inflight=16,
        fault=FaultConfig(
            lease_ttl_s=0.4,
            heartbeat_s=0.1,
            task_deadline_s=1.5,
            watchdog_period_s=0.05,
            startup_wait_s=2.0,
            max_retries=4,
            configure_timeout_s=10.0,
        ),
    )
    pipe = ServingPipeline(plan, variables, devices=devices[:6], config=config)
    tracer = global_tracer()
    tracer.clear()
    tracer.enabled = True
    try:
        pipe.start()
        pipe.warmup(x0)
        expected = {}
        futures = {}
        stop_chaos = threading.Event()
        spawned = []

        def chaos():
            """Kill a random live worker (crash or hang) every ~150 ms and
            occasionally add a fresh worker — but always keep >= 2 alive."""
            idx = len(pipe.workers)
            while not stop_chaos.is_set():
                time.sleep(random.uniform(0.1, 0.2))
                live = [
                    w
                    for w in pipe.workers + spawned
                    if w.state is not WorkerState.DEAD and not w._hung.is_set()
                ]
                if len(live) > 2 and random.random() < 0.7:
                    victim = random.choice(live)
                    victim.kill(random.choice(["crash", "hang"]))
                elif random.random() < 0.5:
                    w = StageWorker(
                        worker_id=f"joined-{idx}",
                        device=devices[idx % 6],
                        registry=pipe.registry,
                        result_queue=pipe.dispatcher.result_queue,
                        fault=config.fault,
                    )
                    idx += 1
                    pipe.dispatcher.attach_worker(w)
                    w.start()
                    spawned.append(w)

        chaos_t = threading.Thread(target=chaos, daemon=True)
        chaos_t.start()

        full = jax.jit(g.apply)
        n_requests = 60
        for i in range(n_requests):
            x = jnp.full((2, 8), float(i % 7) - 3.0)
            futures[i] = pipe.dispatcher.submit(x)
            expected[i] = np.asarray(full(variables, x))
            time.sleep(random.uniform(0.0, 0.02))

        completed, failed = 0, 0
        for i, f in futures.items():
            try:
                y = f.result(timeout=60.0)
                np.testing.assert_allclose(
                    np.asarray(y), expected[i], rtol=1e-5, atol=1e-5
                )
                completed += 1
            except Exception:
                failed += 1
        stop_chaos.set()
        chaos_t.join(timeout=2.0)

        # Invariant 1: everything accounted for.
        assert completed + failed == n_requests
        # Invariant 2: the pool never dropped below 2 live workers, so the
        # overwhelming majority must complete (failures only possible if a
        # request burned all retries on freshly-killed workers).
        assert completed >= n_requests * 0.9, (completed, failed)
        # Invariant 3: in-flight registry drains.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with pipe.dispatcher._inflight_lock:
                if not pipe.dispatcher._inflight:
                    break
            time.sleep(0.05)
        with pipe.dispatcher._inflight_lock:
            assert not pipe.dispatcher._inflight
        # Tracing hook saw real concurrent execution.
        spans = tracer.spans("stage_exec")
        assert len(spans) >= completed * 3  # >= one span per stage per req
        # Request-latency histogram populated.
        snap = pipe.metrics()
        assert snap["histograms"]["request.latency_s"]["count"] >= completed
    finally:
        tracer.enabled = False
        pipe.shutdown()


def test_membership_fuzz_with_cross_host_join(rng, devices):
    """Exactly-once must hold while the pool GROWS across hosts: mid-burst,
    a remote worker process joins through the WorkerGateway while local
    workers are being killed (the reference's scheduling pool grew and
    shrank the same way, src/dispatcher.py:176-201 + node_state.py:17-20)."""
    from adapt_tpu.comm.remote import WorkerGateway
    from adapt_tpu.config import CodecConfig
    from adapt_tpu.models.vit import vit_tiny

    random.seed(99)
    g = vit_tiny()
    x0 = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(rng, x0)
    from adapt_tpu.graph import partition as partition_fn

    plan = partition_fn(g, ["encoder_block_1"])
    config = ServeConfig(
        max_inflight=8,
        fault=FaultConfig(
            lease_ttl_s=0.6,
            heartbeat_s=0.15,
            task_deadline_s=8.0,
            watchdog_period_s=0.1,
            startup_wait_s=5.0,
            max_retries=4,
            configure_timeout_s=30.0,
        ),
        codec=CodecConfig(name="bf16", weights="lz"),
    )
    from adapt_tpu.control.dispatcher import Dispatcher

    disp = Dispatcher(plan, variables, config=config)
    local = disp.spawn_workers(devices[:3])
    gateway = WorkerGateway(
        disp,
        model_config={
            "model": "vit_tiny",
            "num_classes": 10,
            "cuts": ["encoder_block_1"],
            "input_shape": [2, 32, 32, 3],
        },
    )
    full = jax.jit(g.apply)
    y_ref = np.asarray(full(variables, x0))
    procs = []
    # Invariant 3 below is "the joiner BECAME a member": an event, so
    # it is recorded as one. Polling `alive()` after the burst missed
    # it under load: with this fuzz's 0.6 s leases a starved joiner
    # joins, lapses and never re-registers, all before the poll starts.
    joined = threading.Event()
    disp.registry.watch(
        lambda event, wid: joined.set()
        if (event, wid) == ("join", "fuzz-joiner")
        else None
    )
    try:
        disp.start()
        gateway.start()
        disp.warmup(x0)

        futures = {}
        n_requests = 24
        for i in range(n_requests):
            futures[i] = disp.submit(x0)
            if i == 4:
                # Pool grows: remote joiner dials in mid-burst.
                from conftest import spawn_worker_proc

                procs.append(
                    spawn_worker_proc(
                        "--connect", f"127.0.0.1:{gateway.port}",
                        "--worker-id", "fuzz-joiner", "--heartbeat", "0.1",
                    )
                )
            if i == 10:
                # Pool shrinks: one local worker crashes, one hangs.
                local[0].kill("crash")
                local[1].kill("hang")
            time.sleep(random.uniform(0.0, 0.05))

        completed = failed = 0
        for i, f in futures.items():
            try:
                y = f.result(timeout=120.0)
                # bf16 activation codec on the remote hop: loose tolerance.
                np.testing.assert_allclose(
                    np.asarray(y), y_ref, rtol=0.1, atol=0.1
                )
                completed += 1
            except Exception:
                failed += 1
        # Invariant 1: everything accounted for, none lost/duplicated.
        assert completed + failed == n_requests
        # Invariant 2: >= 1 worker always lived, so the stream survives.
        assert completed >= n_requests * 0.9, (completed, failed)
        # Invariant 3: the joiner actually became a member. The limit
        # covers a cold `python -m` child (jax+flax import) on a LOADED
        # machine; registration itself is milliseconds once the process
        # is up.
        assert joined.wait(60.0), "joiner never registered"
        # Invariant 4: in-flight registry drains.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with disp._inflight_lock:
                if not disp._inflight:
                    break
            time.sleep(0.05)
        with disp._inflight_lock:
            assert not disp._inflight
    finally:
        for p in procs:
            p.terminate()
            p.wait(timeout=10)
        gateway.stop()
        disp.shutdown()
