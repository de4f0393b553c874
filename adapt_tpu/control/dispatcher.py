"""Adaptive dispatcher: the Gen-2 hub-and-spoke control loop, completed.

This is the working re-expression of the reference's *intended* design —
the five lost methods of ``/root/reference/src/dispatcher.py`` rebuilt on a
device mesh (SURVEY.md §0, §2.6-2.7):

- ``_worker_monitor``       -> registry watch callbacks (:276)
- ``_get_available_workers``-> ``WorkerRegistry.alive()`` (:285)
- ``_intermediate_result_server`` -> ``_result_loop`` draining the result
  queue every worker posts to (:298; fragment :121-161)
- ``_task_watchdog``        -> ``_watchdog_loop`` over the in-flight
  registry (:303)
- ``_acquire_and_configure_worker`` -> ``_acquire`` + lazy
  ``StageWorker.configure`` (:178; config handshake :223-264)

Semantics beyond the reference (SURVEY.md §7.4): requests carry ids and
attempt counters, so watchdog re-dispatch plus a late-completing original
worker cannot duplicate or drop a request (the reference could do both).
"""

from __future__ import annotations

import itertools
import os
import queue
import random
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any

import jax

from adapt_tpu.config import ObservabilityConfig, ServeConfig
from adapt_tpu.control.registry import WorkerRegistry
from adapt_tpu.control.worker import (
    PING_STAGE,
    StageWorker,
    Task,
    TaskResult,
    WorkerState,
)
from adapt_tpu.graph.partition import PartitionPlan
from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import (
    aggregate_size_fn,
    global_compile_sentinel,
    global_engine_obs,
)
from adapt_tpu.utils.tracing import global_flight_recorder, global_tracer

log = get_logger("dispatcher")

#: Live dispatchers (weak): per-stage compile watches SUM across them
#: (profiling.aggregate_size_fn) — a second dispatcher must not
#: silently unwatch the first.
_LIVE_DISPATCHERS: "weakref.WeakSet[Dispatcher]" = weakref.WeakSet()


class RequestFailed(RuntimeError):
    """A request exhausted its retries (or no workers remain)."""


class PipelineFuture:
    """Completion handle for one submitted request."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self.submit_time = time.monotonic()
        self._event = threading.Event()
        self._value: Any = None
        self._error: str | None = None

    def _complete(self, value: Any = None, error: str | None = None) -> bool:
        if self._event.is_set():
            return False  # exactly-once: late duplicates dropped
        self._value, self._error = value, error
        self._event.set()
        return True

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> Any:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not done within {timeout}s"
            )
        if self._error is not None:
            raise RequestFailed(self._error)
        return self._value


@dataclass
class _Inflight:
    """Reference in-flight entry: ``{(worker_ip, partition_idx):
    {partition, data, start_time}}`` with the raw payload retained for
    re-send (``src/dispatcher.py:186-194``) — keyed here by request id,
    extended with attempt/retry counters for exactly-once."""

    request_id: int
    stage_index: int
    attempt: int
    payload: Any
    worker_id: str
    start_time: float
    retries: int = 0
    future: PipelineFuture = field(default=None)  # type: ignore[assignment]
    # Workers that already failed/stalled this request: re-dispatch excludes
    # ALL of them, not just the latest (a pool with several hung workers
    # must not bounce one request among them until retries burn out).
    tried: set[str] = field(default_factory=set)
    #: Chain mode (comm.remote direct forwarding): the stage index whose
    #: result completes this request. None = hub routing (the entry's own
    #: stage). A chain entry holds the ORIGINAL stage-0 payload, so any
    #: chain failure re-dispatches end-to-end through the hub path.
    final_stage: int | None = None


class Dispatcher:
    """Hub dispatcher over in-process stage workers."""

    def __init__(
        self,
        plan: PartitionPlan,
        variables,
        registry: WorkerRegistry | None = None,
        config: ServeConfig | None = None,
        journal=None,
    ):
        """``journal`` — optional :class:`~adapt_tpu.control.journal.
        DispatcherJournal`: accepted requests and the dial-out worker
        table survive a dispatcher crash, and :meth:`recover` rebuilds a
        serving dispatcher from them (the reference's
        etcd-outlives-the-dispatcher property, ``src/start_etcd.sh:81-94``,
        re-scoped per SURVEY §7.5). Journaling an accepted request costs
        one host fetch + fsync per submit."""
        self.plan = plan
        self.config = config or ServeConfig()
        self._journal = journal
        # Push the observability knobs onto the process-global tracer /
        # flight recorder. Both are apply-only-when-opinionated: tracing
        # switched on by env (ADAPT_TPU_TRACE) or another component
        # stays on, and a DEFAULT capacity never clobbers a ring another
        # component explicitly sized (a second default-config dispatcher
        # in-process must not truncate the first one's history).
        obs = self.config.obs
        if obs.trace_enabled:
            global_tracer().enabled = True
        _obs_defaults = ObservabilityConfig()
        if obs.trace_capacity != _obs_defaults.trace_capacity:
            global_tracer().set_capacity(obs.trace_capacity)
        if obs.flight_capacity != _obs_defaults.flight_capacity:
            global_flight_recorder().set_capacity(obs.flight_capacity)
        # Engine-tier knobs ride the same apply-only-when-opinionated
        # rules: obs_engine is enable-only, compile_warmup applies only
        # when non-default (utils.profiling).
        if obs.obs_engine:
            global_engine_obs().enabled = True
        if obs.compile_warmup != _obs_defaults.compile_warmup:
            global_compile_sentinel().warmup_samples = obs.compile_warmup
        self.registry = registry or WorkerRegistry(
            default_ttl_s=self.config.fault.lease_ttl_s
        )
        # One shared jitted fn per stage: jit caches executables per device,
        # so configuring the same stage on another same-kind device reuses
        # the compiled program (recovery = weight move, not recompile).
        self._stage_fns = [
            jax.jit(plan.stage_apply(spec)) for spec in plan.stages
        ]
        # Compile-sentinel watch on the stage programs: a failover
        # re-bind is supposed to be a weight move, never a recompile —
        # the sentinel turns a violation into a counted, logged event.
        # Watches sum over the weakly-held live-dispatcher set (two
        # concurrent dispatchers aggregate, neither is silently
        # unwatched; a collected dispatcher's stages drop out).
        _LIVE_DISPATCHERS.add(self)
        for i in range(len(self._stage_fns)):
            global_compile_sentinel().register(
                f"dispatch.stage{i}",
                size_fn=aggregate_size_fn(
                    _LIVE_DISPATCHERS,
                    lambda d, i=i: (
                        d._stage_fns[i]._cache_size()
                        if i < len(d._stage_fns) else None
                    ),
                ),
            )
        self._stage_host_vars = plan.extract_variables(variables)
        # Precompiled re-shard plans (SURVEY.md §7.2.5): example input spec
        # per stage (recorded on first dispatch) + the set of (stage,
        # device) pairs whose executable is already in the jit cache.
        # Prewarming every pair during warmup means a failover re-bind is a
        # weight move, not an XLA recompile — the <2 s recovery budget.
        self._stage_examples: dict[int, jax.ShapeDtypeStruct] = {}
        self._prewarmed: set[tuple[int, Any]] = set()
        self._prewarm_lock = threading.Lock()
        self._prewarm_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="dispatcher-prewarm"
        )
        self._workers: dict[str, StageWorker] = {}
        self._workers_lock = threading.Lock()
        self.result_queue: queue.Queue[TaskResult] = queue.Queue()
        self._inflight: dict[int, _Inflight] = {}
        self._inflight_lock = threading.Lock()
        self._sem = threading.Semaphore(self.config.max_inflight)
        # Over a pre-existing journal, start past every id it has seen:
        # a fresh counter would recycle journaled ids — overwriting a
        # crashed request's payload and clearing its pending mark with
        # the new request's done.
        self._req_ids = itertools.count(
            journal.next_request_id if journal is not None else 0
        )
        self._watchdog_paused = False
        # Strike-based quarantine: a worker that keeps missing task
        # deadlines while heartbeating (a hang) is never evicted by lease
        # expiry; after `quarantine_strikes` deadline misses the scheduler
        # stops acquiring it (the reference's socket-error eviction,
        # src/dispatcher.py:153-161, generalized to hangs).
        #
        # How strikes accrue once rank demotes a struck worker (and real
        # traffic stops reaching it): the watchdog sends canary *probe*
        # tasks (PING_STAGE) to any alive worker that has been silent
        # beyond the probe window; a probe that misses the task deadline is
        # a strike like any other. Probes also self-heal: an answered probe
        # forgives probe-miss strikes (and, under quarantine, slowly decays
        # real-task strikes), so a recovered worker returns to service.
        #
        # _health_lock guards all four maps below — they are touched from
        # the result loop, the watchdog, and the forward pool concurrently.
        self._health_lock = threading.Lock()
        self._strikes: dict[str, int] = {}
        # Of those, the strikes earned by probe misses: an answered probe
        # forgives only these — a ping proves the exec loop drains, not
        # that the worker completes real tasks in time, so real-task
        # deadline strikes persist until a timely real completion.
        self._probe_strikes: dict[str, int] = {}
        self._quarantined: set[str] = set()
        # worker_id -> monotonic time of its last completed task or probe.
        self._last_ok: dict[str, float] = {}
        # worker_id -> (probe request_id, send time) for in-flight probes.
        self._probes: dict[str, tuple[int, float]] = {}
        # worker_id -> most recent probe id ever sent: only the *latest*
        # probe's answer earns forgiveness/decay, so a long-hung worker's
        # backlog of queued pings cannot, on revive, replay as a burst
        # that drains accumulated real-task strikes in one tick.
        self._last_probe_id: dict[str, int] = {}
        self._probe_ids = itertools.count(-2, -1)  # never a request id
        self._boot_time = time.monotonic()
        # Tie-break shuffle runs on forward-pool threads concurrently and
        # random.Random is not thread-safe -> one RNG per thread.
        self._tls = threading.local()
        self._rng_seeds = itertools.count(0x5EED)
        # Forward/re-dispatch pool: _acquire can block on a weight transfer
        # (configure), which must never stall the result loop or the
        # registry reaper (the reference likewise forwards in spawned
        # threads, src/dispatcher.py:137-144).
        self._forward_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="dispatcher-forward"
        )
        self._shutdown = threading.Event()
        self._threads: list[threading.Thread] = []
        self._started = False
        #: Chain forwarding (opt-in, setup_chain): ordered worker ids, one
        #: per stage; data hops worker→worker, only the tail's result (and
        #: any error) returns to the hub. None = hub routing.
        self._chain: list[str] | None = None
        self._chain_lock = threading.Lock()

    # -- worker pool --------------------------------------------------------

    def spawn_workers(self, devices) -> list[StageWorker]:
        """One in-process worker per device (single-host mode: TPU chips as
        the reference's 'machines' — its localhost mode, SURVEY.md §4)."""
        workers = []
        for i, dev in enumerate(devices):
            w = StageWorker(
                worker_id=f"worker-{i}",
                device=dev,
                registry=self.registry,
                result_queue=self.result_queue,
                fault=self.config.fault,
            )
            self.attach_worker(w)
            workers.append(w)
        return workers

    def attach_worker(self, worker: StageWorker) -> None:
        with self._workers_lock:
            self._workers[worker.worker_id] = worker
        # Dial-out remote proxies are re-adoptable after a dispatcher
        # crash (their server keeps listening); journal their address +
        # configure recipe. In-process workers die with this process and
        # gateway joiners redial on their own, so neither is journaled.
        if self._journal is not None:
            addr = getattr(worker, "chain_address", None)
            if addr is not None:
                self._journal.record_worker(
                    worker.worker_id,
                    addr[0],
                    addr[1],
                    meta={
                        "model_config": worker._model_config,
                        "codec": worker._codec_name,
                        "weights_codec": worker._wcodec.name,
                    },
                )

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "Dispatcher":
        if self._started:
            return self
        self.registry.start()
        with self._workers_lock:
            workers = list(self._workers.values())
        for w in workers:
            w.start()
        if not self.registry.wait_for_workers(
            1, self.config.fault.startup_wait_s
        ):
            # Reference: clean shutdown when no worker appears in 5 s
            # (src/dispatcher.py:290-295).
            self.shutdown()
            raise RequestFailed(
                f"no workers registered within "
                f"{self.config.fault.startup_wait_s}s"
            )
        self.registry.watch(self._on_membership)
        for name, target in (
            ("results", self._result_loop),
            ("watchdog", self._watchdog_loop),
        ):
            t = threading.Thread(
                target=target, name=f"dispatcher-{name}", daemon=True
            )
            t.start()
            self._threads.append(t)
        self._started = True
        return self

    def hard_stop(self) -> None:
        """Crash simulation (tests / chaos drills): abandon everything
        NOW — no draining, no future completion, no journal marks, no
        deregistration. The process state a SIGKILL leaves behind, minus
        the process exit. Worker processes keep running and listening;
        :meth:`recover` is the other half."""
        # Detach the journal FIRST: in-flight forward/result threads
        # erroring on the closed sockets must not write done marks a real
        # SIGKILL could never write (each would silently shrink the
        # recovery replay set).
        self._journal = None
        self._shutdown.set()
        self.result_queue.put(None)  # type: ignore[arg-type]
        with self._workers_lock:
            workers = list(self._workers.values())
        for w in workers:
            sock = getattr(w, "_sock", None)
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
        self._forward_pool.shutdown(wait=False, cancel_futures=True)
        self._prewarm_pool.shutdown(wait=False, cancel_futures=True)
        self.registry.stop()

    @classmethod
    def recover(
        cls,
        plan: PartitionPlan,
        variables,
        journal,
        config: ServeConfig | None = None,
    ) -> tuple["Dispatcher", dict[int, PipelineFuture]]:
        """Rebuild a serving dispatcher from a crashed one's journal.

        Re-adopts every journaled dial-out worker whose server still
        answers (unreachable addresses are skipped with a warning — the
        pool heals, it doesn't block), seeds the request-id counter past
        every journaled id, and REPLAYS each submitted-but-never-completed
        request from its retained payload. Returns ``(dispatcher,
        {request_id: future})`` for the replayed requests — each completes
        exactly once. Replay respects ``max_inflight`` and so may block
        admitting the tail of a large backlog while the head completes.

        Model/weights come from ``plan``/``variables`` (the operator's
        checkpoint, ``utils/checkpoint.py``) — the journal owns
        control-plane state only."""
        from adapt_tpu.comm.remote import RemoteWorkerProxy

        workers, pending, _ = journal.load()
        # cls() seeds the request-id counter from the journal's horizon.
        disp = cls(plan, variables, config=config, journal=journal)
        attached = 0
        proxies = []
        for worker_id, info in workers.items():
            meta = info.get("meta", {})
            proxy = RemoteWorkerProxy(
                worker_id,
                (info["host"], info["port"]),
                disp.registry,
                disp.result_queue,
                model_config=meta.get("model_config", {}),
                codec_name=meta.get("codec", "none"),
                weights_codec=meta.get("weights_codec", "lz"),
                fault=disp.config.fault,
            )
            disp.attach_worker(proxy)
            proxies.append(proxy)
            attached += 1
        if not attached:
            raise RequestFailed(
                "journal holds no re-adoptable workers; nothing to recover"
            )
        # start() dials every attached proxy; a dead address raises from
        # its start() — dial here instead, CONCURRENTLY (a pool with dead
        # addresses must not serialize startup_wait_s stalls), pruning
        # the unreachable from the journal so they never stall another
        # recovery (re-attaching a revived worker re-journals it).
        with disp._workers_lock:
            disp._workers.clear()
        alive = []

        def _dial(proxy):
            try:
                proxy.start()
                return proxy, None
            except Exception as e:  # noqa: BLE001
                return proxy, e

        with ThreadPoolExecutor(
            max_workers=min(16, max(1, len(proxies))),
            thread_name_prefix="recover-dial",
        ) as pool:
            for proxy, err in pool.map(_dial, proxies):
                if err is not None:
                    log.warning(
                        "recovery: worker %s at %s not re-adoptable (%s); "
                        "pruned from the journal",
                        proxy.worker_id,
                        proxy.address,
                        err,
                    )
                    journal.forget_worker(proxy.worker_id)
                    continue
                with disp._workers_lock:
                    disp._workers[proxy.worker_id] = proxy
                alive.append(proxy)
        if not alive:
            raise RequestFailed(
                "no journaled worker answered; cannot recover the pool"
            )
        disp.start()
        futures: dict[int, PipelineFuture] = {}
        for rid, payload in pending.items():
            disp._sem.acquire()
            future = PipelineFuture(rid)
            futures[rid] = future
            try:
                disp._dispatch(rid, 0, payload, future, attempt=0, retries=0)
            except Exception as e:  # noqa: BLE001
                disp._finish(future, error=str(e))
        log.info(
            "recovered: %d workers re-adopted, %d requests replayed",
            len(alive),
            len(futures),
        )
        global_metrics().inc("dispatcher.recovered", 1)
        recorder = global_flight_recorder()
        recorder.record(
            "recovery", workers=len(alive), replayed=len(futures)
        )
        if disp.config.obs.snapshot_on_recovery:
            # Post-mortem artifact: the fault timeline that preceded the
            # crash/recovery, dumped beside the journal so it outlives
            # the ring (and the process).
            try:
                path = os.path.join(
                    journal.root, f"flight-{int(time.time())}.json"
                )
                recorder.snapshot_to(path)
                log.info("flight-recorder snapshot: %s", path)
            except Exception as e:  # noqa: BLE001 — best-effort: a
                # failed post-mortem dump must not abort a recovery
                # whose dispatcher and replayed futures are already live.
                log.warning("flight-recorder snapshot failed: %s", e)
        return disp, futures

    def shutdown(self) -> None:
        self._shutdown.set()
        self.result_queue.put(None)  # type: ignore[arg-type]
        for t in self._threads:
            t.join(timeout=2.0)
        # Fail outstanding futures promptly instead of letting callers
        # sleep out their timeouts.
        self._forward_pool.shutdown(wait=False, cancel_futures=True)
        self._prewarm_pool.shutdown(wait=False, cancel_futures=True)
        with self._inflight_lock:
            abandoned = list(self._inflight.values())
            self._inflight.clear()
        for e in abandoned:
            self._finish(e.future, error="dispatcher shut down")
        with self._workers_lock:
            workers = list(self._workers.values())
        for w in workers:
            w.stop()
        self.registry.stop()
        if self._journal is not None:
            self._journal.close()

    # -- request API --------------------------------------------------------

    def submit(self, x) -> PipelineFuture:
        """Enqueue one request into the pipeline (reference input pump,
        ``src/dispatcher.py:99-107``); bounded by the concurrency
        semaphore (``:151,183``)."""
        if self._shutdown.is_set():
            raise RequestFailed("dispatcher is shut down")
        self._sem.acquire()
        request_id = next(self._req_ids)
        future = PipelineFuture(request_id)
        if self._journal is not None:
            # Write-ahead, strictly before dispatch: with journaling on,
            # an accepted request must be recoverable — a submit the
            # journal can't record is refused, not silently volatile.
            try:
                self._journal.record_submit(request_id, x)
            except Exception as e:
                self._sem.release()
                raise RequestFailed(f"journal write failed: {e}") from e
        try:
            self._dispatch(request_id, 0, x, future, attempt=0, retries=0)
        except Exception as e:  # no worker at all -> fail fast
            self._finish(future, error=str(e))
        return future

    def infer(self, x, timeout: float | None = 60.0) -> Any:
        return self.submit(x).result(timeout)

    def warmup(self, example, timeout: float | None = 300.0) -> None:
        """Run one request end-to-end with the watchdog paused, so
        first-compile time (tens of seconds on TPU) is paid here instead of
        triggering spurious re-dispatches in serving. Then prewarm every
        (stage, device) executable so failover never recompiles."""
        self._watchdog_paused = True
        deadline = None if timeout is None else time.monotonic() + timeout
        try:
            self.infer(example, timeout)
            self.prewarm_executables(wait=True, deadline=deadline)
        finally:
            self._watchdog_paused = False

    # -- precompiled re-shard plans -----------------------------------------

    def prewarm_executables(
        self, wait: bool = False, deadline: float | None = None
    ) -> None:
        """Seed the shared jit cache with every (stage, live-worker-device)
        executable, using each stage's recorded example input spec. The jit
        cache keys on avals/shardings, not values, so compilation uses
        device-created zero weights — no weight transfer, no lasting HBM
        cost. With ``wait=True`` blocks until all pairs are compiled (or
        ``deadline``, monotonic seconds, passes — best effort)."""
        if self._shutdown.is_set():
            return
        with self._workers_lock:
            # Remote proxies carry no local device (their server compiles
            # its own stage programs); prewarm only covers in-process
            # workers' devices.
            devices = {
                w.device
                for w in self._workers.values()
                if w.state is not WorkerState.DEAD
                and getattr(w, "device", None) is not None
            }
        with self._prewarm_lock:
            examples = dict(self._stage_examples)
        futures = []
        for stage_index, spec in examples.items():
            for dev in devices:
                with self._prewarm_lock:
                    if (stage_index, dev) in self._prewarmed:
                        continue
                    self._prewarmed.add((stage_index, dev))
                try:
                    futures.append(
                        self._prewarm_pool.submit(
                            self._prewarm_one, stage_index, dev, spec
                        )
                    )
                except RuntimeError:  # pool shut down concurrently
                    with self._prewarm_lock:
                        self._prewarmed.discard((stage_index, dev))
                    return
        if wait:
            for f in futures:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    log.warning(
                        "prewarm deadline passed with compiles outstanding; "
                        "continuing in background"
                    )
                    break
                try:
                    f.result(timeout=remaining)
                except TimeoutError:
                    log.warning(
                        "prewarm deadline passed with compiles outstanding; "
                        "continuing in background"
                    )
                    break

    def _prewarm_one(self, stage_index: int, device, spec) -> None:
        try:
            # Zero-valued weights created directly on the target device:
            # compiles the identical executable (cache keys are avals +
            # shardings) without moving the real weights. The device_put
            # commits the already-on-device arrays — committed and
            # uncommitted args key DIFFERENT cache entries, and serving
            # calls use committed (device_put) arrays.
            with jax.default_device(device):
                variables = jax.tree.map(
                    lambda a: jax.numpy.zeros(a.shape, a.dtype),
                    self._stage_host_vars[stage_index],
                )
                x = jax.numpy.zeros(spec.shape, spec.dtype)
            variables = jax.device_put(variables, device)
            x = jax.device_put(x, device)
            jax.block_until_ready(self._stage_fns[stage_index](variables, x))
            global_metrics().inc("dispatcher.prewarmed")
        except Exception as e:  # noqa: BLE001 — prewarm is best-effort
            with self._prewarm_lock:
                self._prewarmed.discard((stage_index, device))
            log.warning(
                "prewarm of stage %d on %s failed: %s", stage_index, device, e
            )

    def serve_stream(self, inputs, timeout_per_request: float = 120.0):
        """Pump a stream through the pipeline, preserving order (reference
        driver semantics, ``test/test.py:48-50``)."""
        futures = [self.submit(x) for x in inputs]
        return [f.result(timeout_per_request) for f in futures]

    def metrics_snapshot(self) -> dict:
        return global_metrics().snapshot()

    # -- chain forwarding (opt-in data-plane topology) -----------------------

    def setup_chain(self, worker_ids: list[str] | None = None) -> list[str]:
        """Opt-in direct worker→worker forwarding for a static healthy
        pool: stage ``i``'s output hops straight to stage ``i+1``'s worker
        (reference Gen-1 topology, ``/root/reference/src/node.py:163-179``)
        and only the tail's result returns to the hub — halving the DCN
        hops of hub routing (SURVEY §3.2's 2·S critique). The hub keeps
        the whole control plane: probes, deadlines, exactly-once and
        re-dispatch are unchanged, and ANY chain failure (error frame,
        deadline, member death) disables the chain and replays the
        request end-to-end through the proven late-binding hub path —
        the in-flight entry retains the original stage-0 payload.

        ``worker_ids``: one per stage, in stage order. Default: the
        dial-out remote proxies in attach order. Members must be
        ``RemoteWorkerProxy``-shaped (send_route) and every non-head
        member must be dialable by its predecessor (``chain_address``)."""
        with self._workers_lock:
            pool = {
                wid: w
                for wid, w in self._workers.items()
                if w.state is not WorkerState.DEAD
            }
        if worker_ids is None:
            worker_ids = [
                wid
                for wid, w in pool.items()
                if getattr(w, "chain_address", None) is not None
            ][: self.plan.num_stages]
        if len(worker_ids) != self.plan.num_stages:
            raise ValueError(
                f"chain needs exactly {self.plan.num_stages} workers "
                f"(one per stage), got {len(worker_ids)}"
            )
        workers = []
        for i, wid in enumerate(worker_ids):
            w = pool.get(wid)
            if w is None:
                raise ValueError(f"worker {wid!r} is not in the live pool")
            if not hasattr(w, "send_route"):
                raise TypeError(
                    f"worker {wid!r} cannot chain (in-process workers "
                    "share the hub's memory; chaining is a cross-host "
                    "topology)"
                )
            if i > 0 and w.chain_address is None:
                raise ValueError(
                    f"worker {wid!r} has no dialable listen address "
                    "(gateway joiners don't announce one)"
                )
            workers.append(w)
        for i, w in enumerate(workers):
            if not w.is_configured(i):
                self._configure_with_timeout(w, i)
        # Tail-first: no hop ever forwards into a worker missing its route.
        for i in reversed(range(len(workers))):
            if i + 1 < len(workers):
                workers[i].send_route(i, workers[i + 1].chain_address, i + 1)
            else:
                workers[i].send_route(i, None)
        with self._chain_lock:
            self._chain = list(worker_ids)
        log.info("chain forwarding enabled: %s", " -> ".join(worker_ids))
        global_metrics().inc("dispatcher.chain_enabled")
        return list(worker_ids)

    def disable_chain(self, reason: str = "requested") -> None:
        """Back to hub routing. Route clears are best-effort and async —
        correctness doesn't need them: hub traffic uses plain MSG_DATA,
        which ignores any stale route left on an unreachable worker."""
        with self._chain_lock:
            chain, self._chain = self._chain, None
        if chain is None:
            return
        log.warning(
            "chain forwarding disabled (%s); hub routing resumes", reason
        )
        global_metrics().inc("dispatcher.chain_disabled")
        global_flight_recorder().record("chain_disabled", reason=reason)
        with self._workers_lock:
            pool = dict(self._workers)

        def _clear(stage: int, worker) -> None:
            try:
                worker.send_route(stage, None, clear=True)
            except Exception:  # noqa: BLE001 — link may be down/dead
                pass

        for i, wid in enumerate(chain):
            w = pool.get(wid)
            if w is not None and hasattr(w, "send_route"):
                try:
                    self._forward_pool.submit(_clear, i, w)
                except RuntimeError:  # pool shut down
                    break

    # -- scheduling ---------------------------------------------------------

    def _acquire(self, stage_index: int, exclude: set[str]) -> StageWorker:
        """Late binding: pick a live worker for this stage *now* (reference
        ``_acquire_and_configure_worker``, call site
        ``src/dispatcher.py:178``). Preference: already-configured >
        holding the fewest other stages > idle > shallowest queue;
        excluded (suspect) workers only as a last resort."""
        # Role-tagged leases partition the pool: a worker registered
        # under a dedicated role (the disaggregated serving tier's
        # role="prefill" pool, runtime/disagg) must never be acquired
        # for pipeline stages — its capacity is spoken for. Untagged
        # leases (every pre-role registration) stay fully schedulable.
        # One registry lock hold (alive_untagged), not one per worker.
        alive = set(self.registry.alive_untagged())
        with self._workers_lock:
            pool = [
                w
                for wid, w in self._workers.items()
                if wid in alive and w.state is not WorkerState.DEAD
            ]
        if not pool:
            raise RequestFailed("no live workers")
        with self._health_lock:
            strikes = dict(self._strikes)
            quarantined = set(self._quarantined)
        # Preference cascade: healthy & untried > quarantined & untried
        # (quarantine is a soft signal; a worker this request hasn't tried
        # yet still beats re-picking one that just failed it) > anyone.
        healthy = [w for w in pool if w.worker_id not in quarantined]
        candidates = (
            [w for w in healthy if w.worker_id not in exclude]
            or [w for w in pool if w.worker_id not in exclude]
            or healthy
            or pool
        )

        def rank(w: StageWorker):
            return (
                # Any missed deadline (even below the quarantine threshold)
                # demotes a worker: a hung worker looks perfectly idle and
                # configured — the most attractive rank — so strike
                # feedback must outweigh attractiveness. Workers with NO
                # strikes stay fully schedulable (the watchdog's canary
                # probes, not scheduling starvation, are what detect a
                # silent hang — see _watchdog_loop).
                1 if strikes.get(w.worker_id, 0) else 0,
                0 if w.is_configured(stage_index) else 1,
                # Spread before stacking: a stage that must be newly
                # bound goes to the worker holding the fewest stages,
                # so a pool with a chip per stage pipelines across
                # chips (and a failover lands on the least-loaded
                # survivor) instead of piling onto whichever idle
                # worker the shuffle put first.
                len(w.configured_stages()),
                0 if w.state is WorkerState.IDLE else 1,
                w.queue_depth,
            )

        # Random tie-break: concurrent re-dispatch waves must scatter over
        # equal-rank candidates, not herd onto one deterministic victim
        # (which would burn one deadline per worker, serially).
        self._shuffle(candidates)
        last_error: Exception | None = None
        for worker in sorted(candidates, key=rank):
            if worker.is_configured(stage_index):
                return worker
            try:
                self._configure_with_timeout(worker, stage_index)
                return worker
            except Exception as e:  # noqa: BLE001 — try the next candidate
                log.warning(
                    "configure of stage %d on %s failed: %s",
                    stage_index,
                    worker.worker_id,
                    e,
                )
                last_error = e
        raise RequestFailed(
            f"no worker could be configured for stage {stage_index}: "
            f"{last_error}"
        )

    def _shuffle(self, seq: list) -> None:
        rng = getattr(self._tls, "rng", None)
        if rng is None:
            rng = self._tls.rng = random.Random(next(self._rng_seeds))
        rng.shuffle(seq)

    def _configure_with_timeout(
        self, worker: StageWorker, stage_index: int
    ) -> None:
        """Bounded config handshake (reference ACK timeout,
        ``src/dispatcher.py:246-260``). On timeout the worker thread is
        abandoned but *cancelled*: the ``abort`` token is checked by the
        worker immediately before installing the binding, so a timed-out
        configure can never install state (or pin weight HBM) after this
        dispatcher has declared it failed and moved on."""
        done = threading.Event()
        abandoned = threading.Event()
        errors: list[Exception] = []

        def _cfg():
            try:
                gen = worker.configure(
                    stage_index,
                    self._stage_fns[stage_index],
                    self._stage_host_vars[stage_index],
                    spec=self.plan.stages[stage_index],
                    abort=abandoned.is_set,
                )
                if abandoned.is_set():
                    # Install won the race with the timeout decision by a
                    # hair: undo it so no binding (or pinned weights)
                    # survives a configure the dispatcher reported failed.
                    # Gen-scoped: a newer configure's binding survives.
                    worker.unconfigure(stage_index, gen)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                done.set()

        t = threading.Thread(target=_cfg, daemon=True)
        t.start()
        if not done.wait(self.config.fault.configure_timeout_s):
            abandoned.set()
            raise RequestFailed(
                f"configure of stage {stage_index} on {worker.worker_id} "
                f"timed out after {self.config.fault.configure_timeout_s}s"
            )
        if errors:
            raise errors[0]

    def _dispatch(
        self,
        request_id: int,
        stage_index: int,
        payload,
        future: PipelineFuture,
        attempt: int,
        retries: int,
        exclude: set[str] | None = None,
    ) -> None:
        if stage_index not in self._stage_examples:
            try:
                spec = jax.ShapeDtypeStruct(
                    jax.numpy.shape(payload), payload.dtype
                )
                with self._prewarm_lock:
                    self._stage_examples[stage_index] = spec
            except Exception:  # noqa: BLE001 — non-array payloads: skip
                pass
        exclude = exclude or set()
        with self._chain_lock:
            chain = self._chain
        if (
            chain is not None
            and stage_index == 0
            and retries == 0
            and not exclude
        ):
            # Chain fast path: one submit to the head; the final result
            # arrives from the tail worker's link. Retries/excludes never
            # take it — a failed chain attempt replays through the hub.
            with self._workers_lock:
                head = self._workers.get(chain[0])
            if head is not None and head.state is not WorkerState.DEAD:
                entry = _Inflight(
                    request_id=request_id,
                    stage_index=0,
                    attempt=attempt,
                    payload=payload,
                    worker_id=head.worker_id,
                    start_time=time.monotonic(),
                    retries=retries,
                    future=future,
                    tried={head.worker_id},
                    final_stage=self.plan.num_stages - 1,
                )
                with self._inflight_lock:
                    self._inflight[request_id] = entry
                try:
                    head.submit(
                        Task(
                            request_id=request_id,
                            stage_index=0,
                            attempt=attempt,
                            payload=payload,
                            chained=True,
                        )
                    )
                except Exception as e:  # noqa: BLE001 — link just died
                    with self._inflight_lock:
                        self._inflight.pop(request_id, None)
                    self.disable_chain(f"chain head submit failed: {e}")
                else:
                    global_metrics().inc("dispatcher.tasks_sent")
                    global_metrics().inc("dispatcher.chain_dispatched")
                    return
            else:
                self.disable_chain("chain head worker gone")
        worker = self._acquire(stage_index, exclude)
        entry = _Inflight(
            request_id=request_id,
            stage_index=stage_index,
            attempt=attempt,
            payload=payload,
            worker_id=worker.worker_id,
            start_time=time.monotonic(),
            retries=retries,
            future=future,
            tried=exclude | {worker.worker_id},
        )
        with self._inflight_lock:
            self._inflight[request_id] = entry
        worker.submit(
            Task(
                request_id=request_id,
                stage_index=stage_index,
                attempt=attempt,
                payload=payload,
            )
        )
        global_metrics().inc("dispatcher.tasks_sent")

    def _forward(self, result: TaskResult, entry: _Inflight, next_stage: int) -> None:
        """Forward a stage output to the next stage (runs on the forward
        pool; _acquire may block on a configure)."""
        try:
            self._dispatch(
                result.request_id,
                next_stage,
                result.output,
                entry.future,
                attempt=0,
                retries=0,
            )
        except Exception as e:  # noqa: BLE001
            self._finish(entry.future, error=str(e))

    def _redispatch(self, entry: _Inflight, reason: str) -> None:
        """Watchdog / failure path: re-send the retained payload to a
        different worker (reference watchdog intent, ``src/dispatcher.py:
        302-304`` + §2.7 'late binding'). A chain entry replays from its
        original stage-0 payload through the hub path — the chain (if
        still up) is disabled first, so the retry cannot re-enter the
        topology that just failed it."""
        if entry.final_stage is not None:
            self.disable_chain(f"chain request replay: {reason}")
        if entry.retries + 1 > self.config.fault.max_retries:
            with self._inflight_lock:
                self._inflight.pop(entry.request_id, None)
            global_flight_recorder().record(
                "request_failed",
                request=entry.request_id,
                stage=entry.stage_index,
                retries=entry.retries,
                reason=reason,
            )
            self._finish(
                entry.future,
                error=(
                    f"request {entry.request_id} stage {entry.stage_index} "
                    f"failed after {entry.retries} retries ({reason})"
                ),
            )
            return
        global_metrics().inc("dispatcher.redispatched")
        global_flight_recorder().record(
            "redispatch",
            request=entry.request_id,
            stage=entry.stage_index,
            attempt=entry.attempt + 1,
            worker=entry.worker_id,
            reason=reason,
        )
        log.warning(
            "re-dispatching request %d stage %d (%s), attempt %d",
            entry.request_id,
            entry.stage_index,
            reason,
            entry.attempt + 1,
        )
        try:
            self._dispatch(
                entry.request_id,
                entry.stage_index,
                entry.payload,
                entry.future,
                attempt=entry.attempt + 1,
                retries=entry.retries + 1,
                exclude=entry.tried,  # includes entry.worker_id by construction
            )
        except Exception as e:
            with self._inflight_lock:
                self._inflight.pop(entry.request_id, None)
            self._finish(entry.future, error=str(e))

    def _finish(self, future: PipelineFuture, value=None, error=None) -> None:
        if future._complete(value, error):
            if self._journal is not None:
                # Terminal either way (value OR reported error): replay
                # is for requests that never completed. A crash before
                # this mark replays the request once — the documented
                # at-least-once window.
                try:
                    self._journal.record_done(future.request_id)
                except Exception:  # noqa: BLE001 — worst case: one replay
                    log.warning(
                        "journal done-mark failed for request %d",
                        future.request_id,
                    )
            self._sem.release()
            global_metrics().inc(
                "dispatcher.completed" if error is None else "dispatcher.failed"
            )
            latency = time.monotonic() - future.submit_time
            if error is None:
                global_metrics().observe("request.latency_s", latency)
            tracer = global_tracer()
            if tracer.enabled:
                end = tracer.now()
                tracer.add_span(
                    "request",
                    start=end - latency,
                    end=end,
                    request=future.request_id,
                    ok=error is None,
                )

    # -- loops --------------------------------------------------------------

    def _result_loop(self) -> None:
        """The intermediate-result server (reference fragment
        ``src/dispatcher.py:121-161``): every stage output returns to the
        hub; forward to the next stage or emit the final result."""
        while not self._shutdown.is_set():
            result = self.result_queue.get()
            if result is None:
                break
            if result.stage_index < 0:
                # Probe (canary) answer: proof the exec loop is draining
                # again — even a stale ping from before a re-probe counts.
                # Forgives probe-miss strikes (a lifted hang) but not
                # real-task deadline strikes, and lifts quarantine only if
                # what remains is below the threshold. Ignored entirely if
                # the worker has left membership since (a rejoin under the
                # same id must start with a clean slate).
                wid = result.worker_id
                if wid not in self.registry.alive():
                    global_metrics().inc("dispatcher.probes_ignored")
                    continue
                with self._health_lock:
                    self._last_ok[wid] = time.monotonic()
                    if result.request_id != self._last_probe_id.get(wid):
                        # Stale ping from a revive-burst: liveness proof
                        # (recorded above) but no forgiveness — only the
                        # newest probe's answer absolves, one per
                        # round-trip actually sent.
                        global_metrics().inc("dispatcher.probes_ok")
                        continue
                    self._probes.pop(wid, None)
                    forgiven = self._probe_strikes.pop(wid, 0)
                    remaining = self._strikes.get(wid, 0) - forgiven
                    remaining = max(remaining, 0)
                    if (
                        wid in self._quarantined
                        and remaining >= self.config.fault.quarantine_strikes
                    ):
                        # Quarantine earned from real-task strikes, whose
                        # late results were dropped as stale and so can
                        # never absolve: each answered probe decays one
                        # real strike, so a transiently-stalled worker
                        # works its way back (to demoted-but-available,
                        # not to full trust) instead of being sidelined
                        # forever. Decay only applies under quarantine —
                        # a merely-demoted slow worker must NOT oscillate
                        # back to full rank on probe answers alone.
                        remaining -= 1
                    if remaining > 0:
                        self._strikes[wid] = remaining
                    else:
                        self._strikes.pop(wid, None)
                    if remaining < self.config.fault.quarantine_strikes:
                        self._quarantined.discard(wid)
                global_metrics().inc("dispatcher.probes_ok")
                continue
            with self._inflight_lock:
                entry = self._inflight.get(result.request_id)
                if entry is not None and entry.final_stage is not None:
                    # Chain entry: SUCCESS must come from the tail stage;
                    # an ERROR matches from ANY hop (a mid-chain worker
                    # reports its failures hub-ward with its own stage
                    # index).
                    matches = entry.attempt == result.attempt and (
                        result.error is not None
                        or result.stage_index == entry.final_stage
                    )
                else:
                    matches = (
                        entry is not None
                        and entry.stage_index == result.stage_index
                        and entry.attempt == result.attempt
                    )
                if not matches:
                    # Stale duplicate (late completion after re-dispatch) —
                    # the duplication bug the reference had (SURVEY §7.4).
                    global_metrics().inc("dispatcher.stale_results")
                    continue
                del self._inflight[result.request_id]
            if result.error is not None:
                if entry.final_stage is not None:
                    # A broken chain never self-heals into the same break:
                    # fall back to hub routing for everything, then replay
                    # this request end-to-end from its retained original
                    # payload.
                    self.disable_chain(
                        f"chain error at stage {result.stage_index}: "
                        f"{result.error}"
                    )
                self._forward_pool.submit(
                    self._redispatch, entry, f"error: {result.error}"
                )
                continue
            # A successful result clears the worker's strike record — a
            # transient stall (queue backlog, first compile) must not
            # sideline a healthy worker forever — and refreshes its
            # liveness evidence (which defers the watchdog's probes).
            with self._health_lock:
                self._last_ok[result.worker_id] = time.monotonic()
                self._probe_strikes.pop(result.worker_id, None)
                if self._strikes.pop(result.worker_id, None) is not None:
                    self._quarantined.discard(result.worker_id)
            next_stage = result.stage_index + 1
            if next_stage < self.plan.num_stages:
                self._forward_pool.submit(
                    self._forward, result, entry, next_stage
                )
            else:
                self._finish(entry.future, value=result.output)
            stage_latency = time.monotonic() - entry.start_time
            global_metrics().observe(
                f"stage{result.stage_index}.latency_s", stage_latency
            )
            tracer = global_tracer()
            if tracer.enabled:
                # Dispatch -> result round-trip, tagged with the SAME
                # request/attempt the framing header carried — remote
                # workers' annex-ingested spans nest under this one in
                # the stitched trace.
                end = tracer.now()
                tracer.add_span(
                    "dispatch.stage_rtt",
                    start=end - stage_latency,
                    end=end,
                    request=result.request_id,
                    attempt=result.attempt,
                    stage=result.stage_index,
                    worker=result.worker_id,
                )

    def _add_strike_locked(
        self, worker_id: str, from_probe: bool = False
    ) -> bool:
        """Record one missed deadline (caller holds ``_health_lock``);
        returns True when this strike crosses the quarantine threshold."""
        strikes = self._strikes.get(worker_id, 0) + 1
        self._strikes[worker_id] = strikes
        if from_probe:
            self._probe_strikes[worker_id] = (
                self._probe_strikes.get(worker_id, 0) + 1
            )
        newly_quarantined = (
            strikes >= self.config.fault.quarantine_strikes
            and worker_id not in self._quarantined
        )
        if newly_quarantined:
            self._quarantined.add(worker_id)
        return newly_quarantined

    def _quarantine_drain(self, worker_id: str, why: str) -> None:
        """A just-quarantined worker's other in-flight tasks are almost
        certainly doomed too — re-dispatch them now instead of one
        deadline at a time."""
        global_metrics().inc("dispatcher.quarantined")
        global_flight_recorder().record(
            "quarantine", worker=worker_id, why=why
        )
        log.warning("worker %s quarantined (%s)", worker_id, why)
        with self._inflight_lock:
            doomed = [
                e for e in self._inflight.values() if e.worker_id == worker_id
            ]
            for e in doomed:
                del self._inflight[e.request_id]
        for e in doomed:
            self._forward_pool.submit(
                self._redispatch, e, "co-resident with quarantine"
            )

    def _add_strike(self, worker_id: str, why: str) -> None:
        with self._health_lock:
            newly_quarantined = self._add_strike_locked(worker_id)
        if newly_quarantined:
            self._quarantine_drain(worker_id, why)

    def _probe_silent_workers(self, now: float, deadline: float) -> None:
        """Canary liveness probes: a hung worker heartbeats (so membership
        keeps it) and, once struck, is rank-demoted (so real traffic stops
        reaching it) — probes are the only way further strikes can accrue
        and quarantine stays reachable. Conversely, a recovered worker's
        answered probe lifts its quarantine (see _result_loop)."""
        silence = self.config.fault.probe_silence_s
        if silence is None:
            silence = self.config.fault.task_deadline_s
        # Expire overdue probes first: each costs one strike. Detection
        # and strike are one atomic critical section, so an answer racing
        # in through the result loop either lands before (probe entry gone,
        # no strike) or after (forgives the probe strike it just earned).
        with self._health_lock:
            missed = [
                wid
                for wid, (_, sent) in self._probes.items()
                if now - sent > deadline
            ]
            quarantine_now = []
            for wid in missed:
                del self._probes[wid]
                if self._add_strike_locked(wid, from_probe=True):
                    quarantine_now.append(wid)
        for wid in missed:
            global_metrics().inc("dispatcher.probes_missed")
            global_flight_recorder().record("probe_miss", worker=wid)
        for wid in quarantine_now:
            self._quarantine_drain(wid, "probe missed")
        alive = set(self.registry.alive())
        with self._workers_lock:
            pool = [
                w
                for wid, w in self._workers.items()
                if wid in alive and w.state is not WorkerState.DEAD
            ]
        for w in pool:
            with self._health_lock:
                if w.worker_id in self._probes:
                    continue
                last = self._last_ok.get(w.worker_id, self._boot_time)
                if now - last <= silence:
                    continue
                if w.worker_id not in self.registry.alive():
                    # Evicted since the pool snapshot above; inserting now
                    # would resurrect health state _on_membership('leave')
                    # just cleared (phantom strikes on rejoin). Safe to
                    # call alive() here: registry watchers fire outside its
                    # lock, so health->registry is the only ordering.
                    continue
                pid = next(self._probe_ids)
                self._probes[w.worker_id] = (pid, now)
                self._last_probe_id[w.worker_id] = pid
            try:
                w.submit(
                    Task(
                        request_id=pid,
                        stage_index=PING_STAGE,
                        attempt=0,
                        payload=None,
                    )
                )
            except Exception as e:  # noqa: BLE001 — e.g. remote socket gone
                # An unsendable probe is not a strike: a dead link stops
                # the proxy's lease renewals, so membership eviction (not
                # the probe path) retires the worker.
                with self._health_lock:
                    self._probes.pop(w.worker_id, None)
                log.warning("probe send to %s failed: %s", w.worker_id, e)
                continue
            global_metrics().inc("dispatcher.probes_sent")

    def _watchdog_loop(self) -> None:
        """Deadline scan over the in-flight registry (the reference's
        ``_task_watchdog``, ``src/dispatcher.py:302-304``, body lost —
        rebuilt here), plus canary probing of silent workers."""
        period = self.config.fault.watchdog_period_s
        deadline = self.config.fault.task_deadline_s
        while not self._shutdown.wait(period):
            if self._watchdog_paused:
                continue
            # The watchdog is the single recovery mechanism for hangs; it
            # must outlive any per-iteration surprise (a worker interface
            # raising, a registry hiccup) — skip the tick, never die.
            try:
                now = time.monotonic()
                overdue: list[_Inflight] = []
                with self._inflight_lock:
                    for rid, entry in list(self._inflight.items()):
                        # A chain entry spans the WHOLE pipeline between
                        # hub touches; its deadline scales with the
                        # stage count.
                        limit = deadline * (
                            self.plan.num_stages
                            if entry.final_stage is not None
                            else 1
                        )
                        if now - entry.start_time > limit:
                            overdue.append(entry)
                            del self._inflight[rid]
                for entry in overdue:
                    if entry.final_stage is None:
                        # Chain entries carry the HEAD's id, but the stall
                        # can be at any hop — striking (and eventually
                        # quarantining) a possibly-healthy head for a hung
                        # tail is wrong. Probes find the actual hung
                        # worker; the replay below goes hub-path anyway.
                        self._add_strike(
                            entry.worker_id, "task deadline exceeded"
                        )
                    self._forward_pool.submit(
                        self._redispatch, entry, "deadline exceeded"
                    )
                self._probe_silent_workers(now, deadline)
            except Exception:  # noqa: BLE001
                log.exception("watchdog iteration failed; continuing")

    def _on_membership(self, event: str, worker_id: str) -> None:
        """Reference ``_worker_monitor`` (:276): on worker death, don't wait
        for task deadlines — immediately re-dispatch its in-flight tasks.
        On join, prewarm the newcomer's executables in the background."""
        if event == "join":
            self.prewarm_executables()
            return
        if event != "leave":
            return
        global_flight_recorder().record("worker_leave", worker=worker_id)
        # A departed worker's record dies with it; a future re-join under
        # the same id starts with a clean slate.
        with self._health_lock:
            self._strikes.pop(worker_id, None)
            self._probe_strikes.pop(worker_id, None)
            self._quarantined.discard(worker_id)
            self._last_ok.pop(worker_id, None)
            self._probes.pop(worker_id, None)
            self._last_probe_id.pop(worker_id, None)
        # A chain member's death breaks the chain for every in-flight
        # chain request, whatever hop each is at — the hub only tracks
        # the head, so orphan them all, now, not at deadline × stages.
        with self._chain_lock:
            in_chain = self._chain is not None and worker_id in self._chain
        if in_chain:
            self.disable_chain(f"chain member {worker_id} left")
        with self._inflight_lock:
            orphaned = [
                e
                for e in self._inflight.values()
                if e.worker_id == worker_id
                or (in_chain and e.final_stage is not None)
            ]
            for e in orphaned:
                del self._inflight[e.request_id]
        for e in orphaned:
            self._forward_pool.submit(
                self._redispatch, e, f"worker {worker_id} left"
            )
