"""Stage workers: device-owning executors with heartbeats and kill modes.

The TPU-native analog of the reference's ``Node`` (``/root/reference/src/
node.py``): a worker owns a compute resource (there: the whole machine's TF
runtime; here: one JAX device), accepts stage configurations (there: model
JSON + weights over port 6001 with an ACK, ``src/node.py:65-98``; here: a
jitted stage fn + device_put of its variables), executes data tasks (there:
``model.predict`` per request, ``:177``; here: the XLA stage program), and
posts every result back to the dispatcher hub (Gen-2 star topology,
``src/dispatcher.py:121-151``).

Kill modes for fault injection (SURVEY.md §5 'chaos hook'):
- ``crash``: stop heartbeating AND stop processing -> lease expiry evicts
  the worker from membership.
- ``hang``: keep heartbeating but stop processing -> only the task-deadline
  watchdog can catch it (the harder failure; the reference's watchdog
  exists for exactly this, ``src/dispatcher.py:302-304``).
"""

from __future__ import annotations

import enum
import itertools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import jax

from adapt_tpu.config import FaultConfig
from adapt_tpu.control.registry import WorkerRegistry
from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.tracing import global_flight_recorder, global_tracer

log = get_logger("worker")


class WorkerState(enum.Enum):
    """Reference ``StateEnum`` (``src/node_state.py:163-167``)."""

    IDLE = "idle"
    BUSY = "busy"
    DEAD = "dead"


#: Sentinel stage index for liveness-probe (canary) tasks: the worker
#: answers immediately without touching any stage binding. A hung worker's
#: exec loop swallows the ping exactly like a real task — that is the
#: signal the dispatcher's watchdog turns into a strike.
PING_STAGE = -1


@dataclass
class Task:
    """One stage-execution request (reference: 4-byte stage index + framed
    payload on port 6000, ``src/dispatcher.py:209-213``)."""

    request_id: int
    stage_index: int
    attempt: int
    payload: Any  # host or device array
    #: Chain-mode head submit (comm.remote chain forwarding): the result
    #: returns on a DIFFERENT worker's link, so the receiving proxy must
    #: not count it against its own in-flight depth.
    chained: bool = False
    #: Stamped by StageWorker.submit (perf-counter clock): how long the
    #: task sat in the inbox feeds the ``worker.queue_wait_s`` histogram.
    t_enqueue: float = 0.0


@dataclass
class TaskResult:
    request_id: int
    stage_index: int
    attempt: int
    worker_id: str
    output: Any = None
    error: str | None = None


@dataclass
class _StageBinding:
    fn: Any  # shared jitted (variables, x) -> y
    variables: Any  # device-resident
    device: jax.Device
    spec: Any = field(default=None)
    generation: int = 0  # which configure installed this binding


class StageWorker:
    """In-process worker bound to one JAX device."""

    def __init__(
        self,
        worker_id: str,
        device: jax.Device,
        registry: WorkerRegistry,
        result_queue: "queue.Queue[TaskResult]",
        fault: FaultConfig | None = None,
    ):
        self.worker_id = worker_id
        self.device = device
        self._registry = registry
        self._results = result_queue
        self._fault = fault or FaultConfig()
        self._inbox: queue.Queue[Task | None] = queue.Queue()
        self._bindings: dict[int, _StageBinding] = {}
        self._bind_gen = itertools.count(1)
        self._bind_lock = threading.Lock()
        self._state = WorkerState.IDLE
        self._state_lock = threading.Lock()
        self._crashed = threading.Event()
        self._stopping = threading.Event()  # clean stop() vs crash
        self._hung = threading.Event()
        self._threads: list[threading.Thread] = []

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "StageWorker":
        self._registry.register(
            self.worker_id,
            meta={"device": str(self.device)},
            ttl_s=self._fault.lease_ttl_s,
        )
        for name, target in (
            ("exec", self._exec_loop),
            ("heartbeat", self._heartbeat_loop),
        ):
            t = threading.Thread(
                target=target, name=f"{self.worker_id}-{name}", daemon=True
            )
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stopping.set()
        self._crashed.set()
        self._inbox.put(None)
        for t in self._threads:
            t.join(timeout=2.0)
        self._registry.deregister(self.worker_id)

    # -- fault injection ----------------------------------------------------

    def kill(self, mode: str = "crash") -> None:
        global_flight_recorder().record(
            "worker_killed", worker=self.worker_id, mode=mode
        )
        if mode == "crash":
            self._crashed.set()
            self._inbox.put(None)
            log.warning("worker %s crashed (injected)", self.worker_id)
            with self._state_lock:
                self._state = WorkerState.DEAD
        elif mode == "hang":
            # A real hang keeps heartbeating and stays schedulable — the
            # dispatcher must discover it via task deadlines, not state.
            self._hung.set()
            log.warning("worker %s hung (injected)", self.worker_id)
        else:
            raise ValueError(f"unknown kill mode {mode!r}")

    def revive(self) -> None:
        """Chaos hook: clear an injected hang. The exec loop resumes
        draining its inbox — including any queued canary probes, whose
        answers lift the dispatcher's quarantine (self-healing)."""
        self._hung.clear()

    # -- dispatcher-facing API ----------------------------------------------

    @property
    def state(self) -> WorkerState:
        with self._state_lock:
            return self._state

    def is_configured(self, stage_index: int) -> bool:
        with self._bind_lock:
            return stage_index in self._bindings

    def configured_stages(self) -> tuple[int, ...]:
        """Stage indices currently bound to this worker's device."""
        with self._bind_lock:
            return tuple(sorted(self._bindings))

    def configure(
        self, stage_index: int, fn, host_variables, spec=None, abort=None
    ) -> int:
        """Install a stage on this worker's device; returns when weights are
        resident (the reference's JSON+weights+ACK handshake,
        ``src/dispatcher.py:223-264`` / ``src/node.py:65-98``, collapsed to
        a device_put + blocking ready wait).

        ``abort`` is an optional zero-arg callable checked before the slow
        weight transfer and again immediately before installing the
        binding: a dispatcher that timed out this handshake sets it, so the
        abandoned configure thread cannot install state (and pin HBM) after
        the dispatcher moved on.

        Returns a generation handle for :meth:`unconfigure` — a revoke is
        scoped to the configure that earned it, so undoing an abandoned
        handshake can never drop a newer configure's binding."""
        if self._crashed.is_set():
            raise RuntimeError(f"worker {self.worker_id} is dead")
        if abort is not None and abort():
            raise RuntimeError("configure aborted before weight transfer")
        variables = jax.device_put(host_variables, self.device)
        jax.block_until_ready(variables)  # the ACK
        generation = next(self._bind_gen)
        with self._bind_lock:
            if abort is not None and abort():
                raise RuntimeError("configure aborted (caller timed out)")
            self._bindings[stage_index] = _StageBinding(
                fn=fn,
                variables=variables,
                device=self.device,
                spec=spec,
                generation=generation,
            )
        global_metrics().inc("worker.configured")
        return generation

    def unconfigure(self, stage_index: int, generation: int | None = None) -> None:
        """Drop a stage binding (releases the device weight references).
        With ``generation``, only if that configure's binding is still the
        installed one."""
        with self._bind_lock:
            binding = self._bindings.get(stage_index)
            if binding is None:
                return
            if generation is not None and binding.generation != generation:
                return
            del self._bindings[stage_index]

    def submit(self, task: Task) -> None:
        task.t_enqueue = time.perf_counter()
        self._inbox.put(task)

    @property
    def queue_depth(self) -> int:
        return self._inbox.qsize()

    # -- loops --------------------------------------------------------------

    def _heartbeat_loop(self) -> None:
        # A crashed worker stops renewing; the registry reaper evicts it
        # after lease_ttl (reference: etcd lease expiry on /workers/<ip>).
        while not self._crashed.wait(self._fault.heartbeat_s):
            renewed = self._registry.heartbeat(
                self.worker_id, ttl_s=self._fault.lease_ttl_s
            )
            if not renewed and not self._crashed.is_set():
                # Lease lapsed (e.g. a long compile stalled this thread)
                # but we are alive: re-register rather than serve forever
                # while invisible to the scheduler.
                self._registry.register(
                    self.worker_id,
                    meta={"device": str(self.device)},
                    ttl_s=self._fault.lease_ttl_s,
                )
                if self._crashed.is_set():
                    # Check-then-act race with the exec loop's
                    # crash-eviction deregister: if the kill landed
                    # between our pre-check and the register above, the
                    # eviction may already have run and our register
                    # just resurrected a dead worker's lease. The
                    # post-register re-check closes every interleaving:
                    # whichever side runs last removes the lease.
                    self._registry.deregister(self.worker_id)

    def _exec_loop(self) -> None:
        try:
            self._exec_loop_inner()
        finally:
            if self._crashed.is_set() and not self._stopping.is_set():
                # Event-driven crash eviction: an in-process worker whose
                # exec loop died is gone NOW — deregister instead of
                # letting membership wait out the lease TTL. The
                # reference evicts on socket error, not timeout
                # (src/dispatcher.py:153-161), and the cross-host path
                # here already deregisters when the link closes
                # (comm/remote.py); this is the local equivalent. A hang
                # keeps its lease by design — only the watchdog can call
                # that.
                self._registry.deregister(self.worker_id)
                global_metrics().inc("worker.crash_evicted")
                global_flight_recorder().record(
                    "worker_crash_evicted", worker=self.worker_id
                )
                log.warning(
                    "worker %s evicted on crash (event, not TTL)",
                    self.worker_id,
                )

    def _exec_loop_inner(self) -> None:
        while not self._crashed.is_set():
            task = self._inbox.get()
            if task is None or self._crashed.is_set():
                break
            if self._hung.is_set():
                # Hung worker: swallow the task, never reply. The
                # dispatcher's watchdog must recover it.
                continue
            if task.stage_index < 0:
                # Liveness probe: answer without executing anything. Must
                # flow through this loop (not a side channel) so a blocked
                # exec loop fails the probe the way it fails real tasks.
                self._results.put(
                    TaskResult(
                        request_id=task.request_id,
                        stage_index=task.stage_index,
                        attempt=task.attempt,
                        worker_id=self.worker_id,
                    )
                )
                continue
            if task.t_enqueue:
                # Inbox wait: workers drain serially, so queue depth is
                # latency — the per-worker serving-SLO signal.
                global_metrics().observe(
                    "worker.queue_wait_s",
                    time.perf_counter() - task.t_enqueue,
                )
            with self._state_lock:
                self._state = WorkerState.BUSY
            try:
                with self._bind_lock:
                    binding = self._bindings.get(task.stage_index)
                if binding is None:
                    raise RuntimeError(
                        f"stage {task.stage_index} not configured on "
                        f"{self.worker_id}"
                    )
                with global_tracer().span(
                    "stage_exec",
                    stage=task.stage_index,
                    worker=self.worker_id,
                    request=task.request_id,
                    attempt=task.attempt,
                ):
                    x = jax.device_put(task.payload, self.device)
                    y = binding.fn(binding.variables, x)
                    # Pytree-safe: decode-session stages return (output,
                    # caches) tuples, not a single array.
                    jax.block_until_ready(y)
                self._results.put(
                    TaskResult(
                        request_id=task.request_id,
                        stage_index=task.stage_index,
                        attempt=task.attempt,
                        worker_id=self.worker_id,
                        output=y,
                    )
                )
                global_metrics().inc("worker.tasks_ok")
            except Exception as e:  # noqa: BLE001 — report, don't die
                log.error("worker %s task failed: %s", self.worker_id, e)
                self._results.put(
                    TaskResult(
                        request_id=task.request_id,
                        stage_index=task.stage_index,
                        attempt=task.attempt,
                        worker_id=self.worker_id,
                        error=str(e),
                    )
                )
                global_metrics().inc("worker.tasks_failed")
            finally:
                with self._state_lock:
                    if self._state is not WorkerState.DEAD:
                        self._state = WorkerState.IDLE
