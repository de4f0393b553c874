"""Cross-host workers: a stage server process + a dispatcher-side proxy.

This is the multi-machine path of the reference, rebuilt: a worker process
(reference: ``python -m src.node``, ``/root/reference/src/node.py:210-211``)
serves stage configuration and data over TCP (there: four ports with
implicit message types, ``src/node.py:19-22``; here: one duplex connection
with typed frames, ``comm.framing``), and the dispatcher drives it through
``RemoteWorkerProxy`` — the same interface as the in-process
``StageWorker``, so the control plane (late binding, watchdog, re-dispatch)
is topology-blind.

Configuration transfers the model by *name + cut list + weights* (the
worker rebuilds the graph from the shared model registry and loads
flax-serialized weights), the TPU-native analog of the reference shipping
Keras architecture JSON + weight arrays (``src/dispatcher.py:223-264``,
``src/node.py:40-45``). Activations cross with a configurable codec
(``comm.codec``) — the zfp+lz4-at-DCN-boundaries design of SURVEY §2.3.

Heartbeats ride the same connection as typed ping frames; the proxy renews
the worker's registry lease only when pings arrive, so a dead process or a
cut link expires the lease exactly like a crashed in-process worker.
"""

from __future__ import annotations

import hmac
import itertools
import json
import socket
import threading
import time
from typing import Any

import jax
import numpy as np

from adapt_tpu.comm import codec as codec_lib
from adapt_tpu.comm.framing import (
    MSG_ACK,
    MSG_CONFIG,
    MSG_DATA,
    MSG_ERROR,
    MSG_RESULT,
    MSG_TELEMETRY,
    Message,
    payload_bytes,
    recv_msg,
    send_msg,
)
from adapt_tpu.config import FaultConfig
from adapt_tpu.control.registry import WorkerRegistry
from adapt_tpu.control.worker import TaskResult, WorkerState
from adapt_tpu.utils.compile_cache import ensure_compile_cache
from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.telemetry import (
    TelemetryReporter,
    global_federated_store,
)
from adapt_tpu.utils.tracing import (
    export_spans,
    global_flight_recorder,
    global_tracer,
)

log = get_logger("remote")

MSG_KILL = 6  # chaos hook for fault-injection tests
MSG_PING = 7
MSG_CONFIG_ERR = 8
#: Dispatcher-initiated canary probe (control.dispatcher watchdog) and its
#: answer. Distinct from MSG_PING: pings are *server-initiated* transport
#: heartbeats that only prove the link + ping thread; a probe answer must
#: round-trip the serve loop itself, so a hung server misses it.
MSG_PROBE = 9
MSG_PROBE_ACK = 10
#: Streamed configure (reference: count-prefixed sequence of per-array
#: compressed frames, ``src/dispatcher.py:76-89`` / ``src/node.py:
#: 101-119``): MSG_CONFIG carries the JSON header (model, cuts, stage,
#: array count, generation in ``request_id``), then one MSG_CONFIG_ARRAY
#: per weight leaf (``attempt`` = leaf index), then MSG_CONFIG_END. Each
#: frame takes the send lock independently, so probes and data interleave
#: with a multi-hundred-MB weights transfer instead of queueing behind it.
MSG_CONFIG_ARRAY = 11
MSG_CONFIG_END = 12
#: Worker-initiated join (reference: the WORKER writes /workers/<ip> into
#: etcd and the dispatcher discovers it, ``src/node_state.py:17-20``):
#: a fresh worker dials the dispatcher's WorkerGateway and announces
#: itself with MSG_HELLO {worker_id}; the gateway wraps the accepted
#: socket in a RemoteWorkerProxy, registers the lease, and answers
#: MSG_HELLO_ACK. The pool can now GROW at runtime, not only shrink.
MSG_HELLO = 13
MSG_HELLO_ACK = 14
#: Drop a stage binding (and/or an in-flight configure). Sent by the proxy
#: when a configure fails or is aborted after CONFIG_END already went out:
#: without it the server would install and pin the stage weights for a
#: handshake the dispatcher has already declared dead. ``request_id`` is
#: the generation to revoke, or 0 to drop whatever is installed.
MSG_UNCONFIGURE = 15
#: Install (or clear) a direct next-hop for a stage's outputs: Gen-1 chain
#: topology (the reference worker forwards activations straight to the
#: next worker's data port, ``/root/reference/src/node.py:163-179``),
#: rebuilt as an OPT-IN fast path for static healthy pools. Payload JSON:
#: ``{"next": [host, port], "next_stage": j}`` = forward my stage's
#: output as MSG_DATA for stage ``j`` directly to that worker;
#: ``{"next": null}`` = I am the chain tail — send MSG_RESULT on the
#: dispatcher link; ``{"clear": true}`` = revert to hub routing. The
#: worker ACKs with the frame's ``request_id`` (a proxy generation), so
#: route installs are reliable, not fire-and-forget. Errors (exec OR
#: forward failures) always go hub-ward on the dispatcher link — the
#: chain carries the data plane only, the hub keeps the control plane
#: (probes, deadlines, exactly-once, re-dispatch).
MSG_SET_ROUTE = 16
#: Chain-routed data. Routes apply ONLY to this type: after a chain
#: failure the hub falls back to per-stage dispatch with plain MSG_DATA,
#: which must return results hub-ward even if a stale route is still
#: installed on the worker (clears are best-effort on a possibly-dead
#: link). The frame type, not worker state, decides the topology.
MSG_DATA_CHAINED = 17


# --------------------------------------------------------------------------
# Worker-process side
# --------------------------------------------------------------------------


class RemoteStageServer:
    """Serves stage configure/execute for one device over one TCP port."""

    def __init__(
        self,
        port: int,
        device_index: int = 0,
        heartbeat_s: float = 0.5,
        host: str = "127.0.0.1",
        allow_registry: bool = True,
        telemetry_s: float = 2.0,
    ):
        """``allow_registry=False`` — serve ONLY architecture-by-value
        configures (``graph_spec`` in the header): the stance of a bare
        worker image that ships the framework but no model zoo
        (reference: any worker can ``model_from_json`` anything,
        ``src/node.py:40-45``).

        ``telemetry_s`` — cadence of telemetry-federation reports
        (``MSG_TELEMETRY``: windowed metric deltas, flight events,
        span exports) pushed on the DISPATCHER link's heartbeat
        thread; 0 disables the push. Reports ride only the primary
        (dispatcher) connection — chain-peer links would discard them
        unread, and two links pushing would split the deltas."""
        self.port = port
        self.host = host
        self.device = jax.devices()[device_index]
        self.heartbeat_s = heartbeat_s
        self.allow_registry = allow_registry
        self.telemetry_s = telemetry_s
        #: How this process names itself in telemetry reports (the
        #: dispatcher-side ingest overrides it with the lease's
        #: worker id — a dial-out server only knows its port).
        self.telemetry_worker = f"{host}:{port}"
        self._telemetry: TelemetryReporter | None = None
        #: Reports collected but not delivered (the link died between
        #: collect and send): collect() CONSUMES its snapshot window,
        #: so a dropped report would permanently lose that window's
        #: deltas from the fleet totals. Bounded — a long outage
        #: degrades to losing the oldest windows, loudly countable as
        #: a seq gap on the parent, never unbounded memory here.
        self._telemetry_backlog: list[tuple[int, bytes]] = []
        self._graph_cache: dict[str, Any] = {}
        self._stages: dict[int, tuple[Any, Any]] = {}  # idx -> (fn, vars)
        self._stage_gen: dict[int, int] = {}  # idx -> installing generation
        self._codec: codec_lib.Codec = codec_lib.get_codec("none")
        self._hung = False
        self._crashed = False
        #: stage -> {"next": (host, port) | None, "next_stage": int}.
        #: Present = chain mode for that stage; "next" None = chain tail.
        self._routes: dict[int, dict] = {}
        #: (host, port) -> (socket, send lock) persistent forward links.
        self._fwd: dict[tuple, tuple[socket.socket, threading.Lock]] = {}
        self._fwd_lock = threading.Lock()
        #: reply() of the dispatcher connection (the one control frames
        #: arrive on). Chain-tail results and chain errors go here — the
        #: data may have arrived on a peer worker's connection, but the
        #: hub owns completion and recovery.
        self._primary_reply = None

    def _build_stage(self, cfg: dict, leaves: list):
        """Rebuild the model — by REGISTRY NAME (shared model zoo) or by
        VALUE (``graph_spec``: the serialized LayerGraph itself, so an
        empty-registry worker can serve custom cuts/hyperparams/DAGs;
        reference ``model_from_json``, ``src/node.py:40-45``) — slice it,
        and load the stage weights from the streamed per-array ``leaves``
        (reference receiver: ``src/node.py:101-119``, count-prefixed
        per-array frames)."""
        from adapt_tpu.graph.partition import partition
        from adapt_tpu.graph.spec import graph_from_spec

        model_kwargs = cfg.get("model_kwargs", {})
        graph_spec = cfg.get("graph_spec")
        key = json.dumps(
            [
                cfg.get("model"),
                graph_spec,
                cfg.get("num_classes", 1000),
                cfg["cuts"],
                model_kwargs,
            ],
            sort_keys=True,
        )
        if key not in self._graph_cache:
            if graph_spec is not None:
                graph = graph_from_spec(graph_spec)
                input_shape = cfg.get("input_shape")
                if input_shape is None:
                    raise ValueError(
                        "graph_spec configure needs an explicit input_shape"
                    )
            elif not self.allow_registry:
                raise RuntimeError(
                    "this worker serves architecture-by-value only "
                    "(--no-registry); send a graph_spec, not a model name"
                )
            else:
                from adapt_tpu.models import MODEL_REGISTRY

                factory, default_shape = MODEL_REGISTRY[cfg["model"]]
                # model_kwargs: extra factory arguments (e.g. resnet50's
                # stem="s2d") — the joiner must rebuild the EXACT graph the
                # dispatcher partitioned or the streamed weights won't fit.
                graph = factory(
                    num_classes=cfg.get("num_classes", 1000), **model_kwargs
                )
                input_shape = cfg.get("input_shape") or [1, *default_shape]
            plan = partition(graph, cfg["cuts"])
            template = jax.eval_shape(
                graph.init,
                jax.random.PRNGKey(0),
                jax.ShapeDtypeStruct(tuple(input_shape), jax.numpy.float32),
            )
            self._graph_cache[key] = (plan, template)
        plan, template = self._graph_cache[key]
        idx = cfg["stage_index"]
        if not 0 <= idx < plan.num_stages:
            raise ValueError(
                f"stage index {idx} out of range (plan has "
                f"{plan.num_stages} stages)"
            )
        spec = plan.stages[idx]
        stage_template = {n: template[n] for n in spec.node_names}
        t_leaves, treedef = jax.tree_util.tree_flatten(stage_template)
        if len(leaves) != len(t_leaves):
            raise ValueError(
                f"stage {idx}: got {len(leaves)} weight arrays, template "
                f"has {len(t_leaves)}"
            )
        variables = jax.tree_util.tree_unflatten(treedef, leaves)
        variables = jax.device_put(variables, self.device)
        jax.block_until_ready(variables)
        fn = jax.jit(plan.stage_apply(spec))
        self._stages[idx] = (fn, variables)
        self._codec = codec_lib.get_codec(cfg.get("codec", "none"))

    #: Bound on forward-link sends: a wedged next hop must error this
    #: request hub-ward (where the replay machinery lives), not freeze the
    #: serving thread forever while pings keep the lease alive.
    FWD_SEND_TIMEOUT_S = 15.0

    def _fwd_connect(
        self, addr: tuple
    ) -> tuple[socket.socket, threading.Lock]:
        """Persistent forward link to the next chain worker. The peer's
        serve loop answers pings (and nothing we care about) on it, so a
        drain thread discards inbound frames — without it the peer's ping
        writes would slowly fill the TCP buffer of a socket nobody reads."""
        with self._fwd_lock:
            entry = self._fwd.get(addr)
            if entry is not None:
                return entry
            sock = socket.create_connection(addr, timeout=5.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # Timeout bounds SENDS; the drain thread's reads retry through
            # it (framing's retry_on_timeout default).
            sock.settimeout(self.FWD_SEND_TIMEOUT_S)
            entry = (sock, threading.Lock())
            self._fwd[addr] = entry

        def drain():
            try:
                while True:
                    recv_msg(sock)
            except (ConnectionError, OSError):
                self._fwd_drop(addr, sock)

        threading.Thread(target=drain, daemon=True).start()
        return entry

    def _fwd_drop(self, addr: tuple, sock: socket.socket) -> None:
        """Evict (and close) a forward link. A send failure MUST come
        through here: bytes may be half-written, so the stream is
        unusable — a later ``setup_chain`` over the same topology has to
        re-dial, not cache-hit a desynced socket."""
        with self._fwd_lock:
            if self._fwd.get(addr) is not None and self._fwd[addr][0] is sock:
                del self._fwd[addr]
        try:
            sock.close()
        except OSError:
            pass

    def _fwd_gc(self) -> None:
        """Close forward links no live route references (route cleared or
        re-pointed): without this, every chain reconfiguration would leak
        a socket here plus a handler+ping thread pair on the peer."""
        live = {r["next"] for r in self._routes.values() if r["next"]}
        with self._fwd_lock:
            dead = [
                (a, s) for a, (s, _) in self._fwd.items() if a not in live
            ]
        for addr, sock in dead:
            self._fwd_drop(addr, sock)

    def _handle(self, conn: socket.socket) -> int:
        """Serve one connection until it closes; returns the number of
        messages processed (0 = the peer closed before saying anything —
        the shape of a gateway join rejection)."""
        n_msgs = 0
        stop_ping = threading.Event()
        # The ping thread and the serve loop both write this connection;
        # without a lock a ping frame can land inside a partially-sent
        # result frame and corrupt the stream.
        send_lock = threading.Lock()

        def reply(msg: Message) -> None:
            with send_lock:
                send_msg(conn, msg)

        def ping_loop():
            # Telemetry cadence in heartbeat units (the push shares the
            # ping thread so a wedged serve loop stops reporting — which
            # is exactly the staleness signal the parent's
            # fleet.report_age_s gauge surfaces).
            every = (
                max(1, round(self.telemetry_s / self.heartbeat_s))
                if self.telemetry_s > 0
                else 0
            )
            beats = 0
            while not stop_ping.wait(self.heartbeat_s):
                if self._crashed:
                    return
                try:
                    reply(Message(MSG_PING, 0, 0, 0, b""))
                except OSError:
                    return
                beats += 1
                if not every or beats % every:
                    continue
                if self._primary_reply is not reply:
                    continue  # only the dispatcher link carries reports
                try:
                    if self._telemetry is None:
                        self._telemetry = TelemetryReporter(
                            "stage", self.telemetry_worker
                        )
                        # Capacity plane: a stage worker is a minimal
                        # source — which stages it holds, so the fleet
                        # capacity view shows it with first-class
                        # staleness. Function-scoped import: comm must
                        # not depend on runtime at module level.
                        from adapt_tpu.runtime.capacity import stage_book

                        self._telemetry.capacity_provider = (
                            lambda: stage_book(len(self._stages))
                        )
                    report = self._telemetry.collect()
                    # default=str: a non-JSON value (numpy scalar in a
                    # gauge or flight datum) degrades to its repr —
                    # the same hazard rule the exporter's JSON
                    # endpoints apply — instead of killing this
                    # worker's telemetry forever.
                    self._telemetry_backlog.append(
                        (
                            int(report["seq"]),
                            json.dumps(report, default=str).encode(),
                        )
                    )
                    del self._telemetry_backlog[:-8]
                    # Oldest first (the store's seq-gap loss detector
                    # relies on in-order arrival); a frame that fails
                    # to send stays queued for the next beat or the
                    # next dispatcher connection.
                    while self._telemetry_backlog:
                        seq, blob = self._telemetry_backlog[0]
                        reply(Message(MSG_TELEMETRY, 0, seq, 0, blob))
                        self._telemetry_backlog.pop(0)
                except OSError:
                    return
                except Exception:  # noqa: BLE001 — telemetry must never
                    log.exception("telemetry push failed")  # kill pings

        threading.Thread(target=ping_loop, daemon=True).start()
        # (stage, generation) -> {"cfg": dict, "arrays": {index: ndarray}}:
        # a configure in flight, assembled from interleaved frames. Two
        # concurrent configures for the same stage (the dispatcher recovery
        # path) stay separate because the generation disambiguates.
        pending: dict[tuple[int, int], dict] = {}
        try:
            while not self._crashed:
                msg = recv_msg(conn)
                n_msgs += 1
                if pending:
                    # Purge abandoned configures on every message: an
                    # aborted mid-stream configure whose UNCONFIGURE also
                    # got lost must not retain its buffered weight arrays
                    # for the life of the connection. Idle-based (not
                    # supersede-on-same-stage) so neither a LIVE concurrent
                    # configure of the same stage — the dispatcher recovery
                    # path — nor a slow-but-streaming transfer is evicted.
                    now = time.monotonic()
                    for key in [
                        k
                        for k, e in pending.items()
                        if now - e["ts"] > 300.0
                    ]:
                        del pending[key]
                if msg.msg_type == MSG_CONFIG:
                    # Only the dispatcher configures; remember its link so
                    # chained results/errors route hub-ward even when the
                    # triggering data frame came from a peer worker.
                    self._primary_reply = reply
                    cfg = json.loads(payload_bytes(msg.payload).decode())
                    pending[(msg.stage_index, msg.request_id)] = {
                        "cfg": cfg,
                        "arrays": {},
                        "ts": time.monotonic(),
                    }
                elif msg.msg_type == MSG_SET_ROUTE:
                    self._primary_reply = reply
                    try:
                        info = json.loads(payload_bytes(msg.payload).decode())
                        if info.get("clear"):
                            self._routes.pop(msg.stage_index, None)
                            self._fwd_gc()
                        else:
                            nxt = info.get("next")
                            route = {
                                "next": tuple(nxt) if nxt else None,
                                "next_stage": info.get("next_stage", -1),
                            }
                            if route["next"] is not None:
                                # Pre-dial so an unreachable next hop fails
                                # the install, not the first request.
                                self._fwd_connect(route["next"])
                            self._routes[msg.stage_index] = route
                            self._fwd_gc()
                        reply(
                            Message(
                                MSG_ACK, msg.stage_index, msg.request_id, 0, b""
                            )
                        )
                    except Exception as e:  # noqa: BLE001
                        log.error("route install failed: %s", e)
                        reply(
                            Message(
                                MSG_CONFIG_ERR,
                                msg.stage_index,
                                msg.request_id,
                                0,
                                str(e).encode(),
                            )
                        )
                elif msg.msg_type == MSG_CONFIG_ARRAY:
                    entry = pending.get((msg.stage_index, msg.request_id))
                    if entry is not None:
                        entry["arrays"][msg.attempt] = codec_lib.unpack(
                            msg.payload
                        )
                        # Keep-alive: the purge below is idle-based, so a
                        # legitimately slow (>300 s) streaming transfer is
                        # never evicted while frames still arrive.
                        entry["ts"] = time.monotonic()
                elif msg.msg_type == MSG_CONFIG_END:
                    key = (msg.stage_index, msg.request_id)
                    entry = pending.pop(key, None)
                    try:
                        if entry is None:
                            raise RuntimeError(
                                f"CONFIG_END for unknown configure {key}"
                            )
                        cfg, arrays = entry["cfg"], entry["arrays"]
                        n = cfg["n_arrays"]
                        if len(arrays) != n:
                            raise RuntimeError(
                                f"stage {msg.stage_index}: received "
                                f"{len(arrays)}/{n} weight arrays"
                            )
                        leaves = [arrays[i] for i in range(n)]
                        self._build_stage(cfg, leaves)
                        self._stage_gen[msg.stage_index] = msg.request_id
                        reply(
                            Message(
                                MSG_ACK,
                                msg.stage_index,
                                msg.request_id,
                                0,
                                b"",
                            )
                        )
                    except Exception as e:  # noqa: BLE001
                        log.error("remote configure failed: %s", e)
                        reply(
                            Message(
                                MSG_CONFIG_ERR,
                                msg.stage_index,
                                msg.request_id,
                                0,
                                str(e).encode(),
                            )
                        )
                elif msg.msg_type == MSG_UNCONFIGURE:
                    gen = msg.request_id
                    pending.pop((msg.stage_index, gen), None)
                    # Revoke the install only if it came from the revoked
                    # generation (or unconditionally for gen 0) — a newer
                    # configure's binding must survive an old revoke.
                    if gen == 0 or self._stage_gen.get(msg.stage_index) == gen:
                        self._stages.pop(msg.stage_index, None)
                        self._stage_gen.pop(msg.stage_index, None)
                        log.info(
                            "stage %d unconfigured (gen %d)",
                            msg.stage_index,
                            gen,
                        )
                elif msg.msg_type == MSG_HELLO_ACK:
                    continue  # join handshake answer; nothing to do
                elif msg.msg_type in (MSG_DATA, MSG_DATA_CHAINED):
                    if self._hung:
                        continue  # swallow; watchdog must recover
                    self._execute(reply, msg)
                elif msg.msg_type == MSG_PROBE:
                    if self._hung:
                        continue  # swallow like data; probe deadline fires
                    reply(
                        Message(
                            MSG_PROBE_ACK,
                            msg.stage_index,
                            msg.request_id,
                            msg.attempt,
                            b"",
                        )
                    )
                elif msg.msg_type == MSG_KILL:
                    mode = payload_bytes(msg.payload).decode()
                    log.warning("remote worker kill: %s", mode)
                    if mode == "hang":
                        self._hung = True
                    else:
                        self._crashed = True
                        break
        except (ConnectionError, OSError):
            pass
        finally:
            stop_ping.set()
            conn.close()
        return n_msgs

    def _execute(self, reply, msg: Message) -> None:
        # Chain errors must reach the HUB (which owns re-dispatch), not the
        # upstream peer whose forward socket nobody answers on (its drain
        # thread discards frames). Routes bind to the frame type: hub-path
        # MSG_DATA ignores them.
        chained = msg.msg_type == MSG_DATA_CHAINED
        route = self._routes.get(msg.stage_index) if chained else None
        err_reply = (self._primary_reply or reply) if chained else reply
        try:
            if chained and route is None:
                # The route was cleared while this frame was in flight:
                # there is no legitimate routeless chained frame. Error
                # hub-ward NOW so the dispatcher replays immediately
                # instead of waiting out a full chain deadline.
                raise RuntimeError(
                    f"chained frame for stage {msg.stage_index} arrived "
                    "after its route was cleared"
                )
            entry = self._stages.get(msg.stage_index)
            if entry is None:
                raise RuntimeError(f"stage {msg.stage_index} not configured")
            fn, variables = entry
            # Span tagged with the header's OWN request/attempt ids — the
            # key the dispatcher stitches this back into the originating
            # request's trace with (no side-channel correlation).
            t_exec = time.perf_counter()
            with global_tracer().span(
                "remote.stage_exec",
                request=msg.request_id,
                attempt=msg.attempt,
                stage=msg.stage_index,
            ) as sp:
                x = codec_lib.unpack(msg.payload)
                y = fn(variables, jax.device_put(x, self.device))
                y.block_until_ready()
                # Device array handed to the codec directly: int8dev
                # quantizes on-chip before the host fetch; host codecs
                # coerce themselves. pack_frames + the framing layer's
                # scatter write: the encoded payload goes to the kernel as
                # buffer views, never concatenated host-side (zero framing
                # copies per hop).
                out = codec_lib.pack_frames(self._codec, y)
            # Worker-process telemetry: counters + an exec-wall
            # histogram in THIS process's registry (federated to the
            # dispatcher as MSG_TELEMETRY reports) and a flight edge
            # naming the request — the worker's half of the
            # /debug/request/<id> forensics story.
            global_metrics().inc("remote.stage_execs")
            global_metrics().observe(
                "remote.stage_exec_s", time.perf_counter() - t_exec
            )
            global_flight_recorder().record(
                "remote_exec",
                request=msg.request_id,
                stage=msg.stage_index,
                attempt=msg.attempt,
            )
            # Trace annex: this hop's span, appended to any spans already
            # riding the inbound frame (mid-chain hops accumulate, so the
            # tail result delivers the WHOLE chain's spans hub-ward).
            annex = None
            if sp is not None or msg.annex:
                # A corrupt inbound annex must NEVER fail the stage (the
                # compute already succeeded): any parse surprise just
                # drops the upstream spans. Chains are at most num_stages
                # hops, so the re-parse per hop stays trivial.
                acc = []
                if msg.annex:
                    try:
                        parsed = json.loads(msg.annex.decode())
                        if isinstance(parsed, list):
                            acc = parsed
                    except (ValueError, UnicodeDecodeError):
                        pass
                acc.extend(export_spans([sp]))
                annex = json.dumps(acc).encode()
            if route is None:
                # Hub routing: the stage output returns whence it came.
                reply(
                    Message(
                        MSG_RESULT,
                        msg.stage_index,
                        msg.request_id,
                        msg.attempt,
                        out,
                        annex=annex,
                    )
                )
            elif route["next"] is None:
                # Chain tail: the FINAL result goes to the dispatcher link
                # (the request's data may have hopped in from a peer).
                (self._primary_reply or reply)(
                    Message(
                        MSG_RESULT,
                        msg.stage_index,
                        msg.request_id,
                        msg.attempt,
                        out,
                        annex=annex,
                    )
                )
            else:
                # Mid-chain: the activation goes straight to the next
                # worker — the hub never touches it (SURVEY §3.2's 2·S-hop
                # critique; reference Gen-1 ``src/node.py:163-179``).
                sock, lock = self._fwd_connect(route["next"])
                try:
                    with lock:
                        send_msg(
                            sock,
                            Message(
                                MSG_DATA_CHAINED,
                                route["next_stage"],
                                msg.request_id,
                                msg.attempt,
                                out,
                                annex=annex,
                            ),
                        )
                except (TimeoutError, OSError):
                    # Half-written frame: the stream is dead. Evict it so
                    # a chain re-enable re-dials, then report hub-ward.
                    self._fwd_drop(route["next"], sock)
                    raise
        except Exception as e:  # noqa: BLE001
            try:
                err_reply(
                    Message(
                        MSG_ERROR,
                        msg.stage_index,
                        msg.request_id,
                        msg.attempt,
                        str(e).encode(),
                    )
                )
            except Exception:  # noqa: BLE001 — error path must not recurse
                log.warning("could not report execute error hub-ward")

    def serve_forever(self) -> None:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((self.host, self.port))
        srv.listen(4)
        log.info("remote stage server on %s:%d", self.host, self.port)
        while not self._crashed:
            try:
                srv.settimeout(0.5)
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # Thread per connection: chain mode means a PEER worker dials
            # in with data while the dispatcher link is mid-service — a
            # serial accept loop would never serve the second link.
            threading.Thread(
                target=self._handle, args=(conn,), daemon=True
            ).start()
        srv.close()

    def connect_and_serve(
        self,
        address: tuple[str, int],
        worker_id: str,
        retries: int = 20,
        secret: str | None = None,
    ) -> None:
        """Worker-initiated join: dial the dispatcher's WorkerGateway,
        announce ourselves, then serve the connection. The TPU-native
        re-expression of the reference worker self-registering in etcd
        (``/root/reference/src/node_state.py:17-20``) — here the dial +
        MSG_HELLO *is* the registration write, and the gateway-side lease
        renewal rides the same connection's pings. ``secret`` (if the
        gateway requires one) rides in the HELLO; a rejected join shows
        up as the gateway closing the link before any message.

        Joins RETRY (``join_retries``, 1 s apart): the legitimate rejoin
        race is a worker redialing after a link blip while the gateway's
        stale proxy for the SAME worker_id has not yet noticed its dead
        socket — the duplicate-live-id guard rejects the first attempt,
        the stale proxy deregisters within a ping interval, and the next
        attempt lands. A genuine rejection (bad secret, true duplicate)
        exhausts the budget and raises."""
        join_retries = 8
        # Joiners DO know their fleet identity — name telemetry reports
        # with it (dial-out servers fall back to host:port and let the
        # proxy-side ingest rename them).
        self.telemetry_worker = worker_id
        for join_attempt in range(join_retries):
            last: Exception | None = None
            for _ in range(retries):
                try:
                    conn = socket.create_connection(address, timeout=5.0)
                    break
                except OSError as e:
                    last = e
                    time.sleep(0.25)
            else:
                raise ConnectionError(
                    f"cannot reach gateway at {address}: {last}"
                )
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # create_connection's 5 s dial timeout must NOT linger on the
            # serving socket: a timed-out mid-frame result send would
            # desync the stream and a slow ping send would kill the
            # heartbeat thread. Serving uses blocking sends, like the
            # dial-in accept path.
            conn.settimeout(None)
            info = {"worker_id": worker_id}
            if secret is not None:
                info["secret"] = secret
            send_msg(
                conn, Message(MSG_HELLO, 0, 0, 0, json.dumps(info).encode())
            )
            log.info("dialed gateway %s:%d as %s", *address, worker_id)
            if self._handle(conn) > 0 or self._crashed:
                # A real session ran (or we were killed through it);
                # done. A later link drop is the gateway proxy's problem.
                return
            log.warning(
                "gateway closed the join as %s without serving "
                "(rejected or stale-duplicate race), attempt %d/%d",
                worker_id,
                join_attempt + 1,
                join_retries,
            )
            time.sleep(1.0)
        raise ConnectionError(
            f"gateway refused join as {worker_id!r} "
            f"after {join_retries} attempts"
        )


# --------------------------------------------------------------------------
# Dispatcher side
# --------------------------------------------------------------------------


class RemoteWorkerProxy:
    """Drives a RemoteStageServer; presents the StageWorker interface."""

    def __init__(
        self,
        worker_id: str,
        address: tuple[str, int],
        registry: WorkerRegistry,
        result_queue,
        model_config: dict,
        codec_name: str = "none",
        weights_codec: str = "lz",
        fault: FaultConfig | None = None,
        sock: socket.socket | None = None,
        blob_cache: dict | None = None,
    ):
        """``sock`` — an already-connected socket (gateway path: the worker
        dialed us); when None, :meth:`start` dials ``address``.

        ``blob_cache`` — optional dict shared across proxies (the gateway
        passes one): packed stage-weight frames are deterministic for a
        given (stage, codec), so N joining workers — or one recovery storm
        re-configuring the same stage — pay the compression pass once."""
        self.worker_id = worker_id
        self.address = address
        #: Dial-out proxies know the worker's LISTENING address — the one
        #: a chain peer can reach it at. Gateway joiners' ``address`` is
        #: an ephemeral client port, useless as a next hop.
        self._dialed_out = sock is None
        #: MSG_RESULT/MSG_ERROR frames this link delivered — lets tests
        #: (and the chain A/B) prove the hub never saw mid-chain traffic.
        self.results_received = 0
        self.result_bytes_received = 0
        self._registry = registry
        self._results = result_queue
        self._fault = fault or FaultConfig()
        self._model_config = model_config
        self._codec = codec_lib.get_codec(codec_name)
        self._codec_name = codec_name
        self._wcodec = codec_lib.get_codec(weights_codec)
        self._sock: socket.socket | None = sock
        self._send_lock = threading.Lock()
        self._configured: dict[int, int] = {}  # stage -> newest gen installed
        # Config handshake state keyed by (stage_index, generation): two
        # concurrent configures for the same stage (reachable from two
        # forward threads on the recovery path) get independent events
        # instead of clobbering each other's.
        self._config_gen = itertools.count(1)
        self._blob_cache = blob_cache
        self._ack_lock = threading.Lock()
        self._config_acks: dict[tuple[int, int], threading.Event] = {}
        self._config_errors: dict[tuple[int, int], str] = {}
        self._inflight_count = 0
        #: (stage, request, attempt) submits this proxy counted into
        #: _inflight_count — the only results allowed to decrement it
        #: (chain-tail results for head-submitted requests are not).
        self._counted: set[tuple[int, int, int]] = set()
        self._count_lock = threading.Lock()
        self._stop = threading.Event()
        self._reader: threading.Thread | None = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "RemoteWorkerProxy":
        # Idempotent: recovery dials proxies one by one (skipping the
        # unreachable) before Dispatcher.start() walks the pool calling
        # start() again — a second call must not stack a second reader
        # thread or lease.
        if self._reader is not None:
            return self
        if self._sock is None:
            deadline = time.monotonic() + self._fault.startup_wait_s
            last: Exception | None = None
            while time.monotonic() < deadline:
                try:
                    self._sock = socket.create_connection(
                        self.address, timeout=5.0
                    )
                    self._sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                    )
                    break
                except OSError as e:
                    last = e
                    time.sleep(0.1)
            if self._sock is None:
                raise ConnectionError(
                    f"cannot reach remote worker at {self.address}: {last}"
                )
        # Socket timeout bounds blocked *sends* (wedged peer, full TCP
        # buffers); the reader side retries through timeouts (framing).
        self._sock.settimeout(self._fault.send_timeout_s)
        # Keep the ownership token: if THIS connection dies after a
        # replacement worker re-registered the same id, our deregister
        # must not evict the replacement's lease.
        self._lease_token = self._registry.register(
            self.worker_id,
            meta={"address": f"{self.address[0]}:{self.address[1]}"},
            ttl_s=self._fault.lease_ttl_s,
        )
        self._reader = threading.Thread(
            target=self._read_loop, name=f"{self.worker_id}-reader", daemon=True
        )
        self._reader.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        if self._reader is not None:
            self._reader.join(timeout=2.0)
        self._registry.deregister(
            self.worker_id, token=getattr(self, "_lease_token", None)
        )

    def _mark_dead(self, why: str) -> None:
        """Tear the link down after a send timeout/failure: a partial send
        leaves the stream state unknowable, so the only safe move is to
        drop the connection and let membership re-dispatch our in-flight
        work (immediately, via deregister — no need to wait out the lease)."""
        if self._stop.is_set():
            return
        log.warning("remote %s link dropped: %s", self.worker_id, why)
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        self._registry.deregister(
            self.worker_id, token=getattr(self, "_lease_token", None)
        )

    def _send(self, msg: Message, lock_timeout: float | None = None) -> None:
        """Bounded send: both the wait for the channel lock and the socket
        write itself are time-limited (reference analog: non-blocking
        sends with select backpressure, ``src/node_state.py:39-89``). A
        lock timeout raises but keeps the link (the channel was merely
        busy); a *socket* timeout kills the link (bytes may be half-sent)."""
        if self._stop.is_set():
            raise ConnectionError(
                f"remote worker {self.worker_id} link is down"
            )
        timeout = (
            self._fault.send_timeout_s if lock_timeout is None else lock_timeout
        )
        if not self._send_lock.acquire(timeout=timeout):
            raise TimeoutError(
                f"{self.worker_id} send channel busy for {timeout}s"
            )
        try:
            send_msg(self._sock, msg)
        except TimeoutError:
            self._mark_dead("send timed out (peer not draining)")
            raise ConnectionError(
                f"send to {self.worker_id} timed out; link dropped"
            ) from None
        except OSError as e:
            self._mark_dead(f"send failed: {e}")
            raise
        finally:
            self._send_lock.release()

    # -- StageWorker interface ----------------------------------------------

    @property
    def state(self) -> WorkerState:
        if self._stop.is_set():
            return WorkerState.DEAD
        with self._count_lock:
            return (
                WorkerState.BUSY if self._inflight_count else WorkerState.IDLE
            )

    @property
    def queue_depth(self) -> int:
        with self._count_lock:
            return self._inflight_count

    def is_configured(self, stage_index: int) -> bool:
        return stage_index in self._configured

    def configured_stages(self) -> tuple[int, ...]:
        return tuple(sorted(self._configured))

    def configure(
        self, stage_index: int, fn, host_variables, spec=None, abort=None
    ) -> int:
        """Ship (model name, cuts, stage index) + the stage weights as a
        count-prefixed stream of per-array compressed frames (reference:
        ``src/dispatcher.py:76-89``), then wait for the generation's ACK.
        ``fn`` is ignored — the remote compiles its own stage program.
        Each array frame takes the send lock independently, so data and
        probe traffic interleave with a large weights transfer instead of
        queueing behind one monolithic send."""
        del fn, spec
        if self._stop.is_set():
            raise ConnectionError(
                f"remote worker {self.worker_id} link is down"
            )
        gen = next(self._config_gen)
        key = (stage_index, gen)
        cache_key = (stage_index, self._wcodec.name)
        blobs = (
            self._blob_cache.get(cache_key)
            if self._blob_cache is not None
            else None
        )
        if blobs is None:
            leaves = jax.tree_util.tree_leaves(host_variables)
            blobs = [
                codec_lib.pack(self._wcodec, np.asarray(leaf))
                for leaf in leaves
            ]
            if self._blob_cache is not None:
                self._blob_cache[cache_key] = blobs
        header = json.dumps(
            {
                **self._model_config,
                "stage_index": stage_index,
                "codec": self._codec_name,
                "n_arrays": len(blobs),
            }
        ).encode()
        ack = threading.Event()
        with self._ack_lock:
            self._config_acks[key] = ack
        end_sent = False
        try:
            self._send(Message(MSG_CONFIG, stage_index, gen, 0, header))
            for i, blob in enumerate(blobs):
                if abort is not None and abort():
                    raise RuntimeError(
                        f"configure of stage {stage_index} aborted "
                        f"mid-stream (caller timed out)"
                    )
                self._send(
                    Message(MSG_CONFIG_ARRAY, stage_index, gen, i, blob)
                )
            end_sent = True
            self._send(Message(MSG_CONFIG_END, stage_index, gen, 0, b""))
            if not ack.wait(self._fault.configure_timeout_s):
                raise TimeoutError(
                    f"no config ACK for stage {stage_index} (gen {gen}) "
                    f"from {self.worker_id}"
                )
            with self._ack_lock:
                err = self._config_errors.pop(key, None)
            if err is not None:
                raise RuntimeError(f"remote configure failed: {err}")
            if abort is not None and abort():
                raise RuntimeError(
                    f"configure of stage {stage_index} aborted "
                    f"(caller timed out)"
                )
            self._configured[stage_index] = max(
                self._configured.get(stage_index, 0), gen
            )
            return gen
        except BaseException:
            # CONFIG_END already went out (or an abort fired late): the
            # server may install — or have installed — the stage for a
            # handshake we just declared failed. Revoke this generation so
            # the worker doesn't pin abandoned weights; the revoke is
            # gen-scoped, so a racing newer configure's binding survives.
            if end_sent:
                try:
                    self._send(
                        Message(MSG_UNCONFIGURE, stage_index, gen, 0, b"")
                    )
                except Exception:  # noqa: BLE001 — link may be down
                    pass
            raise
        finally:
            with self._ack_lock:
                self._config_acks.pop(key, None)
                self._config_errors.pop(key, None)

    @property
    def chain_address(self) -> tuple[str, int] | None:
        """Where a chain peer can dial this worker, or None when unknown
        (gateway joiners don't announce a listen port)."""
        return self.address if self._dialed_out else None

    def send_route(
        self,
        stage_index: int,
        next_addr: tuple[str, int] | None,
        next_stage: int = -1,
        clear: bool = False,
    ) -> None:
        """Install (or clear) the worker's direct next-hop for
        ``stage_index``. Installs wait for the ACK — reliable, like
        configure. CLEARS are fire-and-forget with a short lock wait:
        they run on the shared forward pool right when a chain just
        failed, and correctness never depends on them (hub traffic uses
        plain MSG_DATA, which ignores routes) — blocking recovery threads
        for configure_timeout_s per clear would starve the replay path.
        ``next_addr=None`` (without ``clear``) marks the chain tail."""
        gen = next(self._config_gen)
        key = (stage_index, gen)
        payload = json.dumps(
            {"clear": True}
            if clear
            else {
                "next": list(next_addr) if next_addr else None,
                "next_stage": next_stage,
            }
        ).encode()
        if clear:
            self._send(
                Message(MSG_SET_ROUTE, stage_index, gen, 0, payload),
                lock_timeout=1.0,
            )
            return
        ack = threading.Event()
        with self._ack_lock:
            self._config_acks[key] = ack
        try:
            self._send(Message(MSG_SET_ROUTE, stage_index, gen, 0, payload))
            if not ack.wait(self._fault.configure_timeout_s):
                raise TimeoutError(
                    f"no route ACK for stage {stage_index} from "
                    f"{self.worker_id}"
                )
            with self._ack_lock:
                err = self._config_errors.pop(key, None)
            if err is not None:
                raise RuntimeError(f"route install failed: {err}")
        finally:
            with self._ack_lock:
                self._config_acks.pop(key, None)
                self._config_errors.pop(key, None)

    def unconfigure(
        self, stage_index: int, generation: int | None = None
    ) -> None:
        """Drop the stage binding on the remote (and locally): interface
        parity with ``StageWorker.unconfigure``. With ``generation``, the
        revoke is scoped to that configure (gen 0 = unconditional) so a
        newer configure's binding survives an old undo."""
        if generation is None:
            self._configured.pop(stage_index, None)
        elif self._configured.get(stage_index) == generation:
            self._configured.pop(stage_index, None)
        try:
            self._send(
                Message(
                    MSG_UNCONFIGURE, stage_index, generation or 0, 0, b""
                )
            )
        except Exception:  # noqa: BLE001 — best effort; link may be down
            pass

    def submit(self, task) -> None:
        if task.stage_index < 0:
            # Canary probe (control.dispatcher watchdog): no payload, no
            # in-flight accounting — the dispatcher tracks it in _probes.
            # Extra-short lock wait: the watchdog thread calls this and a
            # dropped probe is recoverable (it just re-probes later).
            self._send(
                Message(
                    MSG_PROBE,
                    task.stage_index,
                    task.request_id,
                    task.attempt,
                    b"",
                ),
                lock_timeout=1.0,
            )
            return
        # Pass the payload through un-coerced: device-side codecs
        # (int8dev) quantize on-chip BEFORE the host fetch; host codecs
        # call np.ascontiguousarray themselves. pack_frames: the encoded
        # payload rides as buffer views into the framing layer's scatter
        # write — no host-side header+payload concatenation.
        payload = codec_lib.pack_frames(self._codec, task.payload)
        if getattr(task, "chained", False):
            # Chain-mode head submit: the RESULT arrives on the TAIL
            # worker's link, so counting it here would leak this proxy's
            # in-flight depth forever. The dispatcher tracks chain
            # requests in its own in-flight registry.
            self._send(
                Message(
                    MSG_DATA_CHAINED,
                    task.stage_index,
                    task.request_id,
                    task.attempt,
                    payload,
                )
            )
            return
        key = (task.stage_index, task.request_id, task.attempt)
        with self._count_lock:
            self._inflight_count += 1
            self._counted.add(key)
        try:
            self._send(
                Message(
                    MSG_DATA,
                    task.stage_index,
                    task.request_id,
                    task.attempt,
                    payload,
                )
            )
        except Exception:
            with self._count_lock:
                if key in self._counted:
                    self._counted.discard(key)
                    self._inflight_count = max(0, self._inflight_count - 1)
            raise

    def kill(self, mode: str = "crash") -> None:
        self._send(Message(MSG_KILL, 0, 0, 0, mode.encode()))

    # -- internals -----------------------------------------------------------

    def _read_loop(self) -> None:
        while not self._stop.is_set():
            try:
                msg = recv_msg(self._sock)
            except (ConnectionError, OSError):
                break
            if msg.msg_type == MSG_PING:
                self._registry.heartbeat(
                    self.worker_id, ttl_s=self._fault.lease_ttl_s
                )
            elif msg.msg_type == MSG_TELEMETRY:
                # Fold the worker's report into the process-global
                # federated store under THIS lease's worker id (the
                # report only knows its port). Malformed reports are
                # counted, never allowed to kill the read loop.
                try:
                    global_federated_store().ingest(
                        json.loads(payload_bytes(msg.payload).decode()),
                        worker=self.worker_id,
                    )
                    global_metrics().inc("fleet.reports_total")
                except Exception:  # noqa: BLE001
                    global_metrics().inc("fleet.report_rejected_total")
            elif msg.msg_type == MSG_PROBE_ACK:
                self._results.put(
                    TaskResult(
                        request_id=msg.request_id,
                        stage_index=msg.stage_index,
                        attempt=msg.attempt,
                        worker_id=self.worker_id,
                    )
                )
            elif msg.msg_type == MSG_ACK:
                with self._ack_lock:
                    ev = self._config_acks.get(
                        (msg.stage_index, msg.request_id)
                    )
                if ev is not None:
                    ev.set()
            elif msg.msg_type == MSG_CONFIG_ERR:
                key = (msg.stage_index, msg.request_id)
                with self._ack_lock:
                    self._config_errors[key] = payload_bytes(
                        msg.payload
                    ).decode()
                    ev = self._config_acks.get(key)
                if ev is not None:
                    ev.set()
            elif msg.msg_type in (MSG_RESULT, MSG_ERROR):
                self.results_received += 1
                self.result_bytes_received += len(msg.payload)
                if msg.annex:
                    # Remote-recorded spans for this request: stitch them
                    # into the local trace ring (they keep the worker's
                    # pid/tid, so /trace.json shows them on their own
                    # process row, correlated by args.request).
                    tracer = global_tracer()
                    if tracer.enabled:
                        # ingest() is garbage-tolerant (non-list JSON,
                        # malformed entries); only the decode itself can
                        # raise here. NOTHING may escape — an exception
                        # would kill the read loop without _mark_dead
                        # and silently strand every future result.
                        try:
                            tracer.ingest(json.loads(msg.annex.decode()))
                        except (ValueError, UnicodeDecodeError):
                            global_metrics().inc("tracer.ingest_rejected")
                # Only a result matching a submit THIS proxy counted may
                # decrement: a chain tail delivers results for requests
                # the HEAD proxy submitted (never counted here), and
                # blindly decrementing would deflate this link's
                # in-flight depth and skew least-loaded _acquire ranking
                # toward the tail worker (ADVICE r5).
                key = (msg.stage_index, msg.request_id, msg.attempt)
                with self._count_lock:
                    if key in self._counted:
                        self._counted.discard(key)
                        self._inflight_count = max(
                            0, self._inflight_count - 1
                        )
                if msg.msg_type == MSG_RESULT:
                    self._results.put(
                        TaskResult(
                            request_id=msg.request_id,
                            stage_index=msg.stage_index,
                            attempt=msg.attempt,
                            worker_id=self.worker_id,
                            output=codec_lib.unpack(msg.payload),
                        )
                    )
                else:
                    self._results.put(
                        TaskResult(
                            request_id=msg.request_id,
                            stage_index=msg.stage_index,
                            attempt=msg.attempt,
                            worker_id=self.worker_id,
                            error=payload_bytes(msg.payload).decode(),
                        )
                    )
        # Socket gone: mark the link dead so the scheduler stops picking
        # us and membership re-dispatches in-flight work immediately
        # (stopping lease renewal alone would add a full TTL of latency).
        self._mark_dead("connection closed")
        # Unblock any configure() still waiting on an ACK that can never
        # arrive now.
        with self._ack_lock:
            for key, ev in self._config_acks.items():
                self._config_errors.setdefault(key, "link down")
                ev.set()


class WorkerGateway:
    """Dispatcher-side listener for worker-initiated joins.

    The reference's pool can grow because the *worker* registers itself in
    etcd and the dispatcher discovers it (``/root/reference/src/
    node_state.py:17-20``, read at ``src/dispatcher.py:285-289``). Here a
    fresh worker dials this gateway (``python -m adapt_tpu.comm.remote
    --connect host:port``), announces MSG_HELLO, and the gateway wraps the
    accepted socket in a :class:`RemoteWorkerProxy`, registers its lease,
    and attaches it to the dispatcher — which fires the registry ``join``
    watch and prewarms the newcomer's executables
    (``control/dispatcher.py`` ``_on_membership``). From that point the
    joined worker is indistinguishable from a dial-out proxy: late
    binding, probes, quarantine, and re-dispatch all apply.

    Codec routing: the activation and weights codecs come from the
    dispatcher's ``ServeConfig.codec`` — the one knob configures every
    worker that joins.

    Hardening (above reference parity — the reference has no auth
    anywhere, SURVEY.md §2.8): a joiner announcing a ``worker_id`` that
    is currently LIVE is rejected (it would race the live proxy's lease
    and confuse result routing; lease tokens protect eviction, not
    identity), and an optional ``secret`` must match the HELLO's
    (constant-time compare) — closing the open-port spoof when the
    gateway listens beyond localhost."""

    def __init__(
        self,
        dispatcher,
        model_config: dict,
        host: str = "127.0.0.1",
        port: int = 0,
        secret: str | None = None,
    ):
        self._dispatcher = dispatcher
        self._model_config = model_config
        self._secret = secret
        codec_cfg = dispatcher.config.codec
        self._codec_name = codec_cfg.name
        self._weights_codec = codec_cfg.weights
        self._fault = dispatcher.config.fault
        self._host = host
        self._port = port
        self._srv: socket.socket | None = None
        self._stop = threading.Event()
        self._accept_thread: threading.Thread | None = None
        self._proxies: list[RemoteWorkerProxy] = []
        self._proxies_lock = threading.Lock()
        # Shared across all joined workers: the packed weight frames for a
        # stage are identical for every joiner, so compress once.
        self._blob_cache: dict = {}

    @property
    def port(self) -> int:
        return self._port

    def start(self) -> "WorkerGateway":
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((self._host, self._port))
        self._srv.listen(16)
        self._port = self._srv.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="gateway-accept", daemon=True
        )
        self._accept_thread.start()
        log.info("worker gateway listening on %s:%d", self._host, self._port)
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._srv is not None:
            try:
                self._srv.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=2.0)
        with self._proxies_lock:
            proxies = list(self._proxies)
        for p in proxies:
            p.stop()

    def _accept_loop(self) -> None:
        self._srv.settimeout(0.5)
        while not self._stop.is_set():
            try:
                conn, addr = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            try:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                # Hard deadline on HELLO: this loop is serial, so a silent
                # dialer must not block every other join.
                conn.settimeout(10.0)
                msg = recv_msg(conn, retry_on_timeout=False)
                if msg.msg_type != MSG_HELLO:
                    raise ValueError(
                        f"expected HELLO, got msg type {msg.msg_type}"
                    )
                info = json.loads(payload_bytes(msg.payload).decode())
                worker_id = info["worker_id"]
                if self._secret is not None and not hmac.compare_digest(
                    str(info.get("secret", "")), self._secret
                ):
                    raise ValueError(
                        "join rejected: bad or missing gateway secret"
                    )
                if worker_id in self._dispatcher.registry.alive():
                    # A live duplicate would race the existing proxy's
                    # lease and interleave two links' results under one
                    # identity. (A JOINER replacing its own dead link is
                    # fine: the dead proxy deregistered on link close.)
                    raise ValueError(
                        f"join rejected: worker_id {worker_id!r} is "
                        "currently live"
                    )
                proxy = RemoteWorkerProxy(
                    worker_id,
                    addr,
                    self._dispatcher.registry,
                    self._dispatcher.result_queue,
                    model_config=self._model_config,
                    codec_name=self._codec_name,
                    weights_codec=self._weights_codec,
                    fault=self._fault,
                    sock=conn,
                    blob_cache=self._blob_cache,
                )
                proxy.start()  # registers lease -> registry 'join' fires
                self._dispatcher.attach_worker(proxy)
                proxy._send(Message(MSG_HELLO_ACK, 0, 0, 0, b""))
                with self._proxies_lock:
                    # Sweep proxies whose links died (worker churn): the
                    # gateway must not accumulate a dead proxy per join
                    # for its lifetime.
                    self._proxies = [
                        p for p in self._proxies if not p._stop.is_set()
                    ]
                    self._proxies.append(proxy)
                log.info("worker %s joined via gateway (%s)", worker_id, addr)
                global_metrics().inc("gateway.joins")
            except Exception as e:  # noqa: BLE001 — a bad joiner can't kill the loop
                log.warning("gateway join from %s failed: %s", addr, e)
                try:
                    conn.close()
                except OSError:
                    pass


def main() -> None:
    """CLI entry (the reference's ``python -m src.node``, README.md:44):

    - ``python -m adapt_tpu.comm.remote --port 7001`` — listen and wait
      for a dispatcher to dial in (dial-out proxy path).
    - ``python -m adapt_tpu.comm.remote --connect host:port`` — join a
      RUNNING pipeline through its WorkerGateway (worker-initiated
      registration, ``src/node_state.py:17-20``)."""
    import argparse
    import os

    p = argparse.ArgumentParser()
    p.add_argument("--port", type=int, default=None)
    p.add_argument(
        "--connect",
        default=None,
        metavar="HOST:PORT",
        help="dial a dispatcher WorkerGateway and join its pool",
    )
    p.add_argument("--worker-id", default=None)
    p.add_argument(
        "--device-index",
        type=int,
        default=0,
        help="index into THIS process's jax.devices(). A TPU chip "
        "belongs to one process at a time: on real chips run ONE worker "
        "process per host (it sees every local chip), not one per chip "
        "— several local processes each asking for the chip only works "
        "on the CPU backend",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--heartbeat", type=float, default=0.5)
    p.add_argument(
        "--telemetry-s",
        type=float,
        default=2.0,
        help="telemetry-federation report cadence on the dispatcher "
        "link (seconds; 0 disables the push)",
    )
    p.add_argument(
        "--secret",
        default=os.environ.get("ADAPT_TPU_GATEWAY_SECRET"),
        help="gateway join secret (or env ADAPT_TPU_GATEWAY_SECRET)",
    )
    p.add_argument(
        "--no-registry",
        action="store_true",
        help="bare-image stance: serve only architecture-by-value "
        "(graph_spec) configures, never the local model registry",
    )
    args = p.parse_args()
    if (args.port is None) == (args.connect is None):
        p.error("exactly one of --port / --connect is required")
    ensure_compile_cache()
    server = RemoteStageServer(
        args.port or 0,
        device_index=args.device_index,
        heartbeat_s=args.heartbeat,
        host=args.host,
        allow_registry=not args.no_registry,
        telemetry_s=args.telemetry_s,
    )
    if args.connect is not None:
        host, _, port = args.connect.rpartition(":")
        worker_id = args.worker_id or f"remote-{os.getpid()}"
        server.connect_and_serve(
            (host, int(port)), worker_id, secret=args.secret
        )
    else:
        server.serve_forever()


if __name__ == "__main__":
    main()
