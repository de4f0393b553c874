"""Fleet telemetry federation + per-request forensics (ISSUE 11).

Layers, one file:

- reservoir export + weighted merge units (fleet percentiles computed
  from the union of sources' decimating reservoirs, delta chaining
  that never double-counts, event seq-gap loss accounting);
- exporter satellites — dynamic dotted suffixes rendered as Prometheus
  LABELS with a parse test, non-finite floats sanitized to ``null`` on
  ``/metrics.json``, ``/healthz`` liveness fields, a client hanging up
  mid-scrape not killing the serving process;
- the HTTP-pull fallback: a lease advertising ``meta["telemetry"]``
  gets polled into the store;
- forensics — the acceptance bundle for a request that is preempted,
  journal-replayed and finishes: both lives, the preemption edge,
  exactly-once delivery accounting;
- concurrent ``/fleet/*`` scrapes while reports land;
- the two-process acceptance: a REAL remote worker subprocess pushes
  ``MSG_TELEMETRY`` reports to the dispatcher, ``/fleet/metrics``
  carries both processes' counters under role/worker labels,
  ``/debug/request/<id>`` spans both pids, and killing the worker
  flips its ``fleet.report_age_s`` staleness signal instead of
  freezing its gauges.
"""

import json
import os
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from adapt_tpu.utils.exporter import prometheus_text, serve_metrics
from adapt_tpu.utils.metrics import MetricsRegistry, global_metrics
from adapt_tpu.utils.telemetry import (
    FederatedStore,
    TelemetryReporter,
    WeightedReservoir,
    assemble_request,
    global_federated_store,
    source_key,
)
from adapt_tpu.utils.tracing import (
    FlightRecorder,
    global_flight_recorder,
    global_tracer,
)
from conftest import spawn_worker_proc


@pytest.fixture
def clean_slate():
    global_metrics().reset()
    global_flight_recorder().clear()
    yield
    global_metrics().reset()
    global_flight_recorder().clear()


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.read().decode(), r.headers.get("Content-Type")


def _parse_prom(text: str) -> dict:
    """Strict-ish exposition parse: every line is HELP/TYPE or
    ``name[{labels}] value``; returns {(name, labels-frozenset): value}.
    The parse test the label satellite calls for."""
    out = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        sample, value = line.rsplit(" ", 1)
        if sample.endswith("}"):
            name, _, rest = sample.partition("{")
            labels = frozenset(rest[:-1].split(","))
        else:
            name, labels = sample, frozenset()
        assert "{" not in name and '"' not in name, line
        out[(name, labels)] = float(value)
    return out


# -- reservoir merge + report chaining --------------------------------------


def test_weighted_reservoir_merges_disjoint_sources():
    a, b = WeightedReservoir(), WeightedReservoir()
    a.add([1.0] * 100, 1)
    b.add([100.0] * 100, 1)
    p = WeightedReservoir.percentiles([a, b])
    assert p["p99"] == 100.0
    # Equal mass: the weighted p50 sits at the boundary.
    assert p["p50"] in (1.0, 100.0)
    # Weight dominance: 300 observations at stride 3 vs 10 at stride 1.
    c, d = WeightedReservoir(), WeightedReservoir()
    c.add([5.0] * 100, 3)
    d.add([50.0] * 10, 1)
    assert WeightedReservoir.percentiles([c, d])["p50"] == 5.0
    # Decimation keeps memory bounded and total weight roughly stable.
    e = WeightedReservoir()
    for _ in range(20):
        e.add(list(range(1000)), 1)
    assert len(e.samples) <= WeightedReservoir._CAP


def test_fleet_store_merges_and_never_double_counts():
    ra, rb = MetricsRegistry(), MetricsRegistry()
    for _ in range(100):
        ra.observe("h", 1.0)
    for _ in range(100):
        rb.observe("h", 100.0)
    ra.inc("c", 3)
    store = FederatedStore()
    store.attach_local("server", "a", registry=ra)
    store.attach_local("stage", "b", registry=rb)
    fl = store.fleet_snapshot()
    m = fl["merged"]["histograms"]["h"]
    assert m["count"] == 200
    assert m["min"] == 1.0 and m["max"] == 100.0
    assert m["p99"] == 100.0  # merged from BOTH reservoirs
    assert fl["merged"]["counters"]["c"] == 3
    # Second round: only the delta lands; a quiet round adds nothing.
    for _ in range(50):
        ra.observe("h", 1.0)
    ra.inc("c", 2)
    fl2 = store.fleet_snapshot()
    assert fl2["merged"]["histograms"]["h"]["count"] == 250
    assert fl2["merged"]["counters"]["c"] == 5
    fl3 = store.fleet_snapshot()
    assert fl3["merged"]["histograms"]["h"]["count"] == 250
    assert fl3["merged"]["counters"]["c"] == 5
    # Per-source view keeps role/worker identity + per-source numbers.
    key_a = source_key("server", "a", os.getpid())
    assert fl3["sources"][key_a]["counters"]["c"] == 5
    assert fl3["sources"][key_a]["histograms"]["h"]["count"] == 150
    store.close()


def test_reporter_ships_flight_events_with_seq_and_store_detects_loss():
    rec = FlightRecorder(capacity=64)
    reg = MetricsRegistry()
    rep = TelemetryReporter("stage", "w0", registry=reg, recorder=rec)
    rec.record("admit", request=1)
    rec.record("finish", request=1, tokens=3)
    store = FederatedStore()
    store.ingest(rep.collect())
    evs = store.events(request=1)
    assert [e["kind"] for e in evs] == ["admit", "finish"]
    assert all(e["source"].startswith("stage:w0:") for e in evs)
    # Incremental: a second collect ships only NEW events.
    rec.record("cancel", request=2)
    store.ingest(rep.collect())
    assert len(store.events()) == 3
    # A fabricated seq gap (events evicted before shipping) is counted
    # as loss, not silently presented as a complete stream.
    key = source_key("stage", "w0", os.getpid())
    report = {
        "v": 1,
        "source": {"role": "stage", "worker": "w0", "pid": os.getpid()},
        "seq": 99,
        "wall": time.time(),
        "counters": {},
        "gauges": {},
        "histograms": {},
        "events": [{"ts": time.time(), "kind": "admit", "seq": 50,
                    "data": {"request": 9}}],
        "spans": [],
    }
    store.ingest(report)
    assert store.sources()[key]["lost_events"] > 0
    # Malformed reports raise (the comm ingest site guards + counts).
    with pytest.raises(ValueError):
        store.ingest({"v": 999})


def test_fleet_events_order_on_the_wall_clock_across_sources():
    store = FederatedStore()
    t0 = time.time()

    def report(worker, events):
        return {
            "v": 1,
            "source": {"role": "stage", "worker": worker, "pid": 1},
            "seq": 1, "wall": t0, "counters": {}, "gauges": {},
            "histograms": {}, "events": events, "spans": [],
        }

    store.ingest(report("b", [
        {"ts": t0 + 0.2, "kind": "finish", "seq": 1, "data": {}},
    ]))
    store.ingest(report("a", [
        {"ts": t0 + 0.1, "kind": "admit", "seq": 1, "data": {}},
        {"ts": t0 + 0.3, "kind": "cancel", "seq": 2, "data": {}},
    ]))
    assert [e["kind"] for e in store.events()] == [
        "admit", "finish", "cancel",
    ]


def test_duplicate_and_gapped_reports_apply_exactly_once():
    """The push path retransmits frames whose send erred: a duplicate
    report must be dropped by seq (never double-counted), and a seq
    gap (backlog overflow) must be counted as lost reports."""
    store = FederatedStore()

    def report(seq):
        return {
            "v": 1,
            "source": {"role": "stage", "worker": "w0", "pid": 1},
            "seq": seq, "wall": time.time(),
            "counters": {"c": 1.0}, "gauges": {},
            "histograms": {
                "h": {"count": 1, "sum": 1.0, "min": 1.0, "max": 1.0,
                      "samples": [1.0], "stride": 1}
            },
            "events": [{"ts": time.time(), "kind": "admit",
                        "seq": seq, "data": {"request": seq}}],
            "spans": [],
        }

    key = source_key("stage", "w0", 1)
    store.ingest(report(1))
    store.ingest(report(2))
    store.ingest(report(2))  # retransmit
    src = store.sources()[key]
    assert src["duplicate_reports"] == 1
    fl = store.fleet_snapshot(refresh=False)
    assert fl["merged"]["counters"]["c"] == 2.0  # NOT 3.0
    assert fl["merged"]["histograms"]["h"]["count"] == 2
    assert len(store.events()) == 2
    # A gap (reports 3..5 lost to backlog overflow) is accounted.
    store.ingest(report(6))
    assert store.sources()[key]["lost_reports"] == 3
    assert store.fleet_snapshot(refresh=False)["merged"]["counters"][
        "c"
    ] == 3.0


def test_reporter_reopened_after_close_never_recounts():
    """close() then collect(): the reporter must NOT re-ship its
    cumulative totals as a delta (the obs_overhead federation config
    reuses one reporter across trials)."""
    reg = MetricsRegistry()
    reg.inc("c", 5)
    reg.observe("h", 1.0)
    rep = TelemetryReporter("bench", "b0", registry=reg)
    store = FederatedStore()
    store.ingest(rep.collect())  # first: cumulative
    rep.close()
    store.ingest(rep.collect())  # reopened: flagged, empty delta
    key = source_key("bench", "b0", os.getpid())
    fl = store.fleet_snapshot(refresh=False)
    assert fl["merged"]["counters"]["c"] == 5.0
    assert fl["merged"]["histograms"]["h"]["count"] == 1
    assert store.sources()[key]["degraded_reports"] == 1
    # And the chain is healthy again after the reopen round.
    reg.inc("c", 2)
    store.ingest(rep.collect())
    assert store.fleet_snapshot(refresh=False)["merged"]["counters"][
        "c"
    ] == 7.0
    rep.close()


def test_attach_local_replacement_does_not_deadlock():
    """Regression: replacing a local reporter closes the stale one,
    whose final snapshot runs the old registry's collectors — which
    include the store's own staleness collector re-entering the store
    lock. The close must happen OUTSIDE attach_local's lock hold."""
    store = FederatedStore()
    a, b = MetricsRegistry(), MetricsRegistry()
    a.register_collector(store.collector)
    store.attach_local("server", "s0", registry=a)
    store.fleet_snapshot()  # opens the stale reporter's window
    done: list = []
    t = threading.Thread(
        target=lambda: done.append(
            store.attach_local("server", "s0", registry=b)
        ),
        daemon=True,
    )
    t.start()
    t.join(timeout=5)
    assert done, "attach_local deadlocked replacing a local reporter"
    store.close()


# -- exporter satellites -----------------------------------------------------


def test_prometheus_renders_dynamic_suffixes_as_labels_and_parses():
    """Satellite: per-tenant / per-source dotted suffixes become
    labels, never baked-in metric names; counters ending _total don't
    double it; the whole document parses."""
    reg = MetricsRegistry()
    reg.set_gauge("scheduler.queue_depth.gold", 3)
    reg.set_gauge("scheduler.queue_depth.free", 7)
    reg.inc("slo.met_total.gold", 5)
    reg.inc("slo.missed_total.free", 2)
    reg.set_gauge("fleet.report_age_s.stage:w0:123", 1.5)
    reg.inc("scheduler.rejected_total", 4)
    reg.observe("lat_s", 0.25)
    text = prometheus_text(reg.snapshot())
    samples = _parse_prom(text)
    assert samples[
        ("adapt_scheduler_queue_depth", frozenset(['tenant="gold"']))
    ] == 3
    assert samples[
        ("adapt_scheduler_queue_depth", frozenset(['tenant="free"']))
    ] == 7
    assert samples[
        ("adapt_slo_met_total", frozenset(['tenant="gold"']))
    ] == 5
    assert samples[
        ("adapt_slo_missed_total", frozenset(['tenant="free"']))
    ] == 2
    assert samples[
        ("adapt_fleet_report_age_s",
         frozenset(['source="stage:w0:123"']))
    ] == 1.5
    # No baked-suffix spellings and no doubled _total anywhere.
    assert "adapt_scheduler_queue_depth_gold" not in text
    assert "adapt_slo_met_total_gold" not in text
    assert "_total_total" not in text
    assert samples[("adapt_scheduler_rejected_total", frozenset())] == 4
    # HELP/TYPE emit once per family even with several label values.
    assert text.count("# TYPE adapt_scheduler_queue_depth gauge") == 1
    # Histogram family keeps its base-name summary shape.
    assert samples[("adapt_lat_s_count", frozenset())] == 1


def test_metrics_json_sanitizes_non_finite_floats(clean_slate):
    reg = MetricsRegistry()
    reg.set_gauge("roofline.nan", float("nan"))
    reg.set_gauge("roofline.inf", float("inf"))
    reg.set_gauge("roofline.ninf", float("-inf"))
    reg.set_gauge("roofline.ok", 2.5)
    server = serve_metrics(port=0, registry=reg, store=FederatedStore())
    try:
        body, _ = _get(server.server_address[1], "/metrics.json")
        snap = json.loads(body)  # bare json.dumps would emit NaN here
        assert snap["gauges"]["roofline.nan"] is None
        assert snap["gauges"]["roofline.inf"] is None
        assert snap["gauges"]["roofline.ninf"] is None
        assert snap["gauges"]["roofline.ok"] == 2.5
    finally:
        server.shutdown()
        server.server_close()


def test_healthz_fields_and_midscrape_disconnect(clean_slate):
    reg = MetricsRegistry()
    reg.inc("x.completed", 2)
    server = serve_metrics(
        port=0, registry=reg, store=FederatedStore(), role="decode",
    )
    port = server.server_address[1]
    try:
        body, _ = _get(port, "/healthz")
        h = json.loads(body)
        assert h["ok"] is True
        assert h["pid"] == os.getpid()
        assert h["role"] == "decode"
        assert h["uptime_s"] >= 0.0
        # A scraper hanging up right after the request must not kill
        # (or traceback-wedge) the serving process: later scrapes work.
        for _ in range(3):
            s = socket.create_connection(("127.0.0.1", port))
            s.send(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            s.close()
        time.sleep(0.05)
        text, _ = _get(port, "/metrics")
        assert "adapt_x_completed_total 2" in text
    finally:
        server.shutdown()
        server.server_close()


def test_http_pull_fallback_via_lease_metadata(clean_slate):
    """A process the dispatcher doesn't own advertises its exporter's
    /telemetry.json in its registry lease; the store polls it."""
    from adapt_tpu.control.registry import WorkerRegistry

    remote_reg = MetricsRegistry()
    remote_reg.inc("prefill.jobs", 7)
    remote_rec = FlightRecorder(capacity=16)
    remote_rec.record("admit", request=5)
    # The "remote" process's exporter (same pid here; the transport —
    # HTTP against an advertised URL — is exactly the cross-host one).
    rsrv = serve_metrics(
        port=0, registry=remote_reg, recorder=remote_rec,
        store=FederatedStore(), role="prefill", worker="pf0",
    )
    registry = WorkerRegistry()
    url = f"http://127.0.0.1:{rsrv.server_address[1]}/telemetry.json"
    registry.register(
        "prefill:pf0", meta={"role": "prefill", "telemetry": url},
        ttl_s=60.0,
    )
    store = FederatedStore()
    store.attach_registry(registry)
    try:
        fl = store.fleet_snapshot()
        src = [
            s for s in fl["sources"].values() if s["worker"] == "prefill:pf0"
        ]
        assert src, f"poll did not ingest: {list(fl['sources'])}"
        assert src[0]["counters"]["prefill.jobs"] == 7
        assert store.events(request=5)
    finally:
        rsrv.shutdown()
        rsrv.server_close()


# -- forensics ---------------------------------------------------------------


def test_forensic_bundle_preempted_journal_replayed_finished(
    clean_slate, tmp_path
):
    """Satellite acceptance: a request that is preempted, replayed
    from the JOURNAL, and finishes — the bundle shows both lives, the
    preemption edge (with the interrupted life's stamps), and
    exactly-once delivery accounting."""
    from adapt_tpu.config import SchedulerConfig, SLOSpec
    from adapt_tpu.control.journal import DispatcherJournal
    from adapt_tpu.models.transformer_lm import lm_tiny
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    lm = lm_tiny(vocab=29, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    journal = DispatcherJournal(str(tmp_path / "wal"))
    bat = ContinuousBatcher(
        lm, variables, slots=1, chunk=4, journal=journal,
        scheduler=SchedulerConfig(
            preempt=True, preempt_ttft_fraction=0.5, degrade=False
        ),
    )
    delivered: dict[int, list] = {}

    def cb(rid, tok, idx):
        delivered.setdefault(rid, []).append((idx, tok))

    p_low = np.arange(10, dtype=np.int32) % 29
    p_hi = (np.arange(7, dtype=np.int32) * 3) % 29
    low = bat.submit(
        p_low, 20, slo=SLOSpec(tenant="free", priority=0), on_token=cb
    )
    bat.tick()
    bat.tick()
    assert len(delivered.get(low, [])) > 0
    hi = bat.submit(
        p_hi, 10,
        slo=SLOSpec(ttft_budget_s=1e-4, tenant="gold", priority=10),
        on_token=cb,
    )
    out = bat.run()

    store = FederatedStore()
    store.attach_local("server", "disp0")
    store.attach_journal(journal)
    bundle = assemble_request(low, store=store)
    # Both lives, via the admit edges.
    assert bundle["delivery"]["lives"] == 2
    assert len(bundle["lives"]) == 2
    # The preemption edge, naming who it yielded to, replayed from the
    # journal, with the interrupted life's stamps.
    assert len(bundle["preemptions"]) == 1
    pre = bundle["preemptions"][0]
    assert pre["for_request"] == hi
    assert pre["source"] == "journal"
    assert pre["tokens_discarded"] == len(delivered[low]) or (
        pre["tokens_discarded"] >= 1
    )
    assert pre.get("ttft_s") is not None  # first life's TTFT
    # Exactly-once delivery accounting: indices 0..n-1 each exactly
    # once and the finish edge's token count matches.
    idxs = [i for i, _ in delivered[low]]
    assert idxs == list(range(len(out[low])))
    assert bundle["delivery"]["finished"]
    assert bundle["delivery"]["tokens"] == len(out[low])
    assert bundle["delivery"]["ttft_s"] is not None
    assert len(bundle["delivery"]["life_stamps"]) == 2
    # Wall-clock ordering across the lifecycle: admit before preempt
    # before the second admit before finish.
    kinds = [e["kind"] for e in bundle["events"]]
    assert kinds.index("preempted") > kinds.index("admit")
    assert kinds[-1] == "finish"
    # Journal: done-marked at finish -> no longer pending.
    assert bundle["journal"] == {"pending": False, "meta": None}
    # The high-priority winner's own bundle exists too.
    hb = assemble_request(hi, store=store)
    assert hb["delivery"]["lives"] == 1
    assert hb["delivery"]["tokens"] == len(out[hi])
    bat.close()
    journal.close()
    store.close()


def test_fleet_scrapes_concurrent_with_reports(clean_slate):
    """Concurrent /fleet/* scrapes while reports land: every response
    parses, no torn merges."""
    store = FederatedStore()
    reg = MetricsRegistry()
    server = serve_metrics(
        port=0, registry=reg, store=store, role="server", worker="d0"
    )
    port = server.server_address[1]
    stop = threading.Event()
    errors: list = []

    def feeder():
        i = 0
        while not stop.is_set():
            i += 1
            try:
                store.ingest({
                    "v": 1,
                    "source": {
                        "role": "stage", "worker": f"w{i % 3}", "pid": 1,
                    },
                    "seq": i, "wall": time.time(),
                    "counters": {"remote.stage_execs": 1.0},
                    "gauges": {"g": float(i)},
                    "histograms": {
                        "remote.stage_exec_s": {
                            "count": 2, "sum": 0.2, "min": 0.1,
                            "max": 0.1, "samples": [0.1, 0.1],
                            "stride": 1,
                        }
                    },
                    "events": [{
                        "ts": time.time(), "kind": "remote_exec",
                        "seq": i, "data": {"request": i},
                    }],
                    "spans": [],
                })
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    t = threading.Thread(target=feeder, daemon=True)
    t.start()
    try:
        for _ in range(10):
            text, _ = _get(port, "/fleet/metrics")
            _parse_prom(text)
            body, _ = _get(port, "/fleet/metrics.json")
            fl = json.loads(body)
            assert "merged" in fl and "sources" in fl
            body, _ = _get(port, "/fleet/events")
            json.loads(body)
    finally:
        stop.set()
        t.join(timeout=2)
        server.shutdown()
        server.server_close()
    assert not errors
    fl = store.fleet_snapshot()
    m = fl["merged"]["histograms"]["remote.stage_exec_s"]
    assert m["count"] > 0 and m["p50"] == pytest.approx(0.1)


# -- two-process acceptance --------------------------------------------------


def test_two_process_fleet_metrics_forensics_and_staleness(
    clean_slate, devices
):
    """Acceptance: dispatcher + a REAL worker subprocess pushing
    MSG_TELEMETRY. /fleet/metrics carries both processes' counters
    under role/worker labels with the worker's histogram percentiles
    present; /debug/request/<id> returns one bundle whose
    events/spans span both processes; killing the worker flips its
    fleet.report_age_s staleness signal. Capacity plane riding the
    same transports: the worker's MSG_TELEMETRY reports carry its
    stage book, a registry lease advertises another, and
    /fleet/capacity merges them with the local provider's — each
    replica labeled and aged per source, the killed worker's age
    growing instead of its book freezing silently."""
    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import (
        FaultConfig,
        ObservabilityConfig,
        ServeConfig,
    )
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny

    tracer = global_tracer()
    was_enabled = tracer.enabled
    tracer.clear()
    store = FederatedStore()  # fresh store; proxies feed the GLOBAL one

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    plan = partition(g, ["encoder_block_1"])  # 2 stages

    port = 17663
    os.environ["ADAPT_TPU_TRACE"] = "1"
    try:
        proc = spawn_worker_proc(
            "--port", str(port), "--heartbeat", "0.1",
            "--telemetry-s", "0.3",
        )
    finally:
        del os.environ["ADAPT_TPU_TRACE"]
    cfg = ServeConfig(
        fault=FaultConfig(
            # A lease no starved child can lapse: under the suite's six
            # workers the worker process, compiling its stage, has gone
            # 2 s without a ping; its lease lapsed, the dispatcher
            # rebound stage 1 to the in-process worker, the request
            # completed THERE and ``remote.stage_execs`` never moved in
            # the worker whose reports this test reads (PR 48: a 3 s
            # SIGSTOP of the worker before the submit reproduces it).
            # The staleness checks below read report AGE after a kill,
            # not the lease.
            lease_ttl_s=120.0,
            heartbeat_s=0.2,
            task_deadline_s=30.0,
            watchdog_period_s=0.2,
            startup_wait_s=15.0,
            configure_timeout_s=60.0,
        ),
        obs=ObservabilityConfig(trace_enabled=True),
    )
    disp = Dispatcher(plan, variables, config=cfg)
    disp.spawn_workers(devices[:1])  # stage 0 in-process
    proxy = RemoteWorkerProxy(
        "fleet-remote-0",
        ("127.0.0.1", port),
        disp.registry,
        disp.result_queue,
        model_config={
            "model": "vit_tiny",
            "num_classes": 10,
            "cuts": ["encoder_block_1"],
            "input_shape": [2, 32, 32, 3],
        },
        fault=cfg.fault,
    )
    disp.attach_worker(proxy)
    disp.start()
    from adapt_tpu.runtime.capacity import stage_book

    server = serve_metrics(
        port=0, role="server", worker="disp0",
        capacity_provider=lambda: stage_book(2),
    )
    http = server.server_address[1]
    gstore = global_federated_store()
    # A third capacity source: a registry lease advertising its book
    # in meta["capacity"] (the DisaggServer path, minus the server).
    gstore.attach_registry(disp.registry)
    disp.registry.register(
        "cap-lease-0", meta={"capacity": stage_book(1, backlog=3)},
        ttl_s=60.0,
    )
    try:
        proxy.start()
        proxy.configure(1, None, plan.extract_variables(variables)[1])
        fut = disp.submit(x)
        fut.result(timeout=60.0)
        rid = fut.request_id

        # Wait for at least one POST-EXEC report from the worker
        # (pushed every ~0.3 s on the dispatcher link's ping thread;
        # the reports before it are pre-exec).
        deadline = time.monotonic() + 60.0
        wkey = None
        post_exec = False
        while time.monotonic() < deadline and not post_exec:
            for key, s in gstore.sources().items():
                if s["role"] == "stage" and s["worker"] == (
                    "fleet-remote-0"
                ):
                    wkey = key
            if wkey is not None:
                fl = gstore.fleet_snapshot()
                src = fl["sources"][wkey]
                post_exec = bool(
                    src["counters"].get("remote.stage_execs")
                )
            if not post_exec:
                time.sleep(0.1)
        assert wkey is not None, "no telemetry report arrived"
        assert post_exec, "no post-exec telemetry report arrived"
        worker_pid = fl["sources"][wkey]["pid"]
        assert worker_pid != os.getpid()

        # /fleet/metrics: both processes' counters, role/worker
        # labels, and the worker histogram's percentiles (merged from
        # its shipped reservoir).
        text, _ = _get(http, "/fleet/metrics")
        samples = _parse_prom(text)
        exec_keys = [
            (n, lab) for (n, lab) in samples
            if n == "adapt_remote_stage_execs_total"
            and 'worker="fleet-remote-0"' in lab
        ]
        assert exec_keys and 'role="stage"' in next(iter(exec_keys))[1]
        disp_keys = [
            (n, lab) for (n, lab) in samples
            if n == "adapt_dispatcher_completed_total"
            and 'worker="disp0"' in lab and 'role="server"' in lab
        ]
        assert disp_keys, "dispatcher's own counters missing from fleet"
        assert ("adapt_remote_stage_exec_s_p99", frozenset()) in samples
        assert any(
            n == "adapt_remote_stage_exec_s_count" for n, _ in samples
        )

        # /fleet/events: the worker's remote_exec edge rode the report.
        body, _ = _get(http, "/fleet/events")
        evs = json.loads(body)["events"]
        assert any(
            e["kind"] == "remote_exec"
            and e["data"]["request"] == rid
            for e in evs
        )

        # /fleet/capacity: three replica books over three transports —
        # the worker's rode MSG_TELEMETRY, the lease one rides
        # registry meta, the local provider's rides its reporter —
        # each labeled and aged per source.
        body, ctype = _get(http, "/fleet/capacity")
        assert ctype.startswith("application/json")
        caps = json.loads(body)["replicas"]
        wcap = caps[wkey]
        assert wcap["via"] == "telemetry"
        assert wcap["book"]["kind"] == "stage"
        assert wcap["book"]["headroom"]["stages"] >= 1
        assert wcap["age_s"] < 5.0
        lease = caps["lease:cap-lease-0"]
        assert lease["via"] == "lease"
        assert lease["book"]["headroom"]["backlog"] == 3
        local = [
            c for k, c in caps.items()
            if c["via"] == "telemetry" and c["worker"] == "disp0"
        ]
        assert local and local[0]["book"]["headroom"]["stages"] == 2
        assert local[0]["pid"] == os.getpid()

        # Forensics: one bundle, both processes present.
        body, _ = _get(http, f"/debug/request/{rid}")
        bundle = json.loads(body)
        assert bundle["request"] == rid
        span_pids = {s["pid"] for s in bundle["spans"]}
        assert os.getpid() in span_pids
        assert worker_pid in span_pids, (
            f"expected both pids in bundle spans, got {span_pids}"
        )
        ev_sources = {
            e["source"] for e in bundle["events"]
        }
        assert any(k.startswith("stage:") for k in ev_sources)

        # Staleness: kill the worker; its report age grows past the
        # cadence instead of its gauges freezing silently.
        proc.kill()
        proc.wait(timeout=10)
        time.sleep(1.2)
        text, _ = _get(http, "/fleet/metrics")
        samples = _parse_prom(text)
        age = samples[
            ("adapt_fleet_report_age_s",
             frozenset([f'source="{wkey}"']))
        ]
        assert age > 0.9, f"staleness did not move: {age}"
        # ... and the parent's own /metrics carries the same signal.
        text, _ = _get(http, "/metrics")
        psamples = _parse_prom(text)
        assert psamples[
            ("adapt_fleet_report_age_s",
             frozenset([f'source="{wkey}"']))
        ] > 0.9
        # The killed worker's capacity book stays listed with a
        # GROWING age — a router sees staleness, not a frozen book.
        caps = json.loads(_get(http, "/fleet/capacity")[0])["replicas"]
        assert caps[wkey]["age_s"] > 0.9
    finally:
        server.shutdown()
        server.server_close()
        disp.shutdown()
        tracer.enabled = was_enabled
        tracer.clear()
        if proc.poll() is None:
            proc.terminate()
            proc.wait(timeout=10)
