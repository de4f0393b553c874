"""Comm-layer tests: codec round-trips (incl. the native C++ LZ codec),
framing, and a real cross-process remote worker driven by the dispatcher
over TCP — the reference's multi-machine mode exercised hermetically via
localhost (its own test affordance, SURVEY.md §4)."""

import os
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.comm import codec as codec_lib
from adapt_tpu.comm import native
from adapt_tpu.comm.framing import MSG_DATA, Message, recv_msg, send_msg
from conftest import chain_cfg, chain_pool, spawn_worker_proc


# -- native codec -----------------------------------------------------------


def test_native_build_and_roundtrip():
    data = (b"the quick brown fox " * 100) + os.urandom(64)
    comp = native.compress(data)
    assert native.decompress(comp, len(data)) == data
    # Repetitive data must actually compress.
    rep = b"ab" * 4096
    assert len(native.compress(rep)) < len(rep) // 4


def test_native_empty_and_tiny():
    for data in (b"", b"a", b"abcdefg", b"x" * 15):
        comp = native.compress(data)
        assert native.decompress(comp, len(data)) == data


def test_native_malformed_rejected():
    if native.load() is None:
        pytest.skip("no native toolchain")
    with pytest.raises(ValueError):
        native.decompress(b"Q\x10\x00\x00\x00garbage", 16)


@pytest.mark.parametrize("size", [1 << 10, 1 << 16, (1 << 20) + 17])
def test_native_large_random_and_structured(size):
    rng = np.random.default_rng(0)
    # float32 activations quantized to int16 (the zfp-codec path shape).
    x = (rng.standard_normal(size // 2)).astype(np.float16).tobytes()[:size]
    comp = native.compress(x)
    assert native.decompress(comp, len(x)) == x


# -- tensor codecs ----------------------------------------------------------


@pytest.mark.parametrize(
    "name,rtol",
    [
        ("none", 0),
        ("bf16", 1e-2),
        ("int8", 2e-2),
        ("zfp", 1e-2),
        ("lz", 0),
        ("int8dev", 2e-2),
    ],
)
def test_codec_roundtrip(name, rtol):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((4, 32, 32, 8)).astype(np.float32)
    codec = codec_lib.get_codec(name)
    blob, meta = codec.encode(x)
    y = codec.decode(blob, meta)
    assert y.shape == x.shape and y.dtype == x.dtype
    if name in ("none", "lz"):
        np.testing.assert_array_equal(x, y)
    else:
        assert np.max(np.abs(x - y)) < rtol * max(1.0, np.max(np.abs(x)))


def test_lz_codec_lossless_any_dtype():
    """The weights-path codec must be bit-exact for every dtype a model
    carries (f32, bf16 params, int32 step counters in opt state)."""
    import ml_dtypes

    rng = np.random.default_rng(5)
    for arr in (
        rng.standard_normal((16, 16)).astype(np.float32),
        rng.standard_normal((7, 3)).astype(ml_dtypes.bfloat16),
        rng.integers(-100, 100, size=(12,)).astype(np.int32),
    ):
        codec = codec_lib.get_codec("lz")
        blob, meta = codec.encode(arr)
        y = codec.decode(blob, meta)
        assert y.dtype == arr.dtype
        np.testing.assert_array_equal(np.asarray(arr), np.asarray(y))


def test_int8dev_codec_matches_host_oracle():
    """The on-device (Pallas) codec must agree with the pure-jnp blockwise
    quantization oracle it re-expresses."""
    from adapt_tpu.ops.quantize import dequantize_reference, quantize_reference

    rng = np.random.default_rng(6)
    x = rng.standard_normal((3, 50, 17)).astype(np.float32) * 4.0
    codec = codec_lib.get_codec("int8dev")
    blob, meta = codec.encode(jnp.asarray(x))
    y = codec.decode(blob, meta)
    oracle = np.asarray(dequantize_reference(quantize_reference(jnp.asarray(x))))
    np.testing.assert_allclose(y, oracle, rtol=0, atol=1e-6)


def test_zfp_tolerance_honored():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((1024,)).astype(np.float32)
    for tol in (1e-2, 1e-3):
        codec = codec_lib.get_codec("zfp", tolerance=tol)
        blob, meta = codec.encode(x)
        y = codec.decode(blob, meta)
        # step = max(tol, absmax/32767); here absmax/32767 << tol, so the
        # round-off error is bounded by step/2 = tol/2.
        assert np.max(np.abs(x - y)) <= tol / 2 + 1e-7


def test_pack_unpack_self_describing():
    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    for name in codec_lib.CODECS:
        buf = codec_lib.pack(codec_lib.get_codec(name), x)
        y = codec_lib.unpack(buf)
        assert y.shape == x.shape
        if name == "none":
            np.testing.assert_array_equal(x, y)


def test_unknown_codec_rejected():
    with pytest.raises(ValueError, match="unknown codec"):
        codec_lib.get_codec("lz77max")


# -- framing ----------------------------------------------------------------


def test_framing_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        msg = Message(MSG_DATA, 3, 123456789, 2, b"\x00" * 100_000)
        t = threading.Thread(target=send_msg, args=(a, msg))
        t.start()
        got = recv_msg(b)
        t.join()
        assert got == msg
    finally:
        a.close()
        b.close()


def test_framing_peer_close_raises():
    a, b = socket.socketpair()
    a.close()
    with pytest.raises(ConnectionError):
        recv_msg(b)
    b.close()


def test_framing_negative_ids_roundtrip():
    """Canary probes carry stage_index = PING_STAGE (-1) and negative
    request ids — the header must be signed (regression: '>BIQI' raised
    struct.error and killed the dispatcher's watchdog thread)."""
    a, b = socket.socketpair()
    try:
        msg = Message(MSG_DATA, -1, -7, 0, b"")
        t = threading.Thread(target=send_msg, args=(a, msg))
        t.start()
        got = recv_msg(b)
        t.join()
        assert got == msg
    finally:
        a.close()
        b.close()


# -- zero-copy framing (the serving hot path) -------------------------------


def test_raw_unpack_shares_receive_buffer():
    """The zero-copy receive contract: ``unpack`` on the raw codec
    returns an array VIEWING the frame buffer — mutating the buffer's
    payload region must show through the array, and shares_memory must
    agree."""
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    buf = codec_lib.pack(codec_lib.get_codec("none"), x)
    y = codec_lib.unpack(buf)
    np.testing.assert_array_equal(x, y)
    assert np.shares_memory(y, np.frombuffer(buf, dtype=np.uint8))
    buf[-4:] = np.float32(123.5).tobytes()  # poke the last element
    assert y[-1, -1] == 123.5


def test_pack_payload_copy_budget():
    """Framing-layer copy budget, counted not asserted-by-docstring:
    ``pack_frames`` performs ZERO payload copies (scatter-write parts);
    ``pack`` exactly ONE (frame assembly — the old encode-then-concat
    scheme paid two); lossy codecs stay within the same budget (their
    transform output is the payload, not a copy of it)."""
    x = np.random.RandomState(0).standard_normal((32, 256)).astype(
        np.float32
    )
    for name in codec_lib.CODECS:
        c = codec_lib.get_codec(name)
        codec_lib.reset_copy_stats()
        frames = codec_lib.pack_frames(c, x)
        assert codec_lib.copy_stats()["calls"] == 0, name
        payload = codec_lib.frames_nbytes(frames) - len(frames[0])
        codec_lib.reset_copy_stats()
        codec_lib.pack(c, x)
        stats = codec_lib.copy_stats()
        assert stats["calls"] == 1, name
        assert stats["bytes"] <= payload, name
    codec_lib.reset_copy_stats()


def test_pack_into_reuses_pooled_buffer():
    """``pack_into`` grows the caller's pool once, then reuses it: the
    returned views of two same-size packs alias the same bytearray."""
    x = np.arange(100, dtype=np.float32)
    pool = bytearray()
    v1 = codec_lib.pack_into(codec_lib.get_codec("none"), x, pool)
    n1 = len(pool)
    v2 = codec_lib.pack_into(codec_lib.get_codec("none"), x + 1, pool)
    assert len(pool) == n1  # no regrowth for an equal-size frame
    assert v2.obj is pool
    np.testing.assert_array_equal(codec_lib.unpack(v2), x + 1)
    assert v1.nbytes == v2.nbytes


def test_framing_scatter_send_multipart_payload():
    """``send_msg`` accepts a ``pack_frames`` list (header + payload
    views) and the receiver sees one contiguous frame whose ``unpack``
    recovers the array — the end-to-end zero-copy hop: no host-side
    payload concatenation on send, a buffer-viewing array on receive."""
    x = np.random.RandomState(3).standard_normal((16, 128)).astype(
        np.float32
    )
    frames = codec_lib.pack_frames(codec_lib.get_codec("none"), x)
    a, b = socket.socketpair()
    try:
        msg = Message(MSG_DATA, 1, 42, 0, frames)
        t = threading.Thread(target=send_msg, args=(a, msg))
        t.start()
        got = recv_msg(b)
        t.join()
        assert isinstance(got.payload, memoryview)
        y = codec_lib.unpack(got.payload)
        np.testing.assert_array_equal(x, y)
        # int8dev's two payload parts (values + scales) ride the same way
        frames2 = codec_lib.pack_frames(
            codec_lib.get_codec("int8dev"), jnp.asarray(x)
        )
        assert len(frames2) >= 3  # header + >= 2 parts
        t = threading.Thread(
            target=send_msg, args=(a, Message(MSG_DATA, 1, 43, 0, frames2))
        )
        t.start()
        got2 = recv_msg(b)
        t.join()
        y2 = codec_lib.unpack(got2.payload)
        assert y2.shape == x.shape
        np.testing.assert_allclose(
            y2, x, atol=2e-2 * max(1.0, np.max(np.abs(x)))
        )
    finally:
        a.close()
        b.close()


# -- remote worker end-to-end ----------------------------------------------


@pytest.fixture(scope="module")
def remote_worker_proc():
    """A real worker process serving stages over TCP (CPU backend)."""
    port = 17591
    proc = spawn_worker_proc("--port", str(port), "--heartbeat", "0.1")
    yield "127.0.0.1", port
    proc.terminate()
    proc.wait(timeout=10)


def test_remote_worker_full_pipeline(remote_worker_proc, devices):
    """Dispatcher drives a mixed pool: 2 in-process workers + 1 remote
    process, ViT-tiny split in 2 stages, int8 activation codec across the
    host boundary."""
    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import FaultConfig, ServeConfig
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    plan = partition(g, ["encoder_block_1"])
    y_ref = np.asarray(g.apply(variables, x))

    cfg = ServeConfig(
        fault=FaultConfig(
            lease_ttl_s=1.0,
            heartbeat_s=0.2,
            task_deadline_s=30.0,
            watchdog_period_s=0.1,
            startup_wait_s=10.0,
            configure_timeout_s=60.0,
        )
    )
    disp = Dispatcher(plan, variables, config=cfg)
    disp.spawn_workers(devices[:2])
    proxy = RemoteWorkerProxy(
        "remote-0",
        remote_worker_proc,
        disp.registry,
        disp.result_queue,
        model_config={
            "model": "vit_tiny",
            "num_classes": 10,
            "cuts": ["encoder_block_1"],
            "input_shape": [2, 32, 32, 3],
        },
        codec_name="int8",
        fault=cfg.fault,
    )
    disp.attach_worker(proxy)
    disp.start()
    try:
        proxy_started = proxy.start() if proxy._sock is None else proxy
        assert "remote-0" in disp.registry.alive()
        # Force the remote to own stage 1: configure it there explicitly.
        proxy_started.configure(1, None, plan.extract_variables(variables)[1])
        assert proxy_started.is_configured(1)
        # Run requests; results must match within int8 quantization error.
        outs = disp.serve_stream([x] * 4, timeout_per_request=60.0)
        for y in outs:
            assert np.max(np.abs(np.asarray(y) - y_ref)) < 0.3
        # Kill the remote (crash): lease must lapse and serving continue on
        # local workers only.
        proxy_started.kill("crash")
        deadline = time.monotonic() + 5.0
        while "remote-0" in disp.registry.alive():
            assert time.monotonic() < deadline, "remote lease never expired"
            time.sleep(0.05)
        outs2 = disp.serve_stream([x] * 2, timeout_per_request=60.0)
        assert len(outs2) == 2
    finally:
        disp.shutdown()


def test_remote_probe_roundtrip_and_hang_swallow():
    """The dispatcher's canary probes must round-trip the remote serve
    loop (not just the transport ping thread): a healthy server answers a
    PING_STAGE task; a hung server swallows it so the probe deadline can
    fire. Regression for probes crashing on the remote submit path."""
    import queue as queue_mod

    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import FaultConfig
    from adapt_tpu.control.registry import WorkerRegistry
    from adapt_tpu.control.worker import PING_STAGE, Task

    port = 17593
    proc = spawn_worker_proc("--port", str(port), "--heartbeat", "0.1")
    registry = WorkerRegistry(default_ttl_s=2.0).start()
    results: "queue_mod.Queue" = queue_mod.Queue()
    proxy = RemoteWorkerProxy(
        "remote-probe",
        ("127.0.0.1", port),
        registry,
        results,
        model_config={},
        fault=FaultConfig(startup_wait_s=10.0),
    )
    try:
        proxy.start()
        probe = Task(
            request_id=-5, stage_index=PING_STAGE, attempt=0, payload=None
        )
        proxy.submit(probe)
        ans = results.get(timeout=5.0)
        assert ans.stage_index == PING_STAGE
        assert ans.request_id == -5
        assert ans.worker_id == "remote-probe"
        assert ans.error is None
        # Probes must not count as in-flight work on the proxy.
        assert proxy.queue_depth == 0
        proxy.kill("hang")
        time.sleep(0.2)
        proxy.submit(
            Task(request_id=-6, stage_index=PING_STAGE, attempt=0, payload=None)
        )
        with pytest.raises(queue_mod.Empty):
            results.get(timeout=1.5)
    finally:
        proxy.stop()
        registry.stop()
        proc.terminate()
        proc.wait(timeout=10)


# -- chain forwarding (direct worker→worker data plane) ----------------------


def test_chain_forwarding_bypasses_hub(devices):
    """3 remote workers in chain mode: every intermediate activation hops
    worker→worker (reference Gen-1 topology, ``src/node.py:163-179``);
    the hub's links deliver ONLY the tail's final results, and outputs
    equal the unpartitioned forward bit-for-bit (codec 'none')."""
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_block_cuts, vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    cuts = vit_block_cuts(4, 3)
    plan = partition(g, cuts)
    y_ref = np.asarray(g.apply(variables, x))
    cfg = chain_cfg()
    disp = Dispatcher(plan, variables, config=cfg)
    procs, proxies = chain_pool(disp, cfg, cuts, [17621, 17622, 17623])
    try:
        disp.start()
        for pr in proxies:
            pr.start()
        order = disp.setup_chain([pr.worker_id for pr in proxies])
        assert order == ["chain-0", "chain-1", "chain-2"]
        outs = disp.serve_stream([x] * 6, timeout_per_request=120.0)
        for y in outs:
            np.testing.assert_allclose(
                np.asarray(y), y_ref, rtol=1e-5, atol=1e-5
            )
        # The hub never touched an intermediate activation: the head and
        # mid proxies delivered ZERO result frames; every result came in
        # on the tail's link.
        assert proxies[0].results_received == 0
        assert proxies[1].results_received == 0
        assert proxies[2].results_received == 6
    finally:
        disp.shutdown()
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def test_chain_failure_falls_back_to_hub_exactly_once(devices):
    """Kill the MID-chain worker: the chain disables itself and serving
    continues through the late-binding hub path on the survivors + local
    workers — every request completes exactly once with the right
    answer."""
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_block_cuts, vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    cuts = vit_block_cuts(4, 3)
    plan = partition(g, cuts)
    y_ref = np.asarray(g.apply(variables, x))
    # The death below is learnt from the link, not from the lease: a
    # 2 s lease lapsed under load while the worker compiled (conftest).
    cfg = chain_cfg(lease_ttl_s=120.0)
    disp = Dispatcher(plan, variables, config=cfg)
    # Local fallback capacity for after the kill.
    disp.spawn_workers(devices[:2])
    procs, proxies = chain_pool(disp, cfg, cuts, [17631, 17632, 17633])
    mid_left = threading.Event()
    try:
        disp.start()
        # Registered after the dispatcher's own watcher (start() adds
        # it): when this one fires, the dispatcher has already handled
        # the same leave.
        disp.registry.watch(
            lambda event, wid: mid_left.set()
            if (event, wid) == ("leave", proxies[1].worker_id)
            else None
        )
        for pr in proxies:
            pr.start()
        disp.setup_chain([pr.worker_id for pr in proxies])
        outs = disp.serve_stream([x] * 2, timeout_per_request=120.0)
        for y in outs:
            np.testing.assert_allclose(
                np.asarray(y), y_ref, rtol=1e-5, atol=1e-5
            )
        proxies[1].kill("crash")
        # Membership notices (link drop -> deregister) and the chain
        # disables itself: wait on that event, with a limit of its own.
        assert mid_left.wait(60.0), "mid-chain death never reached membership"
        assert disp._chain is None, "chain still enabled after its member left"
        outs2 = disp.serve_stream([x] * 4, timeout_per_request=120.0)
        for y in outs2:
            np.testing.assert_allclose(
                np.asarray(y), y_ref, rtol=1e-5, atol=1e-5
            )
    finally:
        disp.shutdown()
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def test_chain_rejects_in_process_workers(devices):
    """Chaining is a cross-host topology; in-process workers share the
    hub's memory, so setup_chain must refuse them loudly."""
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    plan = partition(g, ["encoder_block_1"])
    disp = Dispatcher(plan, variables)
    workers = disp.spawn_workers(devices[:2])
    disp.start()
    try:
        with pytest.raises(TypeError, match="cannot chain"):
            disp.setup_chain([w.worker_id for w in workers])
    finally:
        disp.shutdown()


def test_chain_forwarding_composes_with_codec(devices):
    """Chain hops carry codec-packed activations (frames are
    self-describing, so each hop unpacks whatever its upstream packed):
    int8-quantized activations over a 3-hop chain must still produce
    outputs within quantization tolerance of the full model."""
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_block_cuts, vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    cuts = vit_block_cuts(4, 3)
    plan = partition(g, cuts)
    y_ref = np.asarray(g.apply(variables, x))
    cfg = chain_cfg()
    disp = Dispatcher(plan, variables, config=cfg)
    procs, proxies = chain_pool(
        disp, cfg, cuts, [17645, 17646, 17647],
        codec_name="int8", prefix="cchain",
    )
    try:
        disp.start()
        for pr in proxies:
            pr.start()
        disp.setup_chain([pr.worker_id for pr in proxies])
        outs = disp.serve_stream([x] * 4, timeout_per_request=120.0)
        for y in outs:
            assert np.max(np.abs(np.asarray(y) - y_ref)) < 0.3
        assert proxies[0].results_received == 0
        assert proxies[2].results_received == 4
    finally:
        disp.shutdown()
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def test_chain_kill_mid_burst_exactly_once(devices):
    """Kill the TAIL chain worker while a burst is in flight: chain
    entries in every state (queued at head, mid-hop, awaiting tail) must
    replay end-to-end through the hub path — exactly once, right
    answers, no hangs. This is the riskiest chain path: whole-request
    replay racing live traffic."""
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_block_cuts, vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    cuts = vit_block_cuts(4, 3)
    plan = partition(g, cuts)
    y_ref = np.asarray(g.apply(variables, x))
    cfg = chain_cfg()
    disp = Dispatcher(plan, variables, config=cfg)
    # Local fallback pool so replays have somewhere to land even while
    # remote membership churns.
    disp.spawn_workers(devices[:3])
    procs, proxies = chain_pool(disp, cfg, cuts, [17641, 17642, 17643])
    try:
        disp.start()
        for pr in proxies:
            pr.start()
        disp.setup_chain([pr.worker_id for pr in proxies])
        disp.serve_stream([x] * 2, timeout_per_request=120.0)  # warm chain
        futures = [disp.submit(x) for _ in range(10)]
        proxies[2].kill("crash")  # tail dies with the burst in flight
        outs = [f.result(180.0) for f in futures]
        for y in outs:
            np.testing.assert_allclose(
                np.asarray(y), y_ref, rtol=1e-5, atol=1e-5
            )
        assert disp._chain is None  # the failure disabled the chain
        # Exactly-once: every submitted future completed with a value
        # (no double-complete is possible through PipelineFuture, and
        # none errored).
        assert all(f._error is None for f in futures)
    finally:
        disp.shutdown()
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


# -- architecture-by-value ---------------------------------------------------


def test_registry_less_worker_serves_partitioned_resnet(devices):
    """A worker started with --no-registry (bare image: framework, no
    model zoo) serves a partitioned ResNet-50 configured entirely BY
    VALUE — the serialized LayerGraph rides in MSG_CONFIG (reference
    ``model.to_json()`` → ``model_from_json``, ``src/dispatcher.py:235``
    / ``src/node.py:40-45``). A by-NAME configure to the same worker must
    fail loudly."""
    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import FaultConfig, ServeConfig
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import graph_to_spec, partition
    from adapt_tpu.models.resnet import RESNET50_3STAGE_CUTS, resnet50

    g = resnet50(num_classes=10)
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    cuts = list(RESNET50_3STAGE_CUTS)
    plan = partition(g, cuts)
    y_ref = np.asarray(g.apply(variables, x))

    port = 17651
    proc = spawn_worker_proc(
        "--port", str(port), "--heartbeat", "0.2", "--no-registry"
    )
    cfg = ServeConfig(
        fault=FaultConfig(
            lease_ttl_s=2.0,
            heartbeat_s=0.2,
            task_deadline_s=60.0,
            watchdog_period_s=0.5,
            startup_wait_s=15.0,
            configure_timeout_s=120.0,
        )
    )
    disp = Dispatcher(plan, variables, config=cfg)
    proxy = RemoteWorkerProxy(
        "by-value-0",
        ("127.0.0.1", port),
        disp.registry,
        disp.result_queue,
        model_config={
            "graph_spec": graph_to_spec(g),
            "cuts": cuts,
            "input_shape": [2, 64, 64, 3],
        },
        fault=cfg.fault,
    )
    disp.attach_worker(proxy)
    disp.start()
    try:
        proxy.start()
        # Configure ALL stages on the remote: every result the hub gets
        # came from spec-rebuilt stages, none from local registry code.
        for i in range(plan.num_stages):
            proxy.configure(i, None, plan.extract_variables(variables)[i])
        outs = disp.serve_stream([x] * 3, timeout_per_request=120.0)
        for y in outs:
            np.testing.assert_allclose(
                np.asarray(y), y_ref, rtol=1e-5, atol=1e-5
            )
        assert proxy.results_received >= 3 * plan.num_stages
        # By-name configure against the bare worker: loud refusal.
        proxy._model_config = {
            "model": "resnet50",
            "num_classes": 10,
            "cuts": cuts,
            "input_shape": [2, 64, 64, 3],
        }
        with pytest.raises(RuntimeError, match="architecture-by-value"):
            proxy.configure(0, None, plan.extract_variables(variables)[0])
    finally:
        disp.shutdown()
        proc.terminate()
        proc.wait(timeout=10)


# -- data-plane hardening ----------------------------------------------------


def test_concurrent_configures_do_not_clobber(devices):
    """Two configure() calls racing on the SAME proxy (the dispatcher's
    recovery path can reach this from two forward threads) must each get
    their own ACK — generation-keyed handshake state, not a shared
    per-stage dict."""
    import queue as queue_mod

    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import FaultConfig
    from adapt_tpu.control.registry import WorkerRegistry
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny

    port = 17597
    proc = spawn_worker_proc("--port", str(port), "--heartbeat", "0.1")
    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(1), x)
    plan = partition(g, ["encoder_block_1"])
    stage_vars = plan.extract_variables(variables)

    registry = WorkerRegistry(default_ttl_s=2.0).start()
    results: "queue_mod.Queue" = queue_mod.Queue()
    proxy = RemoteWorkerProxy(
        "remote-cc",
        ("127.0.0.1", port),
        registry,
        results,
        model_config={
            "model": "vit_tiny",
            "num_classes": 10,
            "cuts": ["encoder_block_1"],
            "input_shape": [2, 32, 32, 3],
        },
        fault=FaultConfig(startup_wait_s=10.0, configure_timeout_s=60.0),
    )
    try:
        proxy.start()
        errors = []

        def cfg(stage):
            try:
                proxy.configure(stage, None, stage_vars[stage])
            except Exception as e:  # noqa: BLE001
                errors.append((stage, e))

        # Same stage twice concurrently + the other stage: all must land.
        threads = [
            threading.Thread(target=cfg, args=(s,)) for s in (1, 1, 0)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90.0)
        assert not errors, errors
        assert proxy.is_configured(0) and proxy.is_configured(1)
    finally:
        proxy.stop()
        registry.stop()
        proc.terminate()
        proc.wait(timeout=10)


def test_stalled_peer_send_times_out_not_wedges():
    """A peer that stops draining its socket (hung process, full TCP
    buffers) must not wedge the sender forever: the bounded send raises
    within ~send_timeout_s and the proxy marks its link dead so the
    scheduler routes around it."""
    import queue as queue_mod

    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import FaultConfig
    from adapt_tpu.control.registry import WorkerRegistry
    from adapt_tpu.control.worker import Task, WorkerState

    # A server that accepts and then never reads: sendall must eventually
    # block once kernel buffers fill.
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    accepted = []

    def accept_only():
        conn, _ = srv.accept()
        accepted.append(conn)  # keep alive, never read

    t = threading.Thread(target=accept_only, daemon=True)
    t.start()

    registry = WorkerRegistry(default_ttl_s=5.0).start()
    results: "queue_mod.Queue" = queue_mod.Queue()
    proxy = RemoteWorkerProxy(
        "remote-stall",
        ("127.0.0.1", port),
        registry,
        results,
        model_config={},
        fault=FaultConfig(startup_wait_s=5.0, send_timeout_s=1.0),
    )
    try:
        proxy.start()
        proxy._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        big = np.zeros((4 << 20,), np.uint8)  # 4 MB >> buffer space
        start = time.monotonic()
        with pytest.raises((ConnectionError, TimeoutError)):
            for _ in range(8):  # first sends may fit in buffers
                proxy.submit(
                    Task(request_id=1, stage_index=0, attempt=0, payload=big)
                )
        elapsed = time.monotonic() - start
        assert elapsed < 10.0, f"send wedged for {elapsed:.1f}s"
        # The link is condemned: state DEAD, membership eviction immediate.
        assert proxy.state is WorkerState.DEAD
        assert "remote-stall" not in registry.alive()
    finally:
        proxy.stop()
        registry.stop()
        for c in accepted:
            c.close()
        srv.close()


# -- worker-initiated join (the pool can GROW) -------------------------------


def test_worker_joins_running_pipeline_via_gateway(devices):
    """The reference's defining adaptive capability: a FRESH worker
    registers itself with a RUNNING pipeline (src/node_state.py:17-20) and
    subsequently serves stages. Here: mid-stream, a new worker process
    dials the WorkerGateway; after the local workers are crashed, requests
    keep completing — only the joined worker can be serving them."""
    from adapt_tpu.comm.remote import WorkerGateway
    from adapt_tpu.config import CodecConfig, FaultConfig, ServeConfig
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    plan = partition(g, ["encoder_block_1"])
    y_ref = np.asarray(g.apply(variables, x))

    cfg = ServeConfig(
        fault=FaultConfig(
            # Every death here is learnt from an event (the local
            # workers' crash eviction), none from a lapsed lease: at
            # 1 s a joiner compiling its stage under the suite's six
            # workers lost its lease and the last assertion with it.
            lease_ttl_s=120.0,
            heartbeat_s=0.2,
            task_deadline_s=30.0,
            watchdog_period_s=0.1,
            startup_wait_s=10.0,
            configure_timeout_s=60.0,
        ),
        codec=CodecConfig(name="int8", weights="lz"),
    )
    disp = Dispatcher(plan, variables, config=cfg)
    local = disp.spawn_workers(devices[:2])
    gateway = WorkerGateway(
        disp,
        model_config={
            "model": "vit_tiny",
            "num_classes": 10,
            "cuts": ["encoder_block_1"],
            "input_shape": [2, 32, 32, 3],
        },
    )
    proc = None
    procs2: list = []
    try:
        disp.start()
        gateway.start()
        # Pipeline is live and serving before the newcomer exists.
        outs = disp.serve_stream([x] * 3, timeout_per_request=60.0)
        assert all(
            np.max(np.abs(np.asarray(y) - y_ref)) < 0.3 for y in outs
        )

        proc = spawn_worker_proc(
            "--connect", f"127.0.0.1:{gateway.port}",
            "--worker-id", "joiner-0", "--heartbeat", "0.1",
        )
        deadline = time.monotonic() + 30.0
        while "joiner-0" not in disp.registry.alive():
            assert time.monotonic() < deadline, "worker never joined"
            time.sleep(0.05)
        # Pool grew mid-stream; keep serving through the join.
        outs = disp.serve_stream([x] * 3, timeout_per_request=60.0)
        assert all(
            np.max(np.abs(np.asarray(y) - y_ref)) < 0.3 for y in outs
        )
        # A SECOND worker must also be able to join while a device-less
        # remote proxy is already attached (regression: the join-watch
        # prewarm read .device off every worker and crashed the gateway
        # accept loop, capping the pool at one remote).
        proc2 = spawn_worker_proc(
            "--connect", f"127.0.0.1:{gateway.port}",
            "--worker-id", "joiner-1", "--heartbeat", "0.1",
        )
        procs2.append(proc2)
        deadline = time.monotonic() + 30.0
        while "joiner-1" not in disp.registry.alive():
            assert time.monotonic() < deadline, "second worker never joined"
            time.sleep(0.05)
        # Crash every local worker: only the joined remotes can serve now.
        for w in local:
            w.kill("crash")
        deadline = time.monotonic() + 10.0
        while any(w.worker_id in disp.registry.alive() for w in local):
            assert time.monotonic() < deadline, "local leases never lapsed"
            time.sleep(0.05)
        outs = disp.serve_stream([x] * 2, timeout_per_request=90.0)
        for y in outs:
            assert np.max(np.abs(np.asarray(y) - y_ref)) < 0.3
        assert "joiner-0" in disp.registry.alive()
    finally:
        for p in [proc, *procs2]:
            if p is not None:
                p.terminate()
                p.wait(timeout=10)
        gateway.stop()
        disp.shutdown()


def test_serving_pipeline_elastic_gateway(devices):
    """One-constructor elastic serving: ServingPipeline(gateway_model_config=...)
    opens the join gateway; a worker process dials it and serves."""
    from adapt_tpu.config import FaultConfig, ServeConfig
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny
    from adapt_tpu.runtime import ServingPipeline

    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    plan = partition(g, ["encoder_block_1"])
    y_ref = np.asarray(g.apply(variables, x))

    pipe = ServingPipeline(
        plan,
        variables,
        devices=devices[:2],
        config=ServeConfig(
            fault=FaultConfig(
                lease_ttl_s=1.0, heartbeat_s=0.2, startup_wait_s=10.0
            )
        ),
        gateway_model_config={
            "model": "vit_tiny",
            "num_classes": 10,
            "cuts": ["encoder_block_1"],
            "input_shape": [2, 32, 32, 3],
        },
    )
    proc = None
    try:
        pipe.start()
        assert pipe.gateway_port
        proc = spawn_worker_proc(
            "--connect", f"127.0.0.1:{pipe.gateway_port}",
            "--worker-id", "elastic-0", "--heartbeat", "0.1",
        )
        deadline = time.monotonic() + 30.0
        while "elastic-0" not in pipe.registry.alive():
            assert time.monotonic() < deadline, "joiner never registered"
            time.sleep(0.05)
        outs = pipe.stream([x] * 2, timeout_per_request=60.0)
        for y in outs:
            assert np.max(np.abs(np.asarray(y) - y_ref)) < 0.3
    finally:
        if proc is not None:
            proc.terminate()
            proc.wait(timeout=10)
        pipe.shutdown()


def _raw_hello(port: int, worker_id: str, secret: str | None = None):
    """Dial a gateway and send a bare HELLO; returns ("ack", None) on
    acceptance, ("rejected", reason) when the gateway closes the link
    before saying anything. Acceptance = ANY message arrives: the
    dispatcher's join-watch prewarm can put a MSG_CONFIG on the wire
    before the gateway's HELLO_ACK (they race by design)."""
    import json as _json

    from adapt_tpu.comm.remote import MSG_HELLO

    info = {"worker_id": worker_id}
    if secret is not None:
        info["secret"] = secret
    conn = socket.create_connection(("127.0.0.1", port), timeout=5.0)
    send_msg(conn, Message(MSG_HELLO, 0, 0, 0, _json.dumps(info).encode()))
    conn.settimeout(5.0)
    try:
        recv_msg(conn, retry_on_timeout=False)
    except Exception as e:  # noqa: BLE001 — closed link == rejection
        conn.close()
        return "rejected", str(e)
    # Accepted: hand the OPEN socket back — closing it would make the
    # gateway proxy deregister the lease (link-drop eviction) before the
    # caller can observe it.
    return "ack", conn


def test_gateway_rejects_duplicate_live_worker_id_and_bad_secret(devices):
    """Gateway hardening (above reference parity — the reference has no
    auth anywhere, SURVEY.md §2.8): a joiner announcing a LIVE worker's
    id is rejected (it would race that worker's lease and interleave two
    links under one identity), and when the gateway carries a secret, a
    join without the matching one is rejected (constant-time compare)."""
    from adapt_tpu.comm.remote import WorkerGateway
    from adapt_tpu.config import FaultConfig, ServeConfig
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_tiny

    g = vit_tiny()
    x = jnp.ones((1, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    plan = partition(g, ["encoder_block_1"])
    cfg = ServeConfig(
        fault=FaultConfig(
            lease_ttl_s=2.0, heartbeat_s=0.2, startup_wait_s=10.0
        )
    )
    disp = Dispatcher(plan, variables, config=cfg)
    local = disp.spawn_workers(devices[:2])
    gateway = WorkerGateway(
        disp,
        model_config={"model": "vit_tiny", "num_classes": 10,
                      "cuts": ["encoder_block_1"],
                      "input_shape": [1, 32, 32, 3]},
        secret="open-sesame",
    )
    try:
        disp.start()
        gateway.start()
        live_id = local[0].worker_id
        assert live_id in disp.registry.alive()

        # No secret / wrong secret: closed before any attach.
        assert _raw_hello(gateway.port, "mallory")[0] == "rejected"
        assert (
            _raw_hello(gateway.port, "mallory", secret="guess")[0]
            == "rejected"
        )
        assert "mallory" not in disp.registry.alive()

        # Right secret but a LIVE worker's id: rejected, live lease
        # untouched.
        status, _ = _raw_hello(gateway.port, live_id, secret="open-sesame")
        assert status == "rejected"
        assert live_id in disp.registry.alive()

        # Right secret, fresh id: accepted (message flows + lease
        # registered while the link stays open).
        status, conn = _raw_hello(
            gateway.port, "joiner-x", secret="open-sesame"
        )
        assert status == "ack"
        try:
            deadline = time.monotonic() + 10.0
            while "joiner-x" not in disp.registry.alive():
                assert time.monotonic() < deadline
                time.sleep(0.02)
        finally:
            conn.close()
    finally:
        gateway.stop()
        disp.shutdown()
