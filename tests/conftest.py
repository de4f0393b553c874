"""Test bootstrap: simulated 8-device CPU mesh.

The reference's only "multi-node without a cluster" affordance is localhost
aliasing (``/root/reference/src/dispatcher.py:163-173``). Our analog is a
virtual device mesh: force the JAX CPU backend to expose 8 devices so every
multi-stage / multi-worker / fault-injection test runs hermetically in CI
with real (host) transfers between real XLA devices.

Must run before jax initializes a backend, hence env vars at import time.
"""

import os
import re

# Force, don't setdefault: the outer environment may pin JAX_PLATFORMS to a
# real accelerator, but tests must always run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_FLAG = "--xla_force_host_platform_device_count"
_flags = os.environ.get("XLA_FLAGS", "")
_m = re.search(rf"{_FLAG}=(\d+)", _flags)
if _m is None:
    os.environ["XLA_FLAGS"] = f"{_flags} {_FLAG}=8".strip()
elif int(_m.group(1)) < 8:
    os.environ["XLA_FLAGS"] = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}=8", _flags)

import jax  # noqa: E402

# Something may have imported jax before this conftest ran, freezing
# jax_platforms from the pre-existing env. Override via the config API,
# which works after import as long as no backend has been initialized.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long parameterizations excluded from the tier-1 run "
        "(ROADMAP.md runs -m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "statistical: seed-pinned distributional assertions (e.g. the "
        "temperature>0 speculative-sampling equivalence gate) — "
        "deterministic under the pinned seed, but the TEST's tolerance "
        "is a statistical bound, not bit-identity; when one fails "
        "after an intentional sampling change, re-derive the pinned "
        "expectations instead of loosening the bound",
    )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Bound per-process XLA state: after ~240 accumulated compiled
    executables the XLA:CPU compiler segfaulted mid-compile (observed in
    jax 0.9.0's backend_compile_and_load during a late test module; the
    same test passes standalone). Clearing jit/tracing caches at module
    boundaries keeps compiler state small for a suite this size; the
    recompiles it causes are per-module models that would mostly compile
    fresh anyway."""
    jax.clear_caches()
    yield


def _memory_maps() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to bound
        return 0


try:
    with open("/proc/sys/vm/max_map_count") as _f:
        _MAP_BUDGET = int(_f.read()) // 2
except (OSError, ValueError):
    _MAP_BUDGET = 32_000


@pytest.fixture(autouse=True)
def _clear_jax_caches_before_the_map_limit():
    """The cause of that segfault, measured (PR 33): every compiled
    XLA:CPU executable holds ~37 memory mappings, the jit caches never
    let one go (a batcher's programs are keyed on the batcher), and a
    process may hold ``vm.max_map_count`` of them (65,530): one
    benchmark rehearsal leaves ~7,600, and a MODULE that walks some
    twenty-five of them (``tests/chipbench/test_chipbench_run_loop.py``)
    runs into the limit, where the next compile's mmap fails inside
    LLVM. So within a module too: past half the limit, let them go
    before the next test."""
    if _memory_maps() > _MAP_BUDGET:
        jax.clear_caches()
    yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    assert devs[0].platform == "cpu", (
        "tests must run on the virtual CPU mesh, got platform "
        f"{devs[0].platform!r} — a backend was initialized before conftest "
        "could force jax_platforms=cpu"
    )
    return devs


@pytest.fixture(scope="session")
def sim_mesh(devices):
    """Factory for meshes over the virtual device pool — THE test-side
    mesh constructor (the ``--xla_force_host_platform_device_count``
    handling above feeds it). ``sim_mesh(4)`` builds a 1-axis
    ``('tp', 4)`` mesh, ``sim_mesh(4, axis='pp')`` renames the axis, and
    ``sim_mesh((('dp', 2), ('pp', 4)))`` builds a multi-axis mesh.
    Skips the test cleanly when the pool holds fewer devices than the
    mesh needs (e.g. a constrained environment where the XLA flag was
    pinned lower), instead of failing on an opaque reshape."""

    def build(spec, axis: str = "tp"):
        from adapt_tpu.core.mesh import MeshSpec, build_mesh

        axes = ((axis, spec),) if isinstance(spec, int) else tuple(spec)
        mspec = MeshSpec(axes)
        if mspec.num_devices > len(devices):
            pytest.skip(
                f"mesh {axes} needs {mspec.num_devices} devices, "
                f"have {len(devices)}"
            )
        return build_mesh(mspec, devices)

    return build


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


def drained(bat):
    """``bat`` (a ``ContinuousBatcher``), every tick of it committed
    before the next is dispatched (``tick(); drain()``): what a test
    that reads state after each tick drives, and what the overlapped
    order is compared with."""
    tick = bat.tick
    bat.tick = lambda: tick() + bat.drain()
    return bat


def log_softmax_score(logits, tokens):
    """``transformer_lm.chosen_logprob`` as it stood until PR 50 (the
    whole log-softmax, then one value a row): what the tests of the
    score compare with."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jax.numpy.take_along_axis(
        lp, tokens[:, None].astype("int32"), axis=-1
    )[:, 0]


def greedy_by_full_forward(lm, variables, prompt, steps: int):
    """The oracle of the cached-decode parity tests: ``steps`` tokens
    by stepwise argmax of the FULL causal forward, (b, steps). The
    sequence stands in ONE buffer of its final length (what lies past a
    position is masked from it, window or not), so the forward is ONE
    compiled program: a sequence that grows a token a step is a new
    shape a step, every operation of the model compiled again (30
    steps of a 2-layer model took 200 s of the suite)."""
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.models.transformer_lm import logits_full

    b, s0 = prompt.shape
    ids = jnp.zeros((b, s0 + steps), prompt.dtype).at[:, :s0].set(prompt)
    forward = jax.jit(lambda v, ids: logits_full(lm, v, ids))
    for i in range(steps):
        nxt = jnp.argmax(forward(variables, ids)[:, s0 + i - 1], axis=-1)
        ids = ids.at[:, s0 + i].set(nxt.astype(ids.dtype))
    return np.asarray(ids)[:, s0:]


def logits_by_cached_decode(lm, variables, ids, s0: int, quant=False):
    """Teacher-forced incremental decoding, the other side of the cached
    decode parity tests: prefill ``ids[:, :s0]``, then every further
    column through ``embed_at`` + ``decode_step``. Returns the prefill's
    logits (b, s0, V), the steps' (b, s - s0, V) and the prefill's caches,
    a (k, v) a block. TWO compiled programs, the step's position traced:
    applied eagerly a step is some hundred programs of one operation, a
    Python position a new set of them (13-16 s a test of 7 steps)."""
    import jax.numpy as jnp
    import numpy as np

    g = lm.graph
    embed, head = g.node("embed").module, g.node("head").module
    blocks = [g.node(n).module for n in lm.block_names]

    @jax.jit
    def prefill(variables, ids):
        h = embed.apply(variables["embed"], ids)
        caches = []
        for name, block in zip(lm.block_names, blocks):
            h, ck, cv = block.apply(
                variables[name], h, lm.max_len, None, quant, method="prefill"
            )
            caches.append((ck, cv))
        return head.apply(variables["head"], h), caches

    @jax.jit
    def step(variables, caches, ids_t, t):
        x = embed.apply(variables["embed"], ids_t, t, method="embed_at")
        new = []
        for name, block, (ck, cv) in zip(lm.block_names, blocks, caches):
            x, ck, cv = block.apply(
                variables[name], x, ck, cv, t, None, quant,
                method="decode_step",
            )
            new.append((ck, cv))
        return head.apply(variables["head"], x)[:, 0], new

    first, prefilled = prefill(variables, ids[:, :s0])
    caches, steps = prefilled, []
    for t in range(s0, ids.shape[1]):
        logits, caches = step(
            variables, caches, ids[:, t : t + 1], jnp.int32(t)
        )
        steps.append(np.asarray(logits))
    return np.asarray(first), np.stack(steps, axis=1), prefilled


# -- compiling for a described v5e (tests/test_chip_lowering.py and
# tests/test_embed_lanes.py; neither fixture is autouse: only a test that
# asks for the chip loads libtpu) ------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e (the TPU compiler is installed here;
    no chip is attached). Skips where it cannot be described."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without a chip (the next one warns):
    keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def pool_copies(text, shape):
    """``(relayouts, moves)`` of buffers of ``shape`` in a compiled
    program's text: a ``copy`` (or a ``copy-start`` whose two layouts
    differ) rewrites the whole buffer into another physical layout;
    a ``copy-start`` between equal layouts is the compiler staging a
    buffer through fast memory (``S(1)``), which it does to buffers small
    enough to fit."""
    dims = re.escape(",".join(map(str, shape)))
    buf = r"\w+\[" + dims + r"\](\{[^}]*\})"
    sync = re.compile(r"= " + buf + r" copy\(")
    start = re.compile(r"= \(" + buf + ", " + buf + r".*\) copy-start\(")

    def tiles(layout):
        return re.sub(r"S\(\d+\)", "", layout)

    relayouts = moves = 0
    for line in text.splitlines():
        if sync.search(line):
            relayouts += 1
        elif m := start.search(line):
            if tiles(m.group(1)) == tiles(m.group(2)):
                moves += 1
            else:
                relayouts += 1
    return relayouts, moves


def spawn_worker_proc(*cli_args: str) -> "subprocess.Popen":
    """Launch ``python -m adapt_tpu.comm.remote`` as a hermetic CPU child
    (shared by the comm and stress tests — one place owns the env recipe:
    force the CPU backend, put the repo on the path)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(os.path.dirname(__file__)))
    return subprocess.Popen(
        [sys.executable, "-m", "adapt_tpu.comm.remote", *cli_args],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def chain_cfg(configure_timeout_s: float = 60.0, lease_ttl_s: float = 2.0):
    """ServeConfig used by the chain-forwarding tests (shared by
    test_comm and test_control). ``lease_ttl_s``: a test whose subject
    is a death learnt from the LINK (drop -> deregister) passes a lease
    no starved child can lapse — under the suite's six workers a worker
    process compiling its stage has gone 2 s without a ping, its lease
    lapsed before the test's kill, and the kill then had no lease left
    to revoke."""
    from adapt_tpu.config import FaultConfig, ServeConfig

    return ServeConfig(
        fault=FaultConfig(
            lease_ttl_s=lease_ttl_s,
            heartbeat_s=0.2,
            task_deadline_s=30.0,
            watchdog_period_s=0.2,
            startup_wait_s=15.0,
            configure_timeout_s=configure_timeout_s,
        )
    )


def chain_pool(
    disp, cfg, cuts, ports, codec_name: str = "none", prefix: str = "chain"
):
    """Spawn one worker process per port and attach dial-out proxies —
    the shared setup for every chain-forwarding test. Returns
    (procs, proxies)."""
    from adapt_tpu.comm.remote import RemoteWorkerProxy

    procs = [
        spawn_worker_proc("--port", str(p), "--heartbeat", "0.2")
        for p in ports
    ]
    proxies = []
    for i, p in enumerate(ports):
        pr = RemoteWorkerProxy(
            f"{prefix}-{i}",
            ("127.0.0.1", p),
            disp.registry,
            disp.result_queue,
            model_config={
                "model": "vit_tiny",
                "num_classes": 10,
                "cuts": cuts,
                "input_shape": [2, 32, 32, 3],
            },
            codec_name=codec_name,
            fault=cfg.fault,
        )
        disp.attach_worker(pr)
        proxies.append(pr)
    return procs, proxies
