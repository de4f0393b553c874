"""Prove on a real TPU that both serving tiers still start and answer.

One process, no fallbacks: not a TPU -> non-zero exit and no result
line. Phases, each through the entry points a user would call:

1. kernels — every Pallas kernel the serving path can select on a TPU,
   COMPILED, at the shapes the request tier serves, against its own
   ``*_reference`` under ``jax.default_matmul_precision("highest")``.
2. stage tier — ResNet-50 (bf16, 224x224, bs=32) cut into the paper's
   three stages, served by ``ServingPipeline`` over every visible chip,
   checked against the single-program forward; with >= 2 chips a worker
   is killed mid-service and the survivors must keep answering without
   a failed request or a recompile.
3. request tier — GPT-2-small as published (bf16) behind the threaded
   ``ContinuousBatcher`` (paged KV, chunked prefill): mixed prompt
   lengths, one longer than the chunk, one repeated prefix; logprobs
   checked against ``logits_full`` on the served stream.
4. request tier, tp — with >= 2 chips the same server tensor-parallel
   over all of them; every chip must hold its shard of weights and KV.

Weights are random from a seed, so tolerances compare two routes
through the same bf16 model, not a model against published outputs.
The seconds printed are set-up evidence, not metrics.

``--rehearse-cpu`` walks the same phases at toy sizes on the CPU
backend (Pallas interpreter) to debug the script itself; it prints no
result line, and nothing it prints is a device number.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from adapt_tpu.config import (
    FaultConfig,
    KernelConfig,
    ParallelConfig,
    ServeConfig,
)
from adapt_tpu.graph.partition import partition
from adapt_tpu.models.resnet import RESNET50_3STAGE_CUTS, resnet50
from adapt_tpu.models.transformer_lm import (
    chosen_logprob,
    logits_full,
    transformer_lm,
)
from adapt_tpu.ops.attention import attention_reference, flash_attention
from adapt_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
)
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.paged_attention import (
    fuse_kv,
    paged_attention,
    paged_attention_reference,
    paged_chunk_attention,
    paged_chunk_attention_reference,
    kernel_unsupported,
    paged_verify_attention,
    paged_verify_attention_reference,
)
from adapt_tpu.ops.quantize import (
    dequantize,
    dequantize_reference,
    quantize,
    quantize_kv_vectors,
    quantize_reference,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.pipeline import ServingPipeline
from adapt_tpu.utils.compile_cache import ensure_compile_cache
from adapt_tpu.utils.metrics import global_metrics

#: Full width: the published configurations. Depth is not cut either —
#: both models fit one chip whole.
FULL = dict(
    image=224, batch=32, classes=1000,
    lm=dict(vocab=50257, dim=768, depth=12, heads=12, mlp_dim=3072,
            max_len=1024),
    prompts=(12, 100, 300, 600), steps=24, quant_page=1024,
)
#: CPU rehearsal of the script's own control flow. Not a configuration.
TINY = dict(
    image=64, batch=2, classes=10,
    lm=dict(vocab=512, dim=64, depth=2, heads=4, mlp_dim=128, max_len=512),
    prompts=(12, 100, 300), steps=6, quant_page=128,
)
SLOTS, PAGE, CHUNK = 8, 128, 256

#: bf16 in, bf16 out, f32 accumulation inside: kernel and reference may
#: round the same value to neighbouring bf16s, one ulp = 2**-7 relative
#: at worst (the largest error measured on a v5e is exactly that).
#: Errors are scaled by max(1, |reference|); 2e-2 is two such ulps plus
#: room for the kernel's reordered f32 sums and nothing more.
KERNEL_TOL = 2e-2
#: Two XLA programs over the same bf16 network (staged vs single
#: program; paged decode vs full forward) differ by bf16 rounding at
#: every fusion boundary. Stated relative to the reference's own range.
STAGE_REL_TOL = 5e-2
LOGPROB_TOL = 1e-1


class Compiles:
    """Seconds JAX spent in backend compile (cache retrieval included),
    and persistent-cache hits and writes, from ``jax.monitoring``
    (JAX's ``cache_misses`` event fires when an entry is WRITTEN)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += seconds

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.writes += 1

    def snapshot(self):
        with self._lock:
            return self.seconds, self.hits, self.writes


@contextmanager
def phase(name: str, compiles: Compiles, report: list):
    print(f"[{name}] start", flush=True)
    c0, h0, m0 = compiles.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    c1, h1, m1 = compiles.snapshot()
    line = (
        f"[{name}] ok  wall {wall:.1f}s  compile {c1 - c0:.1f}s  "
        f"run {max(wall - (c1 - c0), 0.0):.1f}s  "
        f"cache hits {h1 - h0} writes {m1 - m0}"
    )
    report.append(line)
    print(line, flush=True)


# -- phase 1: kernels ---------------------------------------------------------


def _normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _pool(key, page, npages, kvh, hd, kv_dtype):
    t = _normal(key, (npages, kvh, page, hd))
    if kv_dtype == "native":
        return t
    return quantize_kv_vectors(t, kv_dtype)


def kernel_cases(size, prefer):
    """Yield ``(name, got_fn, want_fn, tol, routed)`` for every Pallas
    kernel the serving path can select, at the request tier's shapes.
    ``got`` runs the dispatcher (compiled kernel on the chip), ``want``
    its reference under ``jax.default_matmul_precision("highest")``.
    ``routed`` is None, or the stated rule by which auto dispatch sends
    these operands to XLA on this backend."""
    lmc = size["lm"]
    kvh, hd = lmc["heads"], lmc["dim"] // lmc["heads"]
    key = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)

    def highest(fn, *a, **kw):
        def run():
            with jax.default_matmul_precision("highest"):
                return fn(*a, **kw)
        return run

    for kv_dtype, page in (
        ("native", PAGE), ("int8", size["quant_page"]),
        ("int4", size["quant_page"]),
    ):
        pps = max(lmc["max_len"] // page, 2)
        n = SLOTS * pps + 1
        pool = fuse_kv(
            _pool(jax.random.fold_in(key, 1), page, n, kvh, hd, kv_dtype),
            _pool(jax.random.fold_in(key, 2), page, n, kvh, hd, kv_dtype),
        )
        table = jnp.asarray(
            1 + rng.permutation(n - 1).reshape(SLOTS, pps), jnp.int32
        )
        span = pps * page
        index = jnp.asarray(
            rng.randint(1, span - 8, size=SLOTS), jnp.int32
        ).at[0].set(span - 8)
        q = _normal(jax.random.fold_in(key, 3), (SLOTS, kvh, 1, hd))
        routed = kernel_unsupported(q, pool)
        for split in (1, None):
            yield (
                f"paged_decode {kv_dtype} split={split}",
                lambda split=split: paged_attention(
                    q, pool, table, index, prefer=prefer, split=split
                ),
                highest(paged_attention_reference, q, pool, table, index),
                KERNEL_TOL,
                routed,
            )
        qv = _normal(jax.random.fold_in(key, 4), (SLOTS, kvh, 5, hd))
        for tree_tail in (0, 2):
            yield (
                f"paged_verify {kv_dtype} tree={tree_tail}",
                lambda tree_tail=tree_tail: paged_verify_attention(
                    qv, pool, table, index, 5, prefer=prefer,
                    tree_tail=tree_tail,
                ),
                highest(
                    paged_verify_attention_reference, qv, pool, table,
                    index, 5, tree_tail=tree_tail,
                ),
                KERNEL_TOL,
                routed,
            )
        chunk = max(CHUNK, page)
        qc = _normal(jax.random.fold_in(key, 5), (1, kvh, chunk, hd))
        pages = table.reshape(-1)[: 2 * chunk // page]
        yield (
            f"paged_chunk {kv_dtype} chunk={chunk}",
            lambda: paged_chunk_attention(
                qc, pool, pages, chunk, chunk, prefer=prefer
            ),
            highest(
                paged_chunk_attention_reference, qc, pool, pages, chunk,
                chunk,
            ),
            KERNEL_TOL,
            routed,
        )

    # The dense decode kernel (generate()'s and the draft's strips;
    # auto keeps XLA, ROADMAP C1b) and ragged flash prefill (auto only
    # past the score budget) are forced: reachable, not default.
    cache_len = lmc["max_len"]
    q = _normal(jax.random.fold_in(key, 6), (SLOTS, kvh, 1, hd))
    idx = jnp.asarray(rng.randint(1, cache_len, size=SLOTS), jnp.int32)
    ck = _normal(jax.random.fold_in(key, 7), (SLOTS, kvh, cache_len, hd))
    cv = _normal(jax.random.fold_in(key, 8), (SLOTS, kvh, cache_len, hd))
    caches = {"native": (ck, cv)}
    if cache_len % 1024 == 0:  # the int8 scale tile's block
        caches["int8"] = (quantize_kv_vectors(ck), quantize_kv_vectors(cv))
    for kv_dtype, (k, v) in caches.items():
        yield (
            f"dense_decode {kv_dtype}",
            lambda k=k, v=v: decode_attention(q, k, v, idx, prefer="pallas"),
            highest(decode_attention_reference, q, k, v, idx),
            KERNEL_TOL,
            None,
        )
    s = min(512, lmc["max_len"])
    qf = _normal(jax.random.fold_in(key, 9), (2, kvh, s, hd))
    vf = jnp.asarray([0, 37], jnp.int32)
    want = highest(attention_reference, qf, qf, qf, causal=True, valid_from=vf)
    # Rows inside a row's own left padding are unspecified by contract.
    yield (
        "flash causal ragged",
        lambda: flash_attention(
            qf, qf, qf, causal=True, prefer="pallas", valid_from=vf
        )[:, :, 37:],
        lambda: want()[:, :, 37:],
        KERNEL_TOL,
        None,
    )
    x = jax.random.normal(jax.random.fold_in(key, 10), (3, 64 * 128))
    yield (
        "quantize values (int8 steps)",
        lambda: quantize(x).values,
        lambda: quantize_reference(x).values,
        1.0,  # a rounding tie may land one int8 step apart
        None,
    )
    yield (
        "quantize scales",
        lambda: quantize(x).scales,
        lambda: quantize_reference(x).scales,
        1e-6,
        None,
    )
    qt = quantize_reference(x)
    yield (
        "dequantize",
        lambda: dequantize(qt),
        lambda: dequantize_reference(qt),
        1e-5,
        None,
    )


def compare(name, got, want, tol) -> str:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"{name}: shape/finite check failed")
    err = float(np.max(np.abs(got - want) / np.maximum(1.0, np.abs(want))))
    if err > tol:
        raise AssertionError(f"{name}: scaled max|err| {err:.3e} > {tol}")
    return f"  {name:<34} pallas  max|err| {err:.2e}  (tol {tol:.0e})"


def _xla_dispatches() -> float:
    return sum(d["xla"] for d in kernel_dispatch_stats().values())


def check_kernels(size, rehearsal: bool) -> list[str]:
    # On the chip auto dispatch must pick the kernel by itself; the CPU
    # rehearsal has to ask for it (auto keeps the interpreter out).
    prefer = "pallas" if rehearsal else None
    lines = []
    for name, got, want, tol, routed in kernel_cases(size, prefer):
        xla0 = _xla_dispatches()
        out = got()
        served_xla = _xla_dispatches() != xla0
        if served_xla != bool(routed):
            raise AssertionError(
                f"{name}: served by {'xla' if served_xla else 'pallas'}, "
                f"the dispatch rule says {routed or 'pallas'}"
            )
        if routed:
            # Oracle against oracle would prove nothing: name the rule.
            lines.append(f"  {name:<34} xla     by rule: {routed}")
        else:
            lines.append(compare(name, out, want(), tol))
    return lines


# -- phase 2: stage tier ------------------------------------------------------


def serve_stages(size, devices) -> list[str]:
    lines = []
    graph = resnet50(num_classes=size["classes"], dtype=jnp.bfloat16)
    shape = (size["batch"], size["image"], size["image"], 3)
    xs = [
        jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
        for i in range(6)
    ]
    variables = jax.jit(graph.init)(jax.random.PRNGKey(100), xs[0])
    plan = partition(graph, RESNET50_3STAGE_CUTS)
    single = jax.jit(graph.apply)
    want = [np.asarray(single(variables, x), np.float32) for x in xs]

    def check(y, ref, what):
        y = np.asarray(y, np.float32)
        if y.shape != ref.shape or not np.isfinite(y).all():
            raise AssertionError(f"stage tier {what}: shape/finite check")
        err = float(np.max(np.abs(y - ref)))
        bound = STAGE_REL_TOL * float(np.max(np.abs(ref)))
        if err > bound:
            raise AssertionError(
                f"stage tier {what}: max|err| {err:.3e} > {bound:.3e}"
            )
        return err

    # Chip-speed failure detection: a killed worker's lease must lapse
    # inside the smoke, not after the default serving TTLs.
    config = ServeConfig(
        fault=FaultConfig(
            lease_ttl_s=1.0, heartbeat_s=0.2, task_deadline_s=20.0,
            watchdog_period_s=0.1, max_retries=4,
        )
    )
    global_metrics().reset()
    with ServingPipeline(plan, variables, devices, config) as s:
        t0 = time.perf_counter()
        s.warmup(xs[0])
        lines.append(
            f"  warmup (compile {len(plan.stages)} stages x "
            f"{len(devices)} devices) {time.perf_counter() - t0:.1f}s"
        )
        sizes = [fn._cache_size() for fn in s.dispatcher._stage_fns]
        errs = [
            check(s.infer(x), ref, f"request {i}")
            for i, (x, ref) in enumerate(zip(xs[:3], want[:3]))
        ]
        served = {
            w.device.id for w in s.workers if w.configured_stages()
        }
        lines.append(
            f"  3 requests ok, max|err| {max(errs):.2e}; stages bound on "
            f"device ids {sorted(served)}"
        )
        if len(served) < min(len(plan.stages), len(devices)):
            raise AssertionError(
                f"stages stacked on devices {sorted(served)} with "
                f"{len(devices)} visible"
            )
        if len(devices) >= 2:
            # Kill a worker that holds a stage, with requests in flight:
            # its stage must re-bind on a survivor (a weight move — the
            # warmup prewarmed every stage on every device).
            victim = next(
                i for i, w in enumerate(s.workers) if w.configured_stages()
            )
            futures = [s.dispatcher.submit(x) for x in xs[3:]]
            s.kill_worker(victim)
            t_kill = time.perf_counter()
            errs = [
                check(f.result(120.0), ref, f"post-kill request {i}")
                for i, (f, ref) in enumerate(zip(futures, want[3:]))
            ]
            lines.append(
                f"  killed worker-{victim} (device "
                f"{s.workers[victim].device.id}, stages "
                f"{s.workers[victim].configured_stages()}) with 3 requests "
                f"in flight; all answered "
                f"{time.perf_counter() - t_kill:.2f}s later, "
                f"max|err| {max(errs):.2e}"
            )
        counters = global_metrics().snapshot()["counters"]
        failed = counters.get("dispatcher.failed", 0)
        if failed:
            raise AssertionError(f"dispatcher.failed == {failed}")
        after = [fn._cache_size() for fn in s.dispatcher._stage_fns]
        if after != sizes:
            raise AssertionError(
                f"stage programs recompiled after warmup: {sizes} -> {after}"
            )
        lines.append(
            f"  dispatcher.completed {counters.get('dispatcher.completed', 0)}"
            f" failed 0 redispatched "
            f"{counters.get('dispatcher.redispatched', 0)}; stage jit cache "
            f"sizes unchanged {after}"
        )
    return lines


# -- phases 3 and 4: request tier --------------------------------------------


def _served_logprobs(lm, variables, prompt, tokens):
    """Teacher-forced logprobs of the served stream through the
    full-sequence forward — the reference route."""
    ids = np.concatenate([prompt, tokens])[None].astype(np.int32)
    logits = logits_full(lm, variables, jnp.asarray(ids))[0]
    rows = logits[len(prompt) - 1: len(prompt) - 1 + len(tokens)]
    return np.asarray(
        chosen_logprob(rows.astype(jnp.float32), jnp.asarray(tokens))
    )


def serve_requests(size, devices, tp: int, rehearsal: bool) -> list[str]:
    lines = []
    lmc = size["lm"]
    lm = transformer_lm(
        lmc["vocab"], lmc["dim"], lmc["depth"], lmc["heads"], lmc["mlp_dim"],
        max_len=lmc["max_len"], dtype=jnp.bfloat16,
    )
    variables = jax.jit(lm.graph.init)(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32)
    )
    rng = np.random.RandomState(1)
    prompts = [
        rng.randint(0, lmc["vocab"], size=n).astype(np.int32)
        for n in size["prompts"]
    ]
    long_prompt = next(p for p in prompts if len(p) > CHUNK)
    # Same pages, new tail: admits as a prefix-cache hit on the pages
    # the first request registered.
    prompts.append(
        np.concatenate([long_prompt[: 2 * PAGE + 5], prompts[0]])
    )
    kw = {}
    if tp > 1:
        kw = dict(
            mesh=Mesh(np.asarray(devices[:tp]), ("tp",)),
            parallel=ParallelConfig(tp=tp),
        )
        if rehearsal:
            # Auto dispatch keeps the interpreter out of CPU serving;
            # the rehearsal forces it so the shard_map route is walked.
            kw["kernel"] = KernelConfig(attn_impl="pallas")
    before = kernel_dispatch_stats()
    with ContinuousBatcher(
        lm, variables, slots=SLOTS, kv_layout="paged", page_size=PAGE,
        prefill_chunk=CHUNK, **kw,
    ) as srv:
        t0 = time.perf_counter()
        first = srv.submit(long_prompt, size["steps"])
        streams = {first: (long_prompt, srv.result(first, timeout=900.0))}
        lines.append(
            f"  first request (prompt {len(long_prompt)} > chunk {CHUNK}, "
            f"compiles included) {time.perf_counter() - t0:.1f}s"
        )
        t0 = time.perf_counter()
        ids = [srv.submit(p, size["steps"]) for p in prompts]
        for rid, p in zip(ids, prompts):
            streams[rid] = (p, srv.result(rid, timeout=900.0))
        lines.append(
            f"  {len(ids)} concurrent requests (prompts "
            f"{[len(p) for p in prompts]}) {time.perf_counter() - t0:.1f}s"
        )
        worst = 0.0
        for rid, (p, toks) in streams.items():
            toks = np.asarray(toks)
            if toks.shape != (size["steps"],) or toks.min() < 0 or (
                toks.max() >= lmc["vocab"]
            ):
                raise AssertionError(f"request {rid}: bad stream {toks}")
            got = np.asarray(srv.logprobs(rid), np.float32)
            want = _served_logprobs(lm, variables, p, toks)
            if not np.isfinite(got).all():
                raise AssertionError(f"request {rid}: non-finite logprobs")
            worst = max(worst, float(np.max(np.abs(got - want))))
        if worst > LOGPROB_TOL:
            raise AssertionError(
                f"logprobs vs logits_full: max|err| {worst:.3e} > "
                f"{LOGPROB_TOL}"
            )
        stats = srv.stats()
        if stats["prefix_hits"] <= 0:
            raise AssertionError("the repeated prefix never hit the cache")
        lines.append(
            f"  {len(streams)} streams ok; logprobs vs logits_full "
            f"max|err| {worst:.2e} (tol {LOGPROB_TOL}); prefix_hits "
            f"{stats['prefix_hits']}, prefill_tokens "
            f"{stats['prefill_tokens']}, ticks {stats['ticks']}"
        )
        if tp > 1:
            lines += _check_tp_placement(srv, devices[:tp])
    srv.close()
    lines += _check_dispatch(before, rehearsal)
    return lines


def _check_tp_placement(srv, devices) -> list[str]:
    """Every chip of the mesh holds a 1/tp shard of the KV pools and of
    the head-split weights — 'everything on chip 0' must not pass."""
    want = {d.id for d in devices}
    pool = jax.tree.leaves(srv._caches)[0]
    holders = {s.device.id for s in pool.addressable_shards}
    shard = pool.addressable_shards[0].data.shape
    if holders != want or shard[1] * len(devices) != pool.shape[1]:
        raise AssertionError(
            f"KV pool shards {shard} on {sorted(holders)}, want a "
            f"1/{len(devices)} head split on {sorted(want)}"
        )
    split = 0
    for leaf in jax.tree.leaves(srv.variables):
        shards = leaf.addressable_shards
        if {s.device.id for s in shards} != want:
            raise AssertionError(f"a weight misses a chip: {leaf.sharding}")
        split += shards[0].data.size * len(devices) == leaf.size
    if not split:
        raise AssertionError("no weight is split across the tp mesh")
    lines = [
        f"  KV pool {pool.shape} -> shard {shard} on device ids "
        f"{sorted(holders)}; {split} weight leaves split {len(devices)}-way"
    ]
    in_use = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "bytes_in_use" in stats:
            in_use.append(stats["bytes_in_use"])
    if in_use:
        if min(in_use) <= 0:
            raise AssertionError(f"a chip holds nothing: {in_use}")
        lines.append(
            "  bytes_in_use per chip "
            + ", ".join(f"{b / 2**20:.0f} MiB" for b in in_use)
        )
    return lines


def _check_dispatch(before, rehearsal: bool) -> list[str]:
    """The rule says: on a TPU, native 128-position pages serve paged
    decode and paged chunk through compiled Pallas. Hold it to that."""
    now = kernel_dispatch_stats()
    lines = []
    for op in ("paged_decode", "paged_chunk"):
        d, b = now.get(op), before.get(op, {"pallas": 0.0, "xla": 0.0})
        if d is None:
            raise AssertionError(f"{op} was never dispatched")
        pallas, xla = d["pallas"] - b["pallas"], d["xla"] - b["xla"]
        lines.append(f"  dispatch {op}: pallas {pallas:.0f} xla {xla:.0f}")
        if not rehearsal and (xla or not pallas):
            raise AssertionError(
                f"{op} did not serve through the Pallas kernel: {d}"
            )
    return lines


# -- driver -------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="toy-size walk through the phases on the CPU backend; "
        "prints no result line",
    )
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if args.rehearse_cpu:
        if dev.platform != "cpu":
            print("--rehearse-cpu needs JAX_PLATFORMS=cpu", file=sys.stderr)
            return 2
        size, cache_dir = TINY, "off (rehearsal)"
    elif dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU — JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); this script only reports from a chip",
            file=sys.stderr,
        )
        return 2
    else:
        size, cache_dir = FULL, ensure_compile_cache()
    print(
        f"device {dev.platform} / {dev.device_kind} x {len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}"
        + ("; REHEARSAL (cpu, toy sizes)" if args.rehearse_cpu else ""),
        flush=True,
    )
    compiles, report = Compiles(), []
    with phase("kernels", compiles, report):
        print(
            "\n".join(check_kernels(size, args.rehearse_cpu)), flush=True
        )
    with phase("stage tier", compiles, report):
        print("\n".join(serve_stages(size, devices)), flush=True)
    with phase("request tier", compiles, report):
        print(
            "\n".join(serve_requests(size, devices, 1, args.rehearse_cpu)),
            flush=True,
        )
    tp = max(t for t in (1, 2, 4) if t <= len(devices))
    if tp > 1:
        with phase(f"request tier tp={tp}", compiles, report):
            print(
                "\n".join(
                    serve_requests(size, devices, tp, args.rehearse_cpu)
                ),
                flush=True,
            )
    print("phases:")
    print("\n".join(report))
    print("kernel dispatch table (trace-time resolutions, pallas/xla):")
    for op, d in sorted(kernel_dispatch_stats().items()):
        derived = "".join(
            f"  {k} {v:.0f}" for k, v in sorted(d.items())
            if k not in ("pallas", "xla", "last")
        )
        print(
            f"  {op:<14} pallas {d['pallas']:.0f}  xla {d['xla']:.0f}"
            + derived
        )
    if args.rehearse_cpu:
        print("rehearsal complete: no device was measured, no result line")
        return 0
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
