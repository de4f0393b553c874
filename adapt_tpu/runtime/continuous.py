"""Continuous batching: requests join and leave a RUNNING decode batch.

``generate()`` serves one static batch: every row starts together and the
program runs to the longest request's end — a short request pays for the
longest, and a request arriving mid-flight waits for the whole batch.
This module is the serving loop modern LM servers run instead: a fixed
number of SLOTS (the lockstep decode width) decode as ONE compiled step
per tick (static shapes — XLA-friendly) over a shared PAGED KV pool,
and each slot independently admits a new request the moment its
current one finishes. No reference analog (the reference is CNN-only
request/response, SURVEY.md §2.2); this is the "request-level
concurrency" column (§2.2) applied to autoregressive serving,
TPU-first:

- **One compiled decode step for any slot mix.** Per-slot sequence
  lengths ride as a (B,) position vector; `decode_step_paged`'s per-row
  append (``ops/paged_attention.append_kv_paged``) puts each slot's
  token into its own page at its own offset, and the kernel's per-slot
  live window keeps every slot's attention independent. Inactive slots
  sit at a negative position sentinel: their writes route to the trash
  page and they compute garbage that nothing reads — branchless, so
  the step never recompiles as slots churn.
- **Chunked ticks.** One tick runs a fixed CHUNK of decode steps as a
  single compiled ``lax.scan`` with ONE host sync at the end — the
  per-token host round trip that makes naive continuous batching lose
  to ``generate()``'s fused scan is paid once per chunk instead.
  Requests finishing mid-chunk compute a garbage tail that the host
  truncates (bounded waste: < chunk steps per retirement); admission
  and EOS detection happen at chunk boundaries (``chunk`` is the
  latency/efficiency knob, and ``chunk=1`` is the fully reactive mode).
- **Bucketed prefill.** Prompts compile per bucket length (powers of two
  by default), not per prompt length: a new request pads to the smallest
  bucket, runs the full causal prefill (the measured flash dispatch),
  and its K/V lands in the request's pages as one compiled scatter per
  pool (``runtime/paged.insert_prefill_pages``).
- **Exact per-request streams.** Sampling uses each request's OWN key
  schedule (the same split/fold pattern as ``generate``), so a request
  served through the batcher emits token-for-token what ``generate``
  would have emitted for it alone — tested with staggered arrivals and
  mixed greedy/sampled traffic. Slot scheduling is invisible in outputs.
  A greedy row beside sampled ones keeps its arg-max bit for bit
  (``jnp.where(greedy, ...)``); ``stats()["ticks_sampled"]`` of
  ``["ticks"]`` says how many ticks had a row that read the draw.

``kv_cache_dtype="int8"`` stores KV caches quantized (absmax per K/V
vector, the same scheme as ``generate``): ~2-4x the resident context
per page and proportionally less per-step cache traffic. Quantization
is a property of the POOL's format (``runtime/paged.alloc_kv_pools``),
not a mode of one path — it composes with every decode family: a pool
becomes an ``(int8 values, f32 K scales, f32 V scales)`` pytree triple
(a scale plane is one f32 per vector, page-addressed by the same
table, so prefix-shared pages carry their scales), speculative verify
quantizes its multi-token appends through the same scheme, and under
tensor parallelism every plane head-shards together. Greedy quantized streams
are bit-identical to the same-quantized solo
``generate(kv_cache_dtype="int8")`` on the whole-prompt prefill paths;
prefix-cache suffix passes and chunked prefill attend the
already-quantized earlier window (there is no native copy), so those
admissions carry the cache's quantization error into the first
token's logits — the same class of fine print as chunk fp contraction
widths, one quantization step coarser (tested via top-1-agreement
bounds vs fp32 rather than exact equality).

**The KV cache is a shared page POOL** (``runtime/paged``: the pool's
format — ONE plane a decoder block, a position's K and V side by side
on the lanes of one row — and the allocator; ``ops/paged_attention``:
how a plane is appended to and read, and the scalar-prefetch kernels).
Each request
reserves just the pages its window needs and frees them on retirement,
so HBM scales with resident tokens instead of ``slots x max_len`` —
size it with ``pool_pages`` (default: worst case, i.e. no saving until
you lower it). Admission is FIFO all-or-nothing: a request that
doesn't fit waits (head-of-line); one that can NEVER fit raises at
``submit``. There is no other layout: per-slot dense strips left in
PR 29 (``kv_layout=`` is accepted only as ``"paged"``); the one dense
family left is the speculative DRAFT model's strips, below.

``prefill_chunk`` (a multiple of ``page_size``) turns a
long prompt's admission into CHUNKED PREFILL: one page-aligned chunk
pass per tick, interleaved with the decode batch, so a long admission
never stalls the requests already decoding (the Sarathi-style
latency/throughput knob; ``None`` = whole-prompt prefill, the default).
Numerical contract: greedy streams match solo ``generate()`` (tested);
chunk boundaries change fp contraction widths, so cached K/V can
differ at ulp scale from the one-pass values — a high-temperature
categorical draw at an exact tie may pick differently (equivalence is
distributional there, not bitwise).

Slots get PREFIX CACHING for free: a full page of prompt K/V is
content-addressed (hash of the whole token prefix it depends on) and
refcounted, so a request whose prompt starts with an already-resident
prefix — the shared-system-prompt workload — shares those pages (live
or retired) and prefills only its suffix in one ``verify_chunk`` pass.
Retired pages linger as an evict-under-pressure LRU. Hit/miss/cached
counts surface in :meth:`stats`; outputs stay token-identical to solo
``generate()`` on every tested workload, including two live requests
sharing pages and sampled streams — with the same fine print as
chunked prefill: the suffix pass's contraction width differs from the
one-pass prefill's, so a categorical draw at an exact fp tie could in
principle diverge (greedy cannot, short of an exact argmax tie).

``top_k`` is per-REQUEST despite being shape-like (see
``_truncate_rows``); ticks with no truncating request skip the filter
entirely via a static flag.

**Device-resident hot path.** All per-slot sampling state (last token,
cache position, temperature, top_k, top_p, key schedule, key cursor)
lives in pre-allocated batched DEVICE arrays (``_dstate``), not host
scalars: admitting a slot stages its whole row with one donated jitted
``dynamic_update_slice`` setter (O(1) fused transfers — packed int/float
scalar vectors plus the key block — instead of one ``jnp.asarray`` per
field), retiring one clears the row the same way, and the steady-state
decode tick stages NOTHING — ``_step_chunk`` reads and re-writes the
donated state in place, gathering each step's per-slot keys from the
resident schedules. Every host->device staging transfer in this module
goes through :meth:`_h2d`, so ``stats()["h2d_transfers"]`` measures the
host overhead directly (``benchmarks/micro/tick_host_overhead.py``
asserts the steady-state tick stays at zero).

**Request timelines** (``docs/OBSERVABILITY.md``): every request's
lifecycle (submitted -> admitted -> prefill -> first token -> each
decode commit -> finished/cancelled) is stamped on the perf-counter
clock and fed to the process registry as the serving SLO histograms —
``continuous.queue_wait_s``, ``continuous.ttft_s``,
``continuous.itl_s`` (inter-token latency, flushed once per tick) and
``continuous.request_latency_s``. One branch (``obs_timeline``)
disables the histograms; flight-recorder lifecycle events
(admit/finish/cancel — per-request, not per-token) are always on, and
spans (prefill, decode chunk) additionally require the global tracer.

**SLO tracking + goodput** (``docs/OBSERVABILITY.md`` "Workload
telemetry"): ``submit(slo=config.SLOSpec(ttft_budget_s=...,
itl_budget_s=..., tenant=...))`` attaches a latency budget that the
SAME lifecycle stamps evaluate — TTFT once at the first emitted token,
ITL at each later commit. Results feed ``slo.ttft_attainment`` /
``slo.itl_attainment`` gauges, ``slo.{ttft,itl}_{met,missed}_total``
counters, per-tenant ``slo.{met,missed}_total.<tenant>`` request
verdicts at finish, one ``slo_missed`` flight event at a request's
FIRST violation, and ``continuous.goodput_tokens_s`` — tokens/s from
requests still inside budget over a rolling window
(``goodput_window_s``), next to cumulative
``continuous.{tokens,good_tokens}_total`` counters for windowed
phase deltas. All of it is host arithmetic on stamps already taken,
flushed to the registry once per tick: zero extra h2d transfers, zero
compiled-program impact, and ``obs_timeline=False`` one-branch-disables
it with the rest of the timeline. ``benchmarks/load`` drives this
instrumentation into goodput-vs-offered-load curves.

**Batched speculative decoding** (``draft_lm=``/``draft_variables=`` +
``config.SpeculativeConfig``): every serving tick becomes a fixed-shape
``draft_k + 1``-step draft scan over ALL slots
(``models/speculative.draft_chunk`` — the same jit the single-request
loop runs, batch-shaped) followed by ONE fused verify pass
(``_spec_verify``), then per-slot longest-agreeing-prefix acceptance.
Rows DESYNCHRONIZE — slot A commits 5 tokens this tick while slot B
commits 1 — but positions, page tables and cache write masks are all
per-slot device vectors, so the two compiled programs never change
shape and nothing recompiles (guarded by a compile-count test).
Rejected speculation needs no rollback on either model's cache: each
carries ``draft_k`` SLACK positions (the draft's strips grow by
``draft_k``, admissions reserve the slack pages), so overshoot writes
land past every slot's accepted position and are overwritten by later
rounds — the same trash-page/masked-write discipline as the rest of
this module. Per-row greedy LOSSLESSNESS is the tested contract: each
request's stream equals its solo ``generate()`` token-for-token
whatever the draft proposes and however acceptance staggers across
slots. ``temperature > 0`` requests are served via SPECULATIVE
SAMPLING (the same verify pass, static ``sample`` flag): each
proposal is accepted with the target's own probability of that token
under the request's temperature/top-k/top-p processing and a
rejection resamples from the residual distribution — provably the
target's sampling distribution per position (lossless in
DISTRIBUTION; greedy rows in the same batch still commit their exact
argmax stream). The draft model keeps its own dense slot strips
(it exists to be small — paging its cache buys capacity that is not
the bottleneck) and is fully prefilled per admission; EOS/stop/cancel
latch at acceptance boundaries through the ordinary commit path. The
steady-state spec tick stages ZERO host arrays and performs ONE fused
device->host fetch (tokens + logprobs + accepted counts) — the PR-1
fused-staging contract, extended.

Request lifecycle niceties: ``submit(stop=[[...], ...])`` ends a stream
at the first emitted occurrence of any stop token-sequence (host-side
tail check — the emitted prefix still equals solo ``generate()``), and
:meth:`cancel` drops a queued request or retires a mid-flight one at
the next commit boundary with its partial stream as the result (slot
and pages free immediately after).

**Tensor-parallel serving** (``mesh=`` + ``config.ParallelConfig{tp}``;
``docs/SERVING.md`` "Tensor-parallel serving"): the whole request tier
runs SPMD over a mesh's ``tp`` axis. Weights place by the megatron-style
rules in ``parallel/sharding.lm_tp_rules`` (qkv/mlp-in column-split,
attn-out/mlp-out row-split — exactly ONE psum pair per block per token,
so the decode tick's latency does not drown in ICI hops), and the KV
pools shard on their HEAD axis (GQA-aware: kv_heads % tp == 0), so
per-device KV bytes are the
logical bytes / tp: models whose weights + KV exceed one chip's HBM
serve, and models that fit stop leaving N-1 chips idle. Everything the
host touches stays REPLICATED — page tables, the device-resident
sampling state, staged admission vectors — so admission, commit, cancel,
prefix caching and the pager are sharding-blind, and all the hot-path
invariants survive unchanged and re-pinned by tests: zero host arrays
per steady-state tick, the two-program compile footprint, buffer
donation, and per-row greedy losslessness vs single-device
``generate()``, speculative mode included (the draft
model deliberately replicates — it is small by construction and a
replicated draft scan is collective-free). ``stats()`` reports
``cache_bytes`` (logical) next to ``cache_bytes_per_device``; the
``memory.*_per_device`` gauges mirror it at scrape.

**Elastic mesh recovery** (``health=`` +
``control.registry.DeviceHealthMonitor``, knobs in
``config.RecoveryConfig``; ``docs/SERVING.md`` "Elastic recovery"):
losing one chip of the tp mesh no longer kills every in-flight
request. The monitor feeds the TTL-lease membership machinery — a
simulated kill (or a real lease expiry) arrives as a ``leave`` event,
and the next tick re-shards: the mesh rebuilds from the surviving
devices (tp=4 -> tp=2; largest divisor the survivors can host),
weights re-place by the megatron rules, the program families re-lower
with explicit shardings (sentinel warmups re-armed — ONE expected
variant per family, no phantom alarms), and live KV/sampling state
migrates via an explicit redistribution plan
(``parallel.sharding.KVReshardPlan``: per-shard device-to-device
moves for surviving shards, host staging only for the lost shard's
heads), so migrated greedy requests finish **bit-identical** to an
uninterrupted run. Requests that do not migrate (mid-chunked-prefill,
or ``policy="replay"``) REPLAY from the journal (``journal=`` — a
``control.journal.DispatcherJournal`` that records every submit's
payload + sampling knobs and every finish's done mark), re-entering
through the prefix cache when the prompt pages are still
resident — identical tokens, paid by a suffix prefill instead of
state migration. Lifecycle: ``device_lost`` / ``mesh_reshard`` /
``kv_migrated`` / ``replayed_from_journal`` flight events,
``recovery.wall_s`` histogram and ``recovery.{migrated,replayed,
dropped}_total`` counters.

**Traffic control** (``scheduler=`` + ``config.SchedulerConfig``;
``runtime/scheduler``; ``docs/SERVING.md`` "Traffic control"): the
submit queue is a bounded ``AdmissionQueue`` — per-tenant quotas
(weights + burst caps), deficit-round-robin weighted fair queueing
inside strict priority classes (``SLOSpec.priority``), and explicit
synchronous rejection (``QueueFullError`` + ``request_rejected``
flight event) at the global or per-tenant bound, so a full slot map
no longer queues unboundedly and ``result()`` never wedges on a
request that was never accepted. A high-priority request that burns
its TTFT headroom waiting preempts the lowest-priority decode slot
through the recovery REPLAY path (prompt pages into the prefix LRU,
journal-requeue, ``stream_skip``-suppressed re-delivery — exactly-once
across preemption, SLO verdicts carried). A per-tick
``DegradationController`` sheds load before preemption has to:
shrink ``draft_k``, raise the disaggregated busy threshold, evict
cold cached pages, reject best-effort admits. Without a
``SchedulerConfig`` the queue degrades to the bounded FIFO.

Not in scope (v1): pipeline-parallel slots (compose with the pipelined
decoders for models bigger than a TP group).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import weakref
from functools import partial
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from adapt_tpu.config import (
    CacheTierConfig,
    CapacityConfig,
    KernelConfig,
    ObservabilityConfig,
    ParallelConfig,
    PrefillConfig,
    RecoveryConfig,
    SchedulerConfig,
    SLOSpec,
    SpeculativeConfig,
)
from adapt_tpu.control.registry import weak_watch
from adapt_tpu.models.speculative import accept_speculation, draft_chunk
from adapt_tpu.models.ssm import zero_state
from adapt_tpu.models.transformer_lm import (
    TransformerLM,
    chosen_logprob,
    embed_tables_for,
    lane_tiled,
    nucleus_filter,
    validate_tp,
)
from adapt_tpu.ops.decode_attention import check_head_parity
from adapt_tpu.ops.paged_attention import (
    append_kv_paged,
    fuse_kv,
    pool_planes,
    pool_values,
)
from adapt_tpu.ops.quantize import dequantize_params, quantize_params
from adapt_tpu.parallel.sharding import (
    fetch_head_shards,
    kv_head_sharding,
    lm_tp_rules,
    plan_kv_handoff,
    plan_kv_reshard,
    tree_shardings,
)
from adapt_tpu.parallel.sp_prefill import SPPrefiller, build_sp_mesh
from adapt_tpu.runtime.capacity import CapacityModel
from adapt_tpu.runtime.paged import (
    CACHE_PROPERTIES,
    HostKVTier,
    Pager,
    alloc_kv_pools,
    cache_layout,
    group_pool_pages,
    insert_prefill_pages,
    pool_geometry,
)
from adapt_tpu.runtime.scheduler import (
    AdmissionQueue,
    DegradationController,
    QueueFullError,
    request_priority,
    request_tenant,
)
from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import (
    aggregate_size_fn,
    device_local_nbytes,
    global_compile_sentinel,
    global_engine_obs,
    program_cost_analysis,
    register_memory_source,
    register_roofline_source,
    unregister_memory_source,
    unregister_roofline_source,
)
from adapt_tpu.utils.tracing import global_flight_recorder, global_tracer

log = get_logger("continuous")


class DeviceLostError(RuntimeError):
    """A device of the batcher's mesh was reported dead and automatic
    resharding is off (``config.RecoveryConfig.auto_reshard=False``), or
    recovery itself is impossible (every device lost, or the surviving
    pool cannot support ``min_tp``). Call
    :meth:`ContinuousBatcher.recover` — or re-raise to the serving
    layer."""

#: Live batchers (weak — telemetry must never pin a retired batcher's
#: device arrays). The ONE "continuous.prefill" sentinel watch sums the
#: per-instance prefill jit families over this set
#: (profiling.aggregate_size_fn), so a second batcher's construction
#: aggregates rather than silently replacing the first one's watch,
#: and closing the last batcher prunes the watch.
_LIVE_BATCHERS: "weakref.WeakSet[ContinuousBatcher]" = weakref.WeakSet()


def _prefill_family_size(bat: "ContinuousBatcher") -> int:
    # list(): a ticking thread may be inserting a new bucket's jit
    # closure while an exporter scrape sums.
    return sum(f._cache_size() for f in list(bat._prefill_cache.values()))


@dataclasses.dataclass
class _Request:
    req_id: int
    prompt: np.ndarray  # (s0,) int32
    steps: int
    temperature: float
    top_k: int  # == vocab -> no truncation
    top_p: float  # == 1.0 -> no nucleus truncation
    eos_id: int | None
    folded_keys: np.ndarray  # (steps, 2) uint32 — pre-folded per-step keys
    #: Host-side stop sequences: the stream ends (inclusive) at the
    #: first emitted occurrence of any of these token tuples.
    stop: tuple[tuple[int, ...], ...] = ()
    #: Optional streaming callback (req_id, token, index) per commit.
    on_token: Callable[[int, int, int], None] | None = None
    #: Tokens already DELIVERED before an elastic-recovery replay
    #: re-queued this request: the re-run regenerates indices
    #: 0..skip-1 identically (greedy, or the journaled key schedule),
    #: so ``on_token`` suppresses them — the client's transcript stays
    #: exactly-once — and the TTFT stamp (already taken at the original
    #: first token) is not re-observed.
    stream_skip: int = 0
    #: Snapshot of the tokens (and logprobs) already delivered when an
    #: elastic-recovery replay re-queued this request: a cancel landing
    #: before the re-run catches up (queued, or live mid-regeneration)
    #: resolves result() with these — result() must never contradict
    #: the stream the client already received.
    delivered_tokens: np.ndarray | None = None
    delivered_lps: np.ndarray | None = None
    #: Perf-clock stamp of the last token the client RECEIVED before a
    #: replay re-queued this request: the first post-regeneration
    #: token's ITL gap measures from here, so the kill-to-recovery
    #: stall the client actually experienced is judged against the
    #: budget exactly like a migrated request's is.
    t_last_delivered: float = 0.0
    #: Perf-clock stamp of the recovery re-queue (0.0 = first life):
    #: the re-admission's queue-wait sample measures from here — from
    #: t_submit it would span the whole first life plus the recovery,
    #: which is not a queue wait.
    t_requeued: float = 0.0
    #: Lifecycle anchor (perf-counter clock, stamped by submit):
    #: queue-wait, TTFT and request latency all measure from here.
    t_submit: float = 0.0
    #: Optional latency budget (``config.SLOSpec``): TTFT judged at the
    #: first emitted token, ITL per commit; evaluation rides the
    #: obs_timeline gate.
    slo: SLOSpec | None = None
    #: Set at the request's FIRST budget violation and carried across
    #: recovery replays: the client experienced the miss, so a second
    #: life must not re-enter goodput, re-fire ``slo_missed``, or
    #: finish with a ``met`` tenant verdict.
    slo_violated: bool = False
    #: ``submit_fanout`` group id (-1 = ordinary request). Consumed at
    #: admission (cleared there, so a pool-pressure re-queue or a
    #: recovery replay never double-decrements the group).
    fanout_group: int = -1
    #: Submit-time TTFT forecast (``runtime/capacity``; 0.0 = no
    #: capacity model, or nothing learned yet). Compared against the
    #: realized TTFT at first-token commit — the forecaster's
    #: self-calibration loop.
    ttft_forecast_s: float = 0.0


@dataclasses.dataclass
class _FanoutGroup:
    """One :meth:`ContinuousBatcher.submit_fanout` group's shared
    bookkeeping. ``remaining`` counts siblings not yet admitted (or
    cancelled); the group dies when it reaches zero. For GREEDY groups
    the first admitted sibling also records its last prompt page
    (``page`` — rc-claimed via ``Pager.retain`` so it outlives that
    sibling's retirement) and its first token/logprob: later siblings
    whose prefix probe matches every earlier page take the
    copy-on-write fork — one device page copy plus the cached first
    commit — instead of recomputing the suffix forward. Sampled
    groups leave ``page`` unset: each sibling needs fresh last-position
    logits to draw its own first token from, so the suffix pass runs
    anyway (the full prefix pages still share through the probe)."""

    remaining: int
    greedy: bool
    page: int | None = None
    first: int | None = None
    first_lp: float | None = None


@dataclasses.dataclass
class _Slot:
    idx: int = -1  # position in the slot list (page-table row)
    req: _Request | None = None
    #: chunked prefill progress: next position to prefill, or -1 when
    #: not mid-prefill (the slot decodes). A slot with pf_done >= 0
    #: holds its request but sits out the decode batch.
    pf_done: int = -1
    s0: int = 0  # prompt length
    #: cache position where the next tick's CONSUMED token (last_token,
    #: stream index emitted-1) writes its K/V: s0 + emitted - 1.
    pos: int = 0
    emitted: int = 0
    last_token: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    lps: list = dataclasses.field(default_factory=list)
    #: Timeline stamps (perf-counter): first emitted token (0.0 = none
    #: yet) and last emitted token — feed the TTFT and
    #: inter-token-latency histograms (queue wait measures from
    #: ``req.t_submit`` at admission). ``obs_count`` is the token count
    #: as of the last stamp: an ITL sample is recorded only when the
    #: previous commit also stamped, so toggling ``obs_timeline`` off
    #: and back on mid-request cannot inject one giant gap sample.
    t_first: float = 0.0
    t_last: float = 0.0
    obs_count: int = 0
    #: SLO state: True until the request's first budget violation —
    #: only its tokens count toward goodput (requests with no SLOSpec
    #: have nothing to violate and stay True).
    slo_ok: bool = True


class _AsyncFetch:
    """One tick's device→host result fetch with a ``.ready()`` /
    ``.commit()`` split — the SHARED helper behind both the plain-tick
    fetch and ``_spec_verify``'s ``(toks, lps, acc)`` fetch.

    Construction starts the D2H copy immediately
    (``copy_to_host_async`` on every leaf), so the transfer overlaps
    whatever host work runs between dispatch and commit: the WHOLE
    next tick's scheduler pass and dispatch, or before a ``drain()``
    the tracer/phase bookkeeping alone.
    ``commit()`` blocks until the copy lands and returns host numpy
    arrays (cached — commit is idempotent); ``wait_s`` records how
    long it actually blocked, which is the non-overlapped device wall
    the ``runtime.overlap_ratio`` gauge is computed from."""

    __slots__ = ("_arrays", "_host", "wait_s")

    def __init__(self, arrays: tuple):
        self._arrays = arrays
        self._host: tuple | None = None
        self.wait_s = 0.0
        for a in arrays:
            # Plain numpy (already host) has no async-copy hook.
            start = getattr(a, "copy_to_host_async", None)
            if start is not None:
                start()

    def ready(self) -> bool:
        """True when every leaf's device computation + D2H copy has
        completed — ``commit()`` would return without blocking."""
        if self._host is not None:
            return True
        return all(
            bool(getattr(a, "is_ready", lambda: True)())
            for a in self._arrays
        )

    def commit(self) -> tuple:
        """Block until the results land; return host numpy arrays."""
        if self._host is None:
            t0 = time.perf_counter()
            self._host = tuple(
                np.asarray(a) for a in jax.device_get(self._arrays)
            )
            self.wait_s = time.perf_counter() - t0
            self._arrays = ()  # drop the device references
        return self._host


# Rows several features share: one that needs every property, one that
# SHARES a prompt page between requests, one that MOVES pages of K and V.
_ALL = dict.fromkeys(CACHE_PROPERTIES, "")
_SHARES = {"one_group": "", "pages_only": "", "one_plane": ""}
_MOVES = {"pages_only": "", "per_head_pages": "", "one_plane": ""}
#: What each feature needs of the model's cache
#: (``paged.CacheLayout.lacks``): a property it names here it cannot do
#: without, and the value is what a refusal for THAT property says
#: after the feature's name. A tp mesh over recurrent state is the
#: model's to refuse (``validate_tp``).
_CACHE_NEEDS: dict[str, dict[str, str]] = {
    "a draft model": {
        "one_group": " (speculative decoding)",
        "pages_only": " (speculative decoding: a rejected token cannot "
                      "be un-stepped)",
        "per_head_pages": " (speculative decoding: verify_chunk_paged "
                          "reads per-head pages)",
        "one_plane": " (speculative decoding: no verify pass scores and "
                     "selects)",
    },
    "a tp mesh": {"one_group": "", "per_head_pages": "", "one_plane": ""},
    "a host cache tier": _ALL,
    "sequence-parallel prefill": _ALL,
    "cache-aware admission (the radix prefix cache)": _SHARES,
    "elastic recovery (health=)": _MOVES,
    "a quantized KV pool": {
        "pages_only": " beside it (untested)", "per_head_pages": "",
        "one_plane": "",
    },
    "a handoff of prefilled pages": _ALL,
    "the radix prefix cache": _SHARES,
    "copy-on-write fan-out": _SHARES,
}


def _unmet(layout, *features: str) -> tuple[str, str, str] | None:
    """The first thing a cache of ``layout`` cannot do that one of
    ``features`` (rows of ``_CACHE_NEEDS``) needs, property by property
    and then in the order given: the feature as a refusal names it and
    ``CacheLayout.lacks``'s pair. A cache that lacks SEVERAL properties
    the feature needs (a request that owns recurrent states AND latent
    pages) is refused for each: the pairs before the last are chained
    into the second entry. None: all run."""
    for prop in CACHE_PROPERTIES:
        if layout.lacks(prop) is None:
            continue
        for feature in features:
            needs = _CACHE_NEEDS[feature]
            if prop not in needs:
                continue
            *before, (what, detail) = [
                why for p in CACHE_PROPERTIES
                if p in needs and (why := layout.lacks(p))
            ]
            lead = "".join(f"{w} ({d}) and " for w, d in before)
            return (feature + needs[prop], lead + what, detail)
    return None


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-uncommitted decode tick: what a ``tick()``
    leaves for the next one (or a ``drain()``) to commit.

    ``reqs``/``lives`` capture per-slot BINDING IDENTITY at dispatch:
    commit applies a slot's results only when the slot still holds the
    same request object AND the same life (``slot.tokens`` list
    identity — a preemption can release and re-admit the SAME request
    object within the one-tick lag, and its fresh life must not
    receive the old life's tick). Rows whose binding changed are
    skipped: the tick decoded a bounded garbage tail for them (the
    same < chunk-steps-per-retirement waste discipline mid-chunk
    finishes already have)."""

    fetch: _AsyncFetch
    #: Per-slot request captured at dispatch (None = not in the decode
    #: batch that tick) + the life marker (slot.tokens list identity).
    reqs: list
    lives: list
    n_active: int = 0
    #: Speculative-round metadata (None = lockstep chunk tick):
    #: (draft_k_eff, tree_width, active slot indices) captured at
    #: dispatch — set_draft_k may change the live values mid-lag.
    spec: tuple | None = None
    #: Tracer span start for decode_chunk/verify (0.0 = untraced at
    #: dispatch) and EngineObs stamp for the decode/verify phase
    #: (0.0 = obs_engine off at dispatch) — commit closes them only
    #: when both ends were armed (the mid-flight-toggle guard).
    t_span: float = 0.0
    t_eo: float = 0.0
    #: perf_counter at dispatch start / dispatch end. commit reads
    #: them for runtime.overlap_ratio (1 - blocked-fetch-wait over the
    #: dispatch-to-commit wall) and engine.phase.commit_lag_s.
    t0: float = 0.0
    t_dispatched: float = 0.0
    #: Span tags captured at dispatch.
    req_ids: tuple = ()


class ContinuousBatcher:
    """Continuous batching over one LM and one paged KV pool — on one
    device, or tensor-parallel over a mesh's ``tp`` axis (``mesh=`` +
    ``config.ParallelConfig``; weights and KV head-sharded, control
    plane replicated — see the module docstring).

    ``slots`` is the lockstep decode width (static); ``top_k`` here is
    only the DEFAULT for requests that do not pass their own (per-row
    truncation: ``_truncate_rows``). Drive it with :meth:`submit` +
    :meth:`run` (or :meth:`tick` for manual control).
    """

    #: Max UNCLAIMED logprob streams retained (oldest evicted past it).
    _LPS_CAP = 4096

    def __init__(
        self,
        lm: TransformerLM,
        variables,
        slots: int = 8,
        top_k: int | None = None,
        prompt_buckets: tuple[int, ...] | None = None,
        chunk: int = 8,
        kv_cache_dtype: str = "native",
        kv_layout: str = "paged",
        page_size: int = 128,
        pool_pages: int | None = None,
        prefill_chunk: int | None = None,
        draft_lm: TransformerLM | None = None,
        draft_variables=None,
        speculative: SpeculativeConfig | None = None,
        mesh: Mesh | None = None,
        parallel: ParallelConfig | None = None,
        recovery: RecoveryConfig | None = None,
        health=None,
        journal=None,
        scheduler: SchedulerConfig | None = None,
        kernel: KernelConfig | None = None,
        cache_tier: CacheTierConfig | None = None,
        prefill: PrefillConfig | None = None,
        sp_mesh: Mesh | None = None,
        observability: ObservabilityConfig | None = None,
        capacity: CapacityConfig | None = None,
    ):
        t_construct = time.perf_counter()
        self.lm = lm
        # -- tensor parallelism (mesh-native serving) ----------------------
        # ``mesh`` + ``config.ParallelConfig{tp}`` shard the serving tier
        # over the mesh's tp axis: variables place by the megatron rules
        # (parallel.sharding.lm_tp_rules — one psum pair per block), KV
        # caches/pools shard on their HEAD axis (per-device KV bytes ==
        # logical / tp), and every jitted program compiles under GSPMD
        # with explicit cache shardings, so the collectives are inserted
        # by the compiler — the host-side admission/commit logic below
        # is sharding-blind (page tables and _dstate stay replicated).
        if parallel is not None and parallel.tp > 1 and mesh is None:
            raise ValueError(
                f"ParallelConfig(tp={parallel.tp}) requires a mesh"
            )
        self._mesh = mesh
        self._axis = (parallel or ParallelConfig()).axis
        if mesh is not None:
            axis = self._axis
            if axis not in mesh.shape:
                raise ValueError(
                    f"mesh has no {axis!r} axis (axes: "
                    f"{tuple(mesh.axis_names)})"
                )
            tp = int(mesh.shape[axis])
            if parallel is not None and parallel.tp != tp:
                raise ValueError(
                    f"ParallelConfig.tp={parallel.tp} != mesh {axis!r} "
                    f"size {tp}"
                )
            validate_tp(lm, tp)
            self._tp = tp
            if tp == 1:
                # Degenerate mesh: a size-1 tp axis partitions nothing,
                # and 1-device meshes are where jax's sharding
                # normalization is quirkiest — XLA hands back
                # equivalent-but-UNEQUAL NamedShardings (P() vs
                # P(None, 'tp', None)) for physically identical
                # outputs, and every flip is a phantom jit variant in
                # the next consumer. Run the ordinary single-device
                # path instead: same program, no GSPMD, exact
                # compile-count parity with the no-mesh batcher (the
                # tp=1 column of benchmarks/micro/tp_decode.py is this
                # path). The local too: every placement site below
                # branches on it. The ONE thing kept from the mesh is
                # its device: everything commits there via
                # SingleDeviceSharding (the tp=1 REMNANT discipline
                # recover() installs), so ``health=`` can track it —
                # a loss raises DeviceLostError instead of silently
                # dispatching onto the dead chip forever.
                dev0 = list(mesh.devices.flat)[0]
                mesh = None
                self._mesh = None
                self._repl = SingleDeviceSharding(dev0)
                self._kv_sharding = None
                variables = jax.device_put(variables, self._repl)
            else:
                #: Replicated placement for everything the host stages
                #: (prompt ids, fused admission vectors, page tables,
                #: _dstate) — admission/commit logic is sharding-blind.
                self._repl = NamedSharding(mesh, P())
                #: KV pools shard on the HEAD axis (dim 1 of the
                #: (pages, kvh, P, 2 * hd) planes — and of the scale
                #: planes: every member of a quantized (values,
                #: k_scales, v_scales) triple pins to the SAME spec,
                #: parallel.sharding's one definition).
                self._kv_sharding = kv_head_sharding(mesh, axis)
                variables = jax.device_put(
                    variables,
                    tree_shardings(
                        variables, mesh,
                        rules=partial(lm_tp_rules, axis=axis),
                    ),
                )
        else:
            self._tp = 1
            self._repl = None
            self._kv_sharding = None
        self.variables = variables
        self.slots = [_Slot(idx=i) for i in range(slots)]
        self.top_k = top_k
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.chunk = chunk
        if speculative is not None and draft_lm is None:
            raise ValueError(
                "speculative config requires draft_lm/draft_variables"
            )
        if draft_lm is not None:
            if draft_variables is None:
                raise ValueError("draft_lm requires draft_variables")
            if draft_lm.vocab != lm.vocab:
                raise ValueError(
                    f"draft vocab {draft_lm.vocab} != target vocab "
                    f"{lm.vocab}"
                )
            if draft_lm.max_len < lm.max_len:
                # The draft prefills the same prompt buckets and decodes
                # the same positions as the target; a shorter draft
                # context would silently truncate them.
                raise ValueError(
                    f"draft max_len {draft_lm.max_len} < target max_len "
                    f"{lm.max_len}"
                )
            self._spec = speculative or SpeculativeConfig()
            if self._spec.draft_weight_dtype == "int8":
                # Store the draft's matrix weights blockwise int8
                # (replicated under TP, so this is a direct per-chip
                # HBM cut); the draft programs dequantize at use
                # (draft_chunk / _draft_prefill_fn), so the f32 weights
                # never persist.
                draft_variables = quantize_params(draft_variables)
        else:
            self._spec = None
        self._spec_k = self._spec.draft_k if self._spec else 0
        #: EFFECTIVE proposals per round — the degradation ladder's
        #: first rung shrinks it at runtime (:meth:`set_draft_k`).
        #: Cache geometry, admission slack and the idle sentinel all
        #: size for the CONFIGURED ``draft_k`` (the maximum), so a
        #: shrunk round's writes always land inside reserved space;
        #: only the per-tick draft scan and verify chunk narrow.
        self._spec_k_eff = self._spec_k
        #: draft_k values whose spec-program variants have already
        #: been granted a compile allowance (each distinct k lowers
        #: one fresh draft/verify variant; toggling back reuses it).
        self._spec_k_granted = {self._spec_k}
        self._draft_lm = draft_lm
        self._draft_variables = draft_variables
        #: TREE-DRAFT width (``SpeculativeConfig.tree_width``): 0 =
        #: chain speculation; w >= 1 adds w sibling leaf rows to every
        #: verify chunk and up to ONE bonus committed token per round
        #: (the leaf + the target's prediction after it). Geometry
        #: below (cache slack, table width, idle sentinel, admission
        #: reservation) all widen by w so leaf writes land in reserved
        #: masked space.
        self._spec_w = self._spec.tree_width if self._spec else 0
        #: Decode-kernel dispatch knobs threaded into every decode/
        #: verify program this batcher lowers (static per batcher —
        #: the jit families key on self).
        self._kernel = kernel or KernelConfig()
        if kv_cache_dtype not in ("native", "int8", "int4"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: expected 'native', "
                "'int8' or 'int4'"
            )
        if kv_layout != "paged":
            # The keyword outlives the layout only because callers
            # outside this package still pass it (ROADMAP C1d).
            raise ValueError(
                f"kv_layout={kv_layout!r}: the per-slot dense layout "
                "left in PR 29; 'paged' is the only KV layout"
            )
        #: Quantized KV pools: absmax per K/V vector, same scheme as
        #: generate(kv_cache_dtype=...) — ~2-4x (int8) / ~4-8x (int4,
        #: two nibbles packed per int8 lane) more resident context per
        #: page and correspondingly less per-step cache traffic vs
        #: native. Composes with every mode: a pool becomes a (values,
        #: k_scales, v_scales) pytree triple, speculative verify
        #: quantizes its multi-token appends, and under TP every plane
        #: head-shards together — quantization is a property of the
        #: pool's format, not a special mode of one path.
        self._kv_dtype = kv_cache_dtype
        self._kv_quant = kv_cache_dtype != "native"
        if prefill_chunk is not None and (
            prefill_chunk < page_size or prefill_chunk % page_size
        ):
            raise ValueError(
                f"prefill_chunk must be a positive multiple of "
                f"page_size {page_size}, got {prefill_chunk}"
            )
        self._prefill_chunk = prefill_chunk
        if top_k is not None and not (1 <= top_k <= lm.vocab):
            raise ValueError(f"top_k {top_k} outside [1, {lm.vocab}]")
        if prompt_buckets is None:
            prompt_buckets, b = [], 8
            while b < lm.max_len:
                prompt_buckets.append(b)
                b *= 2
            prompt_buckets.append(lm.max_len)
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        g = lm.graph
        self._head = g.node("head").module
        self._blocks = [g.node(n).module for n in lm.block_names]
        specs = [b.spec for b in self._blocks]
        #: What the blocks keep for a request (``paged.CacheLayout``).
        #: Blocks alike in (window, kv_heads, head_dim, row) are a CACHE
        #: GROUP with a pool geometry, a ``Pager`` and a page table: the
        #: FIRST is the one ``pool_pages=`` sizes and every request
        #: reserves whole (the only one where all blocks are alike);
        #: each further group grants pages pass by pass (``Pager.hold``)
        #: from a pool derived from what one request can hold there, so
        #: a window layer keeps its window and not the sequence.
        self._layout = cache_layout(specs)
        groups = self._groups = self._layout.groups
        if not groups:
            raise ValueError(
                "no block of this model keeps pages: the batcher's "
                "positions, admission and preemption are the pager's, so "
                "it serves a model with at least one attention block"
            )
        self._require(*(
            feature for feature, given in {
                "a draft model": draft_lm is not None,
                "a tp mesh": mesh is not None,
                "a host cache tier": cache_tier is not None,
                "sequence-parallel prefill": prefill is not None,
                "cache-aware admission (the radix prefix cache)":
                    scheduler is not None and scheduler.cache_aware,
                "elastic recovery (health=)": health is not None,
                "a quantized KV pool": kv_cache_dtype != "native",
            }.items() if given
        ))
        #: Whether a prompt page may be shared between requests.
        self._shares_pages = (
            _unmet(self._layout, "the radix prefix cache") is None
        )
        #: Blocks whose MLP is the routed-expert layer: their per-expert
        #: token counts leave ``_step_chunk`` with the step's tokens.
        self._moe_blocks = tuple(
            i for i, sp in enumerate(specs) if sp.mlp == "experts"
        )
        #: The linear-attention blocks (``kda.steps``), and the counter
        #: families a state write is booked under.
        self._linear_blocks = sum(
            1 for sp in specs if sp.linear is not None
        )
        #: What a selecting block reads of a context at most
        #: (``dsa.positions_selected``); 0: no block selects.
        self._select_top_k = max(
            (specs[i].latent.index.top_k
             for i in self._layout.selecting_blocks), default=0,
        )
        self._state_families = tuple(
            family for family, has in (
                ("ssm", any(sp.ssm for sp in specs)),
                ("kda", self._linear_blocks),
            ) if has
        )
        #: The recurrent state: not paged. Every request holds exactly
        #: one, overwritten in every step and written WHOLE at admission
        #: (so whatever a dead row's steps left in a retired slot never
        #: reaches its next tenant). Device-resident and donated through
        #: every program that advances it, like the pools.
        self._states = tuple(
            zero_state(specs[i].state_spec, slots, self._blocks[i].dtype)
            for i in self._layout.state_blocks
        ) or None
        #: Blocks whose residual is streams (``BlockSpec.streams``):
        #: two mixes a block and step, booked as ``mhc.mixes``.
        self._stream_blocks = sum(
            1 for sp in specs if sp.streams is not None
        )
        #: Sliding-window models: decode masking lives in the model;
        #: the batcher's job is page RECYCLING behind the window.
        self._window = groups[0].window
        self._page = page_size
        # The table width covers max_len plus the speculative overshoot
        # slack: a verify chunk writes draft_k + 1 + tree_width tokens
        # from each slot's position, and the rejected overshoot must
        # land in reserved, masked space.
        pps, worst = pool_geometry(
            slots, lm.max_len, page_size,
            slack=self._spec_k + self._spec_w,
        )
        if pool_pages is None:
            pool_pages = worst
        if pool_pages < 2:
            raise ValueError(f"pool_pages must be >= 2, got {pool_pages}")
        #: Per-block page POOLS + a shared page table (``runtime/paged``:
        #: format and allocator; ``ops/paged_attention``: append, read,
        #: kernels) — HBM scales with resident tokens, not slots x
        #: max_len.
        self._pager = Pager(pool_pages, slots, pps, page_tokens=page_size)
        self._pool_pages = pool_pages
        #: One pager a group, the first group's being ``_pager``.
        self._pagers = [self._pager] + [
            Pager(
                group_pool_pages(
                    g, slots, pps, page_size, chunk, prefill_chunk
                ),
                slots, pps, page_tokens=page_size,
            )
            for g in groups[1:]
        ]
        # -- hierarchical KV cache tier (docs/SERVING.md §3) ---------------
        #: Host-DRAM spill tier under the prefix LRU: evicted rc=0
        #: pages spill (budgeted per tick) instead of dying, and the
        #: admission probe consults the tier before declaring a prefix
        #: miss — host hits readmit through the adopt_cached /
        #: _adopt_pages landing path and then admit as ordinary
        #: prefix-cache hits.
        self._tier_cfg = cache_tier
        self._tier = HostKVTier(cache_tier) if cache_tier else None
        #: Per-tick tier work budgets (reset at tick entry; seeded here
        #: so pre-first-tick evictions can spill too).
        self._spill_budget = (
            cache_tier.spill_pages_per_tick if cache_tier else 0
        )
        self._readmit_budget = (
            cache_tier.readmit_pages_per_tick if cache_tier else 0
        )
        # Always installed, tier or not: the hook records the
        # radix_evict flight event for every cached-prefix death
        # (spill/drop routing inside it stays tier-gated).
        self._pager.evict_hook = self._on_page_evict
        #: Instance-lifetime tier books (stats() mirrors of the
        #: cache_tier.* registry counters).
        self._tier_spilled = 0
        self._tier_readmitted = 0
        self._tier_dropped = 0
        #: High-water of the tier's own overflow-drop count already
        #: bridged to cache_tier.dropped_total (flushed per tick).
        self._tier_drop_seen = 0
        # Pools hold KV heads: fewer than query heads under GQA (the
        # whole point — a page costs kv_heads/heads the HBM).
        # A block without pages (linear attention) gets no pool: None
        # in its place, an empty subtree to every program and donation.
        self._caches = [
            alloc_kv_pools(
                self._pagers[gi].num_pages, groups[gi].kv_heads,
                page_size, groups[gi].head_dim, block.dtype,
                kv_cache_dtype, row=groups[gi].row,
                index_row=groups[gi].index_row,
            ) if block.spec.linear is None else None
            for gi, block in zip(self._layout.group_of, self._blocks)
        ]
        if mesh is not None:
            # Head-sharded KV: each device holds kv_heads / tp of every
            # pool page — THE capacity win TP buys.
            self._caches = jax.device_put(self._caches, self._kv_sharding)
        #: What the SAME pool geometry would cost in the native dtype
        #: — the denominator of the memory.kv_bytes_ratio gauge, so the
        #: int8 capacity win (values + scale planes vs native) is
        #: directly observable on dashboards. Native batchers read 1.0.
        self._native_cache_bytes = sum(
            self._pagers[gi].num_pages * page_size
            * groups[gi].position_values
            * jnp.dtype(block.dtype).itemsize
            for gi, block in zip(self._layout.group_of, self._blocks)
            if block.spec.linear is None
        )
        #: Idle-row cache position: a negative sentinel that stays
        #: negative across a whole tick's position advance (chunk
        #: steps, or the spec tick's up-to-draft_k+1(+1 with a
        #: tree-draft bonus) commit), routing every garbage write to
        #: the trash page.
        adv = (
            (self._spec_k + 1 + (1 if self._spec_w else 0))
            if self._spec
            else self.chunk
        )
        self._idle_pos = -(adv + 1)
        #: Draft-model slot caches (speculative mode): dense per-slot
        #: strips with the same draft_k + 1 slack as the single-request
        #: loop — the draft is small by construction, so slots x max_len
        #: dense strips cost what paging would save on the big model.
        if self._spec:
            dblock = draft_lm.graph.node(draft_lm.block_names[0]).module
            self._draft_blocks = [
                draft_lm.graph.node(n).module
                for n in draft_lm.block_names
            ]
            self._draft_embed = draft_lm.graph.node("embed").module
            # Tree drafts run one extra scan step (the leaf token's own
            # cache write), so the draft strip carries one more slack
            # position.
            dclen = (
                draft_lm.max_len + self._spec_k + 1
                + (1 if self._spec_w else 0)
            )

            def draft_cache():
                return jnp.zeros(
                    (slots, dblock.cache_heads, dclen, dblock.head_dim),
                    dblock.dtype,
                )

            self._draft_caches = [
                (draft_cache(), draft_cache())
                for _ in draft_lm.block_names
            ]
            if mesh is not None:
                # The DRAFT stays fully replicated: it is small by
                # construction (sharding it buys HBM that is not the
                # bottleneck and would force its head counts to divide
                # tp), and a replicated draft scan is collective-free —
                # the spec tick's ICI budget goes to the target's one
                # psum pair per block.
                self._draft_variables = jax.device_put(
                    draft_variables, self._repl
                )
                self._draft_caches = jax.device_put(
                    self._draft_caches, self._repl
                )
        else:
            self._draft_caches = None
        #: Speculation lifetime counters (instance-scoped, like the
        #: admit/complete counts): drafted proposals vs accepted ones.
        self._spec_drafted = 0
        self._spec_accepted = 0
        #: Host->device staging transfers (every jnp.asarray/device_put
        #: this module issues goes through _h2d). The fused-staging
        #: contract: ZERO on a steady-state decode tick, O(1) per
        #: admission/retirement — benchmarks/micro and tests assert it.
        self._h2d_count = 0
        #: Device-resident per-slot sampling state ("dstate"): one row
        #: per slot, written only by the donated jitted setters
        #: (_stage_slot / _clear_slot) and _step_chunk itself.
        self._dstate = {
            # last committed token (next decode input)
            "tok": jnp.zeros((slots,), jnp.int32),
            # cache position the next consumed token writes at
            "pos": jnp.full((slots,), self._idle_pos, jnp.int32),
            # per-slot folded key schedule + cursor: keys[b, kbase[b]+j]
            # samples step j of the next chunk (clipped to nkeys-1, the
            # final-key convention for steps past the request's end)
            "keys": jnp.zeros((slots, lm.max_len, 2), jnp.uint32),
            "kbase": jnp.zeros((slots,), jnp.int32),
            "nkeys": jnp.ones((slots,), jnp.int32),
            "temp": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.full((slots,), lm.vocab, jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            # live-row mask: the step advances pos/kbase/tok only here,
            # re-parking idle rows at the sentinel every chunk
            "active": jnp.zeros((slots,), bool),
        }
        if mesh is not None:
            # Per-slot sampling state replicates: it is O(slots) scalars
            # — sharding it would trade nothing for collectives in the
            # setters.
            self._dstate = jax.device_put(self._dstate, self._repl)
        #: Device copy of the pager's page table, re-uploaded only when
        #: the host table actually changed (admission/retirement/window
        #: recycling) — a steady-state paged tick stages nothing.
        self._table_dev = None
        self._table_snapshot = None
        #: Per further cache group: (host table, its device copy).
        self._group_tables: list = [None] * len(groups[1:])
        # -- sequence-parallel long-context prefill ------------------------
        #: ``config.PrefillConfig{sp_threshold, sp_width}``: admissions
        #: of at least the threshold prefill SP-SHARDED across a
        #: dedicated ``(sp,)`` / ``(sp, tp)`` mesh
        #: (``parallel/sp_prefill.SPPrefiller`` — ring-transported
        #: window, chunk-oracle attention) and their pages land through
        #: :meth:`adopt_prefill_pages` exactly like a disaggregated
        #: handoff, so the request then admits as a prefix-cache hit
        #: and the decode tier's mesh/programs are untouched. Greedy
        #: streams stay bit-identical to the collocated chunked
        #: prefill's (pinned; the pages differ from its by at most the
        #: rounding of one reordered sum, parallel/sp_prefill). The
        #: prefiller's tp must MATCH
        #: this batcher's (its pages must be what THIS batcher's own
        #: chunked prefill would write, which is tp-sharded math for
        #: tp > 1).
        self._sp_cfg = prefill
        self._sp: SPPrefiller | None = None
        self._sp_prefills = 0
        #: Consecutive sp-dispatch failures: past the breaker the
        #: prefiller retires (every long admission was paying a doomed
        #: dispatch — e.g. a dead ring-only device no batcher-mesh
        #: event will ever recover) until a recovery rebuilds it.
        self._sp_failures = 0
        if prefill is not None and prefill.enabled:
            mesh_sp = sp_mesh
            if mesh_sp is None:
                mesh_sp = build_sp_mesh(
                    prefill.sp_width, self._tp, prefill.sp_axis,
                    self._axis,
                )
            if self._tp > 1:
                sp_tp_axis = self._axis
                if (
                    sp_tp_axis not in mesh_sp.shape
                    or int(mesh_sp.shape[sp_tp_axis]) != self._tp
                ):
                    raise ValueError(
                        f"sp_mesh must carry the batcher's tp axis "
                        f"{sp_tp_axis!r} at size {self._tp} — sp pages "
                        "must be what this batcher's own tp-sharded "
                        "chunked prefill would write"
                    )
            else:
                sp_tp_axis = (
                    self._axis if self._axis in mesh_sp.shape else None
                )
            self._sp = SPPrefiller(
                lm, self.variables, mesh_sp, self._page,
                kv_cache_dtype=kv_cache_dtype,
                sp_axis=prefill.sp_axis,
                tp_axis=sp_tp_axis,
                name="batcher-sp",
            )
            global_metrics().set_gauge(
                "prefill.sp_width", float(self._sp.sp)
            )
        # -- traffic control (docs/SERVING.md "Traffic control") -----------
        #: The submit queue is a runtime/scheduler.AdmissionQueue even
        #: without an explicit SchedulerConfig: bounded (the default
        #: max_queue_depth — a full slot map used to queue
        #: unboundedly) but otherwise STRICT FIFO, so a batcher that
        #: never opted into traffic control keeps its exact
        #: pre-scheduler admission order. An explicit config adds
        #: tenant quotas, weighted fair queueing, priority classes,
        #: preemption and the degradation controller.
        self._sched = scheduler
        self._queue: AdmissionQueue = AdmissionQueue(scheduler)
        if scheduler is not None and scheduler.cache_aware:
            # Cache-aware admission ordering (SchedulerConfig
            # .cache_aware): among one class's queued candidates, the
            # queue prefers the request with the longest (then hottest)
            # RESIDENT radix prefix — a read-only token walk over the
            # pager's radix index, no rc movement, no page claims. The
            # probe returns None on a cold prompt so a probe-less
            # window stays byte-exact FIFO.
            def _probe(r, _pager=self._pager):
                pages, tokens, heat = _pager.radix_probe(r.prompt)
                return (tokens, heat) if pages else None

            self._queue.prefix_probe = _probe
        self._controller = (
            DegradationController(scheduler)
            if scheduler is not None and scheduler.degrade
            else None
        )
        #: Traffic-control books (instance-lifetime, _cv-guarded —
        #: mirrors of the scheduler.{rejected,preempted}_total
        #: counters).
        self._rejected = 0
        self._preempted = 0
        #: Tenants currently holding a scheduler.queue_depth gauge —
        #: tick prunes gauges the queue's bounded tenant map evicted,
        #: so adversarial fresh-label floods cannot grow the registry.
        self._gauged_tenants: set[str] = set()
        self._done: dict[int, np.ndarray] = {}
        #: Per-request logprob streams, claimable via logprobs() after
        #: the tokens are fetched. BOUNDED: callers that never claim
        #: them (the common tokens-only usage) must not leak — beyond
        #: _LPS_CAP unclaimed entries the oldest are evicted
        #: (insertion-ordered dict).
        self._done_lps: dict[int, np.ndarray] = {}
        self._cancelled: set[int] = set()
        #: req_id the ticking thread popped but has not yet bound to a
        #: slot — the only window where a live request is in neither
        #: the queue nor a slot (cancel() must still see it as live).
        self._admitting: int | None = None
        #: Copy-on-write fan-out (submit_fanout) books: group id ->
        #: _FanoutGroup. Mutations are _cv-guarded (submit and cancel
        #: run on client threads); pager claims only ever move on the
        #: ticking thread — client-side group deaths park their claimed
        #: page in ``_fanout_release``, drained at the next admission
        #: sweep (the pager is not thread-safe).
        self._fanout_groups: dict[int, _FanoutGroup] = {}
        self._fanout_next = 0
        self._fanout_release: list[int] = []
        self._next_id = 0
        self._prefill_cache: dict[int, Any] = {}  # bucket -> jitted fn
        # Instance-lifetime counts (stats() must not read the PROCESS
        # counters — two batchers would report each other's traffic).
        self._admitted = 0
        self._completed = 0
        self._ticks = 0
        #: Ticks on which some active request had a temperature; on
        #: the rest every row was greedy and nobody read the draw.
        self._ticks_sampled = 0
        #: Prompt tokens THIS batcher prefilled in-tick (full
        #: admissions, suffix passes, chunk passes — positions actually
        #: computed, prefix-cache hits excluded). Mirrored as the
        #: ``continuous.prefill_tokens_total`` counter so benches can
        #: report prefill-tokens/s and decode-tokens/s separately —
        #: the ratio disaggregation moves (handed-off requests prefill
        #: in the prefill tier, so only their suffix lands here).
        self._prefill_tokens = 0
        #: Request-timeline SLO histograms (queue-wait / TTFT /
        #: inter-token-latency / request latency). ON by default — the
        #: hot-path cost is one perf_counter stamp per committed token
        #: (ITL samples batch into ONE registry-lock acquisition per
        #: tick via observe_many); set False to measure the floor
        #: (benchmarks/micro/obs_overhead.py). Flight-recorder lifecycle
        #: events (admit/finish/cancel) are always-on, independent of
        #: this flag.
        self.obs_timeline = True
        self._itl_pending: list[float] = []
        self._ttft_pending: list[float] = []
        # -- the tick order ------------------------------------------------
        # tick() dispatches tick t, then commits tick t-1's _InFlight
        # while t runs on device — one tick of results stays in flight
        # between calls, drained at every pipeline boundary (run() exit,
        # recover(), drain(), server-loop stop). A further cache group
        # grants and recycles its pages pass by pass from the position
        # each row has been DISPATCHED to (_dispatched_pos), which an
        # in-flight tick has moved though no commit has.
        self._inflight: _InFlight | None = None
        #: SLO accounting (docs/OBSERVABILITY.md "Workload telemetry").
        #: Hot path touches only these plain ints (one attribute inc
        #: per evaluated stamp); the registry sees them once per tick
        #: in _obs_flush. Keys: ttft_met/ttft_missed/itl_met/itl_missed
        #: (this tick's pending) and the instance-lifetime mirrors.
        self._slo_pending = {
            "ttft_met": 0, "ttft_missed": 0,
            "itl_met": 0, "itl_missed": 0,
        }
        self._slo_totals = {
            "ttft_met": 0, "ttft_missed": 0,
            "itl_met": 0, "itl_missed": 0,
        }
        #: Committed tokens this tick (all, and from requests still
        #: inside budget) — flushed as continuous.{tokens,good_tokens}
        #: counters and folded into the goodput gauge.
        self._tick_tokens = 0
        self._tick_good_tokens = 0
        #: Rolling (t, good_tokens) per-tick samples spanning
        #: goodput_window_s — continuous.goodput_tokens_s is their rate
        #: (idle ticks append zeros, so the gauge decays instead of
        #: scraping the last busy tick's rate forever). The window is
        #: ``ObservabilityConfig.goodput_window_s``, shared with the
        #: capacity plane's windowed views.
        self._obs_cfg = observability or ObservabilityConfig()
        self.goodput_window_s = self._obs_cfg.goodput_window_s
        self._goodput_samples: collections.deque[tuple[float, int]] = (
            collections.deque()
        )
        # -- capacity / placement-signal plane (runtime/capacity) ----------
        #: The self-describing replica book: headroom, self-calibrating
        #: TTFT forecaster, prefix-affinity sketch, hysteresis health.
        #: Feeds are O(1) stamps on the submit/admit/commit sites;
        #: rebuilds ride the _obs_flush seam, rate-limited. None when
        #: ``CapacityConfig(enabled=False)`` — zero extra work anywhere
        #: (the obs_overhead capacity arm's floor).
        cap_cfg = capacity or CapacityConfig()
        self._capacity: CapacityModel | None = (
            CapacityModel(
                cap_cfg, kind="decode",
                window_s=self.goodput_window_s,
            )
            if cap_cfg.enabled
            else None
        )
        #: Previous _obs_flush stamp — the tick-gap EWMA feed (the
        #: forecaster's "how long until a queued request's next pickup
        #: opportunity" term). 0.0 until the first flush.
        self._cap_last_flush = 0.0
        #: Engine-tier observability (utils.profiling): per-phase tick
        #: timing behind the process-global EngineObs gate (one branch
        #: per phase when off), plus the compile sentinel sampled once
        #: per tick. Registration re-arms each program's warmup window —
        #: jit caches key on ``self``, so a fresh batcher legitimately
        #: compiles its own first variants.
        self._eobs = global_engine_obs()
        self._sentinel = global_compile_sentinel()
        self._sentinel.register(
            "continuous.step_chunk", type(self)._step_chunk
        )
        self._sentinel.register(
            "continuous.stage_slot", type(self)._stage_slot
        )
        self._sentinel.register(
            "continuous.clear_slot", type(self)._clear_slot
        )
        # Disaggregated-handoff landing program (adopt_prefill_pages —
        # dispatched only when a prefill tier streams pages in).
        self._sentinel.register(
            "continuous.adopt_pages", type(self)._adopt_pages
        )
        # Copy-on-write fan-out fork (one variant ever: no static shape
        # axis — dispatched only by submit_fanout siblings).
        self._sentinel.register(
            "continuous.fork_page", type(self)._fork_page
        )
        if self._spec:
            # The draft's dense strips are the only _insert target.
            self._sentinel.register(
                "continuous.insert", type(self)._insert
            )
            self._sentinel.register(
                "continuous.spec_verify", type(self)._spec_verify
            )
            self._sentinel.register("speculative.draft_chunk", draft_chunk)
        # The prefill family is a per-instance dict of jit closures
        # (bucket/suffix/draft variants): ONE shared watch sums the
        # cache sizes over every live batcher (weakly held), so a
        # second batcher aggregates instead of replacing the first's
        # watch. A late new-bucket admission fires the sentinel by
        # design — that tick really did pay a compile.
        _LIVE_BATCHERS.add(self)
        self._sentinel.register(
            "continuous.prefill",
            size_fn=aggregate_size_fn(_LIVE_BATCHERS, _prefill_family_size),
            names=("prefill", "dprefill"),  # the closures' own names
        )
        #: Pull-style memory accounting: pool / draft-strip bytes and
        #: page occupancy served as memory.* gauges at every
        #: exporter scrape (weakly held — see utils.profiling).
        register_memory_source("continuous", self)
        #: Roofline source: XLA cost_analysis of the decode-path
        #: programs (lazy, cached — see _program_costs) + the engine
        #: phase walls, served as engine.{flops,bytes_accessed,mbu,mfu}
        #: gauges at scrape.
        self._roofline_costs: dict | None = None
        register_roofline_source("continuous", self)
        # Threaded serving (start()/result()/stop()): one condition
        # guards every mutation of the queue/done handoff state and the
        # server-thread lifecycle; compiled work runs outside the lock,
        # on the server thread only.
        self._cv = threading.Condition()
        self._server: threading.Thread | None = None
        self._stopping = False
        #: Exception that killed the server thread's tick (re-raised to
        #: result() waiters instead of a misleading timeout).
        self._server_error: BaseException | None = None
        # -- elastic mesh recovery (docs/SERVING.md "Elastic recovery") ----
        #: Knobs: auto-reshard at tick vs raise DeviceLostError,
        #: migrate-vs-replay policy, min surviving tp.
        self._recovery = recovery or RecoveryConfig()
        #: ``control.registry.DeviceHealthMonitor`` (duck-typed): the
        #: batcher registers its mesh devices as TTL-lease members and
        #: subscribes to ``leave`` events — a simulated kill (or a real
        #: lease expiry) lands in ``_lost_pending`` and the next tick
        #: re-shards (or raises, per ``auto_reshard``).
        self._health = health
        #: Optional ``control.journal.DispatcherJournal``: submits are
        #: journaled (payload + sampling-knob meta), finishes done-
        #: marked, and non-migratable requests at recovery REPLAY from
        #: the journaled record — re-entering through the paged prefix
        #: cache when the prompt pages are still resident.
        self._journal = journal
        if journal is not None:
            # Serving over an existing WAL (crash recovery) must not
            # recycle ids: a fresh counter reaching a still-pending id
            # would os.replace that request's journaled payload and
            # done-mark it away — the exact hazard
            # journal.next_request_id exists to prevent.
            self._next_id = max(self._next_id, journal.next_request_id)
        #: Membership keys of devices reported lost but not yet
        #: recovered from (guarded by ``_cv``; consumed at tick entry).
        self._lost_pending: list[str] = []
        #: The devices serving this batcher, in tp-axis order — kept
        #: distinct from ``_mesh`` because a tp=1 batcher (constructed
        #: with a 1-device mesh, or the remnant a recovery down to tp=1
        #: leaves) sets ``_mesh = None`` (single-device discipline)
        #: while its device must STILL be trackable and
        #: recoverable-from: losing it has to raise, not silently
        #: dispatch onto a dead chip.
        if self._mesh is not None:
            self._mesh_devices: list = list(self._mesh.devices.flat)
        elif isinstance(self._repl, SingleDeviceSharding):
            self._mesh_devices = list(self._repl.device_set)
        else:
            self._mesh_devices = []
        self._mesh_device_ids: set[int] = {
            int(d.id) for d in self._mesh_devices
        }
        #: Static re-trace key for the programs that bake concrete
        #: sharding constraints into their jaxprs (``_shard_kv`` /
        #: ``_repl_state``): jit caches TRACES on avals + statics only,
        #: so without this a post-recovery dispatch would reuse a jaxpr
        #: whose constraints still name the dead device. Bumped once
        #: per recovery.
        self._mesh_epoch = 0
        #: Per program family, the static-variant keys THIS batcher has
        #: dispatched under the current mesh epoch (step_chunk's
        #: (truncate, nucleus) combos, stage_slot's key buckets,
        #: _insert's prompt buckets). ``recover()`` sizes each family's
        #: expected-compile allowance from these — every variant in use
        #: re-traces after the epoch bump, so a mixed-traffic batcher
        #: legitimately re-lowers MORE than one variant per family.
        #: Ticking-thread only (dispatch sites), like the caches.
        self._variants: dict[str, set] = {}
        #: Cumulative expected-compile allowances THIS batcher granted
        #: at its recoveries (program -> units) — close() disarms them
        #: so unconsumed slack cannot outlive the granter on the shared
        #: class-level sentinel watches.
        self._granted: dict[str, int] = {}
        # Instance-lifetime recovery books (stats() mirrors of the
        # recovery.* registry counters).
        self._recoveries = 0
        self._recovery_migrated = 0
        self._recovery_replayed = 0
        self._recovery_dropped = 0
        self._last_recovery_wall_s = 0.0
        #: close() flips this: a retired batcher must stop consuming
        #: membership events (its compiled state is gone).
        self._retired = False
        if health is not None and self._mesh_devices:
            health.track(self._mesh_devices)
            # Weak subscription (control.registry.weak_watch): the
            # watcher list has no unwatch and outlives any batcher — a
            # bound method there would pin a retired batcher's weights
            # and KV pools forever (the same discipline as
            # _LIVE_BATCHERS being a WeakSet). The shim dies into a
            # no-op when the batcher is collected, and goes quiet at
            # close() via _retired.
            weak_watch(health, self, "_on_device_event")
            # A device already dead at construction — or killed between
            # track() and watch() — delivers NO future 'leave' event
            # (its lease is gone and track() refuses to resurrect it),
            # so seed the pending set from the monitor's dead roster or
            # every tick dispatches onto the dead chip undetected.
            for did in sorted(health.dead_ids() & self._mesh_device_ids):
                self._on_device_event("leave", f"device:{did}")
        # The constructor's own wall (pools, state, tables): start-up's
        # share that no compile event covers.
        global_metrics().set_gauge(
            "engine.construct_s", time.perf_counter() - t_construct
        )

    # -- compiled pieces ---------------------------------------------------

    def _h2d(self, x):
        """The ONE host->device staging funnel for this module: counts
        every transfer so tests and benchmarks/micro can assert the
        fused-staging contract (0 per steady tick, O(1) per admission)
        instead of trusting docstrings. Under a mesh, staged arrays are
        placed REPLICATED explicitly (a one-device-committed array mixed
        into a sharded program would force GSPMD reshards); one logical
        transfer either way. ``_repl`` (not ``_mesh``) is the guard: a
        batcher recovered down to tp=1 keeps staging onto its surviving
        device (``SingleDeviceSharding``) — ``jnp.asarray`` would land
        on the default device, which may be the dead one."""
        self._h2d_count += 1
        if self._repl is not None:
            return jax.device_put(x, self._repl)
        return jnp.asarray(x)

    def _shard_kv(self, caches):
        """Explicit in/out cache sharding for the compiled programs:
        pin every KV leaf (pools, int8 scale planes) to
        the head-axis sharding so GSPMD partitions the decode math and
        inserts the block psums, instead of falling back to whatever
        propagation guesses. No-mesh batchers pay one branch.

        The CONCRETE sharding is baked into the traced jaxpr, and jit
        caches traces on avals + STATIC args only — which is why every
        program that calls this (or ``_repl_state``) carries a static
        ``epoch`` argument: elastic recovery bumps ``_mesh_epoch`` so
        the re-lowered families re-TRACE against the shrunk mesh
        instead of reusing a jaxpr whose constraints name dead
        devices."""
        if self._mesh is None:
            return caches
        return jax.tree.map(
            lambda c: lax.with_sharding_constraint(c, self._kv_sharding),
            caches,
        )

    def _head_shard(self):
        """``(mesh, axis)`` for the paged attention dispatchers while
        the programs are tp-partitioned, None otherwise: a Pallas
        kernel inside a GSPMD program must run per head shard under
        ``shard_map`` (``ops.paged_attention._head_sharded``). Read at
        TRACE time, like ``_shard_kv``'s sharding — the static
        ``epoch`` argument re-traces after a recovery."""
        if self._mesh is None:
            return None
        return (self._mesh, self._axis)

    def _repl_state(self, dstate):
        """Explicit in/out sharding for the per-slot sampling state:
        pinned REPLICATED through every donated program. Left to
        propagation, GSPMD may pick different output shardings for the
        pass-through leaves in different programs (observed: the key
        schedules came back head-split from the verify program but
        replicated from the admission setter), and a producer-to-
        producer sharding flip is a phantom jit variant in every
        consumer — the exact recompile class the sentinel exists to
        catch."""
        if self._mesh is None:
            return dstate
        return {
            k: lax.with_sharding_constraint(x, self._repl)
            for k, x in dstate.items()
        }

    @partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("epoch",),
        donate_argnums=(1,),
    )
    def _stage_slot(self, dstate, ints, floats, keys, *, epoch=0):
        """Write one admitted request's whole sampling row into the
        donated device state: ``ints`` (6,) int32 = [slot, tok, pos,
        top_k, nkeys, kbase], ``floats`` (2,) f32 = [temp, top_p],
        ``keys`` (nkb, 2) uint32 = the folded key schedule padded to a
        power-of-two bucket (log2 compile variants; the pad tail is
        never read — the step clips the cursor to nkeys-1). O(1) fused
        transfers per admission, not one per field."""
        i = ints[0]
        d = dict(dstate)
        d["tok"] = dstate["tok"].at[i].set(ints[1])
        d["pos"] = dstate["pos"].at[i].set(ints[2])
        d["top_k"] = dstate["top_k"].at[i].set(ints[3])
        d["nkeys"] = dstate["nkeys"].at[i].set(ints[4])
        d["kbase"] = dstate["kbase"].at[i].set(ints[5])
        d["temp"] = dstate["temp"].at[i].set(floats[0])
        d["top_p"] = dstate["top_p"].at[i].set(floats[1])
        d["keys"] = lax.dynamic_update_slice(
            dstate["keys"], keys[None], (i, 0, 0)
        )
        d["active"] = dstate["active"].at[i].set(True)
        return self._repl_state(d)

    @partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("epoch",),
        donate_argnums=(1,),
    )
    def _clear_slot(self, dstate, slot, *, epoch=0):
        """Retire one slot's device row: park its position at the idle
        sentinel and drop it from the active mask (the step re-parks it
        every chunk thereafter). Identity sampling knobs keep the
        garbage row off the truncate/nucleus sorts."""
        d = dict(dstate)
        d["pos"] = dstate["pos"].at[slot].set(self._idle_pos)
        d["tok"] = dstate["tok"].at[slot].set(0)
        d["kbase"] = dstate["kbase"].at[slot].set(0)
        d["nkeys"] = dstate["nkeys"].at[slot].set(1)
        d["temp"] = dstate["temp"].at[slot].set(0.0)
        d["top_k"] = dstate["top_k"].at[slot].set(self.lm.vocab)
        d["top_p"] = dstate["top_p"].at[slot].set(1.0)
        d["active"] = dstate["active"].at[slot].set(False)
        return self._repl_state(d)

    def _truncate_rows(self, lg, top_ks):
        """Per-row top-k filter with a TRACED k: keep logits >= the k-th
        largest (``sorted[V-k]`` — bitwise the same threshold
        generate()'s ``lax.top_k`` filter uses, so mixed-top_k batches
        match per-request ``generate`` without recompiling); k == V
        keeps everything. Costs a full (B, V) sort, so callers gate it
        behind a STATIC flag and skip it when no active request
        truncates — the hot path must not pay O(V log V) for a no-op
        (``sample_next_tokens``'s lax.top_k rule)."""
        v = lg.shape[-1]
        sorted_lg = jnp.sort(lg, axis=-1)  # ascending
        idx = jnp.clip(v - top_ks, 0, v - 1)
        kth = jnp.take_along_axis(sorted_lg, idx[:, None], axis=-1)
        return jnp.where(lg >= kth, lg, -jnp.inf)

    @partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("truncate", "nucleus", "epoch"),
        donate_argnums=(2, 3, 5),
    )
    def _step_chunk(self, variables, caches, dstate, table, states=None,
                    *, truncate, nucleus, epoch=0):
        """``chunk`` lockstep decode steps as one compiled scan over the
        DEVICE-RESIDENT slot state.

        ``dstate`` carries every per-slot input the old host-staged path
        transferred each tick (token, position, temps, top_ks, top_ps,
        key schedules) — donated in, advanced on device, returned out,
        so a steady-state tick stages zero host scalars. Each step's
        (B, 2) sampling keys gather from the resident per-slot schedules
        at ``kbase + j`` (clipped to ``nkeys - 1``: steps past a
        request's end sample with its final key — garbage the host
        truncation never reads). Greedy selection derives from
        ``temp == 0`` (submit's normalization). Static ``truncate`` /
        ``nucleus`` elide the top-k/top-p sorts when no active request
        needs them (at most 2x2 compiled variants). ``table`` addresses
        each block's pool through its cache group's page table (one
        array; a tuple of them, one a group, for a model with several).
        Inactive rows re-park at the idle sentinel after the chunk's
        optimistic pos advance; rows whose request retires mid-chunk are
        cleared host-side (``_clear_slot``) before the next tick.
        Returns ((chunk, B) emitted tokens, logprobs, caches, dstate,
        moe, states); ONE host sync per call, not per token. ``moe`` is
        None for a model without routed experts, else one int32 vector
        the tick fetches with the tokens (``_moe_counts``). ``states``
        (None for a model without recurrent state): per block with a
        state-space mixer the slots' ``(state, tail)``, donated in,
        advanced by every live row in every step, returned out; a dead
        row's (negative position: idle, or mid-chunked-prefill INTO
        this state) is left as it was."""
        caches = self._shard_kv(caches)
        dstate = self._repl_state(dstate)
        C = self.chunk
        temps = dstate["temp"]
        top_ks = dstate["top_k"]
        top_ps = dstate["top_p"]
        greedy = temps == 0.0
        active = dstate["active"]
        kbase, nkeys = dstate["kbase"], dstate["nkeys"]
        # (B, C) key cursors -> (C, B, 2) per-step keys, one gather.
        cursor = jnp.clip(
            kbase[:, None] + jnp.arange(C)[None, :], 0,
            (nkeys - 1)[:, None],
        )
        keys = jnp.swapaxes(
            jnp.take_along_axis(
                dstate["keys"], cursor[:, :, None], axis=1
            ),
            0, 1,
        )

        def body(carry, step_keys):
            tokens, pos, caches, states = carry
            x = self._embed.apply(
                variables["embed"], tokens[:, None], pos[:, None],
                method="embed_positions",
            )
            new_caches, new_states = [], []
            held = []  # per expert block: (B, held) assignments a row
            for i, (name, block, pool) in enumerate(zip(
                self.lm.block_names, self._blocks, caches
            )):
                out = block.apply(
                    variables[name], x, pool, self._table_of(table, i),
                    pos,
                    attn_impl=self._kernel.attn_impl,
                    head_shard=self._head_shard(),
                    method="decode_step_paged",
                    mutable=["intermediates"] if i in self._moe_blocks
                    else False,
                    **self._carried(states, i),
                )
                if i in self._moe_blocks:
                    out, sown = out
                    held.append(
                        sown["intermediates"]["experts"]["held_tokens"][0]
                    )
                x, pool, *carried = out
                new_caches.append(pool)
                new_states.extend(carried)
            logits = self._head.apply(variables["head"], x)[:, 0]  # (B, V)
            pick_greedy = jnp.argmax(logits, axis=-1)
            lg = logits / jnp.maximum(temps, 1e-6)[:, None]
            if truncate:
                lg = self._truncate_rows(lg, top_ks)
            if nucleus:
                lg = nucleus_filter(lg, top_ps)
            pick_sampled = jax.vmap(jax.random.categorical)(step_keys, lg)
            nxt = jnp.where(greedy, pick_greedy, pick_sampled).astype(
                tokens.dtype
            )
            # Always emitted: the chosen logit less one log-sum-exp of
            # its row, no (B, V) result; chosen_logprob is THE shared
            # scoring convention.
            lp = chosen_logprob(logits, nxt)
            moe = None
            if held:
                # Live rows only: an idle row routes garbage.
                live = pos >= 0
                moe = (
                    jnp.sum(
                        jnp.where(live[None, :, None], jnp.stack(held), 0),
                        axis=1,
                    ),  # (expert blocks, held)
                    jnp.sum(live, dtype=jnp.int32),
                )
            return (
                (nxt, pos + 1, tuple(new_caches), tuple(new_states)),
                (nxt, lp, moe),
            )

        (_, _, caches, states), (toks, lps, moe) = lax.scan(
            body,
            (dstate["tok"], dstate["pos"], tuple(caches),
             tuple(states or ())),
            keys,
        )
        if moe is not None:
            per_step, rows = moe  # (C, blocks, held), (C,)
            moe = jnp.concatenate([
                jnp.sum(per_step, axis=0).reshape(-1),
                jnp.sum(per_step > 0, dtype=jnp.int32)[None],
                jnp.sum(rows)[None],
            ])
        # Optimistic device-side advance: a surviving slot commits all C
        # tokens (any mid-chunk finish retires it and the host clears
        # its row), so pos/kbase/tok land exactly on the next tick's
        # entry invariants. Idle rows re-park at the sentinel — without
        # this, the scan's pos+1 increments would walk a retired row's
        # sentinel up into real page territory.
        new = dict(dstate)
        new["pos"] = jnp.where(active, dstate["pos"] + C, self._idle_pos)
        new["tok"] = jnp.where(active, toks[-1], 0)
        new["kbase"] = jnp.where(active, kbase + C, 0)
        return (
            toks, lps, self._shard_kv(list(caches)),
            self._repl_state(new), moe, states or None,
        )

    def _sampling_flags(self, active) -> tuple[bool, bool, bool]:
        """``(sample, truncate, nucleus)`` of one decode dispatch, read
        from the requests in the batch and nothing else (ONE derivation
        for ``_step_chunk`` and ``_spec_verify``): whether any active
        row has a temperature, and whether the top-k / top-p sorts are
        needed by some active request (never on an all-greedy batch,
        whatever knobs its requests carry)."""
        sample = any(s.req.temperature > 0.0 for s in active)
        return (
            sample,
            sample and any(s.req.top_k < self.lm.vocab for s in active),
            sample and any(s.req.top_p < 1.0 for s in active),
        )

    def _count_tick(self, sample: bool) -> None:
        """Book one decode dispatch, and whether any of its rows
        samples: ``1 - ticks_sampled / ticks`` is the share of ticks
        whose draw nobody reads (every row greedy)."""
        with self._cv:
            self._ticks += 1
            self._ticks_sampled += sample
        m = global_metrics()
        m.inc("continuous.ticks")
        if sample:
            m.inc("continuous.ticks_sampled")

    def _carried(self, states, block: int) -> dict:
        """``decode_step_paged`` / ``prefill_chunk_paged``'s keyword
        for block ``block``: its ``(state, tail)`` among a program's
        ``states`` (one a block with recurrent state, in block order),
        nothing for a block without one."""
        if block not in self._layout.state_blocks:
            return {}
        return {"carried": states[self._layout.state_blocks.index(block)]}

    def _table_of(self, table, block: int):
        """Block ``block``'s page table (or page list) among a
        program's: the one array of a model with one cache group, else
        its group's entry of the tuple."""
        if len(self._groups) == 1:
            return table
        return table[self._layout.group_of[block]]

    def _moe_counts(self, moe) -> None:
        """Book one chunk's expert counts (``_step_chunk``'s last
        output, landed with the tokens): per expert block and held
        expert the tokens it was given, then how many (step, expert)
        pairs got at least one, then the live rows summed over steps."""
        m = global_metrics()
        n = len(self._moe_blocks)
        tokens = moe[:-2].reshape(n, -1)
        for row, block in zip(tokens, self._moe_blocks):
            first = self._blocks[block].spec.experts.held_range[0]
            for e, count in enumerate(row):
                if count:
                    m.inc(f"moe.tokens.{block}.{first + e}", float(count))
        top_k = self._blocks[self._moe_blocks[0]].spec.experts.top_k
        m.inc("moe.steps", float(self.chunk))
        m.inc("moe.experts_hit", float(moe[-2]))
        m.inc("moe.assignments_held", float(tokens.sum()))
        m.inc("moe.assignments_total", float(moe[-1]) * top_k * n)

    @partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("sample", "truncate", "nucleus", "epoch"),
        donate_argnums=(2, 3),
    )
    def _spec_verify(self, variables, caches, dstate, dtoks, table,
                     cands=None, *, sample=False, truncate=False,
                     nucleus=False, epoch=0):
        """The speculative tick's VERIFY program — the second of its
        exactly two compiled programs (the first is the shared
        ``models/speculative.draft_chunk`` scan).

        Static ``sample`` (with ``truncate``/``nucleus``, the
        _step_chunk flag discipline) turns on SPECULATIVE SAMPLING for
        ticks whose batch carries any ``temperature > 0`` row: each
        proposal is accepted with the target's own probability of that
        token under the row's processed distribution (the draft
        proposes its argmax — a delta proposal, so ``min(1, p/q)``
        reduces to ``p(token)``), a rejection resamples from the
        RESIDUAL distribution (proposal mass removed), and the position
        after a fully-accepted chain draws fresh — the standard
        correction, provably the target's per-position sampling
        distribution (lossless in DISTRIBUTION). Greedy rows in the
        same batch keep their exact argmax stream via the final
        select; all-greedy ticks compile ``sample=False``, whose
        program text is unchanged from the greedy-only version.

        Builds every slot's (draft_k + 1) chunk ``[last_token,
        proposals]`` ON DEVICE from the draft scan's output, runs one
        fused ``verify_chunk_paged`` pass over all
        slots at their own positions (rows desynchronize; the program
        does not), reduces each row's longest agreeing prefix
        (``accept_speculation``), and advances the donated device state
        by each row's commit count — so the steady-state spec tick
        stages zero host arrays and the caller performs ONE fused
        device->host fetch of (tokens, logprobs, accepted). Inactive
        rows re-park at the idle sentinel; their writes are
        trash-routed by the verify primitives. Returns ((d+1, B)
        tokens, (d+1, B) logprobs, (B,) accepted counts, caches,
        dstate).

        TREE DRAFTS (``cands`` (B, w) — the draft's top-w ids for the
        position after the chain, ``SpeculativeConfig.tree_width``):
        the chunk grows w LEAF rows verified in the same pass under the
        tree mask. When a row's whole chain accepts AND its correction
        token (the target's own pick for the leaf position) matches a
        leaf, that leaf's cache entry is already written — the first
        matching leaf's K/V moves to the canonical ``pos + d + 1`` slot
        (one per-row gather/scatter per block; a no-op identity copy
        when the match IS the first leaf) — and the target's prediction
        AFTER that leaf commits as a BONUS token: up to d + 2 commits
        per verify pass. Outputs then carry d + 2 token rows and
        ``acc`` counts the bonus (commit limit stays ``acc + 1``)."""
        tree = cands is not None
        w = cands.shape[1] if tree else 0
        caches = self._shard_kv(caches)
        dstate = self._repl_state(dstate)
        # The round's speculation depth comes from the DRAFT OUTPUT's
        # static shape, not self._spec_k: the degradation ladder
        # shrinks the effective draft_k at runtime (set_draft_k), and
        # each distinct depth is its own jit variant keyed by this
        # aval — reading the attribute would silently bake the
        # construction-time value into every variant. (Tree rounds
        # carry d + 2 draft rows: d proposals + the argmax leaf + the
        # leaf-coverage step.)
        d = dtoks.shape[0] - (2 if tree else 1)
        kc = d + 1 + w  # verify chunk rows: chain + leaves
        tok, pos = dstate["tok"], dstate["pos"]
        active = dstate["active"]
        props = jnp.swapaxes(dtoks[:d], 0, 1)  # (B, d)
        parts = [tok[:, None], props.astype(tok.dtype)]
        if tree:
            parts.append(cands.astype(tok.dtype))  # (B, w) leaf rows
        chunk = jnp.concatenate(parts, axis=1)  # (B, kc)
        # Chain rows embed at their own offsets; leaf rows share the
        # post-chain logical position d + 1 (their physical cache slots
        # d + 1 .. d + w stay distinct — the tree mask's contract).
        offs = jnp.minimum(jnp.arange(kc), d + 1)
        pos_ids = pos[:, None] + offs[None, :]
        x = self._embed.apply(
            variables["embed"], chunk, pos_ids, method="embed_positions"
        )
        new_caches = []
        for name, block, pool in zip(
            self.lm.block_names, self._blocks, caches
        ):
            x, pool = block.apply(
                variables[name], x, pool, table, pos,
                attn_impl=self._kernel.attn_impl, tree_tail=w,
                head_shard=self._head_shard(),
                method="verify_chunk_paged",
            )
            new_caches.append(pool)
        logits = self._head.apply(variables["head"], x)  # (B, kc, V)
        preds = jnp.argmax(logits, axis=-1).astype(tok.dtype)
        lps = chosen_logprob(
            logits.reshape(-1, logits.shape[-1]), preds.reshape(-1)
        ).reshape(preds.shape)  # (B, kc)
        acc = accept_speculation(props, preds[:, : d + 1])  # (B,)
        if sample:
            nd = d + 1
            vocab = logits.shape[-1]
            temps = dstate["temp"]
            greedy = temps == 0.0
            kbase, nkeys = dstate["kbase"], dstate["nkeys"]
            # Key discipline matches _step_chunk: the token committed
            # at stream offset j consumes the key at kbase + j (kbase
            # advances by ncommit below). Each key splits once into an
            # acceptance subkey and a resample subkey.
            cursor = jnp.clip(
                kbase[:, None] + jnp.arange(nd)[None, :], 0,
                (nkeys - 1)[:, None],
            )
            skeys = jnp.take_along_axis(
                dstate["keys"], cursor[:, :, None], axis=1
            )  # (B, nd, 2)
            subkeys = jax.vmap(jax.vmap(jax.random.split))(skeys)
            k_acc, k_res = subkeys[:, :, 0, :], subkeys[:, :, 1, :]
            lg = (
                logits[:, :nd]
                / jnp.maximum(temps, 1e-6)[:, None, None]
            )
            flat = lg.reshape(-1, vocab)
            if truncate:
                flat = self._truncate_rows(
                    flat, jnp.repeat(dstate["top_k"], nd)
                )
            if nucleus:
                flat = nucleus_filter(
                    flat, jnp.repeat(dstate["top_p"], nd)
                )
            lgp = flat.reshape(lg.shape)  # processed logits (B, nd, V)
            p_prop = jnp.take_along_axis(
                jax.nn.log_softmax(lgp[:, :d], axis=-1),
                props[:, :, None].astype(jnp.int32), axis=2,
            )[..., 0]  # (B, d): log p_target(proposal_j)
            u = jax.vmap(jax.vmap(jax.random.uniform))(k_acc)  # (B, nd)
            ok = u[:, :d] < jnp.exp(p_prop)
            cum = jnp.cumprod(ok.astype(jnp.int32), axis=1)  # (B, d)
            acc_s = jnp.sum(cum, axis=1)
            # Residual for chain rows: proposal mass removed (a
            # proposal that is the only surviving token has p = 1, is
            # always accepted, and its empty residual is never read).
            # Row d has no proposal — a fresh full-distribution draw.
            res = jnp.where(
                jnp.arange(vocab)[None, None, :]
                == props[:, :, None].astype(jnp.int32),
                -jnp.inf, lgp[:, :d],
            )
            alt = jax.vmap(jax.vmap(jax.random.categorical))(
                k_res, jnp.concatenate([res, lgp[:, d:]], axis=1)
            ).astype(tok.dtype)  # (B, nd)
            out_s = jnp.concatenate(
                [
                    jnp.where(
                        cum.astype(bool), props.astype(tok.dtype),
                        alt[:, :d],
                    ),
                    alt[:, d:],
                ],
                axis=1,
            )  # (B, nd)
            lps_s = chosen_logprob(
                logits[:, :nd].reshape(-1, vocab), out_s.reshape(-1)
            ).reshape(out_s.shape)  # raw-logit scoring, like _step_chunk
            sel = greedy[:, None]
            preds = jnp.concatenate(
                [jnp.where(sel, preds[:, :nd], out_s), preds[:, nd:]],
                axis=1,
            )
            lps = jnp.concatenate(
                [jnp.where(sel, lps[:, :nd], lps_s), lps[:, nd:]],
                axis=1,
            )
            acc = jnp.where(greedy, acc, acc_s)
        out_preds, out_lps = preds, lps
        if tree:
            # Bonus acceptance: full chain + correction token == a leaf
            # candidate -> the leaf's K/V is in cache and the target's
            # prediction after it commits too.
            corr = preds[:, d]  # target's token for position pos + d + 1
            match = cands.astype(corr.dtype) == corr[:, None]  # (B, w)
            hit = jnp.logical_and(acc == d, jnp.any(match, axis=1))
            if sample:
                # Sampled rows take no tree bonus: the leaf's cached
                # K/V and the post-leaf prediction are argmax
                # artifacts — committing them would bias the stream.
                hit = jnp.logical_and(hit, greedy)
            s = jnp.argmax(match, axis=1)  # first matching leaf
            leaf_row = d + 1 + s
            bonus_tok = jnp.take_along_axis(
                preds, leaf_row[:, None], axis=1
            )[:, 0]
            bonus_lp = jnp.take_along_axis(
                lps, leaf_row[:, None], axis=1
            )[:, 0]
            out_preds = jnp.concatenate(
                [preds[:, : d + 1], bonus_tok[:, None]], axis=1
            )  # (B, d+2)
            out_lps = jnp.concatenate(
                [lps[:, : d + 1], bonus_lp[:, None]], axis=1
            )
            # Canonicalize the accepted leaf's cache entry: move leaf s
            # from physical pos + d + 1 + s to pos + d + 1. Rows with
            # s == 0, no hit, or inactive reduce to an identity
            # self-copy at a safe position (dead rows target the trash
            # page — the ordinary garbage discipline).
            do = jnp.logical_and(hit, jnp.logical_and(s > 0, active))
            base = jnp.maximum(pos, 0) + d + 1
            p_dst = jnp.where(do, base, 0)
            p_src = jnp.where(do, base + s, 0)
            pg = self._page
            phys_dst = jnp.take_along_axis(
                table, (p_dst // pg)[:, None], axis=1
            )[:, 0]
            phys_src = jnp.take_along_axis(
                table, (p_src // pg)[:, None], axis=1
            )[:, 0]
            off_dst, off_src = p_dst % pg, p_src % pg

            def fix(pool):
                vec = pool[phys_src, :, off_src, :]  # (B, kvh, wd)
                return append_kv_paged(
                    pool, vec[:, :, None, :], phys_dst[:, None],
                    off_dst[:, None],
                )

            new_caches = jax.tree.map(fix, new_caches)
            acc = acc + hit.astype(acc.dtype)
        ncommit = acc + 1
        last = jnp.take_along_axis(out_preds, acc[:, None], axis=1)[:, 0]
        # Optimistic device-side advance, exactly _step_chunk's
        # discipline: a surviving slot's entry invariants land on
        # pos + ncommit; retired slots are cleared host-side
        # (_clear_slot) before the next tick; idle rows re-park.
        new = dict(dstate)
        new["pos"] = jnp.where(active, pos + ncommit, self._idle_pos)
        new["tok"] = jnp.where(active, last, 0)
        new["kbase"] = jnp.where(active, dstate["kbase"] + ncommit, 0)
        return (
            jnp.swapaxes(out_preds, 0, 1),
            jnp.swapaxes(out_lps, 0, 1),
            acc,
            self._shard_kv(new_caches),
            self._repl_state(new),
        )

    @partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("epoch",),
        donate_argnums=(1,),
    )
    def _adopt_pages(self, caches, pages, kvs, *, epoch=0):
        """Scatter STREAMED page-major KV chunks into the pool — the
        disaggregated-handoff landing program (``runtime/disagg`` ->
        :meth:`adopt_prefill_pages`). ``pages`` (nb,) physical page
        ids (power-of-two padded; pad entries point at the trash
        page), ``kvs`` mirrors ``caches``' per-block structure
        with leaves ``(nb, kvh, page, w)`` already PLACED to the
        pool's sharding by the ``KVHandoffPlan`` — so under a
        head-sharded mesh this scatter is fully shard-local (each
        device writes only its resident heads; no collective, no
        replicated staging). One program for all blocks; specializes
        per page-count bucket (log2 variants)."""
        caches = self._shard_kv(caches)
        kvs = self._shard_kv(kvs)
        out = jax.tree.map(
            lambda pool, kv: pool.at[pages].set(kv.astype(pool.dtype)),
            caches,
            kvs,
        )
        return self._shard_kv(out)

    @partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("epoch",),
        donate_argnums=(1,),
    )
    def _fork_page(self, caches, srcdst, *, epoch=0):
        """Copy-on-write fork: duplicate ONE physical page — every
        block, every plane of a quantized ``(values, k_scales,
        v_scales)`` pool, so the copy's scales travel with its int8
        values — from
        ``srcdst[0]`` into ``srcdst[1]``. The destination is a fan-out
        sibling's freshly allocated private copy of its group's last
        shared prompt page, taken at admission because the sibling's
        decode is about to WRITE into that page (the eager moment of
        "fork on first write": every sibling writes at its first
        step). Pure pool gather/scatter, no forward pass; shard-local
        under a head-sharded mesh (each device copies only its
        resident heads). One compiled variant ever — there is no
        static shape axis."""
        caches = self._shard_kv(caches)
        src, dst = srcdst[0], srcdst[1]
        out = jax.tree.map(
            lambda pool: pool.at[dst].set(pool[src]), caches
        )
        return self._shard_kv(out)

    def adopt_prefill_pages(self, prompt, blocks, page_size: int,
                            quantized) -> int:
        """Land a disaggregated prefill's KV pages in this batcher's
        pool THROUGH THE PREFIX CACHE — the decode-side half of the
        ``runtime/disagg`` handoff. ``blocks`` is one entry per decoder
        block in the pool's own format (``runtime/paged.
        alloc_kv_pools``): a page-major ``(n, kvh, page, 2 * hd)`` host
        array of fused K|V rows (or a ``(values, k_scales, v_scales)``
        tuple of them for quantized pools), holding the K/V of
        ``prompt``'s first ``n`` FULL pages exactly as this batcher's own chunked prefill would have
        written them.

        Pages register under the same content keys the admission
        prefix probe computes (``Pager.prefix_key``), park rc=0 in the
        prefix LRU, and their bytes scatter in via :meth:`_adopt_pages`
        — so a subsequent :meth:`submit` of the same prompt admits as
        a PREFIX-CACHE HIT and prefills only the suffix (the partial
        last page + first-token sampling). That reuse of the existing
        insertion path is what makes int8 pools (both members move
        under one :class:`~adapt_tpu.parallel.sharding.KVHandoffPlan`)
        and speculative mode (the draft prefills decode-side as
        always) compose with disaggregation for free, and keeps greedy
        streams bit-identical to the collocated path.

        Returns the number of pages actually adopted: already-resident
        keys dedupe (first writer won), and pool pressure adopts
        NOTHING (all-or-nothing, like admission) — the caller just
        submits and the request collocates its own prefill. Raises
        ``ValueError`` on geometry mismatches (page size,
        quantization, block count/shapes) — a malformed handoff must
        fail by name, never scatter garbage into live pages."""
        self._require("a handoff of prefilled pages")
        # The device-lost gate tick() runs: a handoff landing between
        # ticks must not device_put shard slices onto a dead device or
        # dispatch the adoption program at a stale mesh epoch (the
        # disaggregated server lands handoffs BEFORE its decode tick).
        self._ensure_mesh()
        if page_size != self._page:
            raise ValueError(
                f"handoff page size {page_size} != pool page size "
                f"{self._page}"
            )
        # ``quantized`` is the sender's kv dtype: a legacy bool (True =
        # int8) or the dtype string — int4 handoffs must land in int4
        # pools (the packed value width is part of the wire geometry).
        sender_dt = (
            quantized
            if isinstance(quantized, str)
            else ("int8" if quantized else "native")
        )
        if sender_dt != self._kv_dtype:
            raise ValueError(
                f"handoff kv dtype {sender_dt!r} but pool "
                f"kv_cache_dtype is {self._kv_dtype!r}"
            )
        if len(blocks) != len(self._blocks):
            raise ValueError(
                f"handoff has {len(blocks)} blocks, model has "
                f"{len(self._blocks)}"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(np.shape(pool_values(blocks[0]))[0])
        if n < 1 or n > (prompt.shape[0] - 1) // self._page:
            raise ValueError(
                f"handoff covers {n} pages; prompt of "
                f"{prompt.shape[0]} tokens shares at most "
                f"{(prompt.shape[0] - 1) // self._page} full pages"
            )
        # EVERY block's geometry validates BEFORE any pager mutation:
        # adopt_cached registers prefix keys, and raising after it
        # would leave content keys pointing at never-written pages —
        # the next same-prefix admission would prefix-hit garbage.
        for b, (pool, block) in enumerate(zip(self._caches, blocks)):
            if isinstance(block, tuple) != self._kv_quant:
                raise ValueError(
                    f"handoff block {b}: "
                    f"{'tuple' if isinstance(block, tuple) else 'array'}"
                    f" in a "
                    f"{'quantized' if self._kv_quant else 'native'}"
                    " pool"
                )
            leaves, planes = pool_planes(block), pool_planes(pool)
            if len(leaves) != len(planes):
                raise ValueError(
                    f"handoff block {b}: {len(leaves)} planes, the "
                    f"pool has {len(planes)}"
                )
            for li, (plane, leaf) in enumerate(zip(planes, leaves)):
                # A handed page is one page of the pool's own plane
                # (runtime/paged.alloc_kv_pools: the fused K|V row,
                # packed lanes for int4; one f32 per vector in a scale
                # plane).
                want = (n,) + tuple(plane.shape[1:])
                if tuple(np.shape(leaf)) != want:
                    raise ValueError(
                        f"handoff block {b}[{li}] shape "
                        f"{tuple(np.shape(leaf))} != expected {want}"
                    )
        keys = [
            Pager.prefix_key(prompt, (j + 1) * self._page)
            for j in range(n)
        ]
        adopted = self._pager.adopt_cached(keys)
        if not adopted:
            return 0
        ords = [i for i, _ in adopted]
        pages = [p for _, p in adopted]
        na = len(ords)
        nb = 1
        while nb < na:
            nb *= 2

        def select(kv):
            kv = np.asarray(kv)
            if na == nb and na == n:
                return kv  # common case: everything fresh, no copy
            out = np.zeros((nb,) + kv.shape[1:], kv.dtype)
            out[:na] = kv[ords]
            return out

        plan = plan_kv_handoff(
            self._kv_sharding if self._mesh is not None else self._repl
        )
        placed = [
            jax.tree.map(select, pair) for pair in blocks
        ]
        placed = [plan.place_tree(pair) for pair in placed]
        # Transfer accounting: one logical staging per placed leaf plus
        # the page-id vector (the same O(1)-per-event contract as
        # admission staging; steady ticks stay at zero), and the
        # plan's host->device byte count as a counter — per-shard
        # slices sum to the logical bytes, i.e. logical/tp per device.
        self._h2d_count += sum(
            len(jax.tree.leaves(pair)) for pair in placed
        )
        global_metrics().inc(
            "disagg.adopt_staged_bytes", float(plan.staged_bytes)
        )
        pages_dev = self._h2d(
            np.asarray(pages + [0] * (nb - na), np.int32)
        )
        self._variants.setdefault("continuous.adopt_pages", set()).add(nb)
        self._caches = self._adopt_pages(
            self._caches, pages_dev, placed, epoch=self._mesh_epoch
        )
        return na

    def _sp_admit(self, req: "_Request") -> None:
        """Sequence-parallel prefill of one long admission: run the
        sp-sharded whole-span program (``parallel/sp_prefill``) and
        land its page-major blocks through :meth:`adopt_prefill_pages`
        — the disaggregated-handoff landing path, loopbacked in
        process — so the admission below then prefix-hits every full
        page and pays only the suffix pass. Failures degrade to the
        ordinary (chunked) prefill: sp is an optimization, never a
        correctness gate."""
        s0 = req.prompt.shape[0]
        m = self._sp.covers(s0)
        if m < 1:
            return
        if self.prefix_cached(req.prompt) >= m:
            return  # hierarchy-resident: nothing to compute
        tracer = global_tracer()
        t0 = tracer.now() if tracer.enabled else 0.0
        try:
            # span=False: batcher.sp_prefill below is the tracer row.
            with self._eobs.region("sp_prefill", span=False):
                n, blocks = self._sp.prefill(req.prompt)
                adopted = self.adopt_prefill_pages(
                    req.prompt, blocks, self._page,
                    self._kv_dtype if self._kv_quant else False,
                )
        except Exception:  # noqa: BLE001 — degrade, never wedge
            log.exception(
                "sp prefill failed for request %d; admission falls "
                "back to the chunked path", req.req_id,
            )
            global_flight_recorder().record(
                "sp_prefill", request=req.req_id, pages=0,
                sp=self._sp.sp, ok=False,
            )
            self._sp_failures += 1
            if self._sp_failures >= 3:
                # Deterministic failure (a dead ring-only device, a
                # broken placement): stop paying a doomed dispatch per
                # long admission — retire the ring until a recovery
                # rebuilds it.
                log.warning(
                    "sp prefill disabled after %d consecutive "
                    "failures", self._sp_failures,
                )
                self._sp.close()
                self._sp = None
                global_metrics().set_gauge("prefill.sp_width", 1.0)
            return
        self._sp_failures = 0
        with self._cv:
            self._sp_prefills += 1
        # The sp tier computed n full pages of prompt positions — the
        # same prefill-work accounting as an in-tick chunk pass.
        self._count_prefill(n * self._page)
        if tracer.enabled:
            tracer.add_span(
                "batcher.sp_prefill",
                start=t0,
                end=tracer.now(),
                request=req.req_id,
                pages=n,
                adopted=adopted,
                sp=self._sp.sp,
            )
        global_flight_recorder().record(
            "sp_prefill",
            request=req.req_id,
            pages=n,
            adopted=adopted,
            sp=self._sp.sp,
        )

    # -- hierarchical KV cache tier (host-DRAM spill under the Pager) ------

    def _fetch_page_host(self, page: int) -> list:
        """Host copy of one pool page's K/V across every block — the
        spill-side D2H. Per-shard slice fetches assembled on the host
        (``parallel.sharding.fetch_head_shards``): under tp each
        device ships only its resident heads, mirroring the readmit
        side's ``KVHandoffPlan`` per-shard placement — never a
        device-side gather. Pools are functional arrays, so the fetch
        reads the page's last-written bytes even when the allocator
        is about to hand the page to a new owner."""
        idx = int(page)
        return jax.tree.map(
            lambda pool: fetch_head_shards(pool, idx), self._caches
        )

    def _spill_page(self, page: int, key: bytes) -> bool:
        """Capture one rc=0 page into the host tier (budget already
        checked by the caller). Idempotent for keys the tier holds."""
        raw, enc = self._tier.put(key, self._fetch_page_host(page))
        if raw == 0 and enc == 0:
            return False  # already host-resident: no new books
        self._tier_spilled += 1
        reg = global_metrics()
        reg.inc("cache_tier.spilled_total")
        reg.inc("cache_tier.codec_bytes_saved_total", float(raw - enc))
        global_flight_recorder().record(
            "kv_spill", page=int(page), bytes=int(enc), raw_bytes=int(raw)
        )
        return True

    def _on_page_evict(self, page: int, key: bytes) -> None:
        """``Pager.evict_hook``: a registered rc=0 page is leaving the
        pool (its radix node dies with it — the pager already dropped
        the key from the radix index). Every eviction records the
        ``radix_evict`` flight event; with a host tier installed,
        host-backed keys then evict for free while un-backed ones
        spill inside the per-tick budget, or count the content as
        dropped — the watermark pre-spill in :meth:`_tier_step` exists
        to make this branch rare."""
        global_flight_recorder().record(
            "radix_evict",
            page=int(page),
            prefix_tokens=len(key) // 4,  # int32 token-block key
        )
        tier = self._tier
        if tier is None:
            return
        if tier.contains(key):
            return  # content already host-resident: eviction is free
        if self._spill_budget <= 0:
            self._tier_dropped += 1
            global_metrics().inc("cache_tier.dropped_total")
            return
        self._spill_budget -= 1
        self._spill_page(page, key)

    def _tier_step(self) -> None:
        """Proactive watermark spill, run once per tick BEFORE
        admission: when the prefix LRU holds at least
        ``spill_watermark`` of the allocatable pool, back the coldest
        un-backed LRU pages (they evict first) down to the low
        watermark — budget-capped, so the decode tick's tier work is
        bounded whatever the backlog. Only rc=0 LRU pages are ever
        scanned: live slots' pages cannot spill, so lossy cold codecs
        can never touch state a decode still reads from HBM."""
        cfg = self._tier_cfg
        self._spill_budget = cfg.spill_pages_per_tick
        self._readmit_budget = cfg.readmit_pages_per_tick
        # Bridge the tier's own cold-overflow drops (demotions past
        # the host capacity with no disk dir) to the registry counter.
        over = self._tier.dropped - self._tier_drop_seen
        if over:
            global_metrics().inc("cache_tier.dropped_total", float(over))
            self._tier_drop_seen = self._tier.dropped
        alloc = self._pager.num_allocatable
        cached = self._pager.cached_pages()
        if len(cached) < cfg.spill_watermark * alloc:
            return
        # Back the coldest `need` pages: everything that would have to
        # evict to bring the LRU down to the low watermark. (Guard the
        # slice: a negative `need` must mean "nothing", not a slice
        # off the wrong end of the LRU.)
        need = len(cached) - int(cfg.spill_low_watermark * alloc)
        if need <= 0:
            return
        for page, key in cached[:need]:
            if self._spill_budget <= 0:
                break
            if self._tier.contains(key):
                continue
            self._spill_budget -= 1
            self._spill_page(page, key)

    def _maybe_readmit(self, req: "_Request") -> int:
        """The admission probe's host-tier consult: before the prefix
        probe declares a miss, readmit the request's longest run of
        host-resident prefix pages back into the pool — decoded from
        the tier, landed through the SAME ``Pager.adopt_cached`` +
        :meth:`_adopt_pages` path as a disaggregated handoff
        (epoch-carrying, tp-sharded per-shard placement), so the probe
        then shares them as ordinary prefix hits. Budgeted per tick;
        pool pressure readmits nothing (recompute is always correct).
        Returns the number of pages readmitted."""
        tier = self._tier
        if tier is None or self._readmit_budget <= 0:
            return 0
        P = self._page
        s0 = req.prompt.shape[0]
        keys: list[bytes] = []
        blocks_list: list[list] = []
        for j in range((s0 - 1) // P):
            key = Pager.prefix_key(req.prompt, (j + 1) * P)
            if self._pager.resident(key):
                continue  # probe will share it without our help
            if len(keys) >= self._readmit_budget:
                break
            blocks = tier.get(key)
            if blocks is None:
                break  # true miss — later pages can't extend the run
            keys.append(key)
            blocks_list.append(blocks)
        if not keys:
            return 0
        adopted = self._pager.adopt_cached(keys)
        if not adopted:
            return 0  # pool pressure — admission recomputes instead
        ords = [i for i, _ in adopted]
        pages = [p for _, p in adopted]
        na = len(ords)
        nb = 1
        while nb < na:
            nb *= 2

        def stack(*leaves):
            out = np.zeros((nb,) + leaves[0].shape, leaves[0].dtype)
            for t, j in enumerate(ords):
                out[t] = leaves[j]
            return out

        placed = [
            jax.tree.map(stack, *[bl[b] for bl in blocks_list])
            for b in range(len(self._blocks))
        ]
        plan = plan_kv_handoff(
            self._kv_sharding if self._mesh is not None else self._repl
        )
        placed = [plan.place_tree(pair) for pair in placed]
        self._h2d_count += sum(
            len(jax.tree.leaves(pair)) for pair in placed
        )
        pages_dev = self._h2d(
            np.asarray(pages + [0] * (nb - na), np.int32)
        )
        self._variants.setdefault("continuous.adopt_pages", set()).add(nb)
        self._caches = self._adopt_pages(
            self._caches, pages_dev, placed, epoch=self._mesh_epoch
        )
        self._readmit_budget -= na
        self._tier_readmitted += na
        reg = global_metrics()
        reg.inc("cache_tier.readmitted_total", float(na))
        global_flight_recorder().record(
            "kv_readmit",
            request=req.req_id,
            pages=na,
            staged_bytes=int(plan.staged_bytes),
        )
        return na

    def prefix_cached(self, prompt) -> int:
        """Leading FULL pages of ``prompt`` servable from the cache
        HIERARCHY without recompute: the longest run of prefix keys
        that are HBM-resident or (when a cache tier is configured)
        host-spilled. Read-only — no shares taken, no readmits, no
        probe accounting moved; the number a prefix-affinity router
        or capacity audit wants (``benchmarks/load/tier_smoke``
        measures the host tier's servable-prefix multiplier with
        it)."""
        self._require("the radix prefix cache")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = 0
        for j in range((prompt.shape[0] - 1) // self._page):
            key = Pager.prefix_key(prompt, (j + 1) * self._page)
            if self._pager.resident(key) or (
                self._tier is not None and self._tier.contains(key)
            ):
                n += 1
            else:
                break
        return n

    def _insert_paged(self, caches, pages, kvs):
        """Scatter a prefilled request's per-block K/V into its pages
        (``runtime/paged.insert_prefill_pages`` per plane; ``pages`` is
        one list, or one a cache group). tree.map
        reaches the (values, k_scales, v_scales) planes of quantized
        pools and the one fused plane of native ones alike — a scale
        plane scatters by the same page list, so the pages' scales
        always travel with their int8 values (prefix sharing
        included)."""
        return [
            jax.tree.map(
                lambda pool, kv, i=i: insert_prefill_pages(
                    pool, self._table_of(pages, i), kv
                ),
                cache, kv,
            )
            for i, (cache, kv) in enumerate(zip(caches, kvs))
        ]

    def _first_pick(self, h_last, variables, keys, temp, top_k, top_p,
                    greedy, truncate, nucleus):
        """Shared first-token sampling tail of both prefill flavors —
        the exact knob semantics of ``submit`` (one body, cannot
        fork)."""
        logits = self._head.apply(variables["head"], h_last)[:, 0]
        pick_greedy = jnp.argmax(logits, axis=-1)
        lg = logits / jnp.maximum(temp, 1e-6)
        if truncate:
            lg = self._truncate_rows(lg, top_k[None])
        if nucleus:
            lg = nucleus_filter(lg, top_p[None])
        sampled = jax.vmap(jax.random.categorical)(keys, lg)
        first = jnp.where(greedy, pick_greedy, sampled)
        return first, chosen_logprob(logits, first)

    def _prefill_fn(self, bucket: int):
        """Jitted prefill for one prompt bucket: full causal forward over
        (1, bucket), logits at the TRUE last position, per-block K/V to
        insert into a slot and, last, the recurrent ``(state, tail)`` of
        each block that has one (``_insert_state``; an empty tuple
        otherwise)."""
        if bucket in self._prefill_cache:
            return self._prefill_cache[bucket]

        # Fused scalar staging: the per-request sampling knobs ride as
        # ONE int vector + ONE float vector (ints = [true_len, top_k],
        # floats = [temp, top_p]; greedy derives from temp == 0, the
        # submit() normalization) instead of a jnp.asarray per field.
        # ``ids`` is NOT donated: int32 staging can never alias the f32
        # outputs, so donating it is only an XLA warning per compile.
        @partial(jax.jit, static_argnames=("truncate", "nucleus"))
        def prefill(variables, ids, ints, floats, keys, *, truncate,
                    nucleus):
            h = self._embed.apply(variables["embed"], ids)
            kvs, states = [], []
            for i, (name, block) in enumerate(
                zip(self.lm.block_names, self._blocks)
            ):
                # A block with recurrent state also returns the
                # (state, tail) its LAST REAL position left.
                h, ck, cv, *carried = block.apply(
                    variables[name], h, bucket, None,
                    self._kv_dtype if self._kv_quant else False,
                    method="prefill",
                    **(
                        {"length": ints[0]}
                        if i in self._layout.state_blocks else {}
                    ),
                )
                # The pool's rows (a latent block's are whole: no V).
                kvs.append(ck if cv is None else fuse_kv(ck, cv))
                states.extend(carried)
            h_last = lax.dynamic_index_in_dim(h, ints[0] - 1, 1)
            first, first_lp = self._first_pick(
                h_last, variables, keys, floats[0], ints[1], floats[1],
                floats[0] == 0.0, truncate, nucleus,
            )
            return first, first_lp, self._shard_kv(kvs), tuple(states)

        self._prefill_cache[bucket] = prefill
        return prefill

    def _prefill_suffix_fn(self, sbucket: int, n_strip: int,
                           sample: bool = True):
        """Jitted INCREMENTAL prefill pass over a paged window: positions
        [pos0, pos0 + true_len) run the forward against everything
        already cached before them, IN PLACE — each block writes the
        chunk's K/V into its own pages (one O(chunk) scatter) and
        attends the window page by page
        (``models.prefill_chunk_paged`` -> ``paged_chunk_attention``,
        per-row causal mask). No gathered strip, no scatter-back: pass
        traffic is O(window) reads + O(chunk) writes.

        Two callers, one body: the prefix-cache hit (single pass,
        ``sample=True``) and chunked prefill (every pass but the last
        uses ``sample=False`` and returns a dummy token). Specializes
        per (chunk bucket, window pages, sample) — chunked callers pad
        the page list to powers of two, so a long prompt compiles log2
        variants."""
        key = ("suffix", sbucket, n_strip, sample)
        if key in self._prefill_cache:
            return self._prefill_cache[key]

        # Fused scalar staging (same scheme as _prefill_fn): ints =
        # [pos0, true_len, top_k], floats = [temp, top_p]. The caches
        # are donated (they alias in place); ids staging is not (int32
        # can't alias the outputs — donation would only warn).
        @partial(jax.jit, static_argnames=("truncate", "nucleus"),
                 donate_argnums=(1, 7))
        def prefill(variables, caches, pages, ids, ints, floats, keys,
                    states=None, *, truncate, nucleus):
            # ``states`` (a model with recurrent state; ints then ends
            # with the SLOT): the pass starts from the slot's own
            # (state, tail), an empty one at position 0, and leaves the
            # one after its last real position there.
            caches = self._shard_kv(caches)
            pos0 = ints[0]
            pos_ids = pos0 + jnp.arange(sbucket)[None]
            h = self._embed.apply(
                variables["embed"], ids, pos_ids, method="embed_positions"
            )
            new_caches, new_states = [], []
            for i, (name, block, pool) in enumerate(zip(
                self.lm.block_names, self._blocks, caches
            )):
                kw = {}
                if i in self._layout.state_blocks:
                    kw = {"length": ints[1], "carried": jax.tree.map(
                        lambda s: jnp.where(
                            pos0 == 0, jnp.zeros((), s.dtype),
                            lax.dynamic_slice_in_dim(s, ints[3], 1),
                        ),
                        self._carried(states, i)["carried"],
                    )}
                h, pool, *carried = block.apply(
                    variables[name], h, pool,
                    self._table_of(pages, i), pos0,
                    head_shard=self._head_shard(),
                    method="prefill_chunk_paged", **kw,
                )
                new_caches.append(pool)
                new_states.extend(carried)
            new_caches = self._shard_kv(new_caches)
            if states is not None:
                states = self._write_state(states, ints[3], new_states)
            if not sample:  # mid-prefill pass: no token yet
                return (jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1,), jnp.float32), new_caches, states)
            h_last = lax.dynamic_index_in_dim(h, ints[1] - 1, 1)
            first, first_lp = self._first_pick(
                h_last, variables, keys, floats[0], ints[2], floats[1],
                floats[0] == 0.0, truncate, nucleus,
            )
            return first, first_lp, new_caches, states

        self._prefill_cache[key] = prefill
        return prefill

    def _draft_prefill_fn(self, bucket: int):
        """Jitted DRAFT prefill for one prompt bucket: full causal
        forward over (1, bucket), per-block K/V to insert into the
        draft's dense slot strips. No sampling tail — the draft never
        emits; it only seeds its cache for the per-tick draft scan.
        int8 draft weights (``draft_weight_dtype``) dequantize inside
        the jit, mirroring ``draft_chunk``."""
        key = ("draft", bucket)
        if key in self._prefill_cache:
            return self._prefill_cache[key]

        @jax.jit
        def dprefill(variables, ids):
            variables = dequantize_params(variables)
            h = self._draft_embed.apply(variables["embed"], ids)
            kvs = []
            for name, block in zip(
                self._draft_lm.block_names, self._draft_blocks
            ):
                h, ck, cv = block.apply(
                    variables[name], h, bucket, None, False,
                    method="prefill",
                )
                kvs.append((ck, cv))
            return kvs

        self._prefill_cache[key] = dprefill
        return dprefill

    def _admit_draft(self, slot_idx: int, req: _Request) -> None:
        """Prefill the DRAFT model's whole prompt into its dense slot
        row. Always the full prompt: the draft has no prefix cache and
        no chunked prefill — it is small by construction, so one
        bucketed pass per admission is the entire cost of keeping its
        cache in lockstep with the target's committed stream."""
        s0 = req.prompt.shape[0]
        bucket = next(b for b in self.prompt_buckets if b >= s0)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :s0] = req.prompt
        kvs = self._draft_prefill_fn(bucket)(
            self._draft_variables, self._h2d(ids)
        )
        self._variants.setdefault("continuous.insert", set()).add(bucket)
        self._draft_caches = self._insert(
            self._draft_caches, self._h2d(np.int32(slot_idx)), kvs
        )

    @partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _insert(self, caches, slot, kvs):
        """Write a prefilled request's DRAFT K/V into row ``slot`` of
        the draft's dense strips (the target's K/V lands in pages:
        ``_insert_paged``)."""
        return [
            jax.tree.map(
                lambda c, n: lax.dynamic_update_slice(
                    c, n.astype(c.dtype), (slot, 0, 0, 0)
                ),
                c_pair,
                n_pair,
            )
            for c_pair, n_pair in zip(caches, kvs)
        ]

    @staticmethod
    def _write_state(states, slot, new):
        """Row ``slot`` of every ``(state, tail)`` <- ``new``'s one
        row, whole."""
        return jax.tree.map(
            lambda s, n: lax.dynamic_update_slice_in_dim(
                s, n.astype(s.dtype), slot, 0
            ),
            tuple(states), tuple(new),
        )

    @partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _insert_state(self, states, slot, new):
        """Write a prefilled request's recurrent state into its slot
        (the whole-prompt admission's twin of ``_insert_paged``)."""
        return self._write_state(states, slot, new)

    def _count_state_write(self, carried: bool) -> None:
        """Book one write of a slot's recurrent state (an admission or
        a chunk pass) and whether the pass began from a carried one."""
        for family in self._state_families:
            global_metrics().inc(f"{family}.state_writes")
            if carried:
                global_metrics().inc(f"{family}.chunks_carried")

    # -- request lifecycle -------------------------------------------------

    def validate_request(
        self,
        prompt,
        steps: int,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        rng=None,
        stop: list | None = None,
        slo: SLOSpec | None = None,
    ) -> tuple[np.ndarray, int | None]:
        """Raise exactly the errors :meth:`submit` would for these
        arguments, without queueing anything — THE one validation
        body. The disaggregated submit path (``runtime/disagg``) calls
        it up front so a bad request fails synchronously like a
        collocated one, instead of minutes later at handoff landing —
        and a future rule added here automatically covers both paths.
        Returns the normalized ``(int32, 1-D)`` prompt and the
        effective ``top_k`` (request's, or the batcher default)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        s0 = prompt.shape[0]
        if s0 < 1:
            raise ValueError("empty prompt")
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if s0 + steps > self.lm.max_len:
            raise ValueError(
                f"prompt {s0} + steps {steps} exceeds max_len "
                f"{self.lm.max_len}"
            )
        if s0 > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt {s0} exceeds largest bucket "
                f"{self.prompt_buckets[-1]}"
            )
        bucket = next(b for b in self.prompt_buckets if b >= s0)
        need = -(
            -max(bucket, s0 + steps + self._spec_k + self._spec_w)
            // self._page
        )
        if need > self._pool_pages - 1:  # page 0 is trash
            # Would queue forever: the pool can never cover it.
            raise ValueError(
                f"request needs {need} pages but the pool holds "
                f"{self._pool_pages - 1} allocatable"
            )
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature > 0 requires an rng key")
        top_k_eff = top_k if top_k is not None else self.top_k
        if top_k_eff is not None and not (1 <= top_k_eff <= self.lm.vocab):
            raise ValueError(
                f"top_k {top_k_eff} outside [1, {self.lm.vocab}]"
            )
        if top_p is not None and not (0.0 < top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if stop is not None and any(len(seq) == 0 for seq in stop):
            raise ValueError("stop sequences must be non-empty")
        if slo is not None and not isinstance(slo, SLOSpec):
            raise TypeError(
                f"slo must be a config.SLOSpec, got {type(slo).__name__}"
            )
        return prompt, top_k_eff

    def submit(
        self,
        prompt,
        steps: int,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        eos_id: int | None = None,
        rng: jax.Array | None = None,
        stop: list | None = None,
        on_token: Callable[[int, int, int], None] | None = None,
        slo: SLOSpec | None = None,
        t_submit: float | None = None,
        _fanout: int = -1,
    ) -> int:
        """Queue one request; returns its id. ``slo`` (optional
        ``config.SLOSpec``) attaches a latency budget: TTFT is judged
        once at the first emitted token, ITL at every later commit,
        feeding the ``slo.*`` attainment metrics, the per-tenant
        met/missed counters and ``continuous.goodput_tokens_s``
        (evaluation rides the ``obs_timeline`` gate — host arithmetic
        on stamps already taken, nothing device-side).
        ``on_token`` (optional
        ``callable(req_id, token, index)``) streams each committed
        token as it lands — invoked on the TICKING thread at commit
        time (chunk granularity: up to ``chunk`` callbacks per tick),
        so keep it cheap and thread-safe. Exceptions poison the tick:
        synchronous drivers see them directly; under :meth:`start` the
        server stops and every ``result()`` waiter re-raises the
        callback's exception (never a silent timeout).
        ``stop`` is a list of
        token-id sequences: the stream ends at the first emitted
        occurrence of any of them, stop tokens included — host-side
        truncation, so the emitted prefix still equals solo
        ``generate()``. ``prompt`` is a 1-D token
        id sequence; ``top_k`` overrides the batcher default for this
        request. The sampling-key schedule matches ``generate`` for a
        solo batch, so outputs are reproducible against it.
        ``t_submit`` (perf-counter clock) overrides the lifecycle
        anchor for requests that entered the SERVING SYSTEM earlier
        than this call — the disaggregated submit path
        (``runtime/disagg``) passes the server-level arrival stamp so
        queue-wait/TTFT/SLO verdicts stay end-to-end honest instead of
        starting the clock after the prefill tier already ran."""
        prompt, top_k_eff = self.validate_request(
            prompt, steps, temperature=temperature, top_k=top_k,
            top_p=top_p, rng=rng, stop=stop, slo=slo,
        )
        s0 = prompt.shape[0]
        do_sample = temperature > 0.0
        if rng is None:
            rng = jax.random.PRNGKey(0)
        if do_sample:
            # generate()'s exact schedule: split -> key0 + per-step
            # keys, each folded with the row index (0 — solo
            # semantics). One vmapped dispatch + one host fetch, not
            # O(steps) of them — this runs on the serving control path.
            rng_next, key0 = jax.random.split(rng)
            if steps > 1:
                step_keys = jnp.concatenate(
                    [key0[None], jax.random.split(rng_next, steps - 1)]
                )
            else:
                step_keys = key0[None]
            folded = np.asarray(
                jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    step_keys, 0
                )
            )
        else:
            # Greedy requests never read a sampling key (the step's
            # sampled pick is discarded by ``jnp.where(greedy, ...)``,
            # and the first-token tail does the same), so skip the
            # schedule build entirely: one zero key row stages as the
            # whole schedule (nkeys=1; the cursor clips to it). This
            # matters beyond tidiness: ``split(rng, steps-1)`` compiles
            # one variant PER DISTINCT steps VALUE, so a greedy load
            # with heavy-tailed output lengths (benchmarks/load) was
            # paying an XLA compile on the submit path for every new
            # length — a multi-second stall of the tick loop that
            # measured as fake ITL.
            folded = np.zeros((1, 2), np.uint32)
        with self._cv:
            req_id = self._next_id
            self._next_id += 1
        req = _Request(
            req_id=req_id,
            prompt=prompt,
            steps=steps,
            temperature=float(temperature) if do_sample else 0.0,
            # Greedy requests discard the sampled pick entirely —
            # normalize their knobs to the identity values so they never
            # force the truncate/nucleus sorts (or variant recompiles)
            # onto a tick.
            top_k=(
                top_k_eff
                if do_sample and top_k_eff is not None
                else self.lm.vocab
            ),
            top_p=top_p if do_sample and top_p is not None else 1.0,
            eos_id=eos_id,
            folded_keys=folded,
            stop=tuple(
                tuple(int(t) for t in seq) for seq in (stop or ())
            ),
            on_token=on_token,
            t_submit=(
                t_submit if t_submit is not None else time.perf_counter()
            ),
            slo=slo,
            fanout_group=_fanout,
        )
        if self._capacity is not None:
            # Submit-time TTFT forecast (client thread): the radix
            # probe is a read-only dict walk (same thread stance as
            # prefix_cached), and the forecaster feeds are per-field
            # scalar reads. Stored on the request; its realized TTFT
            # closes the calibration loop at first-token commit.
            req.ttft_forecast_s = self._capacity.forecast_ttft(
                s0, self._pager.radix_probe(prompt)[1]
            )

        def _reject(e: QueueFullError, journaled: bool) -> None:
            self._record_rejection(
                request_tenant(req), request_priority(req), e,
                request=req_id,
            )
            if journaled:
                # Done-mark so a crash recovery cannot resurrect a
                # request the client was told was rejected.
                self._journal_done(req_id)

        # Shed a flood BEFORE paying journal I/O: under sustained
        # overload (the regime rejection exists for) every rejected
        # submit would otherwise serialize its full payload record
        # plus a done mark. The bounded append below stays the
        # authoritative check — this is the same pre-check/backstop
        # split as admission_check's.
        try:
            with self._cv:
                self._queue.check(
                    request_tenant(req), request_priority(req)
                )
        except QueueFullError as e:
            _reject(e, journaled=False)
            raise
        if self._journal is not None:
            # Payload + knobs BEFORE the request becomes reachable: a
            # replay (elastic recovery) or a crash-recovering process
            # reconstructs the request from this record alone. The key
            # schedule is journaled too, so sampled replays re-emit the
            # identical stream.
            try:
                self._journal.record_submit(
                    req_id,
                    prompt,
                    meta={
                        "steps": steps,
                        "temperature": req.temperature,
                        "top_k": req.top_k,
                        "top_p": req.top_p,
                        "eos_id": eos_id,
                        "stop": [list(s) for s in req.stop],
                        "folded_keys": req.folded_keys.tolist(),
                    },
                )
            except Exception as e:  # noqa: BLE001 — serve anyway, loudly
                log.warning(
                    "journal submit failed for %d: %r", req_id, e
                )
        try:
            with self._cv:
                self._queue.append(req)  # bounded: may raise
                self._cv.notify_all()  # wake the server thread, if any
        except QueueFullError as e:
            # Synchronous rejection IS the admission-control contract:
            # the caller learns now — no id ever waits on result().
            _reject(e, journaled=True)
            raise
        global_metrics().inc("scheduler.admitted_total")
        return req.req_id

    def submit_fanout(
        self,
        prompt,
        n: int,
        steps: int,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        eos_id: int | None = None,
        rng: jax.Array | None = None,
        stop: list | None = None,
        on_token: Callable[[int, int, int], None] | None = None,
        slo: SLOSpec | None = None,
    ) -> list[int]:
        """Queue ``n`` continuations of ONE prompt as a copy-on-write
        fan-out group; returns their request ids in submission order.
        Every sibling shares every common prompt page through the
        prefix probe (rc bumps, no copies), and the group keeps the
        first admitted sibling's last prompt page rc-claimed so later
        siblings can FORK it — one device page copy, no suffix forward
        — even after that sibling retired: fan-out of width N costs
        ~1x the shared prefix's pages plus each sibling's private
        decode tail. Greedy (``temperature == 0``) siblings are
        bit-identical to ``n`` independent :meth:`submit` calls of the
        same prompt. ``temperature > 0`` requires ``rng``; each
        sibling samples under its own split of it (parallel sampling
        semantics — the streams diverge by design, so sampled
        siblings run the ordinary suffix pass for their own
        first-token logits and share only the full prefix pages).
        ``n == 1`` degrades to a plain submit.
        On a mid-group :class:`QueueFullError` the already-queued
        siblings STAY queued (their ids are lost with the raise — a
        caller that must know them should submit serially); the group
        shrinks to the survivors."""
        self._require("copy-on-write fan-out")
        if n < 1:
            raise ValueError(f"fan-out width must be >= 1, got {n}")
        sib_rngs: list = [None] * n
        if temperature > 0.0:
            if rng is None:
                raise ValueError("temperature > 0 requires an rng key")
            sib_rngs = list(jax.random.split(rng, n))
        elif rng is not None:
            sib_rngs = [rng] * n
        gid = -1
        if n > 1:
            with self._cv:
                gid = self._fanout_next
                self._fanout_next += 1
                self._fanout_groups[gid] = _FanoutGroup(
                    remaining=n, greedy=temperature == 0.0
                )
        ids: list[int] = []
        try:
            for j in range(n):
                ids.append(
                    self.submit(
                        prompt,
                        steps,
                        temperature=temperature,
                        top_k=top_k,
                        top_p=top_p,
                        eos_id=eos_id,
                        rng=sib_rngs[j],
                        stop=stop,
                        on_token=on_token,
                        slo=slo,
                        _fanout=gid,
                    )
                )
        except Exception:
            # Shrink the group by the never-submitted siblings; a
            # group emptied here dies on the CLIENT thread, so any
            # claimed page parks for the ticking thread to release
            # (the admitted-out death inside _admit releases directly).
            if gid >= 0:
                with self._cv:
                    fg = self._fanout_groups.get(gid)
                    if fg is not None:
                        fg.remaining -= n - len(ids)
                        if fg.remaining <= 0:
                            self._fanout_kill_locked(gid, fg)
            raise
        return ids

    def _fanout_kill_locked(
        self, gid: int, fg: _FanoutGroup, direct: bool = False
    ) -> None:
        """Drop an emptied fan-out group (``_cv`` held). The claimed
        page — if any — is released immediately when the caller IS the
        ticking thread (``direct=True``: the admission path, so a
        group that drains with its last sibling leaves no claim
        dangling past the tick); client-thread deaths (queued-sibling
        cancel, a failed submit_fanout) park it in ``_fanout_release``
        instead — only the ticking thread may move pager rc — and the
        next admission sweep drains the list."""
        if fg.page is not None:
            if direct:
                self._pager.release_claim(fg.page)
            else:
                self._fanout_release.append(fg.page)
            fg.page = None
        self._fanout_groups.pop(gid, None)

    def _fanout_dec_locked(self, req: "_Request") -> None:
        """Consume ``req``'s fan-out membership (``_cv`` held): clear
        the request's group id and shrink the group — admission and
        queued-cancel both land here, so a pool-pressure re-queue
        (group id already cleared) can never double-decrement."""
        gid = req.fanout_group
        if gid < 0:
            return
        req.fanout_group = -1
        fg = self._fanout_groups.get(gid)
        if fg is None:
            return
        fg.remaining -= 1
        if fg.remaining <= 0:
            self._fanout_kill_locked(gid, fg)

    def cancel(self, req_id: int) -> bool:
        """Cancel a request: queued -> dropped with an empty result;
        live (in a slot, or mid-admission on the ticking thread) ->
        retired at the next commit boundary with the partial stream as
        the result. Returns False only for ids never issued or already
        finished; True means "cancel accepted" — best-effort if the
        request finishes concurrently (the stream may complete). The
        whole decision runs under the handoff lock so it cannot race
        admission (queue-pop -> slot assignment happens on the ticking
        thread between lock holds); markers are consumed by _commit /
        the tick boundary / _finish, never leaked."""
        with self._cv:
            if req_id in self._done or not 0 <= req_id < self._next_id:
                return False
            req = self._queue.remove_id(req_id)
            if req is not None:
                # A marker from an earlier cancel of this id (e.g.
                # while it was mid-admission before being re-queued
                # on pool pressure) must not outlive it.
                self._cancelled.discard(req_id)
                # A cancelled fan-out sibling leaves its group; the
                # last leaver kills the group (claimed page released
                # on the ticking thread).
                self._fanout_dec_locked(req)
                # A freshly queued request delivered nothing, but a
                # recovery-replayed one waiting for re-admission
                # already streamed its first life's tokens: result()
                # returns that snapshot, matching what a live cancel
                # after re-admission would return.
                if req.delivered_tokens is not None:
                    self._done[req_id] = req.delivered_tokens
                    self._done_lps[req_id] = req.delivered_lps
                else:
                    self._done[req_id] = np.zeros((0,), np.int32)
                    self._done_lps[req_id] = np.zeros((0,), np.float32)
                self._cv.notify_all()
                global_flight_recorder().record(
                    "cancel", request=req_id, state="queued"
                )
            else:
                # Live = bound to a slot, or mid-admission on the
                # ticking thread (popped, not yet slot-bound). Anything
                # else with a valid id already finished and was claimed.
                live = req_id == self._admitting or any(
                    s.req is not None and s.req.req_id == req_id
                    for s in self.slots
                )
                if not live:
                    return False
                # Mark it; the ticking thread consumes the marker at
                # its next boundary.
                self._cancelled.add(req_id)
                global_flight_recorder().record(
                    "cancel", request=req_id, state="live"
                )
                return True
        # Queued cancel: the done mark's disk write (periodic fsync,
        # possible WAL compaction) must not run under the handoff lock
        # — _finish and _drop_slot keep the same discipline.
        self._journal_done(req_id)
        return True

    # -- traffic control (docs/SERVING.md "Traffic control") ---------------

    def _record_rejection(
        self,
        tenant: str,
        prio: int,
        err: Exception,
        request: int | None = None,
    ) -> None:
        """THE one rejection-bookkeeping body (books + counter +
        ``request_rejected`` flight event) — submit's bounded append,
        its pre-journal check and :meth:`admission_check` all go
        through here, so a new event field cannot silently diverge
        across the three rejection sites."""
        with self._cv:
            self._rejected += 1
        global_metrics().inc("scheduler.rejected_total")
        ev = {
            "tenant": tenant,
            "priority": prio,
            "reason": str(err)[:200],
        }
        if request is not None:
            ev["request"] = request
        global_flight_recorder().record("request_rejected", **ev)

    def admission_check(
        self, slo: SLOSpec | None = None, request: int | None = None
    ) -> None:
        """Raise :class:`~adapt_tpu.runtime.scheduler.QueueFullError`
        iff a :meth:`submit` carrying ``slo`` would be rejected by
        admission control right now, recording the rejection exactly
        like submit does. The disaggregated path
        (``runtime/disagg.DisaggServer``) calls this BEFORE routing a
        request into the prefill tier, so a doomed request fails
        synchronously instead of after its whole prefill ran (the
        landing-time rejection still backs up the race)."""
        tenant = slo.tenant if slo is not None else "default"
        prio = slo.priority if slo is not None else 0
        try:
            with self._cv:
                self._queue.check(tenant, prio)
        except QueueFullError as e:
            self._record_rejection(tenant, prio, e, request=request)
            raise

    def _maybe_preempt(self) -> None:
        """Decode-slot preemption (ticking thread, start of admission):
        when the queue's top priority class has a request whose TTFT
        budget has burned past ``preempt_ttft_fraction`` waiting and
        neither a slot nor the pages it needs can free
        otherwise, preempt the LOWEST-priority active decode slot
        through the replay path (:meth:`_replay_slot` — prompt pages
        into the prefix LRU, journal-requeue, exactly-once
        re-delivery). At most one victim per tick: admission runs
        right after, so the freed slot serves the waiting request
        before a second preemption could be justified."""
        sched = self._sched
        if sched is None or not sched.preempt:
            return
        with self._cv:
            if len(self._queue) == 0:
                return
            cand = self._queue.preempt_candidate()
        if cand is None:
            return
        req, prio = cand
        if any(s.req is None for s in self.slots):
            # A free slot exists — ordinary admission serves the head,
            # UNLESS it is PAGE-starved: admission is
            # all-or-nothing, and a head whose worst-case reservation
            # the pool cannot cover even after evicting every cold
            # page (can_alloc counts the LRU) waits at the free slot
            # forever while lower-priority decodes hold the pages.
            # Preempting one releases its pages into the evictable
            # set. The need bound is conservative — prefix sharing
            # only shrinks it, so can_alloc(need) true means ordinary
            # admission will succeed.
            s0 = req.prompt.shape[0]
            bucket = next(b for b in self.prompt_buckets if b >= s0)
            need = -(
                -max(bucket, s0 + req.steps + self._spec_k + self._spec_w)
                // self._page
            )
            if self._pager.can_alloc(need):
                return
        waited = time.perf_counter() - (req.t_requeued or req.t_submit)
        if waited < sched.preempt_ttft_fraction * req.slo.ttft_budget_s:
            return
        with self._cv:
            # Re-validate: a client cancel() since the candidate
            # snapshot removed it from the queue — preempting a live
            # decode (discarded tokens, full replay) to serve a
            # request that no longer exists would be pure waste.
            if not any(
                r.req_id == req.req_id for r in self._queue
            ):
                return
        victims = [
            s for s in self.slots
            if s.req is not None
            and s.pf_done < 0  # decode slots only; mid-prefill slots
            # finish their admission (they have emitted nothing yet)
            and request_priority(s.req) < prio
        ]
        if not victims:
            return  # never preempt an equal-or-higher class
        # Lowest class first; ties broken by FEWEST emitted tokens —
        # the cheapest regeneration when the victim re-admits.
        victim = min(
            victims,
            key=lambda s: (request_priority(s.req), len(s.tokens)),
        )
        vid = victim.req.req_id
        vprio = request_priority(victim.req)
        delivered = len(victim.tokens)
        self._replay_slot(
            victim, event="preempted", extra={"for_request": req.req_id}
        )
        with self._cv:
            self._preempted += 1
        global_metrics().inc("scheduler.preempted_total")
        log.info(
            "preempted request %d (priority %d, %d tokens delivered) "
            "for request %d (priority %d, waited %.3fs of %.3fs TTFT)",
            vid, vprio, delivered, req.req_id, prio, waited,
            req.slo.ttft_budget_s,
        )

    def set_draft_k(self, k: int) -> None:
        """Shrink (or restore) the EFFECTIVE speculation depth at
        runtime — the degradation ladder's cheapest rung
        (``runtime/scheduler.DegradationController``). Cache slack,
        page reservations and the idle sentinel all sized for the
        CONFIGURED ``draft_k`` at construction, so any ``k`` in
        ``[1, draft_k]`` keeps every write inside reserved space; the
        next tick's draft scan and verify chunk simply narrow to
        ``k + 1`` rows. Each DISTINCT ``k`` lowers one fresh variant
        of the two spec programs (granted as an expected-compile
        allowance, like recovery's re-lowers — not a phantom-variant
        alarm); toggling back to a seen value reuses its cached
        executables."""
        if self._spec is None:
            raise ValueError(
                "set_draft_k requires speculative mode (draft_lm=)"
            )
        if not 1 <= k <= self._spec.draft_k:
            raise ValueError(
                f"draft_k must be in [1, {self._spec.draft_k}], got {k}"
            )
        if k == self._spec_k_eff:
            return
        if k not in self._spec_k_granted:
            # One fresh draft variant per distinct k, plus one verify
            # variant per sampling-flag combination already in service
            # at this depth (greedy-only traffic has exactly one;
            # sampled traffic adds its (sample, truncate, nucleus)
            # combos — narrowing must stay lossless for them too).
            combos = len({
                v[1:] for v in self._variants.get(
                    "continuous.spec_verify", set()
                )
            }) or 1
            self._sentinel.rearm("continuous.spec_verify", expect=combos)
            self._granted["continuous.spec_verify"] = (
                self._granted.get("continuous.spec_verify", 0) + combos
            )
            self._sentinel.rearm("speculative.draft_chunk", expect=1)
            self._granted["speculative.draft_chunk"] = (
                self._granted.get("speculative.draft_chunk", 0) + 1
            )
            self._spec_k_granted.add(k)
        self._spec_k_eff = k
        log.info("effective draft_k -> %d (configured %d)",
                 k, self._spec.draft_k)

    # -- elastic mesh recovery ---------------------------------------------

    def _on_device_event(self, event: str, key: str) -> None:
        """Membership watch callback (fires on the killer's / reaper's
        thread): a ``leave`` for a device of OUR current mesh is queued
        for the ticking thread to consume — detection is event-driven,
        recovery runs only where the compiled state is owned."""
        if event != "leave" or not key.startswith("device:"):
            return
        try:
            did = int(key.split(":", 1)[1])
        except ValueError:
            return
        with self._cv:
            if did not in self._mesh_device_ids or key in self._lost_pending:
                return
            self._lost_pending.append(key)
            self._cv.notify_all()  # wake an idle server thread
        global_flight_recorder().record(
            "device_lost", device=key, tp=self._tp
        )
        log.warning("mesh device lost: %s (tp=%d)", key, self._tp)

    def device_lost_pending(self) -> bool:
        """True when a mesh device loss awaits recovery (the next tick
        re-shards, or raises under ``auto_reshard=False``)."""
        with self._cv:
            return bool(self._lost_pending)

    def recover(self) -> dict:
        """Re-shard the batcher onto its surviving devices after a
        device loss — the elastic recovery path, end to end:

        1. **shrink the mesh** — new tp is the largest divisor of the
           old tp the survivors can host (divisors keep every
           head-range split aligned, so the model re-validates by
           construction — ``validate_tp`` + per-block
           ``check_head_parity`` run anyway, by name);
        2. **re-place weights** by the megatron rules on the shrunk
           mesh (the checkpoint tier owns weight durability — under
           the simulated kill the still-resident shards re-place
           directly; a real deployment re-streams from checkpoint);
        3. **migrate live state** via an explicit
           ``parallel.sharding.KVReshardPlan``: head-sharded KV
           (pools, every plane of a quantized triple) moves
           per-shard — device-to-device where the shard survives,
           host-staged for the lost shard's heads — and replicated
           state (sampling ``_dstate``, draft weights/caches) re-places
           from a surviving replica. Migrated requests continue
           **bit-identically**;
        4. **replay** requests whose state does not migrate
           (``policy="replay"``, or mid-chunked-prefill slots) from the
           journal — re-entering through the paged prefix cache when
           the prompt pages are still resident — to identical tokens;
        5. **re-arm** the compile sentinel for every program family:
           the re-lowered variants (new shardings) are expected
           compiles, not phantom-variant alarms.

        Runs on the ticking thread (``tick`` calls it under
        ``auto_reshard``); call it directly only with the batcher
        stopped or between synchronous ticks. Returns the recovery
        summary (also recorded as the ``mesh_reshard`` flight event).
        Raises :class:`DeviceLostError` when no recovery exists (all
        devices lost, or survivors below ``min_tp``)."""
        # Pipeline boundary: a dispatched-but-uncommitted tick drains
        # BEFORE the mesh surgery below. Its results were computed on
        # the old layout — under the simulated kill they are still
        # readable — and its commits move
        # slot.tokens/emitted, which the migrate-vs-replay decisions
        # and ``_replay_slot``'s delivered-token arithmetic read. This
        # is where ``_lost_pending`` is consumed relative to the
        # pipeline: at the tick boundary, never mid-flight.
        fl, self._inflight = self._inflight, None
        if fl is not None:
            self._tick_commit(fl)
        t0 = time.perf_counter()
        # NOTE: _lost_pending is cleared only on success (or when there
        # is genuinely nothing to recover from) — a recovery that
        # RAISES (min_tp floor, all devices lost) must leave the loss
        # pending so every subsequent dispatch keeps raising instead of
        # running on the broken layout.
        old_devices = self._mesh_devices
        if not old_devices:
            # Never mesh-native: the monitor never targeted this
            # batcher, so there is nothing to recover from. (A tp=1
            # REMNANT keeps its one-entry device list — losing that
            # device too must fall through to the every-device-lost
            # raise below, not report healthy here.)
            with self._cv:
                self._lost_pending.clear()
            return {"old_tp": self._tp, "new_tp": self._tp, "lost": []}
        dead = (
            self._health.dead_ids() if self._health is not None else set()
        )
        lost_here = sorted(
            int(d.id) for d in old_devices if int(d.id) in dead
        )
        if not lost_here:
            with self._cv:
                self._lost_pending.clear()
            return {"old_tp": self._tp, "new_tp": self._tp, "lost": []}
        survivors = [d for d in old_devices if int(d.id) not in dead]
        if not survivors:
            raise DeviceLostError(
                f"every device of the tp={self._tp} mesh is lost"
            )
        old_tp = self._tp
        new_tp = old_tp
        while new_tp > len(survivors) or old_tp % new_tp:
            new_tp -= 1
        if new_tp < self._recovery.min_tp:
            raise DeviceLostError(
                f"{len(survivors)} survivors support tp={new_tp}, below "
                f"RecoveryConfig.min_tp={self._recovery.min_tp}"
            )
        validate_tp(self.lm, new_tp)
        axis = self._axis
        new_devices = survivors[:new_tp]
        plan = plan_kv_reshard(old_devices, new_devices, lost_here, axis)
        if new_tp > 1:
            new_mesh = Mesh(np.asarray(new_devices), (axis,))
            repl = NamedSharding(new_mesh, P())
            kv_sh = kv_head_sharding(new_mesh, axis)
            self._served = jax.device_put(
                self._served,
                tree_shardings(
                    self._served, new_mesh,
                    rules=partial(lm_tp_rules, axis=axis),
                ),
            )
        else:
            # Single-device remnant: the degenerate-mesh discipline
            # from construction — no GSPMD, everything committed to the
            # one survivor via SingleDeviceSharding (consistent
            # placement, no phantom variants).
            new_mesh = None
            repl = SingleDeviceSharding(new_devices[0])
            kv_sh = repl
            self._served = jax.device_put(self._served, repl)
        # Live-state migration: KV on the head axis per the plan;
        # replicated members from a surviving replica.
        self._caches = plan.migrate_tree(self._caches, kv_sh)
        for name, block, pool in zip(
            self.lm.block_names, self._blocks, self._caches
        ):
            # The partial-TP-migration check, by name, on per-SHARD
            # geometry: migrate() rebuilds at the logical shape, so
            # leaf.shape[1] can never disagree — what a plan bug
            # produces is a shard holding the wrong head span. Each of
            # the new_tp shards must carry exactly heads/new_tp rows.
            leaf = pool_values(pool)
            shard_heads = leaf.addressable_shards[0].data.shape[1]
            check_head_parity(block.cache_heads, shard_heads * new_tp)
        self._dstate = plan.migrate_replicated(self._dstate, repl)
        if self._spec:
            self._draft_variables = plan.migrate_replicated(
                self._draft_variables, repl
            )
            self._draft_caches = plan.migrate_replicated(
                self._draft_caches, repl
            )
        # Install the shrunk layout; the page table re-uploads on the
        # first post-recovery tick (placement changed even where
        # the host table did not).
        self._mesh = new_mesh
        self._tp = new_tp
        self._repl = repl
        self._kv_sharding = kv_sh if new_mesh is not None else None
        self._table_dev = None
        self._table_snapshot = None
        # Force a re-TRACE of every program whose jaxpr bakes concrete
        # sharding constraints (jit caches traces on avals + statics —
        # see _shard_kv), and drop the per-instance prefill closures so
        # each bucket re-traces against the new layout on first use.
        self._mesh_epoch += 1
        prefill_dropped = sum(
            f._cache_size() for f in self._prefill_cache.values()
        )
        self._prefill_cache.clear()
        with self._cv:
            # Consume only the losses THIS recovery handled: a device
            # killed on another thread after the dead_ids() snapshot
            # (its leave already queued against the old membership)
            # must stay pending so the next tick recovers again —
            # clear() would erase the event and leave a dead chip in
            # the just-installed mesh.
            consumed = {f"device:{i}" for i in lost_here}
            self._lost_pending = [
                k for k in self._lost_pending if k not in consumed
            ]
            self._mesh_device_ids = {int(d.id) for d in new_devices}
            self._mesh_devices = list(new_devices)
        # Re-lowering against the shrunk mesh is EXPECTED compilation,
        # but LAZY — stage_slot pays on the next admission, a prefill
        # bucket on its next use, possibly long after recovery — so
        # each family gets an explicit expected-compile ALLOWANCE (not
        # a warmup window that would re-close first): one re-lowered
        # variant per STATIC-VARIANT KEY this batcher dispatched under
        # the old epoch (every variant in use re-traces after the epoch
        # bump — a mixed-traffic batcher holds several: step_chunk's
        # (truncate, nucleus) combos, stage_slot's key buckets, the
        # draft _insert's prompt buckets), plus one per dropped prefill
        # executable. Variants never re-used leave allowance slack on
        # the shared watch (the cost of not knowing future traffic, as
        # with prefill); anything beyond the allowance is still the
        # phantom-variant alarm. Granted BEFORE the replay loop below:
        # _replay_slot/_drop_slot dispatch the epoch-bumped _clear_slot
        # inside it, and a concurrent exporter scrape sampling between
        # that compile and a later rearm would fire a false alarm.
        def nvar(prog: str) -> int:
            # No floor: a family never dispatched under the old epoch
            # had no executable to re-lower, and a banked allowance
            # would mask one future REAL phantom variant.
            return len(self._variants.get(prog, ()))

        # _clear_slot re-lowers if it compiled under the old epoch, or
        # compiles fresh on ANY occupied slot's account — the replay
        # loop below dispatches it directly, a migrated slot's eventual
        # _finish does too. Empty batcher + never compiled: NO banked
        # allowance (the nvar rule — slack on a family recovery gives
        # no reason to compile masks a real phantom).
        will_clear = any(s.req is not None for s in self.slots)
        expected = {
            "continuous.stage_slot": nvar("continuous.stage_slot"),
            "continuous.clear_slot": int(
                bool(nvar("continuous.clear_slot")) or will_clear
            ),
            "continuous.prefill": prefill_dropped,
        }
        # Handoff-adoption and fork variants re-lower like every other
        # sharding-constrained program (nvar rule: only buckets
        # actually dispatched under the old epoch).
        expected["continuous.adopt_pages"] = nvar("continuous.adopt_pages")
        expected["continuous.fork_page"] = nvar("continuous.fork_page")
        if self._spec:
            # _insert dispatches only for the draft admission (its
            # dense strips); the target inserts via _insert_paged.
            expected["continuous.insert"] = nvar("continuous.insert")
            # One re-lower per speculation DEPTH dispatched under the
            # old epoch (the degradation ladder's set_draft_k makes
            # several possible); a spec batcher that never ticked
            # still re-lowers its first tick's variant.
            expected["continuous.spec_verify"] = (
                nvar("continuous.spec_verify") or 1
            )
            expected["speculative.draft_chunk"] = (
                nvar("speculative.draft_chunk") or 1
            )
        else:
            expected["continuous.step_chunk"] = nvar(
                "continuous.step_chunk"
            )
        for prog, n in expected.items():
            if n:
                self._sentinel.rearm(prog, expect=n)
                self._granted[prog] = self._granted.get(prog, 0) + n
        # Sequence-parallel prefiller: its OWN mesh may have included
        # the dead chip, and its tp must track the batcher's — rebuild
        # the ring from survivors (width shrinks by powers of two),
        # or degrade to the ordinary prefill path when no ring fits.
        # The rebuilt instance's program variants are expected
        # compiles: one allowance per bucket dispatched under the old
        # epoch (the nvar rule — a prefiller that never ran banks
        # nothing).
        if self._sp_cfg is not None and self._sp_cfg.enabled:
            cfg = self._sp_cfg
            if self._sp is not None:
                sp_variants = len(self._sp.variants)
                sp_alive = [
                    d for d in self._sp._mesh.devices.flat
                    if int(d.id) not in dead
                ]
                self._sp.close()
                self._sp = None
            else:
                # Breaker-retired earlier (consecutive dispatch
                # failures — plausibly this very loss): rebuild from
                # the platform pool minus the dead set.
                sp_variants = 0
                sp_alive = [
                    d for d in jax.devices() if int(d.id) not in dead
                ]
            self._sp_failures = 0
            w = cfg.sp_width
            while w > 1 and w * new_tp > len(sp_alive):
                w //= 2
            if w > 1:
                try:
                    mesh_sp = build_sp_mesh(
                        w, new_tp, cfg.sp_axis, axis, devices=sp_alive
                    )
                    self._sp = SPPrefiller(
                        self.lm, self.variables, mesh_sp, self._page,
                        kv_cache_dtype=self._kv_dtype,
                        sp_axis=cfg.sp_axis,
                        tp_axis=(axis if new_tp > 1 else None),
                        name="batcher-sp",
                    )
                    if sp_variants:
                        self._sentinel.rearm(
                            "sp.prefill", expect=sp_variants
                        )
                        self._granted["sp.prefill"] = (
                            self._granted.get("sp.prefill", 0)
                            + sp_variants
                        )
                except Exception:  # noqa: BLE001 — degrade, don't wedge
                    log.exception(
                        "sp prefiller rebuild failed; sp prefill "
                        "disabled until the next recovery"
                    )
            else:
                log.warning(
                    "sp prefill disabled: %d surviving ring devices "
                    "support no sp >= 2 at tp=%d",
                    len(sp_alive), new_tp,
                )
            global_metrics().set_gauge(
                "prefill.sp_width",
                float(self._sp.sp if self._sp is not None else 1),
            )
        # Post-recovery dispatches repopulate against the new epoch —
        # a second recovery must size from its own epoch's variants
        # (the replay loop's _clear_slot dispatch is already one).
        self._variants.clear()
        self._roofline_costs = None  # stale: the program re-lowers
        # Per-request policy: decoding slots migrate (their state just
        # did, bit-exactly); mid-chunked-prefill slots — and everything
        # under policy="replay" — replay from the journal instead.
        migrated = replayed = dropped = 0
        replay_ids: list[int] = []
        replay_all = self._recovery.policy == "replay"
        for slot in self.slots:
            if slot.req is None:
                continue
            if replay_all or slot.pf_done >= 0:
                rid = slot.req.req_id
                try:
                    self._replay_slot(slot)
                    replayed += 1
                    replay_ids.append(rid)
                except Exception:  # noqa: BLE001 — drop, don't wedge
                    if slot.req is None:
                        # _replay_slot released the slot and re-queued
                        # the request before failing (e.g. the final
                        # slot-park dispatch): the replay IS in flight
                        # — dropping here would deref a freed slot and
                        # double-handle the queued request.
                        log.exception(
                            "replay of request %d raised after "
                            "re-queue; replay proceeds", rid,
                        )
                        replayed += 1
                        replay_ids.append(rid)
                    else:
                        log.exception(
                            "replay failed for request %d; dropping",
                            rid,
                        )
                        self._drop_slot(slot)
                        dropped += 1
            else:
                migrated += 1
                global_flight_recorder().record(
                    "kv_migrated",
                    request=slot.req.req_id,
                    slot=slot.idx,
                    tokens_kept=len(slot.tokens),
                )
        if len(replay_ids) > 1:
            # Each _replay_slot appendleft'ed in slot order, inverting
            # arrival order among the replays; restore FIFO (req_id is
            # monotone in submit order) so the oldest in-flight request
            # is not re-admitted last onto the shrunk — possibly
            # halved-capacity — mesh. Rebuild by MEMBERSHIP, not by
            # popping `replayed` entries: a client cancel() landing
            # between a replay's re-queue and this reorder deletes its
            # entry, and a blind popleft would then underflow or steal
            # a non-replay request.
            ids = set(replay_ids)
            with self._cv:
                head = sorted(
                    (r for r in self._queue if r.req_id in ids),
                    key=lambda r: r.req_id,
                )
                if head:
                    rest = [
                        r for r in self._queue if r.req_id not in ids
                    ]
                    self._queue.clear()
                    self._queue.extend(head + rest)
        wall = time.perf_counter() - t0
        with self._cv:
            self._recoveries += 1
            self._recovery_migrated += migrated
            self._recovery_replayed += replayed
            self._recovery_dropped += dropped
            self._last_recovery_wall_s = wall
        reg = global_metrics()
        reg.observe("recovery.wall_s", wall)
        if migrated:
            reg.inc("recovery.migrated_total", float(migrated))
        if replayed:
            reg.inc("recovery.replayed_total", float(replayed))
        if dropped:
            reg.inc("recovery.dropped_total", float(dropped))
        summary = plan.summary()
        summary.update(
            migrated=migrated, replayed=replayed, dropped=dropped,
            wall_s=wall,
        )
        global_flight_recorder().record(
            "mesh_reshard",
            old_tp=old_tp,
            new_tp=new_tp,
            lost=lost_here,
            migrated=migrated,
            replayed=replayed,
            dropped=dropped,
            moved_bytes=plan.moved_bytes,
            host_staged_bytes=plan.host_staged_bytes,
            wall_s=round(wall, 6),
        )
        log.warning(
            "mesh reshard: tp %d -> %d (lost %s): %d migrated, "
            "%d replayed, %d dropped in %.3fs",
            old_tp, new_tp, lost_here, migrated, replayed, dropped, wall,
        )
        return summary

    def _replay_slot(
        self,
        slot: _Slot,
        event: str = "replayed_from_journal",
        extra: dict | None = None,
    ) -> None:
        """Replay one slot's request instead of migrating it: free the
        slot (its registered prompt pages drop into the prefix
        LRU, so the re-admission re-enters through the prefix cache —
        a suffix-only prefill instead of a full one), discard the
        partial stream, and re-queue the request reconstructed from
        the JOURNAL when one is configured (payload + sampling-knob
        meta; the in-memory record is the fallback). Greedy replays
        re-emit the identical stream; sampled ones re-use the
        journaled key schedule — identical too.

        Decode-slot PREEMPTION (``runtime/scheduler``) rides this
        exact path with ``event="preempted"``: cancel the slot,
        prompt pages into the prefix LRU, journal-requeue, re-admit
        later as a prefix-cache hit with ``stream_skip`` suppressing
        re-delivery — preemption reuses recovery's exactly-once and
        SLO-carry-across-lives discipline instead of inventing a
        second one."""
        req = slot.req
        # Per-life timing stamps for the INTERRUPTED life, riding the
        # replay/preemption flight edge (its finish event belongs to a
        # later life whose clock starts mid-stream): TTFT only when
        # this life emitted the request's true first token — the
        # forensics bundle (utils.telemetry.assemble_request) reads
        # each life's story straight off these edges.
        life_stamps: dict = {}
        if slot.t_first != 0.0:
            if req.stream_skip == 0:
                life_stamps["ttft_s"] = round(
                    slot.t_first - req.t_submit, 6
                )
            if len(slot.tokens) > 1 and slot.t_last > slot.t_first:
                life_stamps["life_itl_mean_s"] = round(
                    (slot.t_last - slot.t_first)
                    / (len(slot.tokens) - 1),
                    6,
                )
        # Tokens already DELIVERED to the client across this request's
        # lives (a double-kill chain replays a replay: slot.tokens
        # restarts at 0 each life, so the high-water mark carries).
        delivered = max(req.stream_skip, len(slot.tokens))
        # Snapshot the delivered stream so a cancel that lands before
        # the re-run regenerates it can still resolve result() with
        # what the client saw. Mid-regeneration (this life shorter than
        # the last), the previous life's snapshot stays the truth.
        if req.delivered_tokens is None or len(slot.tokens) >= len(
            req.delivered_tokens
        ):
            req.delivered_tokens = np.asarray(slot.tokens, np.int32)
            req.delivered_lps = np.asarray(slot.lps, np.float32)
            if len(slot.tokens) > req.stream_skip:
                # This life delivered NEW tokens, so its last commit is
                # the client's latest delivery: the next new token's
                # ITL measures from it. A life that only regenerated
                # (double kill mid-catch-up) keeps the older stamp —
                # the client received nothing since.
                req.t_last_delivered = slot.t_last
        source = "memory"
        if self._journal is not None:
            try:
                payload = self._journal.read_payload(req.req_id)
                meta = self._journal.submit_meta(req.req_id)
                if meta is not None:
                    req = _Request(
                        req_id=req.req_id,
                        prompt=np.asarray(payload, np.int32).reshape(-1),
                        steps=int(meta["steps"]),
                        temperature=float(meta["temperature"]),
                        top_k=int(meta["top_k"]),
                        top_p=float(meta["top_p"]),
                        eos_id=meta["eos_id"],
                        folded_keys=np.asarray(
                            meta["folded_keys"], np.uint32
                        ).reshape(-1, 2),
                        stop=tuple(
                            tuple(int(t) for t in s)
                            for s in meta.get("stop", [])
                        ),
                        # Host-side attachments are not journalable;
                        # they carry over from the live record.
                        on_token=req.on_token,
                        t_submit=req.t_submit,
                        slo=req.slo,
                        stream_skip=delivered,
                        slo_violated=req.slo_violated,
                        delivered_tokens=req.delivered_tokens,
                        delivered_lps=req.delivered_lps,
                        t_last_delivered=req.t_last_delivered,
                    )
                    source = "journal"
            except Exception as e:  # noqa: BLE001 — fallback, loudly
                log.warning(
                    "journal replay of request %d fell back to the "
                    "in-memory record: %r",
                    req.req_id, e,
                )
        req.stream_skip = delivered  # memory-fallback path (no-op for
        # the journal reconstruction, which was built with it)
        req.t_requeued = time.perf_counter()
        global_flight_recorder().record(
            event,
            request=req.req_id,
            slot=slot.idx,
            source=source,
            tokens_discarded=len(slot.tokens),
            **life_stamps,
            **(extra or {}),
        )
        with self._cv:
            self._release_slot(slot)
            self._queue.appendleft(req)
            self._cv.notify_all()
        self._park_slot_row(slot.idx)

    def _drop_slot(self, slot: _Slot) -> None:
        """Last resort when a replay cannot be constructed: the request
        finishes with an empty result (a result() waiter unblocks with
        the loss visible, never a timeout) and counts as dropped."""
        req = slot.req
        global_flight_recorder().record(
            "request_dropped", request=req.req_id, slot=slot.idx
        )
        if self.obs_timeline:
            # The same per-finish observations _finish records, so the
            # latency histogram count and per-tenant verdict totals keep
            # summing to the finish count. A drop delivered nothing —
            # its verdict is missed regardless of budgets met so far.
            global_metrics().observe(
                "continuous.request_latency_s",
                time.perf_counter() - req.t_submit,
            )
            if req.slo is not None:
                global_metrics().inc(
                    f"slo.missed_total.{req.slo.tenant}"
                )
        # A dropped request still FINISHES (once, reason="dropped"):
        # the admit==finish lifecycle books and the
        # stats()/continuous.completed mirrors must agree with _finish.
        global_flight_recorder().record(
            "finish", request=req.req_id, reason="dropped", tokens=0
        )
        with self._cv:
            self._done[req.req_id] = np.zeros((0,), np.int32)
            self._done_lps[req.req_id] = np.zeros((0,), np.float32)
            self._cancelled.discard(req.req_id)
            self._completed += 1
            self._release_slot(slot)
            self._cv.notify_all()
        self._journal_done(req.req_id)
        global_metrics().inc("continuous.completed")
        self._park_slot_row(slot.idx)

    def _journal_done(self, req_id: int) -> None:
        """Done-mark a request in the journal (no-op without one; a
        journal write failure must not poison the serving path)."""
        if self._journal is None:
            return
        try:
            self._journal.record_done(req_id)
        except Exception as e:  # noqa: BLE001 — serving outlives the WAL
            log.warning("journal done mark failed for %d: %r", req_id, e)

    def _slo_violation(
        self, slot: _Slot, budget: str, budget_s: float, measured_s: float
    ) -> None:
        """First budget violation flips the request OUT of goodput and
        records ONE ``slo_missed`` flight event (per-request-lifecycle
        grade, like admit/finish — later violations of an
        already-missed request only move the attainment counters)."""
        if slot.slo_ok:
            slot.slo_ok = False
            slot.req.slo_violated = True  # survives a recovery replay
            global_flight_recorder().record(
                "slo_missed",
                request=slot.req.req_id,
                tenant=slot.req.slo.tenant,
                budget=budget,
                budget_s=budget_s,
                measured_s=round(measured_s, 6),
            )

    def _obs_flush(self) -> None:
        """Per-tick registry flush of the timeline/SLO bookkeeping the
        commit path accumulated as plain attributes: the batched ITL
        samples, the SLO attainment counters + gauges, the goodput
        token counters and the windowed ``continuous.goodput_tokens_s``
        rate. ONE call per tick (idle ticks included, so goodput decays
        to zero instead of scraping the last busy rate forever); costs
        a handful of registry-lock holds, inside the obs budget
        (benchmarks/micro/obs_overhead.py)."""
        reg = global_metrics()
        if self._ttft_pending:
            reg.observe_many("continuous.ttft_s", self._ttft_pending)
            self._ttft_pending = []
        if self._itl_pending:
            reg.observe_many("continuous.itl_s", self._itl_pending)
            self._itl_pending = []
        pend = self._slo_pending
        if any(pend.values()):
            tot = self._slo_totals
            for key, n in pend.items():
                if n:
                    tot[key] += n
                    reg.inc(f"slo.{key}_total", float(n))
                    pend[key] = 0
            den = tot["ttft_met"] + tot["ttft_missed"]
            if den:
                reg.set_gauge(
                    "slo.ttft_attainment", tot["ttft_met"] / den
                )
            den = tot["itl_met"] + tot["itl_missed"]
            if den:
                reg.set_gauge(
                    "slo.itl_attainment", tot["itl_met"] / den
                )
        if self._tick_tokens:
            reg.inc("continuous.tokens_total", float(self._tick_tokens))
        if self._tick_good_tokens:
            reg.inc(
                "continuous.good_tokens_total",
                float(self._tick_good_tokens),
            )
        # Windowed goodput rate: per-tick (t, good) samples spanning
        # goodput_window_s. The gauge is tokens-inside-budget per
        # second over that window — the "graceful degradation under
        # overload" number the load harness sweeps.
        now = time.perf_counter()
        gs = self._goodput_samples
        gs.append((now, self._tick_good_tokens))
        self._tick_tokens = 0
        self._tick_good_tokens = 0
        cutoff = now - self.goodput_window_s
        while len(gs) > 1 and gs[0][0] < cutoff:
            gs.popleft()
        span = now - gs[0][0]
        if span > 0:
            # gs[0] anchors the window start; its tokens were counted
            # by the PREVIOUS span, so the rate sums the later samples.
            good = sum(g for _, g in list(gs)[1:])
            reg.set_gauge("continuous.goodput_tokens_s", good / span)
        if self._capacity is not None:
            # Capacity plane: tick-gap feed + (rate-limited inside
            # update) book rebuild, sketch refresh, health scoring and
            # the capacity.* gauges. Same seam, same obs budget.
            if self._cap_last_flush:
                self._capacity.on_tick_gap(now - self._cap_last_flush)
            self._cap_last_flush = now
            self._capacity.update(self, now)

    def _release_slot(self, slot: _Slot) -> None:
        """Reset one slot's host-side lifecycle state and return its
        pages to the pool — caller holds ``_cv``. The SINGLE definition
        ``_finish`` / ``_replay_slot`` / ``_drop_slot`` share, so a new
        ``_Slot`` lifecycle field cannot silently diverge across the
        three release paths."""
        slot.req = None
        slot.tokens = []
        slot.lps = []
        slot.pf_done = -1
        slot.slo_ok = True
        slot.t_first = 0.0
        slot.obs_count = 0
        for pager in self._pagers:
            pager.free_slot(slot.idx)

    def _park_slot_row(self, idx: int) -> None:
        """Park a retired slot's device row (one donated setter
        dispatch, outside the lock): active mask off + idle-sentinel
        position, so the next chunk's garbage writes route to the
        trash page again. The SINGLE ``_clear_slot``
        dispatch site ``_finish`` / ``_replay_slot`` / ``_drop_slot``
        share — it also books the family into ``_variants`` so
        ``recover()`` knows an old-epoch executable exists to
        re-lower."""
        self._variants.setdefault("continuous.clear_slot", set()).add(0)
        self._dstate = self._clear_slot(
            self._dstate, self._h2d(np.int32(idx)),
            epoch=self._mesh_epoch,
        )

    def _finish(self, slot: _Slot, reason: str = "completed") -> None:
        req = slot.req
        if self.obs_timeline:
            global_metrics().observe(
                "continuous.request_latency_s",
                time.perf_counter() - req.t_submit,
            )
            if req.slo is not None:
                # Request-level verdict for the tenant books: met =
                # finished with every evaluated budget inside limits.
                kind = "met" if slot.slo_ok else "missed"
                global_metrics().inc(
                    f"slo.{kind}_total.{req.slo.tenant}"
                )
        toks = np.asarray(slot.tokens, np.int32)
        lps = np.asarray(slot.lps, np.float32)
        if req.delivered_tokens is not None and len(toks) < len(
            req.delivered_tokens
        ):
            # A replay cancelled mid-regeneration holds fewer tokens in
            # THIS life than the client received in the last; result()
            # must never contradict the delivered stream.
            toks, lps = req.delivered_tokens, req.delivered_lps
        # Flight events stay UNGATED like cancel's: the recorder's
        # contract is always-on per-lifecycle — a post-mortem must not
        # show cancels for requests with no admit/finish.
        # Per-life timing stamps ride the finish edge when the timeline
        # stamped them (obs_timeline): the per-request forensics bundle
        # (utils.telemetry.assemble_request, GET /debug/request/<id>)
        # reads TTFT and this life's mean inter-token gap straight off
        # the flight stream instead of reverse-engineering them from
        # process-wide histograms.
        stamps: dict = {}
        if slot.t_first != 0.0:
            if req.stream_skip == 0:
                stamps["ttft_s"] = round(
                    slot.t_first - req.t_submit, 6
                )
            if len(slot.tokens) > 1 and slot.t_last > slot.t_first:
                stamps["life_itl_mean_s"] = round(
                    (slot.t_last - slot.t_first)
                    / (len(slot.tokens) - 1),
                    6,
                )
        if req.t_requeued:
            stamps["replayed_life"] = True
        global_flight_recorder().record(
            "finish",
            request=req.req_id,
            reason=reason,
            tokens=len(toks),
            **stamps,
        )
        with self._cv:
            self._done[req.req_id] = toks
            self._done_lps[req.req_id] = lps
            while len(self._done_lps) > self._LPS_CAP:
                evicted = next(iter(self._done_lps))
                self._done_lps.pop(evicted)
                global_flight_recorder().record(
                    "lps_evicted", request=evicted
                )
            # Consume any cancel marker that raced a natural finish —
            # markers must never outlive their request.
            self._cancelled.discard(req.req_id)
            self._cv.notify_all()  # result() waiters
            # Slot retirement + lifetime counters stay inside the lock so
            # stats() can't observe "finished but still counted active"
            # (the torn triple an unlocked _completed/slot.req allowed).
            self._completed += 1
            # Pages return to the pool the moment the request retires —
            # the capacity win continuous paging exists for.
            self._release_slot(slot)
        self._journal_done(req.req_id)
        self._park_slot_row(slot.idx)
        global_metrics().inc("continuous.completed")

    def _commit(self, slot: _Slot, token: int, lp: float) -> None:
        """Append one emitted token; EOS, a stop sequence, or a pending
        cancel latches and finishes the request."""
        req = slot.req
        with self._cv:
            cancelled = req.req_id in self._cancelled
            self._cancelled.discard(req.req_id)
        if cancelled:
            # Partial stream becomes the result; the chunk's remaining
            # tokens for this slot are garbage nobody reads.
            self._finish(slot, reason="cancelled")
            return
        if self.obs_timeline:
            # One perf_counter stamp per committed token. TTFT observes
            # inline (once per request); inter-token gaps batch into
            # _itl_pending and flush under ONE registry-lock hold per
            # tick (observe_many) — the hot-path contention stays O(1)
            # per tick, not O(tokens). Contiguity guards make a
            # mid-request obs_timeline toggle drop samples instead of
            # corrupting them: TTFT only for the request's TRUE first
            # token, ITL only when the previous commit also stamped.
            now = time.perf_counter()
            emitted_before = len(slot.tokens)
            # A replay's regenerated prefix (indices < stream_skip) was
            # already delivered, stamped and counted in the request's
            # first life: it re-runs for state only — no second TTFT,
            # no ITL samples, no goodput/attainment movement.
            regen = emitted_before < req.stream_skip
            if slot.t_first == 0.0:
                slot.t_first = now
                if emitted_before == 0 and req.stream_skip == 0:
                    # TTFT samples batch like ITL: one observe_many per
                    # tick in _obs_flush. The budget COMPARISON stays
                    # inline (plain float compare) — slo_ok must flip
                    # before this tick's later goodput increments read
                    # it.
                    ttft = now - req.t_submit
                    self._ttft_pending.append(ttft)
                    if (
                        self._capacity is not None
                        and req.ttft_forecast_s > 0.0
                    ):
                        # Close the forecast loop: realized-vs-forecast
                        # pairs drain in _obs_flush (calibration gauge,
                        # abs-error histogram, bias update).
                        self._capacity.on_ttft(req.ttft_forecast_s, ttft)
                    if req.slo is not None and (
                        req.slo.ttft_budget_s is not None
                    ):
                        if ttft <= req.slo.ttft_budget_s:
                            self._slo_pending["ttft_met"] += 1
                        else:
                            self._slo_pending["ttft_missed"] += 1
                            self._slo_violation(
                                slot, "ttft", req.slo.ttft_budget_s, ttft
                            )
            elif slot.obs_count == emitted_before and not regen:
                if (
                    emitted_before == req.stream_skip
                    and req.t_last_delivered != 0.0
                ):
                    # First NEW token after a replay: the client's
                    # previous token landed before the kill, so the gap
                    # spans kill + recovery + re-prefill + regeneration
                    # — the stall the client actually saw, judged like
                    # a migrated request's recovery wall is.
                    gap = now - req.t_last_delivered
                else:
                    gap = now - slot.t_last
                self._itl_pending.append(gap)
                if req.slo is not None and (
                    req.slo.itl_budget_s is not None
                ):
                    if gap <= req.slo.itl_budget_s:
                        self._slo_pending["itl_met"] += 1
                    else:
                        self._slo_pending["itl_missed"] += 1
                        self._slo_violation(
                            slot, "itl", req.slo.itl_budget_s, gap
                        )
            slot.t_last = now
            slot.obs_count = emitted_before + 1
            # Goodput accounting: every committed token, split by
            # whether its request is still inside budget (no-SLO
            # requests have nothing to violate and stay good). Plain
            # int incs here; the registry sees one flush per tick.
            if not regen:
                self._tick_tokens += 1
                if slot.slo_ok:
                    self._tick_good_tokens += 1
        slot.tokens.append(token)
        slot.lps.append(lp)
        if req.on_token is not None and len(slot.tokens) > req.stream_skip:
            # stream_skip suppresses re-delivery of the indices a
            # replayed request already streamed pre-kill (the re-run
            # regenerates them identically) — on_token stays
            # exactly-once even across a recovery replay.
            req.on_token(req.req_id, token, len(slot.tokens) - 1)
        if req.eos_id is not None and token == req.eos_id:
            # generate() pads with EOS forever after; a server frees the
            # slot instead — the emitted stream up to EOS is identical.
            self._finish(slot, reason="eos")
            return
        slot.emitted += 1
        slot.last_token = token
        # Host-side stop sequences: purely a stream-tail check — the
        # emitted stream equals solo generate() truncated at the first
        # occurrence (inclusive), whatever the stop tokens are.
        for seq in req.stop:
            n = len(seq)
            if n and len(slot.tokens) >= n and tuple(
                slot.tokens[-n:]
            ) == seq:
                self._finish(slot, reason="stop")
                return
        if slot.emitted >= req.steps:
            self._finish(slot)

    def _admit(self) -> None:
        # Traffic control: a high-priority request past its TTFT
        # headroom may free a slot here (replay-path preemption); the
        # loop below then admits it first (popleft is priority-first).
        self._maybe_preempt()
        # Drain page claims parked by client-thread fan-out group
        # deaths (cancel / mid-group rejection): only this thread may
        # move pager rc.
        with self._cv:
            rel, self._fanout_release = self._fanout_release, []
        for pg in rel:
            self._pager.release_claim(pg)
        for i, slot in enumerate(self.slots):
            if slot.req is not None:
                continue
            with self._cv:
                if not self._queue:
                    continue
                req = self._queue.popleft()
                self._admitting = req.req_id  # cancel() sees it as live
                fg = self._fanout_groups.get(req.fanout_group)
            s0 = req.prompt.shape[0]
            bucket = next(b for b in self.prompt_buckets if b >= s0)
            if self._sp is not None and s0 >= self._sp_cfg.sp_threshold:
                # Long admission: sp-shard the prefill wall across the
                # ring BEFORE the prefix probe — the probe then shares
                # the landed pages as ordinary hits and the suffix
                # pass is all that runs on the decode mesh.
                self._sp_admit(req)
            m = 0
            # Prefix probe: acquire (rc+1) every already-cached FULL
            # prompt page, longest run first-miss-stops. Cap at the
            # page before the last prompt token so the suffix
            # forward is never empty (the first sampled token needs
            # a live last-position hidden state).
            P = self._page
            if self._tier is not None:
                # Consult the host tier BEFORE the probe declares
                # any miss: host-resident prefix pages readmit
                # (budgeted) through the adopt_cached landing path
                # and then share below as ordinary hits.
                self._maybe_readmit(req)
            # (A model with several cache groups shares nothing: a hit
            # would need every window layer's last positions too; one
            # with recurrent state nothing either: a hit grants pages
            # and no state.)
            for j in range((s0 - 1) // P if self._shares_pages else 0):
                key = Pager.prefix_key(req.prompt, (j + 1) * P)
                if self._pager.lookup_share(i, key) is None:
                    break
                m += 1
            # All-or-nothing reservation for the REST of the window
            # (prefill writes `bucket` positions; decode reaches
            # s0 + steps - 1). FIFO head-of-line: if the pool can't
            # cover the next request, admission stops — later
            # (smaller) requests do not jump it.
            # Speculative mode reserves draft_k SLACK pages: the
            # verify chunk's rejected overshoot writes land there,
            # masked, instead of off the end of the window.
            span = max(
                bucket, s0 + req.steps + self._spec_k + self._spec_w
            )
            n_pages = -(-span // P) - m
            if not self._pager.alloc(i, n_pages):
                self._pager.free_slot(i)  # releases the shares too
                with self._cv:
                    self._queue.appendleft(req)
                    self._admitting = None
                return
            # Radix books: token-weighted hit accounting for this
            # admission (partial-hit counting when the match stops
            # short of the last full prompt page).
            self._pager.record_prefix_match(m, s0)
            # Copy-on-write fork eligibility: a greedy fan-out sibling
            # whose probe matched EVERY page before the last prompt
            # token, with the group's source page claimed and its first
            # commit cached — the suffix forward is skipped entirely
            # (the source page already holds the K/V of every prompt
            # position, the last one included).
            cow = (
                fg is not None
                and fg.greedy
                and fg.page is not None
                and fg.first is not None
                and req.temperature == 0.0
                and m == (s0 - 1) // self._page
            )
            chunked = (
                not cow
                and self._prefill_chunk is not None
                and s0 - m * self._page > self._prefill_chunk
            )
            tracer = global_tracer()
            t0 = tracer.now() if tracer.enabled else 0.0
            # Capacity forecaster feed: the admission prefill's wall is
            # measured through the first-token host sync below (the
            # tracer stamp above may be disabled; this one is gated on
            # the capacity plane instead). cow (zero positions) and
            # chunked (spread over ticks) admissions skip the feed —
            # the forecaster's calibration bias absorbs them.
            cap_t0 = (
                time.perf_counter() if self._capacity is not None else 0.0
            )
            cap_tokens = 0
            first = None
            if chunked:
                # Chunked prefill: park the slot in the prefilling state
                # — tick() runs one chunk pass per tick alongside the
                # decode batch, so this long admission never stalls the
                # requests already decoding. The first token samples on
                # the final chunk (no _commit here).
                pass
            elif cow:
                # Copy-on-write fork: one device page copy (data-
                # dependent on the source sibling's prefill through
                # the donated cache buffers, so device-side ordering
                # is free) plus the group's cached first commit below
                # — zero prompt positions recomputed. Junk the source
                # page may carry past the prompt (its owner's decode
                # writes, when s0 is not page-aligned) is overwritten
                # by this sibling's own first decode write or causally
                # masked before any read, so the forked stream stays
                # bit-identical to an independent submit's.
                dst = self._pager.owned(i)[m]
                self._variants.setdefault(
                    "continuous.fork_page", set()
                ).add(0)
                self._caches = self._fork_page(
                    self._caches,
                    self._h2d(np.array([fg.page, dst], np.int32)),
                    epoch=self._mesh_epoch,
                )
                self._pager.note_cow_fork()
                global_flight_recorder().record(
                    "cow_fork",
                    request=req.req_id,
                    src_page=int(fg.page),
                    dst_page=int(dst),
                    prefix_pages=m,
                    saved_positions=s0 - m * self._page,
                )
            elif m:
                # Suffix-only prefill against the shared prefix pages.
                # The suffix pads to whole PAGES, not prompt buckets —
                # page rounding keeps the strip inside the reserved
                # window by construction (ceil(s0/P) <= ceil(span/P)),
                # where bucket rounding could round past it.
                slen = s0 - m * self._page
                sbucket = -(-slen // self._page) * self._page
                n_strip = m + sbucket // self._page
                owned = self._pager.owned(i)
                assert n_strip <= len(owned)
                # Pad the window to a power-of-two page count (pad
                # entries point at the trash page, masked past the
                # causal window) — the SAME discipline as
                # _prefill_step, so a long-context prompt's suffix
                # pass compiles log2 window variants instead of one
                # per prefix page count. Byte-equal by the pinned
                # padding invariance (masked columns contribute exact
                # zeros).
                n_pad = 1
                while n_pad < n_strip:
                    n_pad *= 2
                pages = owned[:n_strip] + [0] * (n_pad - n_strip)
                ids = np.zeros((1, sbucket), np.int32)
                ids[0, :slen] = req.prompt[m * self._page:]
                first, first_lp, self._caches, _ = self._prefill_suffix_fn(
                    sbucket, n_pad
                )(
                    self._served,
                    self._caches,
                    self._h2d(np.asarray(pages, np.int32)),
                    self._h2d(ids),
                    self._h2d(np.array(
                        [m * self._page, slen, req.top_k], np.int32
                    )),
                    self._h2d(np.array(
                        [req.temperature, req.top_p], np.float32
                    )),
                    self._h2d(req.folded_keys[0][None]),
                    truncate=req.top_k < self.lm.vocab,
                    nucleus=req.top_p < 1.0,
                )
                self._count_prefill(slen)
                cap_tokens = slen
            else:
                ids = np.zeros((1, bucket), np.int32)
                ids[0, :s0] = req.prompt
                # The further groups hold the prompt's tail only; their
                # other ordinals point at the trash page, which takes
                # (and never gives back) the rest of the bucket.
                self._hold_groups(i, s0, s0)
                first, first_lp, kvs, carried = self._prefill_fn(bucket)(
                    self._served,
                    self._h2d(ids),
                    self._h2d(np.array([s0, req.top_k], np.int32)),
                    self._h2d(np.array(
                        [req.temperature, req.top_p], np.float32
                    )),
                    self._h2d(req.folded_keys[0][None]),
                    truncate=req.top_k < self.lm.vocab,
                    nucleus=req.top_p < 1.0,
                )
                self._caches = self._insert_paged(
                    self._caches,
                    self._pages_of(i, len(self._pager.owned(i))),
                    kvs,
                )
                if carried:
                    self._states = self._insert_state(
                        self._states, self._h2d(np.int32(i)), carried
                    )
                    self._count_state_write(False)
                self._count_prefill(s0)
                cap_tokens = s0
            if not chunked and self._shares_pages:
                # Publish this request's full prompt pages for future
                # sharing (first writer wins; the shared ones are
                # already registered). Chunked admissions register on
                # their final pass instead.
                owned = self._pager.owned(i)
                for j in range(m, s0 // self._page):
                    self._pager.register(
                        owned[j], Pager.prefix_key(req.prompt, (j + 1) * self._page)
                    )
            if tracer.enabled and not chunked:
                tracer.add_span(
                    "batcher.prefill",
                    start=t0,
                    end=tracer.now(),
                    request=req.req_id,
                    bucket=bucket,
                    prefix_pages=m,
                )
            slot.req = req
            slot.s0 = s0
            slot.pos = s0
            slot.emitted = 0
            slot.tokens = []
            slot.lps = []
            slot.t_first = 0.0  # timeline: no token emitted yet
            slot.obs_count = 0
            # A replayed request that already missed its budget stays
            # missed — its client experienced the violation.
            slot.slo_ok = not req.slo_violated
            slot.pf_done = m * self._page if chunked else -1
            tok0 = lp0 = None
            if not chunked:
                # One host sync per admission either way; the fork path
                # reuses the group's cached first commit (greedy: the
                # first token is a pure function of the prompt).
                if cow:
                    tok0, lp0 = fg.first, fg.first_lp
                else:
                    with self._eobs.region("first_token"):
                        tok0, lp0 = int(first[0]), float(first_lp[0])
                    if self._capacity is not None and cap_tokens:
                        # The int() above is the host sync, so this
                        # wall covers dispatch AND compute.
                        self._capacity.on_prefill(
                            cap_tokens, time.perf_counter() - cap_t0
                        )
            with self._cv:
                self._admitting = None  # slot-bound: visible to cancel()
                self._admitted += 1
                gid = req.fanout_group
                if fg is not None and gid >= 0:
                    req.fanout_group = -1
                    fg.remaining -= 1
                    if (
                        fg.greedy
                        and fg.page is None
                        and fg.remaining > 0
                        and tok0 is not None
                    ):
                        # First admitted greedy sibling: claim its
                        # last prompt page (rc+1 — outlives the
                        # sibling's retirement) and cache its first
                        # commit for the siblings' forks. Chunked
                        # admissions leave the group fork-less
                        # (tok0 is None): later siblings run the
                        # ordinary suffix path.
                        fg.page = self._pager.owned(i)[
                            (s0 - 1) // self._page
                        ]
                        self._pager.retain(fg.page)
                        fg.first, fg.first_lp = tok0, lp0
                    if fg.remaining <= 0:
                        self._fanout_kill_locked(gid, fg, direct=True)
            global_metrics().inc("continuous.admitted")
            # Prefix-cache effectiveness per admission: prompt pages
            # REUSED from the content-addressed cache instead of
            # recomputed (0 on a cold admission). Per-admission, not
            # per-token — always on, like the flight events.
            global_metrics().observe(
                "paged.pages_reused_per_admission", float(m)
            )
            # A replay's wait measures from its re-queue, not from the
            # original submit (that span is first-life decode plus the
            # recovery wall, not time spent queued).
            queue_wait = time.perf_counter() - (
                req.t_requeued or req.t_submit
            )
            if self._capacity is not None:
                self._capacity.on_queue_wait(queue_wait)
            if self.obs_timeline:
                global_metrics().observe(
                    "continuous.queue_wait_s", queue_wait
                )
            global_flight_recorder().record(
                "admit",
                request=req.req_id,
                slot=slot.idx,
                prompt_len=s0,
                chunked=chunked,
                queue_wait_s=round(queue_wait, 6),
            )
            if not chunked:
                self._commit(slot, tok0, lp0)
                if slot.req is req:
                    # Survived the first commit: stage its whole device
                    # row in one fused setter call (and, speculating,
                    # seed the draft's cache with the prompt).
                    if self._spec:
                        self._admit_draft(slot.idx, req)
                    self._stage_decode_row(slot)

    def _stage_decode_row(self, slot: _Slot) -> None:
        """Stage one freshly admitted slot's sampling row into the
        device state: THREE fused transfers (int vector, float vector,
        key block) + one donated setter dispatch, however many sampling
        fields a request carries. The key block pads to a power-of-two
        bucket so _stage_slot compiles log2(max_steps) variants."""
        req = slot.req
        nk = req.folded_keys.shape[0]
        nkb = 1
        while nkb < nk:
            nkb *= 2
        # The bucket must still fit the (slots, max_len, 2) key buffer
        # (nk <= max_len - 1 by submit()'s length check, so the cap
        # never truncates real keys).
        nkb = min(nkb, self.lm.max_len)
        kbuf = np.zeros((nkb, 2), np.uint32)
        kbuf[:nk] = req.folded_keys
        ints = np.array(
            [
                slot.idx,
                slot.last_token,
                # tick-entry invariant: the next step consumes
                # last_token (stream index emitted-1) at s0 + emitted - 1
                slot.s0 + slot.emitted - 1,
                req.top_k,
                nk,
                slot.emitted,
            ],
            np.int32,
        )
        floats = np.array([req.temperature, req.top_p], np.float32)
        self._variants.setdefault("continuous.stage_slot", set()).add(nkb)
        self._dstate = self._stage_slot(
            self._dstate,
            self._h2d(ints),
            self._h2d(floats),
            self._h2d(kbuf),
            epoch=self._mesh_epoch,
        )

    def _ensure_mesh(self) -> None:
        """The device-lost gate, shared by every dispatch ENTRY POINT
        running on the ticking thread (``tick``,
        :meth:`adopt_prefill_pages`): a mesh device died since the
        last pass — recover BEFORE dispatching anything onto the
        broken layout. Under ``auto_reshard`` this re-shards inline
        and proceeds on the shrunk mesh; otherwise every dispatch
        raises until :meth:`recover` is called."""
        if self._lost_pending:
            if self._recovery.auto_reshard:
                self.recover()
            else:
                with self._cv:
                    lost = list(self._lost_pending)
                raise DeviceLostError(
                    f"mesh device(s) lost: {lost} — auto_reshard is "
                    "off; call recover()"
                )

    def _count_prefill(self, n: int) -> None:
        """Book ``n`` prompt positions computed by an in-tick prefill
        pass (instance counter always; the registry counter rides the
        ``obs_timeline`` gate like every other timeline counter — one
        inc per pass, admission-rate, not token-rate)."""
        self._prefill_tokens += n
        if self.obs_timeline:
            global_metrics().inc(
                "continuous.prefill_tokens_total", float(n)
            )

    def _current_table(self):
        """Device copy of the pager's page table, re-uploaded only when
        the host table changed (admissions, retirements, window
        recycling, prefix shares) — a steady-state paged tick performs
        zero table transfers. Snapshot-compare rather than dirty flags:
        self-healing against any new pager mutation site."""
        t = np.asarray(self._pager.table())
        if self._table_dev is None or not np.array_equal(
            t, self._table_snapshot
        ):
            self._table_snapshot = np.array(t, copy=True)
            self._table_dev = self._h2d(self._table_snapshot)
        if len(self._groups) == 1:
            return self._table_dev
        # One table a cache group, each re-uploaded when IT changed (a
        # window group's does whenever a row crosses a page edge).
        for gi, pager in enumerate(self._pagers[1:]):
            t = pager.table()
            held = self._group_tables[gi]
            if held is None or not np.array_equal(t, held[0]):
                self._group_tables[gi] = (t, self._h2d(t))
        return (self._table_dev,) + tuple(
            dev for _, dev in self._group_tables
        )

    def _count_selection(self, fl: "_InFlight") -> None:
        """``dsa.*`` of one committed tick, from the host's own
        positions (no device read): the selecting layers' scan steps,
        and over the tick's live rows, steps and selecting layers the
        positions an indexer scored (the context) and those the
        attention then read (at most ``top_k`` of it)."""
        layers = len(self._layout.selecting_blocks)
        ctx = np.asarray([
            slot.pos for i, slot in enumerate(self.slots)
            if fl.reqs[i] is not None and slot.req is fl.reqs[i]
        ], np.int64)[:, None] + np.arange(1, self.chunk + 1)
        m = global_metrics()
        m.inc("dsa.steps", float(layers * self.chunk))
        m.inc("dsa.positions_scored", float(layers * ctx.sum()))
        m.inc("dsa.positions_selected", float(
            layers * np.minimum(ctx, self._select_top_k).sum()
        ))

    def _require(self, *features: str) -> None:
        """Refuse by name what this model's cache cannot do for one of
        ``features`` (:func:`_unmet`)."""
        unmet = _unmet(self._layout, *features)
        if unmet:
            feature, what, detail = unmet
            raise ValueError(
                f"{feature} does not run for a model with {what} "
                f"({detail}) yet"
            )

    def _hold_groups(self, slot: int, lo_pos: int, hi_pos: int) -> None:
        """Before a pass that writes positions ``[lo_pos, hi_pos)`` of
        ``slot`` (or, ``lo_pos == hi_pos``, one that only needs what
        came before): every cache group after the first holds exactly
        the pages the pass touches (``Pager.hold``) — from the window
        behind the first write, or the whole context where the group
        has no window, through the last write."""
        P = self._page
        for g, pager in zip(self._groups[1:], self._pagers[1:]):
            lo = 0 if g.window is None else max(0, lo_pos - g.window + 1)
            pager.hold(slot, lo // P, -(-hi_pos // P))

    def _dispatched_pos(self, sl: _Slot) -> int:
        """Where ``sl``'s next decode pass starts ON THE DEVICE:
        ``sl.pos`` is what the last commit left, and a tick still in
        flight that decodes the row for the same request and life has
        moved it one chunk on (an overlapped row advances a whole
        chunk or ends; the host learns which at that tick's commit)."""
        fl = self._inflight
        ahead = (
            fl is not None
            and fl.reqs[sl.idx] is sl.req
            and fl.lives[sl.idx] is sl.tokens
        )
        return sl.pos + (self.chunk if ahead else 0)

    def _pages_of(self, slot: int, n: int, pad: int | None = None):
        """``slot``'s first ``n`` logical pages as a program takes
        them, staged: one (pad or n,) list where the model has one
        cache group, else one a group (ordinals a group does not hold
        point at the trash page: behind its window, masked). ``pad``
        (>= n) fills with the trash page."""
        out = []
        for pager in self._pagers:
            row = np.zeros((pad or n,), np.int32)
            row[:n] = pager.table_row(slot)[:n]
            out.append(self._h2d(row))
        return out[0] if len(out) == 1 else tuple(out)

    def _prefill_step(self, slot: _Slot) -> None:
        """One chunked-prefill pass for ``slot``: write positions
        [pf_done, pf_done + clen) through the incremental-prefill body.
        The final pass samples the first token and flips the slot into
        the decode batch."""
        req, s0, P = slot.req, slot.s0, self._page
        tracer = global_tracer()
        t0 = tracer.now() if tracer.enabled else 0.0
        pos0 = slot.pf_done  # page-aligned (chunks are page multiples)
        clen = min(self._prefill_chunk, s0 - pos0)
        final = pos0 + clen >= s0
        # span=False: batcher.prefill_chunk below is the tracer row.
        with self._eobs.region(
            "prefill_chunk",
            span=False,
            request=req.req_id,
            pos0=int(pos0),
            chunk_len=int(clen),
            final=final,
        ):
            cbucket = -(-clen // P) * P
            n_strip = (pos0 + cbucket) // P
            owned = self._pager.owned(slot.idx)
            assert n_strip <= len(owned)
            # Pad the window to a power-of-two page count so a long prompt
            # compiles log2 variants instead of one per chunk ordinal (pad
            # entries point at the trash page; their positions sit past the
            # chunk's causal window, masked and compute-skipped).
            n_pad = 1
            while n_pad < n_strip:
                n_pad *= 2
            self._hold_groups(slot.idx, pos0, pos0 + cbucket)
            ids = np.zeros((1, cbucket), np.int32)
            ids[0, :clen] = req.prompt[pos0:pos0 + clen]
            # A model with recurrent state names the slot whose state
            # the pass carries on from.
            ints = [pos0, clen, req.top_k] + (
                [slot.idx] if self._layout.state_blocks else []
            )
            (first, first_lp, self._caches,
             self._states) = self._prefill_suffix_fn(
                cbucket, n_pad, sample=final
            )(
                self._served,
                self._caches,
                self._pages_of(slot.idx, n_strip, n_pad),
                self._h2d(ids),
                self._h2d(np.array(ints, np.int32)),
                self._h2d(np.array(
                    [req.temperature, req.top_p], np.float32
                )),
                self._h2d(req.folded_keys[0][None]),
                self._states,
                # Only the final pass samples; mid-prefill passes must not
                # fork compile variants over sampling flags they never use.
                truncate=final and req.top_k < self.lm.vocab,
                nucleus=final and req.top_p < 1.0,
            )
            self._count_state_write(pos0 > 0)
            slot.pf_done = pos0 + clen
            self._count_prefill(clen)
            if tracer.enabled:
                tracer.add_span(
                    "batcher.prefill_chunk",
                    start=t0,
                    end=tracer.now(),
                    request=req.req_id,
                    pos0=int(pos0),
                    chunk_len=int(clen),
                    final=final,
                )
            if final:
                # register() skips known keys (one cache group only:
                # several share nothing).
                for j in range(s0 // P if self._shares_pages else 0):
                    self._pager.register(
                        owned[j], Pager.prefix_key(req.prompt, (j + 1) * P)
                    )
                slot.pf_done = -1
                with self._eobs.region("first_token"):
                    tok0, lp0 = int(first[0]), float(first_lp[0])
                self._commit(slot, tok0, lp0)
                if slot.req is req:
                    if self._spec:
                        self._admit_draft(slot.idx, req)
                    self._stage_decode_row(slot)

    def _spec_decode(self, active, tracer):
        """Dispatch one SPECULATIVE decode round for the whole slot
        batch: the fixed-shape draft scan
        (``models/speculative.draft_chunk`` over the device-resident
        per-slot state), then the fused verify-and-accept program
        (``_spec_verify``). Exactly two compiled programs however rows
        desynchronize — guarded by the compile-count test. Stages zero
        host arrays steady-state; the round's (tokens, logprobs,
        accepted) D2H starts here as ONE async fetch and lands in
        ``_tick_commit``, one ``tick()`` later (or at a ``drain()``).
        Returns the round's :class:`_InFlight` (binding identity is
        filled in by ``_tick_dispatch``)."""
        d = self._spec_k_eff
        w = self._spec_w
        # Static sampling flags, the lockstep path's own derivation:
        # an all-greedy batch keeps dispatching the PR-12 program text
        # (bit-identity + compile footprint pinned); any sampled row
        # switches the verify to its speculative-sampling variant, with
        # the truncate/nucleus sorts elided unless some active request
        # needs them.
        sample, truncate, nucleus = self._sampling_flags(active)
        self._variants.setdefault("speculative.draft_chunk", set()).add(d)
        self._variants.setdefault("continuous.spec_verify", set()).add(
            (d, sample, truncate, nucleus)
        )
        eo = self._eobs
        # Only the span tags consume the id tuple — don't build it on
        # the untraced hot path.
        req_ids = (
            tuple(s.req.req_id for s in active) if tracer.enabled else ()
        )
        t_draft = tracer.now() if tracer.enabled else 0.0
        # span=False: decode.draft below is the tracer row.
        with eo.region("draft", span=False):
            if w:
                # Tree drafts: d chain steps + the argmax-leaf step +
                # one leaf-coverage step (the leaf token's own
                # draft-cache write), with the top-w leaf candidates
                # harvested from logits the scan computes anyway (equal
                # draft FLOPs per committed token). cands = the top-w
                # ids of the step that predicts the post-chain position
                # (scan index d).
                dtoks, dtops, self._draft_caches = draft_chunk(
                    self._draft_lm,
                    self._draft_variables,
                    self._dstate["tok"],
                    self._dstate["pos"],
                    self._draft_caches,
                    n=d + 2,
                    tail_w=w,
                )
                cands = dtops[d]  # (B, w); cands[:, 0] == dtoks[d]
            else:
                cands = None
                dtoks, self._draft_caches = draft_chunk(
                    self._draft_lm,
                    self._draft_variables,
                    self._dstate["tok"],
                    self._dstate["pos"],
                    self._draft_caches,
                    n=d + 1,
                )
        if tracer.enabled:
            # Dispatch-side cost of the draft scan; the verify span
            # below carries the host sync. Tagged with the same request
            # ids the framing headers use, so Perfetto correlates these
            # rows with dispatcher/worker spans.
            tracer.add_span(
                "decode.draft",
                start=t_draft,
                end=tracer.now(),
                slots=len(active),
                draft_k=d,
                requests=req_ids,
            )
        # The verify stamp closes in the commit half (eo.phase): armed
        # only if the gate is on NOW, and closed only if still armed.
        t_ph = eo.now() if eo.enabled else 0.0
        t_verify = tracer.now() if tracer.enabled else 0.0
        toks, lps, acc, self._caches, self._dstate = self._spec_verify(
            self._served,
            self._caches,
            self._dstate,
            dtoks,
            self._current_table(),
            cands,
            sample=sample,
            truncate=truncate,
            nucleus=nucleus,
            epoch=self._mesh_epoch,
        )
        self._count_tick(sample)
        # The round's ONE host fetch covers all three arrays — started
        # here (async), landed at commit.
        return _InFlight(
            fetch=_AsyncFetch((toks, lps, acc)),
            reqs=[],
            lives=[],
            spec=(d, w, tuple(s.idx for s in active)),
            t_span=t_verify,
            t_eo=t_ph,
            req_ids=req_ids,
        )

    def tick(self) -> int:
        """Admit waiting requests into free slots, run ONE prefill chunk
        for each slot mid-chunked-prefill, then decode: one chunk of
        lockstep steps (a single compiled scan) — or, in speculative
        mode, one draft-scan + fused-verify round that commits
        1..draft_k+1 tokens per slot (``_spec_decode``). Returns the
        number of active slots whose decode pass was COMMITTED by this
        call (0 = nothing committed).

        The call is split into a host **dispatch** half
        (``_tick_dispatch``: scheduler/admission/prefill + the decode
        dispatch, with the D2H fetch started asynchronously) and a
        **commit** half (``_tick_commit``: land the fetch, apply
        per-slot commits, flush telemetry). This call dispatches tick
        *t* (its programs enqueue behind *t−1*'s on the device stream)
        and then commits tick *t−1* while *t* runs on device: the
        host's fetch, commits, callbacks and the caller's own work
        between calls overlap the device wall, and every result is
        delivered with a one-tick lag. :meth:`drain` lands what is in
        flight and is THE way to stand at a known position;
        :meth:`run` exit and :meth:`recover` drain, the latter also
        from ``_ensure_mesh`` inside the dispatch half on a device
        loss.

        Phases (``utils.profiling.EngineObs``): the call is one
        ``engine.tick`` region holding ``engine.admit`` /
        ``engine.prefill`` (one ``engine.prefill_chunk`` per pass) /
        ``engine.launch`` (with ``engine.draft`` when speculating) /
        ``engine.fetch`` / ``engine.commit`` / ``engine.update``, and
        ``engine.first_token`` around each blocking first-token read.
        Every region is a ``jax.profiler.TraceAnnotation`` always (a
        profiler session puts them on the device trace's clock), and
        with ``obs_engine`` on each also records one
        ``engine.phase.<name>_s`` histogram sample; off, a site costs
        the annotation and one branch. The cross-half stamps verify /
        decode / dispatch / commit_lag are histogram-only:
        decode/verify span dispatch→results-landed, so they OVERLAP
        the other phases of the next call — that overlap is
        the win, gauged as ``runtime.overlap_ratio``. The compile
        sentinel samples once at the end of every commit half, so an
        unexpected recompile is flagged next to the tick that paid for
        it."""
        with self._eobs.region("tick"):
            fl = self._tick_dispatch()
            prev, self._inflight = self._inflight, fl
            if prev is not None:
                return self._tick_commit(prev, overlapped=fl is not None)
            return 0

    def drain(self) -> int:
        """Commit the in-flight tick, if any — the explicit pipeline
        boundary. Call before reading results
        outside :meth:`run` / :meth:`result`, before handing the
        device to another dispatcher (DisaggServer does), or before
        tearing down. Idempotent; returns the committed tick's active
        count (0 = pipeline was empty)."""
        fl, self._inflight = self._inflight, None
        if fl is not None:
            return self._tick_commit(fl)
        return 0

    def _tick_dispatch(self) -> "_InFlight | None":
        """Host half of one tick: degradation/tier steps, admission,
        cancel sweep, chunked-prefill passes, gauge refresh, then ONE
        decode dispatch with its async D2H fetch started. Returns the
        tick's :class:`_InFlight` record, or None for an idle tick
        (nothing dispatched)."""
        self._ensure_mesh()
        t0 = time.perf_counter()  # dispatch wall for overlap_ratio
        if self._controller is not None:
            # Closed-loop degradation BEFORE admission: this tick's
            # admits see the ladder's current shed level.
            self._controller.step(self)
        if self._tier is not None:
            # Host-tier step BEFORE admission: reset the per-tick
            # spill/readmit budgets and pre-spill the coldest LRU
            # pages past the watermark, so admission-pressure
            # evictions this tick find their content host-backed.
            self._tier_step()
        eo = self._eobs
        # Prefill-stall accounting (continuous.prefill_stall_s): when
        # requests were already DECODING at tick entry, every second
        # this tick spends on in-tick prefill work (admission prefill
        # passes, chunked-prefill passes) is decode delay they eat as
        # inter-token latency — the pathology the disaggregated path
        # (runtime/disagg) exists to remove. Two stamps + one counter
        # delta per tick; observed only when prefill actually ran.
        obs_on = self.obs_timeline
        decode_waiting = obs_on and any(
            s.req is not None and s.pf_done < 0 for s in self.slots
        )
        t_stall0 = time.perf_counter() if decode_waiting else 0.0
        pf_tokens0 = self._prefill_tokens
        with eo.region("admit"):
            self._admit()
        for slot in self.slots:
            if slot.req is None:
                continue
            with self._cv:
                cancelled = slot.req.req_id in self._cancelled
                self._cancelled.discard(slot.req.req_id)
            if cancelled:  # mid-prefill or between chunks
                self._finish(slot, reason="cancelled")
        with eo.region("prefill"):
            for slot in self.slots:
                if slot.req is not None and slot.pf_done >= 0:
                    self._prefill_step(slot)  # interleaves with decode
        if decode_waiting and self._prefill_tokens > pf_tokens0:
            global_metrics().observe(
                "continuous.prefill_stall_s",
                time.perf_counter() - t_stall0,
            )
        active = [
            s for s in self.slots
            if s.req is not None and s.pf_done < 0
        ]
        # Gauges refresh BEFORE the idle early-return, or an empty
        # batcher would scrape its last busy tick's values forever.
        # active_slots means OCCUPANCY (request held), matching
        # stats()["active"]; the prefilling subset gets its own gauge —
        # a device busy with chunk passes must not scrape as idle.
        global_metrics().set_gauge(
            "continuous.active_slots",
            sum(1 for s in self.slots if s.req is not None),
        )
        global_metrics().set_gauge(
            "continuous.prefilling_slots",
            sum(1 for s in self.slots
                if s.req is not None and s.pf_done >= 0),
        )
        global_metrics().set_gauge("continuous.queue_depth", len(self._queue))
        if self._sched is not None:
            # Per-tenant queue-depth gauges — bounded cardinality: the
            # queue retains at most _MAX_TENANTS drained tenants (so
            # recent ones read 0 instead of going stale), and gauges
            # for tenants it evicted are removed here in step.
            with self._cv:
                depths = self._queue.depths()
            for tenant in self._gauged_tenants - depths.keys():
                global_metrics().remove_gauge(
                    f"scheduler.queue_depth.{tenant}"
                )
            for tenant, depth in depths.items():
                global_metrics().set_gauge(
                    f"scheduler.queue_depth.{tenant}", float(depth)
                )
            self._gauged_tenants = set(depths)
        # Bridge PR-1's fused-staging counter to /metrics: transfers are
        # cumulative, so dashboards derive the steady-state rate (the
        # contract: flat between admissions).
        global_metrics().set_gauge(
            "continuous.h2d_transfers", float(self._h2d_count)
        )
        if not active:
            if self.obs_timeline:
                # Idle ticks still flush (first-token commits from an
                # admission whose request finished in one step, goodput
                # decay toward zero).
                self._obs_flush()
            self._sentinel.sample(write_gauges=False)
            return None
        tracer = global_tracer()
        # Snapshot the gate ONCE for the cross-half stamps armed below
        # (decode's open, dispatch's close): flipping obs_engine
        # mid-tick must never pair a 0.0 open with an enabled close (a
        # perf-counter-sized garbage sample).
        eo_on = eo.enabled
        with eo.region("launch"):
            if self._spec is not None:
                fl = self._spec_decode(active, tracer)
            else:
                t_ph = eo.now() if eo_on else 0.0
                # The whole per-slot staging block the old path rebuilt
                # and transferred here every tick (tokens/pos/keys/
                # temps/top_ks/top_ps/greedy — O(slots x fields)
                # jnp.asarray calls) is GONE: the state already lives
                # on device (_dstate, staged once per admission), so a
                # steady-state tick stages zero host scalars and the
                # paged table re-uploads only when it changed.
                sample, truncate, nucleus = self._sampling_flags(active)
                self._variants.setdefault(
                    "continuous.step_chunk", set()
                ).add((truncate, nucleus))
                t_chunk = tracer.now() if tracer.enabled else 0.0
                if len(self._groups) > 1:
                    # The scan writes [pos, pos + chunk) from where the
                    # DEVICE will stand, within the request's span
                    # (what overshoots a finishing request goes to the
                    # trash page, as does every write of a row that
                    # ended inside the tick in flight: pos is then at
                    # or past the span's end and nothing is granted).
                    # A page released here may still be read by the
                    # tick in flight: through the table uploaded at ITS
                    # dispatch, before any program enqueued after this
                    # point can write the page.
                    for sl in active:
                        pos = self._dispatched_pos(sl)
                        self._hold_groups(sl.idx, pos, min(
                            pos + self.chunk, sl.s0 + sl.req.steps
                        ))
                (toks, lps, self._caches, self._dstate,
                 moe, self._states) = self._step_chunk(
                    self._served,
                    self._caches,
                    self._dstate,
                    self._current_table(),
                    self._states,
                    truncate=truncate,
                    nucleus=nucleus,
                    epoch=self._mesh_epoch,
                )
                self._count_tick(sample)
                # The chunk's ONE host fetch covers both arrays —
                # started here (async), landed at commit.
                fl = _InFlight(
                    fetch=_AsyncFetch(
                        (toks, lps) if moe is None else (toks, lps, moe)
                    ),
                    reqs=[],
                    lives=[],
                    t_span=t_chunk,
                    t_eo=t_ph,
                )
        # Binding identity for every slot in the decode batch: commit
        # applies a slot's column only while it still holds the same
        # request object AND the same life (slot.tokens list identity —
        # see _InFlight). Captured AFTER the dispatch so a prefill-
        # finishing slot that joined `active` this tick is included.
        fl.reqs = [
            s.req if (s.req is not None and s.pf_done < 0) else None
            for s in self.slots
        ]
        fl.lives = [
            s.tokens if fl.reqs[i] is not None else None
            for i, s in enumerate(self.slots)
        ]
        fl.n_active = len(active)
        fl.t0 = t0
        if eo_on:
            # Total host-side cost of this dispatch half — what the
            # pipelined loop overlaps with the device wall.
            eo.phase("dispatch", t0, span=False)
        fl.t_dispatched = time.perf_counter()
        return fl

    def _tick_commit(self, fl: "_InFlight", overlapped: bool = False) -> int:
        """Commit half of one tick: land ``fl``'s async fetch, close
        the decode/verify spans it opened, apply per-slot token
        commits (skipping slots whose binding changed since dispatch —
        their columns are a bounded garbage tail nobody reads), then
        window recycling, the telemetry flush, and the compile-
        sentinel sample. ``overlapped`` says another decode dispatch
        followed ``fl``'s before this commit
        (``runtime.ticks_overlapped`` counts those commits,
        ``runtime.ticks_synchronous`` the rest: a :meth:`drain`, the
        last tick of a :meth:`run`)."""
        eo = self._eobs
        eo_on = eo.enabled
        if eo_on and fl.t_dispatched:
            # Dispatch-end -> commit-entry: the NEXT tick's dispatch
            # wall (the lag the stream timing docs describe), ~0 at a
            # drain().
            eo.phase("commit_lag", fl.t_dispatched, span=False)
        # The blocked part of the result fetch (``fetch.wait_s``): what
        # the device, or the transfer after it, made the host wait.
        with eo.region("fetch"):
            host = fl.fetch.commit()
        tracer = global_tracer()
        if fl.spec is None:
            toks, lps = host[:2]
            if len(host) > 2:
                self._moe_counts(host[2])
            if self._stream_blocks:
                global_metrics().inc(
                    "mhc.mixes", float(2 * self._stream_blocks * self.chunk)
                )
            if self._linear_blocks:
                global_metrics().inc(
                    "kda.steps", float(self._linear_blocks * self.chunk)
                )
            if self._layout.latent_blocks:
                global_metrics().inc(
                    "mla.steps",
                    float(len(self._layout.latent_blocks) * self.chunk),
                )
            if self._select_top_k:
                self._count_selection(fl)
            limits = np.full((toks.shape[1],), self.chunk, np.int64)
            if tracer.enabled and fl.t_span:
                # Dispatch -> results-landed of one compiled decode
                # chunk — the Perfetto row that shows tick cadence and
                # chunk cost (overlaps other rows under the pipelined
                # loop).
                tracer.add_span(
                    "batcher.decode_chunk",
                    start=fl.t_span,
                    end=tracer.now(),
                    slots=fl.n_active,
                    chunk=self.chunk,
                )
            if eo_on and fl.t_eo:
                # span=False: batcher.decode_chunk above is already the
                # tracer row for this window.
                eo.phase("decode", fl.t_eo, span=False)
        else:
            toks, lps, acc = host
            d, w, active_idx = fl.spec
            if tracer.enabled and fl.t_span:
                tracer.add_span(
                    "decode.verify",
                    start=fl.t_span,
                    end=tracer.now(),
                    slots=len(active_idx),
                    draft_k=d,
                    requests=fl.req_ids,
                )
            if eo_on and fl.t_eo:
                # Ends when the round's ONE fused fetch lands
                # (decode.verify is the tracer row for the same
                # window).
                eo.phase("verify", fl.t_eo, span=False)
            # Acceptance accounting: drafted/accepted proposals for
            # the rows ACTIVE at dispatch only (idle rows verify
            # garbage nobody commits). Both counters move under _cv so
            # a concurrent stats() snapshot cannot tear across them
            # (the ADVICE-r4 rule the other lifetime counters follow).
            # (d, w) come from the dispatch snapshot — set_draft_k
            # mid-lag must not misattribute the round.
            acc_counts = [int(acc[i]) for i in active_idx]
            with self._cv:
                # Tree rounds draft d chain proposals + w leaf
                # candidates per slot (acc counts a leaf hit as one
                # more accepted).
                self._spec_drafted += (d + w) * len(active_idx)
                self._spec_accepted += sum(acc_counts)
                ratio = (
                    self._spec_accepted / self._spec_drafted
                    if self._spec_drafted
                    else 0.0
                )
            global_metrics().set_gauge("continuous.spec_acceptance", ratio)
            if self.obs_timeline:
                # One histogram sample per active slot per tick (one
                # registry-lock hold, like the ITL flush).
                global_metrics().observe_many(
                    "continuous.spec_accepted_per_tick",
                    [float(a) for a in acc_counts],
                )
            limits = np.asarray(acc, np.int64) + 1
        if fl.t0:
            # Overlap gauge: the fraction of the dispatch->results
            # wall the host did NOT spend blocked on the fetch: -> 1
            # when the next dispatch hides the device wall, ~0 for a
            # device-bound commit with nothing dispatched behind it.
            wall = time.perf_counter() - fl.t0
            if wall > 0:
                global_metrics().set_gauge(
                    "runtime.overlap_ratio",
                    max(0.0, 1.0 - fl.fetch.wait_s / wall),
                )
        past_end = 0
        with eo.region("commit"):
            for i, slot in enumerate(self.slots):
                req = fl.reqs[i]
                if req is None:
                    continue
                if (
                    slot.req is not req
                    or slot.tokens is not fl.lives[i]
                    or slot.pf_done >= 0
                ):
                    # The binding moved since dispatch (retire + re-admit,
                    # preempt + replay — possible only under the one-tick
                    # lag): this column belongs to a dead life. Drop it.
                    past_end += 1
                    continue
                # limits[i] is the slot's committable token count this tick:
                # the full chunk in lockstep mode, the accepted prefix + 1
                # correction token in speculative mode (rows desynchronize).
                for j in range(int(limits[i])):
                    self._commit(slot, int(toks[j, i]), float(lps[j, i]))
                    if slot.req is not req:  # finished (steps or EOS)
                        break
                if slot.req is req:
                    # pos invariant at tick entry: the next step consumes
                    # last_token (stream index emitted-1) at s0 + emitted - 1.
                    slot.pos = slot.s0 + slot.emitted - 1
        # "update" = post-commit bookkeeping: window recycling, the
        # batched ITL flush, occupancy gauges, the sentinel sample.
        with eo.region("update"):
            # How often the overlapped order engaged, and its known
            # waste: rows this tick decoded for a request the commit
            # before it had already retired (at most one tick each).
            m = global_metrics()
            m.inc(
                "runtime.ticks_overlapped" if overlapped
                else "runtime.ticks_synchronous"
            )
            if past_end:
                m.inc("runtime.rows_past_end", float(past_end))
            if self._window is not None:
                # Rolling-window recycling: pages wholly behind every future
                # read ((o+1)*P <= pos - window + 1 — reads from here on
                # mask positions < index - window + 1 and writes land at
                # >= pos) go back to the pool MID-REQUEST, so pool pressure
                # bounds by the window, not the sequence.
                for slot in self.slots:
                    if slot.req is None or slot.pf_done >= 0:
                        continue
                    dead = max(
                        0, slot.pos - self._window + 1
                    ) // self._page - self._pager.base(slot.idx)
                    if dead > 0:
                        self._pager.release_prefix(slot.idx, dead)
            # Flush the tick's timeline/SLO bookkeeping in O(1) registry
            # lock acquisitions (not one per committed token): batched ITL
            # samples, SLO attainment counters/gauges, goodput counters +
            # windowed rate gauge.
            if self.obs_timeline:
                self._obs_flush()
            # Post-commit occupancy: slots retired by this chunk are gone.
            global_metrics().set_gauge(
                "continuous.active_slots",
                sum(1 for sl in self.slots if sl.req is not None),
            )
            self._sentinel.sample(write_gauges=False)
        return fl.n_active

    def capacity_book(self) -> dict | None:
        """The capacity plane's last rebuilt book (None when the plane
        is disabled). JSON-safe — the exact object telemetry providers
        and lease meta advertise; its ``wall`` stamp lets any consumer
        age it. Before the first ``_obs_flush`` rebuild this is the
        constructor's empty-headroom book, still well-formed."""
        if self._capacity is None:
            return None
        return self._capacity.book()

    @property
    def variables(self):
        """The MODEL's tree, as placed: what the graph's own modules, a
        ``PrefillWorker`` or a reference apply, and what a caller sets.
        The engine's programs take ``_served``: the same tree where a
        row of the embedding tables is whole lane tiles, else the tables
        padded to the next tile, once, when the tree is set (so no
        program rewrites the token table row-major before its gather:
        ``transformer_lm.lane_tiled``), applied by ``_embed``, the
        module that knows the width; this cuts them back."""
        if self._served is None:
            return None
        return embed_tables_for(
            self.lm.graph.node("embed").module, self._served
        )

    @variables.setter
    def variables(self, tree):
        # None: a caller dropping the weights before it builds the next.
        self._embed = lane_tiled(self.lm.graph.node("embed").module)
        self._served = (
            None if tree is None else embed_tables_for(self._embed, tree)
        )

    def stats(self) -> dict:
        """Serving observability snapshot: slot occupancy, queue depth,
        and THIS batcher's lifetime admit/complete/tick counts
        (instance-scoped — mirror counters also land in
        ``utils.metrics.global_metrics`` for process-level scraping)."""
        # Snapshot under _cv so the counts are mutually consistent even
        # when the server thread is mid-tick (ADVICE r4 — unlocked reads
        # were benign under the GIL but could tear across fields).
        with self._cv:
            out = {
                "slots": len(self.slots),
                "active": sum(1 for s in self.slots if s.req is not None),
                "queued": len(self._queue),
                "finished_unclaimed": len(self._done),
                "admitted": self._admitted,
                "completed": self._completed,
                "ticks": self._ticks,
                # Of those, the ticks on which some row sampled.
                "ticks_sampled": self._ticks_sampled,
                # Whether a dispatched tick awaits its commit right now.
                "inflight": self._inflight is not None,
                # Prompt positions prefilled IN-TICK by this batcher
                # (full/suffix/chunk passes; prefix-cache hits and
                # disaggregated handoffs excluded) — pair with the
                # committed-token counters for a prefill/decode
                # tokens-per-second split.
                "prefill_tokens": self._prefill_tokens,
                # Lanes added to a row of the embedding tables this
                # engine holds (``variables``): 0 where the model's
                # rows are whole lane tiles.
                "embed_row_pad": (
                    (self._embed.table_dim or self._embed.dim)
                    - self._embed.dim
                ),
                # Host->device staging transfers this batcher issued
                # (every jnp.asarray in this module funnels through
                # _h2d): the fused-staging contract is ZERO per
                # steady-state tick, O(1) per admission/retirement.
                "h2d_transfers": self._h2d_count,
                # Resident KV pool bytes (scale planes of a quantized
                # pool included) — the capacity number
                # benches and dashboards report. cache_bytes is the
                # LOGICAL size; under tensor parallelism each device
                # holds cache_bytes_per_device == cache_bytes / tp (the
                # head axis shards), which is the number HBM planning
                # must use.
                "cache_bytes": sum(
                    x.nbytes for x in jax.tree.leaves(self._caches)
                ),
                "cache_bytes_per_device": sum(
                    device_local_nbytes(x)
                    for x in jax.tree.leaves(self._caches)
                ),
                # Quantized ÷ native-equivalent cache bytes (scale
                # planes counted): the honest capacity multiplier —
                # 1.0 for native caches, (hd + 4) / (hd * itemsize)
                # for int8 + f32-scale ones.
                "cache_bytes_ratio": sum(
                    x.nbytes for x in jax.tree.leaves(self._caches)
                ) / float(self._native_cache_bytes),
                "tp": self._tp,
                # Elastic-recovery books (instance-lifetime mirrors of
                # the recovery.* registry counters; wall_s is the most
                # recent recovery's detection->migrated span).
                "recoveries": self._recoveries,
                "recovery_migrated": self._recovery_migrated,
                "recovery_replayed": self._recovery_replayed,
                "recovery_dropped": self._recovery_dropped,
                "last_recovery_wall_s": self._last_recovery_wall_s,
                # SLO attainment books (instance-lifetime, flushed
                # per tick — mirrors of the slo.* registry counters).
                "slo_ttft_met": self._slo_totals["ttft_met"],
                "slo_ttft_missed": self._slo_totals["ttft_missed"],
                "slo_itl_met": self._slo_totals["itl_met"],
                "slo_itl_missed": self._slo_totals["itl_missed"],
                # Traffic-control books (mirrors of the scheduler.*
                # registry counters). "queued" above is the BOUNDED
                # admission-queue depth — it can never exceed the
                # scheduler's max_queue_depth.
                "rejected": self._rejected,
                "preempted": self._preempted,
            }
            if self._controller is not None:
                out["degradation_level"] = self._controller.level
            if self._spec is not None:
                out["spec_drafted"] = self._spec_drafted
                out["spec_accepted"] = self._spec_accepted
                out["spec_acceptance"] = (
                    self._spec_accepted / self._spec_drafted
                    if self._spec_drafted
                    else 0.0
                )
                out["draft_cache_bytes"] = sum(
                    x.nbytes
                    for x in jax.tree.leaves(self._draft_caches)
                )
            if len(self._groups) > 1:
                # A pool and a page table a cache group; the unsuffixed
                # keys below are the first group's, as ever.
                for g, pager in zip(self._groups, self._pagers):
                    gs = pager.stats()
                    out[f"pool_pages.{g.name}"] = gs.num_pages
                    out[f"pages_in_use.{g.name}"] = gs.in_use
            #: Recurrent state beside the pages (0 where the model has
            #: none): bytes resident, and the slots that hold one each.
            out["state_bytes"] = sum(
                x.nbytes for x in jax.tree.leaves(self._states)
            )
            out["state_slots"] = (
                len(self.slots) if self._layout.state_blocks else 0
            )
            if not self._shares_pages:
                out["prefix_cache"] = "off: " + _unmet(
                    self._layout, "the radix prefix cache"
                )[1]
            ps = self._pager.stats()
            out["pool_pages"] = ps.num_pages
            #: What ONE position stores in a block of the first cache
            #: group (K and V of every KV head, or a latent row), in
            #: values and in bytes of the pool's own representation.
            out["pool_row_values"] = self._groups[0].position_values
            out["pool_row_bytes"] = sum(
                x.nbytes for x in jax.tree.leaves(
                    self._caches[self._groups[0].blocks[0]]
                )
            ) // (ps.num_pages * self._page)
            out["pages_in_use"] = ps.in_use
            out["pages_free"] = ps.free
            out["pages_cached"] = ps.cached
            out["prefix_hits"] = ps.prefix_hits
            out["prefix_misses"] = ps.prefix_misses
            out["prefix_capacity_skips"] = ps.prefix_capacity_skips
            # Radix prefix-cache books: resident token-block tree
            # size, partial-hit admissions (match stopped short of
            # the last full prompt page), token-weighted hit mass,
            # and radix-node evictions.
            out["radix_nodes"] = ps.radix_nodes
            out["radix_partial_hits"] = ps.radix_partial_hits
            out["radix_hit_tokens"] = ps.radix_hit_tokens
            out["radix_evictions"] = self._pager.radix_evictions
            # Copy-on-write fan-out books.
            out["cow_forks"] = ps.cow_forks
            out["fanout_groups"] = len(self._fanout_groups)
            if self._sp_cfg is not None:
                # Sequence-parallel prefill books: the live ring width
                # (1 = degraded to the ordinary path) and how many
                # admissions took the sp program.
                out["sp_width"] = (
                    self._sp.sp if self._sp is not None else 1
                )
                out["sp_prefills"] = self._sp_prefills
            if self._tier is not None:
                ts = self._tier.stats()
                out["host_pages"] = ts.pages
                out["host_bytes"] = ts.host_bytes
                out["tier_spilled"] = self._tier_spilled
                out["tier_readmitted"] = self._tier_readmitted
                out["tier_dropped"] = self._tier_dropped + ts.dropped
                out["tier_codec_bytes_saved"] = ts.codec_bytes_saved
        return out

    def _memory_stats(self) -> dict[str, float]:
        """Pull-style memory source for ``utils.profiling``'s engine
        collector (runs on exporter scrape threads — reads only, no
        locks, tolerant of racing a live tick). Keys are final metric
        names; the collector SUMS across live batchers:

        - ``memory.pool_bytes`` (LOGICAL pool bytes, int8 value+scale
          pairs included) / ``memory.pool_bytes_per_device`` (the
          per-chip resident bytes — == pool_bytes / tp under a
          head-sharded mesh; equal otherwise) plus page occupancy —
          ``memory.pages_used + pages_free + pages_cached ==
          memory.pool_pages`` (allocatable pool, trash page excluded) —
          and the pager's prefix-cache effectiveness counters
          (``paged.prefix_{hits,misses,capacity_skips}``);
        - speculative mode: ``memory.draft_cache_bytes`` (the draft's
          dense strips; it replicates under TP, so its per-device
          bytes ARE its logical bytes);
        - ``memory.kv_bytes_ratio`` — actual pool bytes (scale planes
          INCLUDED) over what the same geometry would cost in the
          native dtype. 1.0 native; ~(hd + 4)/(hd * itemsize)
          quantized — the 2-4x capacity win as a dashboard number.
        """
        cache_bytes = float(
            sum(x.nbytes for x in jax.tree.leaves(self._caches))
        )
        per_device = float(
            sum(
                device_local_nbytes(x)
                for x in jax.tree.leaves(self._caches)
            )
        )
        out: dict[str, float] = {}
        ps = self._pager.stats()
        out["memory.pool_bytes"] = cache_bytes
        out["memory.pool_bytes_per_device"] = per_device
        out["memory.pool_pages"] = float(self._pager.num_allocatable)
        out["memory.pages_used"] = float(ps.in_use)
        out["memory.pages_cached"] = float(ps.cached)
        # PagerStats.free counts evictable cached pages as free
        # (allocator view); the gauges partition instead.
        out["memory.pages_free"] = float(ps.free - ps.cached)
        out["paged.prefix_hits"] = float(ps.prefix_hits)
        out["paged.prefix_misses"] = float(ps.prefix_misses)
        out["paged.prefix_capacity_skips"] = float(
            ps.prefix_capacity_skips
        )
        # Radix prefix cache + copy-on-write fan-out gauges
        # (docs/OBSERVABILITY.md "Paged KV"): resident radix-tree size,
        # partial-hit admissions, token-weighted hit mass, and the
        # cumulative fork count (also an inc'd counter at the fork
        # site — the gauge makes it scrape-visible even between
        # exporter windows).
        out["paged.radix_nodes"] = float(ps.radix_nodes)
        out["paged.radix_partial_hits"] = float(ps.radix_partial_hits)
        out["paged.radix_hit_tokens"] = float(ps.radix_hit_tokens)
        out["paged.cow_forks_total"] = float(ps.cow_forks)
        if self._tier is not None:
            # Host-tier occupancy: pages_spilled counts pages RESIDENT
            # in host memory (warm + cold), host_bytes their post-codec
            # footprint. The HBM partition above (used + free + cached
            # == pool_pages) is untouched — the tier is a copy below
            # it, never double-counted.
            ts = self._tier.stats()
            out["memory.host_bytes"] = float(ts.host_bytes)
            out["memory.pages_spilled"] = float(ts.pages)
        out["memory.kv_bytes_ratio"] = cache_bytes / float(
            self._native_cache_bytes
        )
        if self._draft_caches is not None:
            out["memory.draft_cache_bytes"] = float(
                sum(x.nbytes for x in jax.tree.leaves(self._draft_caches))
            )
        if self._states is not None:
            out["memory.state_bytes"] = float(
                sum(x.nbytes for x in jax.tree.leaves(self._states))
            )
        return out

    def _program_costs(self) -> dict[str, dict[str, float]]:
        """Per-execution XLA ``cost_analysis`` (flops, bytes accessed)
        of this batcher's decode-path program — ``_step_chunk`` in
        lockstep mode, ``_spec_verify`` in speculative mode — computed
        ONCE, lazily, at the first roofline scrape. Lowering uses
        ``ShapeDtypeStruct`` stand-ins (never touches live buffers —
        a scrape can race a ticking thread's donation) and never
        compiles, so the watched jit caches do not grow: pulling
        roofline numbers must not itself read as a recompile
        (sentinel-checked in tests). Failures (exotic backend, no
        analysis support) cache as empty — a scrape degrades to no
        roofline gauges, never to an error."""
        if self._roofline_costs is not None:
            return self._roofline_costs
        av = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self._served, self._caches, self._dstate, self._states),
        )
        a_vars, a_caches, a_dstate, a_states = av
        a_table = jax.ShapeDtypeStruct(
            (len(self.slots), self._pager.pages_per_slot), jnp.int32
        )
        if len(self._groups) > 1:
            a_table = (a_table,) * len(self._groups)
        costs: dict[str, dict[str, float]] = {}
        try:
            if self._spec is not None:
                a_dtoks = jax.ShapeDtypeStruct(
                    (self._spec_k + (2 if self._spec_w else 1),
                     len(self.slots)),
                    jnp.int32,
                )
                a_cands = (
                    jax.ShapeDtypeStruct(
                        (len(self.slots), self._spec_w), jnp.int32
                    )
                    if self._spec_w
                    else None
                )
                costs["verify"] = program_cost_analysis(
                    type(self)._spec_verify,
                    self, a_vars, a_caches, a_dstate, a_dtoks, a_table,
                    a_cands,
                    epoch=self._mesh_epoch,
                )
            else:
                costs["decode"] = program_cost_analysis(
                    type(self)._step_chunk,
                    self, a_vars, a_caches, a_dstate, a_table, a_states,
                    truncate=False, nucleus=False,
                    epoch=self._mesh_epoch,
                )
        except Exception as e:  # noqa: BLE001 — degrade, don't break scrape
            log.info("roofline cost analysis unavailable: %r", e)
        self._roofline_costs = costs
        return costs

    def _roofline_stats(self) -> dict[str, dict[str, float]]:
        """Pull-style roofline source (``utils.profiling``): static
        flops/bytes per program execution joined with the live phase
        wall times (``EngineObs.last_s`` — populated when
        ``obs_engine`` is enabled; without it the gauges carry
        flops/bytes but no utilization, same contract as an unknown
        peak)."""
        out: dict[str, dict[str, float]] = {}
        last = self._eobs.last_s
        for prog in self._program_costs():
            st = dict(self._roofline_costs[prog])
            # Program names deliberately equal their tick-phase names
            # ("decode" / "verify") — the join is a dict lookup.
            st["wall_s"] = last.get(prog)
            out[prog] = st
        return out

    def logprobs(self, req_id: int) -> np.ndarray:
        """Per-token model logprobs of a FINISHED request's stream —
        the same raw-log-softmax convention as
        ``generate(return_logprobs=True)``, recorded for every request
        (the reduction is one cheap (B, V) take per step). Claims them;
        fetch after :meth:`run` / :meth:`result`."""
        with self._cv:
            if req_id not in self._done_lps:
                raise KeyError(
                    f"no logprobs for request {req_id} "
                    "(not finished, or already claimed)"
                )
            return self._done_lps.pop(req_id)

    def run(self, max_ticks: int = 100_000) -> dict[int, np.ndarray]:
        """Tick until every submitted request completed; returns
        {req_id: (tokens,) int32} and clears the finished set. The
        synchronous driver — do not mix with :meth:`start`."""
        ticks = 0
        while self._queue or any(s.req is not None for s in self.slots):
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"run() exceeded {max_ticks} ticks")
        # Pipeline boundary: the loop exits when every slot RETIRED,
        # which the pipelined runtime only does at commit — so any
        # remaining in-flight tick is pure garbage tail. Drain it so
        # the next caller (or a disagg handoff) sees an empty pipeline.
        self.drain()
        done, self._done = self._done, {}
        return done

    # -- threaded serving --------------------------------------------------

    def start(self) -> "ContinuousBatcher":
        """Serve on a background thread: callers :meth:`submit` from any
        thread and block on :meth:`result`. All compiled work runs on
        the server thread; the condition variable only guards the
        queue/done handoff."""
        with self._cv:
            if self._server is not None:
                raise RuntimeError("batcher already started")
            self._stopping = False
            # Reserve the slot under the lock so a concurrent start()
            # cannot also pass the guard; the thread object replaces
            # the placeholder below.
            self._server = threading.current_thread()  # placeholder

        def loop():
            while True:
                with self._cv:
                    while (
                        not self._stopping
                        and not self._queue
                        and all(s.req is None for s in self.slots)
                    ):
                        self._cv.wait(timeout=0.1)
                    if self._stopping:
                        break
                try:
                    self.tick()
                except BaseException as e:  # noqa: BLE001 — re-raised
                    # A tick exception (e.g. from a user's on_token
                    # callback) must not strand result() waiters in a
                    # silent 300s timeout: stash it, stop, wake them —
                    # they re-raise it with provenance.
                    with self._cv:
                        self._server_error = e
                        self._stopping = True
                        self._cv.notify_all()
                    log.error("server tick failed: %r", e)
                    return
                with self._cv:
                    self._cv.notify_all()  # results may have landed
            # Stopping: drain the pipelined runtime's in-flight tick ON
            # THE TICKING THREAD — stop() runs on the caller's thread
            # and must not touch device state — so the last dispatched
            # results commit before the thread exits and result()
            # waiters wake to them.
            try:
                self.drain()
            except BaseException as e:  # noqa: BLE001 — re-raised
                with self._cv:
                    self._server_error = e
                log.error("drain on stop failed: %r", e)
            with self._cv:
                self._cv.notify_all()

        server = threading.Thread(
            target=loop, name="continuous-batcher", daemon=True
        )
        with self._cv:
            self._server = server
        server.start()
        return self

    def stop(self) -> None:
        with self._cv:
            server = self._server
            if server is None:
                return
            self._stopping = True
            self._cv.notify_all()
        server.join(timeout=30.0)
        if server.is_alive():
            # A tick stuck in a long compile/stall: forgetting the
            # thread here would let a later start() run TWO tickers over
            # the same donated caches. Keep it registered and fail loud.
            raise RuntimeError(
                "batcher server thread did not stop within 30s "
                "(stuck tick?); retry stop()"
            )
        with self._cv:
            self._server = None

    def __enter__(self) -> "ContinuousBatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def close(self) -> None:
        """Retire this batcher from the engine telemetry: drop it from
        the ``memory.*`` gauge sums and the shared prefill compile
        watch. Needed because the jit caches pin ``self`` (static
        argnum), so GC alone never removes a replaced batcher — without
        close(), an operator swapping in a new batcher sees both
        instances' bytes summed (a phantom leak). Idempotent; call
        after :meth:`stop` when the batcher is permanently done."""
        unregister_memory_source("continuous", self)
        unregister_roofline_source("continuous", self)
        _LIVE_BATCHERS.discard(self)
        self._inflight = None  # drop any undrained device references
        if self._sp is not None:
            self._sp.close()
            self._sp = None
        self._retired = True  # stop consuming membership events
        # Revoke this batcher's unconsumed recovery allowances: the
        # class-level watches outlive it, and leftover slack (a family
        # recovery expected to re-lower but traffic never exercised)
        # would silently absorb ANOTHER live batcher's real phantom
        # variant. Consumed units are already gone, so disarming the
        # full grant strips exactly the leftovers.
        for prog, n in self._granted.items():
            self._sentinel.disarm(prog, n)
        self._granted.clear()

    def result(self, req_id: int, timeout: float = 300.0) -> np.ndarray:
        """Block until ``req_id`` finishes (requires :meth:`start`);
        returns and claims its tokens."""
        with self._cv:
            if not self._cv.wait_for(
                lambda: req_id in self._done or self._stopping,
                timeout=timeout,
            ):
                raise TimeoutError(
                    f"request {req_id} not done within {timeout}s"
                )
            if req_id not in self._done:
                if self._server_error is not None:
                    raise RuntimeError(
                        "batcher server thread died mid-tick"
                    ) from self._server_error
                raise RuntimeError("batcher stopped before completion")
            return self._done.pop(req_id)
