"""Typed configuration.

The reference has no config system — every knob is a source-code constant
(ports ``src/dispatcher.py:14-17``, chunk size ``:24``, worker list / cut
layers / image path hand-edited per README:43-48). Framework-owned upgrade:
one frozen dataclass per subsystem, assembled into ``ServeConfig``.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Timeouts and retry policy (reference analogs cited per field)."""

    # Worker lease TTL; reference: etcd lease on /workers/<ip> (node_state.py:20).
    lease_ttl_s: float = 2.0
    # Heartbeat period (must be < lease_ttl_s).
    heartbeat_s: float = 0.5
    # Per-task deadline before the watchdog re-dispatches; reference:
    # _task_watchdog scanning inflight start_time (dispatcher.py:302-304).
    # Must exceed worst-case first-compile time unless the pipeline is
    # warmed up first (ServingPipeline.warmup) — first XLA compiles on TPU
    # can take tens of seconds.
    task_deadline_s: float = 60.0
    # Watchdog scan period.
    watchdog_period_s: float = 0.25
    # Startup wait for the first worker; reference: 5 s bounded wait then
    # clean shutdown (dispatcher.py:282-295).
    startup_wait_s: float = 5.0
    # Max re-dispatch attempts per task before failing the request.
    max_retries: int = 3
    # Deadline misses before a still-heartbeating worker (a hang) is
    # quarantined — scheduler stops acquiring it except as last resort.
    quarantine_strikes: int = 2
    # Canary probing: a worker that has been silent (no completed task or
    # probe) longer than this window receives a lightweight ping task; a
    # ping that misses the task deadline counts as a strike. This is how a
    # hung-but-heartbeating worker accrues strikes even when the scheduler
    # routes real traffic away from it (rank demotes struck workers), so
    # quarantine stays reachable. None -> task_deadline_s. Set very large
    # to disable probing.
    probe_silence_s: float | None = None
    # Worker-configuration handshake timeout; reference: connect 5 s /
    # ACK 60 s (dispatcher.py:226,250-260).
    configure_timeout_s: float = 60.0
    # Bound on any single cross-host socket send AND on waiting for the
    # send channel lock: a hung peer with a full TCP buffer must never
    # wedge a forward-pool or watchdog thread (the reference's transport
    # is non-blocking with select backpressure for the same reason,
    # node_state.py:39-89). A send that exceeds this marks the connection
    # dead (stream state is unknowable after a partial send).
    send_timeout_s: float = 10.0


@dataclasses.dataclass(frozen=True)
class CodecConfig:
    """Activation/weights codecs at host/DCN boundaries (reference
    compresses every hop with zfp+lz4, dispatcher.py:92-98; on TPU, ICI
    hops need none). Consumed by ``comm.remote.WorkerGateway`` (every
    proxy it spawns for an inbound worker uses these codecs) and by
    ``LocalPipeline.from_config`` hop transforms — in-process device-to-
    device hops ignore it by design."""

    name: str = "none"  # none | bf16 | int8 | int8dev | zfp | lz
    # zfp-style fixed tolerance (absolute) when name == "zfp".
    tolerance: float = 1e-3
    # Codec for stage *weights* on cross-host configure. Lossless by
    # default (the largest payload in the system; reference compresses
    # every weight array, src/dispatcher.py:76-89).
    weights: str = "lz"


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """SPMD pipeline schedule knobs (``parallel.pipeline_spmd``).

    The serial (GPipe) schedule puts every ICI activation hop on the
    critical path; the overlap schedule issues each hop alongside the
    next microbatch's compute so hop latency hides under it (docs/
    SERVING.md "Overlap-scheduled SPMD pipeline"). Consumed by
    ``spmd_pipeline_from_config`` and ``benchmarks/micro/hop_overlap``.
    """

    # "serial" (GPipe; hop on the critical path) or "overlap"
    # (double-buffered; hop issued concurrently with compute).
    schedule: str = "overlap"
    # Microbatches per global batch (more microbatches -> smaller
    # pipeline-fill bubble, smaller per-hop payloads).
    microbatches: int = 8
    # Circular activation-buffer depth for the overlap schedule: a hop
    # gets hop_buffers - 1 ticks to land. 2 = classic double buffering;
    # raise it only when hop latency exceeds one tick's compute.
    hop_buffers: int = 2

    def __post_init__(self):
        if self.schedule not in ("serial", "overlap"):
            raise ValueError(
                f"schedule={self.schedule!r}: expected 'serial' or "
                f"'overlap'"
            )
        if self.microbatches < 1:
            raise ValueError("microbatches must be >= 1")
        if self.hop_buffers < 2:
            raise ValueError("hop_buffers must be >= 2")


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Intra-model tensor parallelism for the serving tier
    (``runtime/continuous`` + ``parallel/sharding.lm_tp_rules``).

    ``tp > 1`` makes the continuous batcher MESH-NATIVE: transformer-LM
    weights place by the megatron-style rules (qkv / mlp-in column-split
    over the ``axis`` mesh axis, attn-out / mlp-out row-split — exactly
    one psum pair per block), and the KV caches (dense slot strips or
    paged pools) shard on their HEAD axis, so per-device KV bytes are
    the logical bytes / tp. Page *tables*, the device-resident sampling
    state and the draft model stay replicated — admission/commit logic
    is sharding-blind. See ``docs/SERVING.md`` "Tensor-parallel
    serving"."""

    #: Mesh size along ``axis``: each block's heads, KV heads, model dim
    #: and MLP hidden must divide by it
    #: (``models.transformer_lm.validate_tp``).
    tp: int = 1
    #: Mesh axis name the splits land on.
    axis: str = "tp"

    def __post_init__(self):
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if not self.axis:
            raise ValueError("axis must be a non-empty mesh axis name")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Decode-kernel dispatch knobs for the serving tier
    (``ops/decode_attention`` + ``ops/paged_attention``;
    ``docs/SERVING.md`` §3).

    ``attn_impl`` picks the attention implementation the batcher's
    decode/verify programs lower against: ``None`` = the measured auto
    rule (``decode_kernel_wins`` / TPU-with-supported-pages), ``"xla"``
    = the einsum oracle, ``"pallas"`` = the streaming kernel (fused
    int8/int4 dequant in VMEM). Which path actually serves is
    observable as the ``engine.kernel_dispatch.<op>`` gauges
    (``docs/OBSERVABILITY.md``)."""

    attn_impl: str | None = None

    def __post_init__(self):
        if self.attn_impl not in (None, "xla", "pallas"):
            raise ValueError(
                f"attn_impl={self.attn_impl!r}: expected None, 'xla' "
                "or 'pallas'"
            )


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """Batched speculative decoding knobs (``runtime/continuous``
    speculative mode; ``docs/SERVING.md`` §5).

    Speculation trades DRAFT compute for target-model weight streams:
    every serving tick runs a fixed-shape ``draft_k + 1``-step draft
    scan over all slots plus ONE fused verify pass, and each slot
    commits its longest agreeing prefix plus the target's own
    correction token — between 1 and ``draft_k + 1`` tokens per tick
    per slot. Greedy requests (temperature 0) get exactly the
    target's argmax stream; sampled requests (temperature > 0) go
    through SPECULATIVE SAMPLING — accept/reject each proposal
    against the target distribution with residual resampling — so
    the emitted distribution equals non-speculative sampling
    (lossless in distribution, not bitwise). The batcher
    activates this mode when constructed with a draft model
    (``ContinuousBatcher(..., draft_lm=, draft_variables=,
    speculative=SpeculativeConfig(...))``).
    """

    #: Proposals per round. Tokens-per-target-weight-stream tops out at
    #: ``draft_k + 1`` (perfect acceptance) and degrades toward 1 as the
    #: draft misses; past ~4-8 the marginal proposal is usually rejected
    #: (acceptance compounds per position).
    draft_k: int = 4
    #: Resident dtype of the DRAFT model's weights: ``"native"`` keeps
    #: them as given; ``"int8"`` stores every matrix leaf blockwise
    #: int8-quantized (``ops.quantize.quantize_params``) with dequant
    #: fused inside the draft programs. The draft REPLICATES under
    #: tensor parallelism, so this directly cuts the per-chip HBM cost
    #: of speculation ~4x (f32 weights); the draft's quality only
    #: affects acceptance rate, never the emitted stream (losslessness
    #: is the target's property), so a slightly-perturbed draft is the
    #: cheapest capacity knob speculation has.
    draft_weight_dtype: str = "native"
    #: TREE-DRAFT width: 0 = chain speculation (the default). w >= 1
    #: appends w SIBLING leaf candidates for the position after the
    #: chain — the draft's top-w next tokens at its final scan step,
    #: harvested from logits the scan already computed (no extra draft
    #: forward) — and the verify chunk scores chain + leaves in ONE
    #: pass via the tree mask (``ops.decode_attention.verify_attention
    #: tree_tail``). When the whole chain accepts AND the target's
    #: correction token matches a leaf, that leaf's K/V is already in
    #: cache and the target's prediction AFTER it commits too: up to
    #: ``draft_k + 2`` tokens per verify pass instead of
    #: ``draft_k + 1``, at equal draft FLOPs per committed token. The
    #: draft scan runs one extra step to keep its own cache covering
    #: the leaf position (w > 1 leaves beyond the draft's argmax leave
    #: a draft-side cache entry for the argmax leaf only — an
    #: acceptance-rate nick on the sibling branches, never a
    #: correctness issue: losslessness is the target's property).
    tree_width: int = 0

    def __post_init__(self):
        if self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {self.draft_k}")
        if self.draft_weight_dtype not in ("native", "int8"):
            raise ValueError(
                f"draft_weight_dtype={self.draft_weight_dtype!r}: "
                "expected 'native' or 'int8'"
            )
        if self.tree_width < 0:
            raise ValueError(
                f"tree_width must be >= 0, got {self.tree_width}"
            )


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """Elastic mesh recovery for the tensor-parallel serving tier
    (``runtime/continuous`` + ``control.registry.DeviceHealthMonitor``;
    ``docs/SERVING.md`` "Elastic recovery").

    When a device of the batcher's mesh is reported dead, the batcher
    rebuilds its mesh from the surviving devices (tp shrinks to the
    largest divisor of the old tp that still fits), re-validates the
    model against the shrunk mesh, re-lowers its program families with
    explicit shardings, and moves live request state across via an
    explicit redistribution plan (``parallel.sharding.KVReshardPlan``)
    — or replays requests from the journal/prefix cache when their
    state cannot migrate. Fault model: COMPUTE loss — the lost shard's
    KV heads are recovered through host staging (the simulated-kill
    stand-in for the host-tier recovery source a real deployment
    plugs in there); requests that opt out of migration replay from
    the journal instead and still emit identical tokens."""

    #: Recover inline at the next ``tick()`` after a loss. False: the
    #: tick raises ``DeviceLostError`` and the operator (or serving
    #: layer) calls :meth:`ContinuousBatcher.recover` explicitly.
    auto_reshard: bool = True
    #: Live-state policy for in-flight requests at recovery time:
    #: ``"migrate"`` moves KV/sampling state to the shrunk mesh
    #: (gather-free for surviving shards, host-staged for the lost
    #: shard's heads) so requests continue bit-identically;
    #: ``"replay"`` re-queues every in-flight request from the journal
    #: (or the in-memory request record) — same final tokens, paid by
    #: re-prefill (cheap again when the paged prefix cache still holds
    #: the prompt pages). Requests mid-chunked-prefill always replay:
    #: they have emitted nothing, so replay costs only the prefill
    #: they had not finished.
    policy: str = "migrate"
    #: Refuse to shrink below this tp (raise ``DeviceLostError``
    #: instead): capacity floor for deployments where a tp=1 remnant
    #: could not hold the model.
    min_tp: int = 1

    def __post_init__(self):
        if self.policy not in ("migrate", "replay"):
            raise ValueError(
                f"policy={self.policy!r}: expected 'migrate' or 'replay'"
            )
        if self.min_tp < 1:
            raise ValueError(f"min_tp must be >= 1, got {self.min_tp}")


@dataclasses.dataclass(frozen=True)
class DisaggConfig:
    """Disaggregated prefill/decode serving (``runtime/disagg``;
    ``docs/SERVING.md`` "Disaggregated prefill/decode").

    Production fleets split compute-bound PREFILL from latency-bound
    DECODE onto separate pools so a long prompt's admission never runs
    inside a decode tick (the decode-stall pathology the load harness
    measures as ``continuous.prefill_stall_s``). The
    ``runtime.disagg.DisaggServer`` placement policy decides PER
    REQUEST between the collocated path (ordinary
    ``ContinuousBatcher.submit`` — prefill runs in the decode tick) and
    the disaggregated path (a ``PrefillWorker`` prefills the prompt's
    full pages against its own pool and streams the KV pages to the
    decode batcher over the comm tier, where they land through the
    paged prefix cache):

    - prompts of at least ``prompt_threshold`` tokens always
      disaggregate (their inline prefill wall is the p99 ITL spike);
    - when the decode tier is BUSY (occupied slots / total slots >=
      ``busy_occupancy``), the threshold drops to
      ``busy_prompt_threshold`` — under load, even mid-length prefills
      steal decode ticks someone is waiting on;
    - everything shorter collocates: the handoff costs one page-stream
      + one suffix pass, which a short prompt's inline prefill
      undercuts.

    The policy also falls back to collocated whenever the prefill
    tier cannot take the request (pool pressure, a dead role-tagged
    lease, a prompt without one full page) — placement is an
    optimization, never a correctness gate."""

    #: Prompts with at least this many tokens always take the
    #: disaggregated path (when one exists). Must exceed the decode
    #: pool's page size — a prompt with no full page has nothing to
    #: hand off.
    prompt_threshold: int = 256
    #: Threshold applied instead when the decode tier is busy.
    busy_prompt_threshold: int = 64
    #: Decode-slot occupancy fraction at/above which the tier counts
    #: as busy.
    busy_occupancy: float = 0.75

    def __post_init__(self):
        if self.prompt_threshold < 1:
            raise ValueError(
                f"prompt_threshold must be >= 1, got "
                f"{self.prompt_threshold}"
            )
        if self.busy_prompt_threshold < 1:
            raise ValueError(
                f"busy_prompt_threshold must be >= 1, got "
                f"{self.busy_prompt_threshold}"
            )
        if self.busy_prompt_threshold > self.prompt_threshold:
            raise ValueError(
                "busy_prompt_threshold must not exceed prompt_threshold "
                f"({self.busy_prompt_threshold} > {self.prompt_threshold})"
            )
        if not 0.0 <= self.busy_occupancy <= 1.0:
            raise ValueError(
                f"busy_occupancy must be in [0, 1], got "
                f"{self.busy_occupancy}"
            )


@dataclasses.dataclass(frozen=True)
class PrefillConfig:
    """Sequence-parallel LONG-CONTEXT prefill
    (``parallel/sp_prefill.SPPrefiller``; ``docs/SERVING.md``
    "Sequence-parallel prefill").

    A prompt of at least ``sp_threshold`` tokens prefills SP-SHARDED:
    the token axis splits over an ``sp`` mesh axis, every chip
    computes its own chunk's projections/MLP sequence-locally, the
    K/V window circulates the ring (``lax.ppermute`` neighbor hops —
    the ring-attention communication pattern), and each chip's
    attention-score block is its chunk's rows only — so the O(S^2)
    prefill wall for one long prompt drops ~linearly with
    ``sp_width`` instead of monopolizing one chip. The resulting
    pages land through the SAME ``KVHandoffPlan`` /
    ``Pager.adopt_cached`` / ``_adopt_pages`` path as a disaggregated
    handoff (head-resharded sender-side, per 2211.05322), so the
    request then admits as an ordinary prefix-cache hit and decode
    stays tp-sharded and untouched; pages equal what the single-device
    chunked prefill would have written up to the rounding of one
    reordered sum, and greedy streams are bit-identical (pinned).

    Wired at both entry points: ``ContinuousBatcher`` collocated
    admission and the ``runtime/disagg.PrefillWorker`` tier (whose
    ``step()`` dispatches sp-eligible jobs to the sp program instead
    of the chunk loop). The landing path IS the paged prefix
    cache."""

    #: Prompts with at least this many tokens prefill sp-sharded
    #: (``None`` disables the sp path entirely). Keep it well above a
    #: page: below a few pages the ring hops cost more than the
    #: score-block split saves (see SERVING.md "when chunked-on-one-
    #: chip wins").
    sp_threshold: int | None = None
    #: Mesh size along ``sp_axis`` — the number of sequence shards
    #: (power of two; 1 turns the sp path off). Composes with tensor
    #: parallelism as an ``(sp, tp)`` mesh: ``sp_width * tp`` devices.
    sp_width: int = 1
    #: Mesh axis name the token-axis split lands on.
    sp_axis: str = "sp"

    def __post_init__(self):
        if self.sp_width < 1 or (self.sp_width & (self.sp_width - 1)):
            raise ValueError(
                f"sp_width must be a power of two >= 1, got "
                f"{self.sp_width}"
            )
        if self.sp_threshold is not None and self.sp_threshold < 1:
            raise ValueError(
                f"sp_threshold must be >= 1, got {self.sp_threshold}"
            )
        if not self.sp_axis:
            raise ValueError("sp_axis must be a non-empty mesh axis name")

    @property
    def enabled(self) -> bool:
        """The sp path is live: a threshold is set and there is a ring
        to split over."""
        return self.sp_threshold is not None and self.sp_width > 1


@dataclasses.dataclass(frozen=True)
class CacheTierConfig:
    """Hierarchical KV cache: a host-DRAM (optionally disk-backed)
    spill tier UNDER the paged prefix cache (``runtime/paged.HostKVTier``
    + ``runtime/continuous``; ``docs/SERVING.md`` §3).

    The HBM prefix LRU caps how many cold prefixes stay warm; without a
    tier, an evicted rc=0 page simply dies and the next same-prefix
    admission recomputes it. With a tier, evicted pages SPILL to host
    buffers (tracked by the same content keys), and the admission
    probe consults the host tier before declaring a prefix miss — a
    host hit re-enters the pool through the existing
    ``Pager.adopt_cached`` / ``_adopt_pages`` landing path (the
    disaggregated-handoff machinery: epoch-carrying, tp-sharded
    placement via ``KVHandoffPlan`` per-shard slices — never a
    gather) and then admits as an ordinary prefix-cache hit.

    Two host sub-tiers, each with its own codec
    (``ops.quantize.encode_page``): WARM pages keep a LOSSLESS codec
    (bit-exact readmits — the default end to end), COLD pages (demoted
    past ``warm_capacity_pages``) may take a LOSSY codec (blockwise
    int8/int4-with-scales or zfp-style mantissa truncation — the
    paper's lz4+zfp transfer-compression DNA). Lossy codecs only ever
    touch SPILLED pages, which are rc=0 by construction — a page
    referenced by a live slot is never spilled, so live decode state
    is never degraded. Spill and readmit work are budgeted PER TICK so
    the decode loop never stalls behind tier traffic."""

    #: Total host-tier capacity in pages (warm + cold, memory-resident).
    host_capacity_pages: int = 1024
    #: Pages held in the WARM sub-tier before demotion to COLD.
    warm_capacity_pages: int = 256
    #: WARM codec — must be lossless ("raw" | "lz"): a warm readmit is
    #: bit-exact by construction.
    warm_codec: str = "lz"
    #: COLD codec — "raw" | "lz" (lossless) or "int8" | "int4" | "zfp"
    #: (lossy; applied to FLOAT page planes only — int value planes of
    #: quantized pools fall back to lossless packing). Default
    #: lossless, so the whole hierarchy is bit-exact unless lossy
    #: compression is opted into.
    cold_codec: str = "lz"
    #: Max pages spilled (D2H fetch + encode) per decode tick — bounds
    #: the tier work any single tick pays. Evictions past the budget
    #: drop their content (``cache_tier.dropped_total``).
    spill_pages_per_tick: int = 8
    #: Max pages readmitted (decode + H2D landing) per decode tick;
    #: prompts whose host hits exceed it recompute the tail instead of
    #: stalling admission.
    readmit_pages_per_tick: int = 8
    #: Proactive spill watermarks, as fractions of the allocatable
    #: pool: when the HBM prefix LRU holds >= ``spill_watermark`` of
    #: the pool, the tier pre-spills the coldest un-backed LRU pages
    #: (budgeted) until the un-backed cold set is down to
    #: ``spill_low_watermark`` — so demand evictions under admission
    #: pressure find their content already host-backed (a free evict)
    #: instead of paying a fetch inside the admission path.
    spill_watermark: float = 0.5
    spill_low_watermark: float = 0.25
    #: Optional disk directory: COLD pages demoted past the host
    #: capacity persist as files there instead of dropping.
    disk_dir: str | None = None
    #: Codec for the disaggregated MSG_KV_PAGES wire
    #: (``runtime/disagg.pack_handoff``): "raw" (today's zero-copy
    #: frames) or any page codec — the crc check runs on the
    #: compressed payload either way. ``DisaggServer`` reads it off
    #: the decode batcher's tier config unless given explicitly.
    wire_codec: str = "raw"

    def __post_init__(self):
        # Direct symbol imports: the ops package re-exports a FUNCTION
        # named ``quantize`` that shadows the module on any
        # ``import ... as`` attribute lookup.
        from adapt_tpu.ops.quantize import (
            LOSSLESS_PAGE_CODECS,
            PAGE_CODECS,
        )

        if self.host_capacity_pages < 1:
            raise ValueError(
                f"host_capacity_pages must be >= 1, got "
                f"{self.host_capacity_pages}"
            )
        if not 0 <= self.warm_capacity_pages <= self.host_capacity_pages:
            raise ValueError(
                f"warm_capacity_pages must be in [0, "
                f"host_capacity_pages], got {self.warm_capacity_pages}"
            )
        if self.warm_codec not in LOSSLESS_PAGE_CODECS:
            raise ValueError(
                f"warm_codec={self.warm_codec!r}: the warm tier must "
                f"be lossless ({LOSSLESS_PAGE_CODECS})"
            )
        for name in ("cold_codec", "wire_codec"):
            v = getattr(self, name)
            if v not in PAGE_CODECS:
                raise ValueError(
                    f"{name}={v!r}: expected one of {PAGE_CODECS}"
                )
        for name in ("spill_pages_per_tick", "readmit_pages_per_tick"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0")
        if not (
            0.0 <= self.spill_low_watermark <= self.spill_watermark <= 1.0
        ):
            raise ValueError(
                "need 0 <= spill_low_watermark <= spill_watermark <= 1, "
                f"got {self.spill_low_watermark} / {self.spill_watermark}"
            )


@dataclasses.dataclass(frozen=True)
class TenantQuota:
    """Per-tenant traffic-control knobs (``config.SchedulerConfig``;
    ``runtime/scheduler.AdmissionQueue``). ``weight`` is the tenant's
    deficit-round-robin share within its priority class (a weight-2
    tenant drains twice the requests of a weight-1 tenant under
    backlog); ``burst`` caps how many of its requests may sit QUEUED
    at once (admission beyond it rejects synchronously with
    ``QueueFullError`` — the per-tenant flood bound; ``None`` leaves
    only the global ``max_queue_depth`` bound)."""

    weight: float = 1.0
    burst: int | None = None

    def __post_init__(self):
        if self.weight <= 0:
            raise ValueError(f"weight must be > 0, got {self.weight}")
        if self.burst is not None and self.burst < 1:
            raise ValueError(f"burst must be >= 1, got {self.burst}")


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Multi-tenant overload control in front of the continuous
    batcher (``runtime/scheduler``; ``docs/SERVING.md`` "Traffic
    control").

    Three mechanisms, in the order they engage under rising load:

    1. **Admission control** — the submit queue becomes a bounded
       ``AdmissionQueue``: per-tenant FIFO queues drained by
       deficit-round-robin within strict priority classes
       (``SLOSpec.priority``; higher admits first), per-tenant
       ``TenantQuota`` weights + burst caps, and a global
       ``max_queue_depth``. A submit past a bound raises
       ``QueueFullError`` SYNCHRONOUSLY (``request_rejected`` flight
       event) — the client learns immediately and ``result()`` never
       wedges on a request that was never accepted.
    2. **Decode-slot preemption** — when a higher-priority request has
       burned ``preempt_ttft_fraction`` of its TTFT budget waiting and
       no slot is free, the scheduler preempts the lowest-priority
       active decode slot through the elastic-recovery REPLAY path:
       the victim's slot frees (paged: its prompt pages drop into the
       prefix LRU), it re-queues (journal-reconstructed when one is
       configured) and later re-admits as a prefix-cache hit, with
       ``stream_skip`` suppressing re-delivery — exactly-once streams
       and SLO verdicts carry across preemption exactly as they do
       across a chip loss.
    3. **Closed-loop degradation** — a per-tick controller reading the
       engine/workload telemetry (queue depth, slot occupancy, TTFT
       attainment) walks a shed ladder BEFORE preemption has to do the
       work: shrink ``draft_k``, raise the disaggregated
       ``busy_prompt_threshold``, evict cold prefix-cache pages, and
       finally reject best-effort admits (``priority < 0``). Each
       transition is a ``degradation_step`` flight event.
    """

    #: Global bound on queued (not yet admitted) requests across every
    #: tenant — the bound behind ``ContinuousBatcher.submit`` (a full
    #: slot map used to queue unboundedly).
    max_queue_depth: int = 4096
    #: DRR credit granted per service turn, multiplied by the tenant's
    #: weight (request units — one request costs 1).
    quantum: float = 1.0
    #: Weight for tenants without an explicit ``TenantQuota``.
    default_weight: float = 1.0
    #: Per-tenant quotas, keyed by ``SLOSpec.tenant``.
    quotas: dict[str, TenantQuota] = dataclasses.field(
        default_factory=dict
    )
    #: Enable decode-slot preemption (mechanism 2).
    preempt: bool = True
    #: Fraction of a waiting high-priority request's TTFT budget that
    #: may burn before the scheduler preempts for it. Requests with no
    #: TTFT budget never trigger preemption.
    preempt_ttft_fraction: float = 0.5
    #: Enable the closed-loop degradation controller (mechanism 3).
    degrade: bool = True
    #: Escalate when queue depth / max_queue_depth reaches this while
    #: occupancy is at/above ``degrade_occupancy`` (or windowed TTFT
    #: attainment falls below ``degrade_attainment`` with a backlog).
    degrade_queue_high: float = 0.5
    #: De-escalate when queue depth / max_queue_depth falls to this.
    degrade_queue_low: float = 0.05
    #: Slot-occupancy fraction that counts as saturated.
    degrade_occupancy: float = 1.0
    #: Windowed TTFT attainment below this (with a backlog) also
    #: escalates.
    degrade_attainment: float = 0.9
    #: Minimum dwell between ladder transitions (hysteresis).
    degrade_dwell_s: float = 0.25
    #: Cache-aware admission ordering: among same-tenant, same-priority
    #: queued requests, admit the one whose prompt has the
    #: hottest/longest prefix RESIDENT in the pager's radix tree first
    #: (``runtime/paged.Pager.radix_probe``). Arrival order only ever
    #: re-orders within one tenant queue — priority classes, DRR
    #: weights and burst caps are untouched — and only by a STRICT
    #: score win, so a cold cache degrades to exact FIFO. Inert
    #: without the paged KV layout.
    cache_aware: bool = False
    #: How many queue-head candidates the cache-aware pick scans per
    #: pop (bounds both the probe cost per admission and how far a hot
    #: request may jump the line).
    cache_aware_window: int = 16

    def __post_init__(self):
        if self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got "
                f"{self.max_queue_depth}"
            )
        if self.quantum <= 0:
            raise ValueError(f"quantum must be > 0, got {self.quantum}")
        if self.default_weight <= 0:
            raise ValueError(
                f"default_weight must be > 0, got {self.default_weight}"
            )
        if not 0.0 < self.preempt_ttft_fraction <= 1.0:
            raise ValueError(
                f"preempt_ttft_fraction must be in (0, 1], got "
                f"{self.preempt_ttft_fraction}"
            )
        if not 0.0 <= self.degrade_queue_low <= self.degrade_queue_high:
            raise ValueError(
                "degrade_queue_low must be in [0, degrade_queue_high] "
                f"({self.degrade_queue_low} vs {self.degrade_queue_high})"
            )
        if not 0.0 <= self.degrade_occupancy <= 1.0:
            raise ValueError(
                f"degrade_occupancy must be in [0, 1], got "
                f"{self.degrade_occupancy}"
            )
        if self.degrade_dwell_s < 0:
            raise ValueError(
                f"degrade_dwell_s must be >= 0, got "
                f"{self.degrade_dwell_s}"
            )
        if self.cache_aware_window < 1:
            raise ValueError(
                f"cache_aware_window must be >= 1, got "
                f"{self.cache_aware_window}"
            )


@dataclasses.dataclass(frozen=True)
class SLOSpec:
    """Per-request latency budget, evaluated by the serving tier's
    existing lifecycle stamps (``runtime/continuous`` request
    timelines; ``docs/OBSERVABILITY.md`` "Workload telemetry").

    ``ContinuousBatcher.submit(..., slo=SLOSpec(...))`` attaches one to
    a request: TTFT is judged once at the first emitted token
    (submit -> first token, queue wait included — the user-visible
    number), ITL at every subsequent commit. A request stays "inside
    budget" until its first violation; tokens committed while inside
    budget count toward ``continuous.goodput_tokens_s``, and the
    request lands in its tenant's ``slo.met_total.<tenant>`` /
    ``slo.missed_total.<tenant>`` counter at finish. Evaluation rides
    the ``obs_timeline`` gate: host-side arithmetic on stamps already
    taken — zero extra device traffic, zero compiled-program impact."""

    #: Submit -> first emitted token budget (None = no TTFT budget).
    ttft_budget_s: float | None = None
    #: Inter-token budget between consecutive commits (None = none).
    itl_budget_s: float | None = None
    #: Accounting label for the per-tenant met/missed counters.
    tenant: str = "default"
    #: Scheduling class (``config.SchedulerConfig`` /
    #: ``runtime/scheduler.AdmissionQueue``): higher admits strictly
    #: first under backlog and may PREEMPT a lower class's decode slot
    #: when its TTFT budget is at risk; ``< 0`` marks the request
    #: best-effort — the degradation ladder's final rung rejects those
    #: admits outright. 0 (the default) is the ordinary class; without
    #: a ``SchedulerConfig`` on the batcher, priority is carried but
    #: inert.
    priority: int = 0

    def __post_init__(self):
        for name in ("ttft_budget_s", "itl_budget_s"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ValueError(f"{name} must be > 0, got {v}")
        if not self.tenant:
            raise ValueError("tenant must be a non-empty label")


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """Tracing + flight-recorder knobs (``utils.tracing``, served by
    ``utils.exporter``). The flight recorder is ALWAYS on (bounded ring,
    per-lifecycle writes only); tracing is opt-in because span recording
    is per-stage-execution. Applying a ``ServeConfig`` (constructing a
    ``Dispatcher``) pushes these onto the process-global tracer/recorder
    — enable-only for ``trace_enabled``, and capacities apply only when
    they differ from the defaults here (a default-config dispatcher must
    never truncate a ring another component explicitly sized). A
    standalone worker process enables tracing with ``ADAPT_TPU_TRACE=1``
    instead."""

    # Record serving-path spans into the global Tracer ring (and ship
    # remote workers' spans back on result frames for stitching). One
    # branch per span site when False.
    trace_enabled: bool = False
    # Span ring size. The ring OVERWRITES oldest spans when full
    # (evictions counted as `tracer.spans_dropped`); size it to cover
    # the window you expect to snapshot via GET /trace.json.
    trace_capacity: int = 65536
    # Flight-recorder ring size: the last N control-plane events
    # (admissions, re-dispatches, quarantines, probe misses,
    # recoveries) retained for GET /debug/events and post-mortem
    # snapshots.
    flight_capacity: int = 2048
    # Dispatcher.recover writes a flight-recorder snapshot JSON beside
    # the journal (flight-<unix_ts>.json) so the fault timeline that led
    # to the crash survives the process.
    snapshot_on_recovery: bool = True
    # Engine-tier per-phase timing (utils.profiling.EngineObs): tick
    # phases (tick/admit/prefill/launch/draft/verify/decode/fetch/
    # commit/update) and pipeline stage/hop phases record
    # engine.phase.<name>_s histograms (+ spans when tracing is on).
    # When False a phase site is one branch and its profiler annotation
    # (always on: a jax.profiler session sees the phases either way);
    # enabled cost measured by benchmarks/micro/obs_overhead.py against
    # the <5% tick budget. Enable-only, like trace_enabled.
    obs_engine: bool = False
    # Compile-sentinel warmup (utils.profiling.CompileSentinel): jit
    # cache growth within a program's first N sentinel samples after
    # (re-)registration is expected compilation; growth after that is
    # flagged as an unintended recompile (engine.compile_events counter,
    # flight event, WARNING, tracer instant event). Applied only when it
    # differs from this default (same rule as the ring capacities).
    compile_warmup: int = 8
    # Rolling window for the windowed rate/attainment views: the
    # continuous.goodput_tokens_s gauge's sample span and the capacity
    # plane's decode-rate ceiling (runtime/capacity.CapacityModel) read
    # the SAME window, so "goodput" means one thing across gauges and
    # forecasts.
    goodput_window_s: float = 2.0

    def __post_init__(self):
        if self.trace_capacity < 1:
            raise ValueError("trace_capacity must be >= 1")
        if self.flight_capacity < 1:
            raise ValueError("flight_capacity must be >= 1")
        if self.compile_warmup < 0:
            raise ValueError("compile_warmup must be >= 0")
        if self.goodput_window_s <= 0:
            raise ValueError("goodput_window_s must be > 0")


@dataclasses.dataclass(frozen=True)
class CapacityConfig:
    """Replica capacity / placement-signal plane
    (``runtime/capacity.CapacityModel``, docs/OBSERVABILITY.md
    "Capacity & affinity signals").

    Every batcher maintains a self-describing **capacity book**: a
    headroom partition (slots/pages/queue), a self-calibrating TTFT
    forecaster, a bounded prefix-affinity sketch, and a hysteresis
    health score — everything a router needs to place a request
    WITHOUT a per-replica prompt round-trip. All host-side, refreshed
    off the critical path through the ``_obs_flush`` seam."""

    #: Master switch. Off = no model attached: zero extra work per
    #: submit/admit/commit/flush (the obs_overhead capacity arm's
    #: floor).
    enabled: bool = True
    #: Min seconds between book rebuilds (headroom + sketch + health).
    #: Feeds (queue-wait/prefill-wall EWMAs, calibration samples) are
    #: O(1) appends regardless; this bounds the rebuild cadence.
    refresh_s: float = 0.25
    #: Prefix-affinity sketch bound: at most this many radix nodes
    #: (hashed content keys), picked by token-weighted heat.
    sketch_k: int = 32
    #: EWMA learning rate for the forecaster's queue-wait, per-bucket
    #: prefill-wall and bias-corrector estimates.
    ewma_alpha: float = 0.2
    #: Rolling count of (forecast, realized) TTFT pairs the
    #: ``capacity.forecast_calibration`` fraction is computed over.
    calibration_window: int = 256
    #: Health hysteresis: a health IMPROVEMENT must hold this long
    #: before the score follows it (worsening applies immediately —
    #: a router should back off fast and return slowly).
    health_dwell_s: float = 1.0
    #: Min seconds between lease-meta book refreshes
    #: (``WorkerRegistry`` re-register with ``meta["capacity"]``).
    lease_refresh_s: float = 1.0

    def __post_init__(self):
        if self.refresh_s < 0:
            raise ValueError("refresh_s must be >= 0")
        if self.sketch_k < 1:
            raise ValueError("sketch_k must be >= 1")
        if not 0 < self.ewma_alpha <= 1:
            raise ValueError("ewma_alpha must be in (0, 1]")
        if self.calibration_window < 1:
            raise ValueError("calibration_window must be >= 1")
        if self.health_dwell_s < 0:
            raise ValueError("health_dwell_s must be >= 0")
        if self.lease_refresh_s < 0:
            raise ValueError("lease_refresh_s must be >= 0")


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Fleet router / autoscaler (``runtime/router.FleetRouter``,
    docs/SERVING.md "Fleet routing").

    The DECISION half of the capacity plane: the router owns N decode
    replicas and places every submit by scoring each live replica's
    capacity book — prefix affinity folded into the TTFT forecast,
    health and queue pressure as additive penalties — so a resident
    prefix on replica A beats a free slot on replica B until A's queue
    costs more than the prefill the hit would save."""

    #: Placement policy: "affinity" (score books: forecast + affinity
    #: + health + queue), "least_loaded" (headroom only — what
    #: affinity degrades to when every book is cold), or "random"
    #: (the A/B control arm ``benchmarks/load/router_smoke.py``
    #: measures against).
    policy: str = "affinity"
    #: Books older than this are not placement candidates (the
    #: router-side bound; ``FederatedStore.capacity_max_age_s`` is the
    #: federation-side evict — this one must be the tighter of the
    #: two).
    book_max_age_s: float = 5.0
    #: Additive placement penalty (seconds-equivalent) for a replica
    #: publishing health "degraded". "critical" replicas are skipped
    #: outright unless every live replica is critical.
    degraded_penalty_s: float = 0.25
    #: Seconds-equivalent cost per request already queued on the
    #: replica — the least-loaded term, and the tiebreak that lets a
    #: cold-but-idle replica beat a hot-but-swamped one.
    queue_cost_s: float = 0.01
    #: Seconds-equivalent placement bonus for the prompt's rendezvous
    #: HOME replica (highest-random-weight hash of its first prefix
    #: page over live replica names). Closes the sketch-latency
    #: window: repeats of a prefix co-locate deterministically even
    #: before its first prefill has registered any page. Sized a few
    #: ``queue_cost_s`` so it decides ties but real queue pressure and
    #: learned forecasts still override; 0 disables.
    rendezvous_bias_s: float = 0.02
    #: Leave-edge recovery budget: on a replica leave the router must
    #: re-place that replica's unfinished work within this many
    #: seconds (the kill-one-of-3 acceptance bound).
    recovery_budget_s: float = 2.0
    #: TTL on each replica's membership lease (heartbeated every
    #: router tick; expiry = leave edge).
    lease_ttl_s: float = 2.0
    #: Bounded ring of placement decisions ``GET /fleet/placements``
    #: serves (why each request landed where it did).
    placements_capacity: int = 256
    #: Autoscaler floor/ceiling on replica count.
    min_replicas: int = 1
    max_replicas: int = 4
    #: Scale up when fleet queue occupancy (queued / total queue
    #: bound) holds above this for ``autoscale_dwell_s``.
    scale_up_queue_frac: float = 0.5
    #: Scale down when a replica has sat idle (no slots, no queue)
    #: this long and the fleet is above ``min_replicas``.
    scale_down_idle_s: float = 3.0
    #: Pressure must HOLD this long before a scale-up fires (one
    #: burst tick must not spawn a replica).
    autoscale_dwell_s: float = 0.5

    def __post_init__(self):
        if self.policy not in ("affinity", "least_loaded", "random"):
            raise ValueError(
                "policy must be 'affinity', 'least_loaded' or "
                f"'random', got {self.policy!r}"
            )
        if self.book_max_age_s <= 0:
            raise ValueError("book_max_age_s must be > 0")
        if self.degraded_penalty_s < 0:
            raise ValueError("degraded_penalty_s must be >= 0")
        if self.queue_cost_s < 0:
            raise ValueError("queue_cost_s must be >= 0")
        if self.rendezvous_bias_s < 0:
            raise ValueError("rendezvous_bias_s must be >= 0")
        if self.recovery_budget_s <= 0:
            raise ValueError("recovery_budget_s must be > 0")
        if self.lease_ttl_s <= 0:
            raise ValueError("lease_ttl_s must be > 0")
        if self.placements_capacity < 1:
            raise ValueError("placements_capacity must be >= 1")
        if self.min_replicas < 1:
            raise ValueError("min_replicas must be >= 1")
        if self.max_replicas < self.min_replicas:
            raise ValueError("max_replicas must be >= min_replicas")
        if not 0 < self.scale_up_queue_frac <= 1:
            raise ValueError("scale_up_queue_frac must be in (0, 1]")
        if self.scale_down_idle_s < 0:
            raise ValueError("scale_down_idle_s must be >= 0")
        if self.autoscale_dwell_s < 0:
            raise ValueError("autoscale_dwell_s must be >= 0")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Top-level serving configuration."""

    # Bounded request concurrency; reference: concurrency semaphore
    # (dispatcher.py:151,183) and queue.Queue(10) (test/test.py:40).
    max_inflight: int = 8
    fault: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    codec: CodecConfig = dataclasses.field(default_factory=CodecConfig)
    pipeline: PipelineConfig = dataclasses.field(
        default_factory=PipelineConfig
    )
    obs: ObservabilityConfig = dataclasses.field(
        default_factory=ObservabilityConfig
    )
    spec: SpeculativeConfig = dataclasses.field(
        default_factory=SpeculativeConfig
    )
    kernel: KernelConfig = dataclasses.field(
        default_factory=KernelConfig
    )
    parallel: ParallelConfig = dataclasses.field(
        default_factory=ParallelConfig
    )
    recovery: RecoveryConfig = dataclasses.field(
        default_factory=RecoveryConfig
    )
    disagg: DisaggConfig = dataclasses.field(
        default_factory=DisaggConfig
    )
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig
    )
    prefill: PrefillConfig = dataclasses.field(
        default_factory=PrefillConfig
    )
    capacity: CapacityConfig = dataclasses.field(
        default_factory=CapacityConfig
    )
    router: RouterConfig = dataclasses.field(
        default_factory=RouterConfig
    )
    #: Hierarchical KV cache tier (None = off: evicted prefix pages
    #: die, today's behavior). Opt-in, unlike the sibling subsystem
    #: configs — a host tier changes where evicted bytes live.
    cache_tier: CacheTierConfig | None = None
