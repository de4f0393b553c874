"""Closed-loop load harness: real batcher ticks under open-loop traffic.

``drive_phase`` submits one :mod:`benchmarks.load.workload` schedule
against a live ``ContinuousBatcher`` on the wall clock (arrivals are
open-loop: the server being slow never slows the offered load), drives
the synchronous tick loop until the phase drains, and reads the phase's
telemetry through the ``MetricsRegistry`` windowed snapshot-delta API —
so TTFT/ITL percentiles, SLO attainment and goodput are THIS phase's,
not cumulative-since-boot. ``run_sweep`` chains phases over an
arrival-rate ladder on ONE batcher (jit caches are per-instance; a
fresh batcher per point would re-pay every compile) and emits the
goodput-vs-offered-load curve as a BENCH-style report, each point
annotated with the roofline gauges (``engine.mbu``/``engine.mfu`` —
how bandwidth-bound the engine actually was at that load).

Determinism contract (pinned in ``tests/test_load.py``): the schedule
is a pure function of ``(spec, seed)``, greedy streams are
request-deterministic whatever the slot scheduling, and cancel marks
live in TOKEN space — two runs submit identical requests and finish
with identical per-request token counts. Wall-clock things (latencies,
goodput) are measurements, not replayable values.

Driver usage (one BENCH-style JSON line on stdout)::

    python benchmarks/load/harness.py --rates 4,8,16,32 --seed 0
    python benchmarks/load/harness.py --rates 8 --cancel-pct 50
    python benchmarks/load/harness.py --preset corpus --cache-tier on
    python benchmarks/load/harness.py --preset agent_trace --fanout on
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.common import int_flag, str_flag  # noqa: E402
from benchmarks.load.workload import (  # noqa: E402
    Arrival,
    WorkloadSpec,
    build_schedule,
    offered_tokens,
    schedule_digest,
)

#: Per-phase wall guard: a wedged phase (stuck tick, runaway compile)
#: must fail the run loudly, not hang CI.
PHASE_WALL_GUARD_S = 300.0


def warmup(bat, vocab: int, steps_max: int, prompt_max: int) -> None:
    """Pre-pay the compile cost the first phase would otherwise eat as
    fake TTFT: one admission per prompt bucket the workload can hit
    (prefill variants — including the LONG-CONTEXT pow2 buckets: the
    chunked-prefill window variants, the sp-prefill program + its
    adopt-pages bucket and the pow2-padded suffix variant all compile
    on that bucket's admission), with step counts covering the
    key-block power-of-two buckets (``_stage_slot`` variants), then
    drain.

    The largest bucket is warmed too: a prompt of ``bucket`` tokens
    leaves no decode room when ``bucket == max_len``, so the admission
    shrinks to ``max_len - 1`` tokens while still mapping into that
    bucket — previously the loop broke there and a first max-bucket
    admission (a 32k prompt on a long-context config) paid its whole
    compile stack mid-phase, measured as fake TTFT."""
    import numpy as np

    rng = np.random.RandomState(0)
    max_len = bat.lm.max_len
    # Chunked-prefill batchers compile one FINAL-chunk variant per
    # (last-chunk page class): a prompt's last pass runs cbucket =
    # ceil((s0 mod chunk)/page)*page tokens, so lengths differing by a
    # page can hit different variants. Warm every class per bucket by
    # admitting page-stepped lengths, not just the bucket length.
    chunk = getattr(bat, "_prefill_chunk", None)
    page = getattr(bat, "_page", 0)
    # Sequence-parallel batchers route warmup admissions >= the sp
    # threshold through the sp program — which warms the sp/adopt/
    # suffix families but leaves the threshold's bucket COLD for the
    # chunked classes sub-threshold phase prompts hit. Warm those with
    # page-stepped lengths just under the threshold too.
    sp_cfg = getattr(bat, "_sp_cfg", None)
    sp_thr = (
        sp_cfg.sp_threshold
        if sp_cfg is not None and getattr(bat, "_sp", None) is not None
        else None
    )
    # One admission per reachable prompt bucket (prefill variants).
    for bucket in bat.prompt_buckets:
        plen = min(bucket, max_len - 1)
        if next(b for b in bat.prompt_buckets if b >= plen) != bucket:
            break  # shrunk length falls into an earlier bucket: done
        lens = {plen}
        if chunk and page:
            for c in range(1, chunk // page):
                shorter = plen - c * page
                if shorter > 0 and next(
                    b for b in bat.prompt_buckets if b >= shorter
                ) == bucket:
                    lens.add(shorter)
        if sp_thr is not None and plen >= sp_thr:
            steps_below = (chunk // page) if (chunk and page) else 1
            for c in range(steps_below):
                shorter = sp_thr - 1 - c * page
                if shorter > 0 and next(
                    b for b in bat.prompt_buckets if b >= shorter
                ) == bucket:
                    lens.add(shorter)
        for length in sorted(lens):
            n_steps = min(2, max_len - length)
            bat.submit(
                rng.randint(0, vocab, size=length).astype(np.int32),
                n_steps,
            )
        if bucket >= prompt_max:
            break  # later buckets are unreachable for this workload
    # Every key-block power-of-two bucket a step count in
    # [1, steps_max] can map to (nkb = pow2ceil(steps)), so no phase
    # admission compiles a fresh _stage_slot variant mid-measurement.
    s = 1
    while True:
        bat.submit(rng.randint(0, vocab, size=2).astype(np.int32),
                   min(s, steps_max))
        if s >= steps_max:
            break
        s *= 2
    bat.run()


def warmup_disagg(srv, vocab: int, steps_max: int,
                  prompt_max: int) -> None:
    """Disaggregated-server warmup: the shared :func:`warmup` pass with
    placement forced COLLOCATED (decode-side prefill buckets + key
    blocks), then one disagg-path admission per reachable full-page
    count — the prefill worker's chunk programs, the adopt-pages
    buckets and the decode side's per-page-count suffix variants all
    compile here instead of as fake mid-phase stalls."""
    import numpy as np

    from adapt_tpu.config import DisaggConfig

    real = srv.cfg
    srv.cfg = DisaggConfig(
        prompt_threshold=10**6, busy_prompt_threshold=10**6
    )
    try:
        warmup(srv, vocab, steps_max, prompt_max)
    finally:
        srv.cfg = real
    P = srv.decode._page
    thr = min(real.prompt_threshold, real.busy_prompt_threshold)
    m_lo = max(1, (thr - 1) // P)
    m_hi = (prompt_max - 1) // P
    # Which page counts to warm. The compiled families key on POWERS
    # OF TWO (worker chunk windows, adopt-pages buckets, the
    # pow2-padded decode-side suffix window) plus the worker's
    # last-chunk remainder class (m mod chunk-pages), so a
    # long-context config (m_hi in the hundreds) warms a pow2/pow2-1
    # LADDER + a dense residue head instead of every page count — the
    # per-m loop that was fine at 8 pages is 500 admissions at 64k
    # tokens. Short configs keep the exact per-m loop.
    if m_hi - m_lo <= 16:
        ms = list(range(m_lo, m_hi + 1))
    else:
        cpp = max(1, (srv.prefill._chunk or P) // P)
        picked = set(range(m_lo, min(m_lo + 2 * cpp, m_hi) + 1))
        p2 = 1
        while p2 <= m_hi:
            for m in (p2 - 1, p2):
                if m_lo <= m <= m_hi:
                    picked.add(m)
            p2 *= 2
        picked.add(m_hi)
        ms = sorted(picked)
    rng = np.random.RandomState(1)
    # Pin BOTH thresholds to the lower (busy) one for the warmup loop:
    # warmup runs at zero occupancy, where the real config would apply
    # only prompt_threshold and silently collocate the busy-tier
    # lengths — leaving their adopt/suffix variants to compile
    # mid-phase, the exact fake stall this function exists to prevent.
    srv.cfg = DisaggConfig(prompt_threshold=thr, busy_prompt_threshold=thr)
    try:
        for m in ms:
            # Smallest prompt with m full pages the policy will
            # actually disaggregate (at least the threshold).
            s0 = min(max(m * P + 1, thr), prompt_max)
            if (s0 - 1) // P != m:
                continue
            srv.submit(
                rng.randint(0, vocab, size=s0).astype(np.int32), 2
            )
        srv.run()
    finally:
        srv.cfg = real


def drive_phase(
    bat,
    schedule: list[Arrival],
    spec: WorkloadSpec,
    registry=None,
    wall_guard_s: float = PHASE_WALL_GUARD_S,
    fanout: bool = False,
) -> dict:
    """Run one phase to drain; returns the phase report (windowed
    metrics + per-request token counts + digests).

    ``fanout=True`` (the ``--fanout on`` arm) submits each run of
    consecutive same-``Arrival.group`` arrivals through ONE
    ``submit_fanout`` call (copy-on-write page sharing across the
    branches); ``fanout=False`` submits the identical schedule
    serially — the two arms ``benchmarks/load/fanout_smoke.py``
    compares. Ungrouped arrivals (``group == -1``) always submit
    serially."""
    import numpy as np

    from adapt_tpu.config import SLOSpec
    from adapt_tpu.utils.metrics import global_metrics
    from adapt_tpu.utils.tracing import global_flight_recorder

    from adapt_tpu.runtime.scheduler import QueueFullError

    reg = registry if registry is not None else global_metrics()
    recorder = global_flight_recorder()
    finishes0 = recorder.kind_counts().get("finish", 0)
    n = len(schedule)
    counts = [0] * n  # emitted tokens per scheduled request
    cancelled = [False] * n
    #: Admission-control rejections (bounded queue / burst caps /
    #: best-effort shed — traffic-control arms only). A rejected
    #: request never produces a finish edge, so the drain loop and
    #: the per-request books both subtract it.
    rejected = [False] * n
    submit_wall = [0.0] * n
    ttfts: list[float | None] = [None] * n
    #: Per-request emitted tokens, in commit order — the bit-identity
    #: half of the determinism contract (A/B smokes compare these
    #: between arms; the per-token append is trivial at bench scale).
    streams: list[list[int]] = [[] for _ in range(n)]

    def make_cb(i: int, a: Arrival):
        def cb(rid, tok, idx, _i=i, _c=a.cancel_after):
            if ttfts[_i] is None:
                # Driver-side per-request TTFT (wall clock from the
                # scheduled submit): the per-TENANT attainment split
                # the overload gate needs, without growing registry
                # cardinality per tenant.
                ttfts[_i] = time.perf_counter() - submit_wall[_i]
            streams[_i].append(int(tok))
            counts[_i] += 1
            if _c is not None and counts[_i] == _c:
                # Token-space cancel mark: the marker is consumed
                # at the next commit boundary, so the final stream
                # length is deterministic (exactly _c tokens).
                cancelled[_i] = True
                bat.cancel(rid)
        return cb

    win = reg.snapshot(window=True)
    t0 = time.perf_counter()
    pi = 0
    stats0 = bat.stats()
    ticks0 = stats0["ticks"]
    sp0 = stats0.get("sp_prefills", 0)
    cow0 = stats0.get("cow_forks", 0)
    #: rid -> per-arrival callback for fan-out groups (one shared
    #: on_token per group; siblings are told apart by request id).
    #: Filled right after submit_fanout returns — safe because the
    #: drive loop is single-threaded, so no tick (hence no token)
    #: can land between the call and the map fill.
    fan_cbs: dict[int, object] = {}

    def fan_cb(rid, tok, idx):
        cb = fan_cbs.get(rid)
        if cb is not None:
            cb(rid, tok, idx)

    while True:
        now = time.perf_counter() - t0
        while pi < n and schedule[pi].t <= now:
            a = schedule[pi]
            slo = SLOSpec(
                ttft_budget_s=spec.ttft_budget_s,
                itl_budget_s=spec.itl_budget_s,
                tenant=a.tenant,
                priority=a.priority,
            )
            if fanout and a.group >= 0:
                # One submit_fanout per run of same-group arrivals
                # (build_schedule emits them contiguously at one t).
                idxs = [pi]
                while (
                    pi + len(idxs) < n
                    and schedule[pi + len(idxs)].group == a.group
                ):
                    idxs.append(pi + len(idxs))
                wall = time.perf_counter()
                for i in idxs:
                    submit_wall[i] = wall
                try:
                    rids = bat.submit_fanout(
                        np.asarray(a.prompt, np.int32),
                        len(idxs),
                        a.steps,
                        slo=slo,
                        on_token=fan_cb,
                    )
                    for rid, i in zip(rids, idxs):
                        fan_cbs[rid] = make_cb(i, schedule[i])
                except QueueFullError:
                    # Mid-group raises lose the queued siblings' ids;
                    # the fan-out arms run without a bounded queue, so
                    # this is a whole-group reject in practice.
                    for i in idxs:
                        rejected[i] = True
                pi += len(idxs)
                continue
            submit_wall[pi] = time.perf_counter()
            try:
                bat.submit(
                    np.asarray(a.prompt, np.int32),
                    a.steps,
                    slo=slo,
                    on_token=make_cb(pi, a),
                )
            except QueueFullError:
                rejected[pi] = True
            pi += 1
        finished = recorder.kind_counts().get("finish", 0) - finishes0
        if pi >= n and finished >= n - sum(rejected):
            break
        if now > wall_guard_s:
            raise RuntimeError(
                f"phase wall guard ({wall_guard_s:.0f}s) exceeded: "
                f"{finished}/{n} finished, {pi}/{n} submitted"
            )
        if pi < n and finished == pi:
            # Fully drained but the next arrival is in the future:
            # nap until it (bounded) instead of busy-spinning ticks.
            gap = schedule[pi].t - (time.perf_counter() - t0)
            if gap > 0:
                time.sleep(min(gap, 0.01))
        bat.tick()
    # The batcher may hold one garbage tick in flight after the last
    # finish edge — drain it so the
    # phase's windowed snapshot (and the next phase) start clean.
    drain = getattr(bat, "drain", None)
    if drain is not None:
        drain()
    wall_s = time.perf_counter() - t0

    delta = reg.snapshot(since=win)
    c = delta["counters"]
    window_s = delta["window_s"]

    def attainment(prefix: str) -> float | None:
        met = c.get(f"slo.{prefix}_met_total", 0.0)
        missed = c.get(f"slo.{prefix}_missed_total", 0.0)
        return met / (met + missed) if met + missed else None

    per_tenant: dict[str, dict[str, float]] = {}
    for key, v in c.items():
        for kind in ("met", "missed"):
            pre = f"slo.{kind}_total."
            if key.startswith(pre):
                per_tenant.setdefault(
                    key[len(pre):], {"met": 0.0, "missed": 0.0}
                )[kind] = v
    req_met = sum(t["met"] for t in per_tenant.values())
    req_missed = sum(t["missed"] for t in per_tenant.values())

    def pct(hname: str) -> dict:
        h = delta["histograms"].get(hname, {})
        return {
            k: round(h[k], 6) for k in ("p50", "p99", "max") if k in h
        }

    roofline = {
        k: v
        for k, v in delta["gauges"].items()
        if k.startswith(
            ("engine.mbu", "engine.mfu", "engine.flops",
             "engine.bytes_accessed")
        )
    }
    # Prefill/decode token-rate SPLIT: one blended tokens/s hides
    # exactly the ratio disaggregation changes, so report prompt
    # positions prefilled per second (decode-tick prefill work plus
    # any prefill-tier work) next to committed decode tokens per
    # second. The stall histogram is the decode-delay the in-tick
    # share of that prefill work caused.
    prefill_tokens = c.get("continuous.prefill_tokens_total", 0.0) + c.get(
        "disagg.prefill_tokens_total", 0.0
    )
    stall = delta["histograms"].get("continuous.prefill_stall_s", {})
    # decode_tokens_s IS throughput_tokens_s today (committed decode
    # tokens over the window); both keys ship so the prefill/decode
    # split reads naturally next to prefill_tokens_s, computed once.
    decode_tokens_s = round(
        c.get("continuous.tokens_total", 0.0) / window_s, 2
    )
    # Prefill-TIER telemetry (disagg arms): the windowed disagg.*
    # counter deltas + handoff-wall percentiles, so a disagg phase
    # report carries the tier's own numbers (placement split, pages
    # and bytes streamed, failed handoffs) next to the decode-side
    # stall histogram it exists to shrink — instead of reporting the
    # stall win with the tier that produced it invisible.
    disagg = {
        k[len("disagg."):]: round(v, 3)
        for k, v in c.items()
        if k.startswith("disagg.") and v
    }
    if disagg:
        disagg["handoff_s"] = pct("disagg.handoff_s")
    return {
        "requests": n,
        "offered_rps": round(n / spec.duration_s, 4),
        "offered_tokens_s": round(
            offered_tokens(schedule) / spec.duration_s, 2
        ),
        "goodput_tokens_s": round(
            c.get("continuous.good_tokens_total", 0.0) / window_s, 2
        ),
        "throughput_tokens_s": decode_tokens_s,
        "decode_tokens_s": decode_tokens_s,
        "prefill_tokens_s": round(prefill_tokens / window_s, 2),
        "prefill_stall_s": {
            k: round(stall[k], 6)
            for k in ("p50", "p99", "max", "sum", "count")
            if k in stall
        },
        "slo_attainment": (
            round(req_met / (req_met + req_missed), 4)
            if req_met + req_missed
            else None
        ),
        "ttft_attainment": attainment("ttft"),
        "itl_attainment": attainment("itl"),
        "per_tenant": per_tenant,
        "ttft_s": pct("continuous.ttft_s"),
        "itl_s": pct("continuous.itl_s"),
        "queue_wait_s": pct("continuous.queue_wait_s"),
        "cancelled": int(sum(cancelled)),
        "rejected": int(sum(rejected)),
        "tokens_delivered": int(sum(counts)),
        "token_counts": counts,
        "token_streams": streams,
        "request_ttfts": ttfts,
        "rejected_flags": rejected,
        "ticks": bat.stats()["ticks"] - ticks0,
        # Sequence-parallel prefill books for the phase (0 on sp-off
        # arms — the long_context A/B's structural check that the sp
        # arm actually took the sp path).
        "sp_prefills": bat.stats().get("sp_prefills", 0) - sp0,
        "sp_width": bat.stats().get("sp_width", 1),
        # Copy-on-write fork count for the phase (0 on --fanout off /
        # dense arms — fanout_smoke's structural check that the fan-out
        # arm actually shared pages instead of prefilling N times).
        "cow_forks": bat.stats().get("cow_forks", 0) - cow0,
        "wall_s": round(wall_s, 3),
        "window_s": round(window_s, 3),
        "roofline": roofline,
        "disagg": disagg,
        "schedule_digest": schedule_digest(schedule),
    }


def run_sweep(
    bat,
    spec: WorkloadSpec,
    rates: list[float],
    seed: int,
    registry=None,
    fanout: bool = False,
) -> list[dict]:
    """One phase per offered rate on ONE batcher (phase seeds derive
    from ``seed`` + the rate index, so every point is independently
    deterministic). Returns the curve points in sweep order."""
    points = []
    for i, rate in enumerate(rates):
        pspec = dataclasses.replace(spec, rate_rps=float(rate))
        schedule = build_schedule(pspec, seed + i)
        report = drive_phase(
            bat, schedule, pspec, registry=registry, fanout=fanout
        )
        report["rate_rps"] = float(rate)
        report["seed"] = seed + i
        points.append(report)
    return points


def build_batcher(
    vocab: int,
    max_len: int,
    slots: int,
    chunk: int,
    page_size: int = 128,
    scheduler=None,
    pool_pages: int | None = None,
    cache_tier=None,
    prefill=None,
    prefill_chunk: int | None = None,
):
    """The harness's model+batcher factory (tiny LM — the harness
    measures the serving tier's behavior under load, not model quality;
    the backend is whatever the caller's environment selects: the CPU
    gates export ``JAX_PLATFORMS=cpu``). ``scheduler`` (a ``config.SchedulerConfig``) turns the
    traffic-control tier on — the quota-on arm of an overload A/B.
    ``cache_tier`` (a ``config.CacheTierConfig``) turns
    the host-DRAM spill tier on — the tier-on arm of the corpus A/B —
    and ``pool_pages`` pins the HBM budget so both arms run flat.
    ``prefill`` (a ``config.PrefillConfig``) turns the
    sequence-parallel long-context prefill path on — the sp-on arm of
    the long_context A/B (the caller must provision
    ``sp_width`` virtual devices first, e.g.
    ``benchmarks.common.force_cpu_mesh``)."""
    import jax
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import lm_tiny
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    lm = lm_tiny(vocab=vocab, max_len=max_len)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return ContinuousBatcher(
        lm, variables, slots=slots, chunk=chunk, page_size=page_size,
        pool_pages=pool_pages, cache_tier=cache_tier,
        scheduler=scheduler, prefill=prefill,
        prefill_chunk=prefill_chunk,
    )


def build_disagg(
    vocab: int,
    max_len: int,
    slots: int,
    chunk: int,
    page_size: int = 16,
    prefill_chunk: int | None = None,
    prompt_threshold: int = 48,
    busy_prompt_threshold: int | None = None,
    scheduler=None,
    prefill=None,
):
    """The disaggregated counterpart of :func:`build_batcher`: a paged
    decode batcher, a chunked ``PrefillWorker`` and the
    ``DisaggServer`` placement policy in front — same driver surface,
    so ``drive_phase``/``run_sweep`` run the SAME schedule through
    either placement for an apples-to-apples curve. ``prefill_chunk``
    defaults to two pages (the per-tick stall bound)."""
    decode = build_batcher(
        vocab, max_len, slots, chunk,
        page_size=page_size, scheduler=scheduler,
    )
    from adapt_tpu.config import DisaggConfig
    from adapt_tpu.runtime.disagg import DisaggServer, PrefillWorker

    worker = PrefillWorker(
        decode.lm,
        decode.variables,
        page_size=page_size,
        prefill_chunk=prefill_chunk or 2 * page_size,
        # Sequence-parallel long-context jobs run sp-sharded in the
        # TIER (`--sp on --placement disagg`): the worker's step()
        # dispatches them through the sp program instead of the chunk
        # loop, and prompts past the pool bound stay servable.
        prefill=prefill,
    )
    # Default busy threshold: two pages, capped at the main threshold.
    # A/B drivers pass busy == prompt_threshold instead, which makes
    # the placement a PURE function of the schedule (occupancy plays
    # no role) — run-to-run comparable.
    cfg = DisaggConfig(
        prompt_threshold=prompt_threshold,
        busy_prompt_threshold=(
            busy_prompt_threshold
            if busy_prompt_threshold is not None
            else min(prompt_threshold, 2 * page_size)
        ),
    )
    return DisaggServer(decode, worker, cfg)


def main() -> int:
    rates_arg = str_flag(sys.argv, "--rates", "4,8,16,32")
    seed = int_flag(sys.argv, "--seed", 0)
    slots = int_flag(sys.argv, "--slots", 4)
    chunk = int_flag(sys.argv, "--chunk", 8)
    duration = int_flag(sys.argv, "--duration", 3)
    cancel_pct = int_flag(sys.argv, "--cancel-pct", 0)
    preset_name = str_flag(sys.argv, "--preset", "")
    placement = str_flag(
        sys.argv, "--placement", "collocated",
        choices=("collocated", "disagg"),
    )
    # Traffic control: "on" fronts admission with the default
    # SchedulerConfig (bounded queue, WFQ, preemption, degradation) so
    # the SAME seeded schedule drives quota-on vs quota-off runs —
    # e.g. `--preset overload --scheduler on` vs `--scheduler off`.
    sched_arg = str_flag(
        sys.argv, "--scheduler", "off", choices=("off", "on")
    )
    # Hierarchical KV: "on" puts the host-DRAM spill tier under the
    # paged prefix cache (default CacheTierConfig) so the SAME seeded
    # schedule drives tier-on vs tier-off arms — e.g.
    # `--preset corpus --cache-tier on` vs `--cache-tier off`
    tier_arg = str_flag(
        sys.argv, "--cache-tier", "off", choices=("off", "on")
    )
    # Sequence-parallel prefill: "on" routes prompts of at least
    # --sp-threshold tokens through the sp-sharded prefill program at
    # --sp-width ring ranks — the sp-on arm
    # of the long_context A/B, e.g.
    # `--preset long_context --sp on` vs `--sp off`. Virtual CPU
    # devices are provisioned automatically (force_cpu_mesh).
    # Copy-on-write fan-out: "on" submits each same-group run of
    # arrivals (the agent_trace preset's branches) through ONE
    # submit_fanout call — shared prefix pages, CoW forks on
    # divergence; "off" submits the identical
    # schedule serially. `--preset agent_trace --fanout on` vs
    # `--fanout off` is the pair benchmarks/load/fanout_smoke.py gates.
    fanout_arg = str_flag(
        sys.argv, "--fanout", "off", choices=("off", "on")
    )
    sp_arg = str_flag(sys.argv, "--sp", "off", choices=("off", "on"))
    sp_width = int_flag(sys.argv, "--sp-width", 2)
    sp_threshold = int_flag(sys.argv, "--sp-threshold", 4096)
    out = str_flag(sys.argv, "--out", "")
    try:
        rates = [float(r) for r in rates_arg.split(",") if r]
        if preset_name:
            from benchmarks.load.workload import preset

            spec = preset(
                preset_name,
                duration_s=float(duration),
                cancel_fraction=cancel_pct / 100.0,
            )
        else:
            spec = WorkloadSpec(
                duration_s=float(duration),
                cancel_fraction=cancel_pct / 100.0,
            )
        from adapt_tpu.utils.profiling import global_engine_obs

        scheduler = None
        if sched_arg == "on":
            from adapt_tpu.config import SchedulerConfig

            scheduler = SchedulerConfig()
        cache_tier = None
        if tier_arg == "on":
            from adapt_tpu.config import CacheTierConfig

            cache_tier = CacheTierConfig()
        sp_cfg = None
        if sp_arg == "on":
            from benchmarks.common import force_cpu_mesh

            from adapt_tpu.config import PrefillConfig

            force_cpu_mesh(max(2, sp_width))
            sp_cfg = PrefillConfig(
                sp_threshold=sp_threshold, sp_width=sp_width
            )
        if placement == "disagg":
            # Same schedule, disaggregated serving path (paged decode +
            # prefill tier) — the apples-to-apples arm of the
            # long-tail-prefill comparison (see load/disagg_smoke.py).
            bat = build_disagg(
                spec.vocab,
                spec.prompt_max + spec.steps_max + 8,
                slots,
                chunk,
                scheduler=scheduler,
                prefill=sp_cfg,
            )
        else:
            bat = build_batcher(
                spec.vocab,
                spec.prompt_max + spec.steps_max + 8,
                slots,
                chunk,
                scheduler=scheduler,
                cache_tier=cache_tier,
                prefill=sp_cfg,
            )
        # Phase timing on: every curve point gets its roofline
        # annotation (mbu/mfu need measured phase walls).
        global_engine_obs().enabled = True
        if placement == "disagg":
            # The disagg-aware warmup: prefill-worker chunk programs,
            # adopt-pages buckets and per-page-count suffix variants
            # must compile here, not as fake mid-phase stalls.
            warmup_disagg(bat, spec.vocab, spec.steps_max, spec.prompt_max)
        else:
            warmup(bat, spec.vocab, spec.steps_max, spec.prompt_max)
        points = run_sweep(
            bat, spec, rates, seed, fanout=fanout_arg == "on"
        )
        peak = max(p["goodput_tokens_s"] for p in points)
        report = {
            "metric": "load_goodput_curve",
            "value": peak,
            "unit": "tokens/s (peak goodput over the sweep)",
            "vs_baseline": 0.0,
            "rates_rps": rates,
            "seed": seed,
            "slots": slots,
            "chunk": chunk,
            "placement": placement,
            "scheduler": sched_arg,
            "fanout": fanout_arg,
            "sp": sp_arg,
            "prefill_cfg": (
                dataclasses.asdict(sp_cfg) if sp_cfg else None
            ),
            # Stamp the ACTIVE CacheTierConfig (capacity/codec/budgets)
            # so perf rows stay comparable across runs — a tier-on row
            # and a tier-off row are different serving configs.
            "cache_tier": (
                dataclasses.asdict(cache_tier) if cache_tier else None
            ),
            "preset": preset_name or None,
            "spec": dataclasses.asdict(spec),
            "points": [
                {k: v for k, v in p.items()
                 if k not in ("token_counts", "token_streams",
                              "request_ttfts", "rejected_flags")}
                for p in points
            ],
        }
        print(json.dumps(report), flush=True)
        if out:
            os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
            with open(out, "w", encoding="utf-8") as f:
                json.dump(report, f, indent=1)
                f.write("\n")
    except Exception as e:  # noqa: BLE001 — always one JSON line, rc 0
        print(
            json.dumps(
                {
                    "metric": "load_goodput_curve",
                    "value": 0.0,
                    "unit": "tokens/s (peak goodput over the sweep)",
                    "vs_baseline": 0.0,
                    "error": str(e)[-300:],
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
