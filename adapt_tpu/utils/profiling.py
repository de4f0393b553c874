"""Engine-tier observability: compile sentinel, memory accounting,
roofline (MBU/MFU) accounting, tick-phase timing.

PR 2 made the *request* tier visible (timelines, stitched spans, flight
recorder); this module watches the *engine* underneath — the things that
silently destroy TPU serving performance without ever failing a test:

- :class:`CompileSentinel` — a registry of the serving hot-path jit
  entry points (the continuous tick's decode/verify programs, the
  admission setters, ``draft_chunk``, pipeline stage fns, the pipelined
  decoder's per-stage programs). Each :meth:`~CompileSentinel.sample`
  reads every registered program's jit cache size, exports it as an
  ``engine.compiles.<program>`` gauge, and — after a configurable
  warmup — treats ANY growth as an unintended recompile: it bumps the
  ``engine.compile_events`` counter, records a ``recompile`` flight-
  recorder event, logs a WARNING, and drops a zero-duration tracer
  event so the recompile lands in the Perfetto timeline next to the
  tick that paid for it. Static-shape serving (the Mesh-TensorFlow
  discipline) makes "the cache grew" a precise proxy for "a tick just
  stalled on XLA"; re-registering a program (every batcher constructor
  does) re-arms its warmup, because jit caches key on ``self`` and a
  new instance legitimately compiles its own first variants. The
  process-global sentinel also keeps the COMPILE ACCOUNT: what every
  program of the process cost to trace, to lower and to compile or
  load, by watch and by stage, as ``jax.monitoring`` reports it where
  the work happens (``engine.compile.*`` gauges and spans;
  :meth:`~CompileSentinel.account`). It only listens: it lowers,
  compiles and traces nothing itself.

- **Memory accounting** — pull-style: components register themselves as
  weakly-held sources (:func:`register_memory_source`) exposing a
  ``_memory_stats() -> {metric: value}`` dict, and
  :func:`engine_collector` (hooked into ``MetricsRegistry.snapshot`` /
  the exporter, like the codec copy-stats bridge) sums them at scrape
  time into ``memory.*`` gauges: dense KV strip bytes, draft-cache
  bytes, paged pool occupancy (``memory.pages_{used,free,cached}`` +
  ``memory.pool_pages``/``pool_bytes``), the pager's prefix-cache
  effectiveness counters (``paged.prefix_{hits,misses}``), and — when
  a hierarchical cache tier is configured — the host-DRAM tier's
  occupancy (``memory.host_bytes`` encoded-resident bytes,
  ``memory.pages_spilled`` host-resident pages; the per-event
  ``cache_tier.*_total`` counters land at their event sites in
  ``runtime/continuous``, not here). Sharded
  components report BOTH logical and per-device bytes
  (``memory.kv_bytes_per_device`` / ``memory.pool_bytes_per_device``
  via :func:`device_local_nbytes`) — under tensor parallelism the
  logical size alone would read as if the whole cache lived on one
  chip. When the
  backend provides ``device.memory_stats()`` (TPU/GPU; CPU does not),
  ``memory.hbm_bytes_in_use`` / ``memory.hbm_bytes_limit`` ride along.
  Sources are weakrefs: a retired batcher drops out of the gauges with
  its arrays, never pinned by telemetry.

- **Roofline accounting** — how close the engine runs to the hardware
  ceiling, from numbers the system already has: components register as
  weakly-held roofline sources (:func:`register_roofline_source`)
  exposing ``_roofline_stats() -> {program: {flops, bytes, wall_s}}``
  — flops/bytes come from XLA's own ``cost_analysis()`` of the watched
  executables (lowered once, lazily; no recompile, no jit-cache
  growth), wall seconds from the :class:`EngineObs` phase timing the
  tick loop already records. :func:`engine_collector` turns them into
  ``engine.flops.<program>`` / ``engine.bytes_accessed.<program>``
  gauges always, and — when the platform's peak numbers are known
  (:func:`roofline_peaks`: TPU table mirroring
  ``benchmarks/tpu_models.py``, or the ``ADAPT_TPU_PEAK_FLOPS`` /
  ``ADAPT_TPU_PEAK_BYTES_S`` env overrides) — ``engine.mfu.<program>``
  / ``engine.mbu.<program>`` plus headline ``engine.mfu`` /
  ``engine.mbu`` taken from the byte-heaviest program (the one whose
  stream defines the decode roofline). The CPU backend exports
  bytes/flops WITHOUT utilization claims — there is no honest CPU
  "peak" to divide by.

- :class:`EngineObs` — the one-branch gate for per-phase tick timing
  (``config.ObservabilityConfig.obs_engine``). Enabled, each serving
  phase (admit / prefill / draft / verify / decode / commit / update in
  ``ContinuousBatcher.tick``; stage / hop in ``LocalPipeline.stream``)
  records an ``engine.phase.<name>_s`` histogram sample and, when the
  tracer is on, a span — ``benchmarks/micro/obs_overhead.py`` measures
  the enabled cost against the <5% tick budget. Disabled (default),
  every phase site costs exactly one attribute check.

Catalog + semantics: ``docs/OBSERVABILITY.md`` "Engine telemetry".
"""

from __future__ import annotations

import math
import os
import threading
import time
import weakref
from collections.abc import Callable

from jax import monitoring
from jax.profiler import TraceAnnotation

from adapt_tpu.utils.logging import get_logger, kv
from adapt_tpu.utils.metrics import MetricsRegistry, global_metrics
from adapt_tpu.utils.tracing import (
    _EPOCH_OFFSET,
    global_flight_recorder,
    global_tracer,
)

log = get_logger("profiling")


# -- compile sentinel -------------------------------------------------------

#: The ``jax.monitoring`` events the compile account listens to (JAX
#: 0.9.0: ``dispatch.log_elapsed_time`` around tracing, lowering and
#: ``compile_or_get_cached``; ``compiler.py`` / ``compilation_cache.py``
#: for the persistent cache) -> the account's stage.
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_COUNT_OF = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_CACHE_SECONDS_OF = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
}
#: A stage's count in an account row: a ``backend`` event is one more
#: executable, so a watch's ``variants`` is its jit-cache size.
_COUNT_OF = {"trace": "traces", "lower": "lowerings", "backend": "variants"}
#: The account's row for programs no watch names, and how many of
#: their names it keeps (the smallest folds into ``(rest)``) and shows.
OTHER = "other"
_OTHER_KEPT = 64
_OTHER_SHOWN = 16


def _new_last() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": "off"}


def _new_row() -> dict:
    return {
        "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
        "traces": 0, "lowerings": 0, "variants": 0,
        #: Of its backend events, those the persistent cache served
        #: and those compiled and written to it.
        "cache_hits": 0, "cache_misses": 0,
        #: The newest program of the row, stage by stage (what the
        #: recompile alarm quotes): reset by its trace event.
        "last": _new_last(),
    }


def _add_row(into: dict, row: dict) -> None:
    for k, v in row.items():
        if k != "last":
            into[k] += v


def _bare_name(fun_name: str) -> str:
    """``jit(_step_chunk)`` -> ``_step_chunk``: JAX names a program by
    its function on the trace event and by its module (the function in
    the API's wrapper) on the other two."""
    if fun_name.endswith(")"):
        head, _, inner = fun_name[:-1].partition("(")
        if inner and head.isidentifier():
            return inner
    return fun_name


def _row_seconds(row: dict) -> float:
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


def _copy_row(row: dict) -> dict:
    return {**row, "last": dict(row["last"])}


class _Watch:
    __slots__ = ("size_fn", "last", "samples", "expected")

    def __init__(self, size_fn: Callable[[], int]):
        self.size_fn = size_fn
        self.last: int | None = None
        self.samples = 0
        #: Outstanding EXPECTED-compile allowance (:meth:`rearm`):
        #: post-warmup growth is absorbed against it, one executable
        #: per unit, before anything is flagged as unexpected.
        self.expected = 0


class CompileSentinel:
    """Watches registered jit entry points for unexpected recompiles.

    ``register(name, fn)`` takes any jit-wrapped callable (jax exposes
    the executable-cache size as ``fn._cache_size()``) or an explicit
    0-arg ``size_fn`` (which may return ``None`` to say "my owner is
    gone" — the watch is then pruned). :meth:`sample` is called once
    per serving tick (and at every exporter scrape via
    :func:`engine_collector`): cheap — one cache-size read per program
    under one lock, plus one gauge write per program on the sampled
    registry (every registry that samples gets the full
    ``engine.compiles.*`` family, not just the one that happened to see
    a change).

    Warmup counts ACTIVE samples only — samples where the program has
    compiled at least once (size > 0). A program registered at startup
    and then scraped for an hour while the serve loop sits idle keeps
    its full grace window: its first real compiles are expected, not
    flagged. After ``warmup_samples`` active samples, any growth is an
    unintended recompile (counter + flight event + WARNING + tracer
    instant event). Growth during warmup still moves the gauge, so the
    expected variant count is visible too.

    One watch per name; re-registering re-arms the warmup and replaces
    the size_fn (latest instance wins — right for class-level shared
    jit caches, where a fresh ``self`` legitimately compiles new
    entries; per-instance program families should register ONE
    aggregate size_fn over their live instances —
    :func:`aggregate_size_fn` builds one). Event DETECTION happens
    once, against the sentinel's own cumulative state; every sampling
    registry's ``engine.compile_events`` counter is then synced up to
    that cumulative count, so a custom registry served by the exporter
    reports the same events as the process registry the ticks drive.

    **The compile account** (the process-global sentinel's: only it is
    given ``jax.monitoring``'s listeners, once a process). What every
    program cost, keyed by program and stage: ``trace`` (Python to
    jaxpr), ``lower`` (jaxpr to an MLIR module) and ``backend`` (XLA's
    compile OR the load from the persistent cache: JAX reports both
    under one event), plus the persistent cache's hits, misses and
    load seconds. A program is a WATCH where the event's function is a
    registered entry point's (``register`` learns ``fn.__name__``; the
    ``size_fn=`` form is told by ``names=``), else ``other`` with the
    function's name kept in a bounded table. JAX reports a function's
    NAME and no more, so two functions of one name share a row. Stage
    events nest (every jitted library function a program calls while
    it is traced fires its own trace event inside the outer one; a
    Pallas kernel is traced inside its caller's lowering): seconds are
    booked only for the span that is outermost on its thread, which
    JAX's start stamps tell exactly, so no second is counted twice and
    no list of spans is kept. Every backend event counts one program,
    nested or not. The cache's events carry no name: they are booked
    by stage only (a thread's open backend span lends them to that
    program's ``cache=`` attribute and to nothing else). Published at
    event time as process-cumulative ``engine.compile.*`` gauges on
    the process registry and, with the tracer on, as
    ``engine.compile.<stage>`` ring spans placed from JAX's own
    clock readings. The account LISTENS only: it never lowers,
    compiles, traces or asks for a cost analysis, so it cannot cost
    what it measures; a process that compiles nothing fires no
    listener."""

    def __init__(self, warmup_samples: int = 8):
        if warmup_samples < 0:
            raise ValueError(
                f"warmup_samples must be >= 0, got {warmup_samples}"
            )
        self._lock = threading.Lock()
        self._watches: dict[str, _Watch] = {}
        self.warmup_samples = warmup_samples
        self._events = 0
        #: Per-registry high-water mark of events already inc'd there
        #: (weak keys: the sentinel must not pin test registries).
        self._synced: "weakref.WeakKeyDictionary[MetricsRegistry, int]" = (
            weakref.WeakKeyDictionary()
        )
        #: Tombstones of pruned watches: every sample clears their
        #: stale ``engine.compiles.*`` gauge from the sampled registry
        #: (a retired program must not scrape as still-compiled).
        #: Bounded by the set of program names ever watched.
        self._pruned: set[str] = set()
        # -- the compile account (class docstring) ----------------------
        #: Function name (as ``jax.monitoring`` reports it) -> watch.
        self._programs: dict[str, str] = {}
        #: Watch (or ``OTHER``) -> row; ``_other``: function name -> row
        #: for the programs no watch names.
        self._rows: dict[str, dict] = {}
        self._other: dict[str, dict] = {}
        self._totals: dict[str, float] = {
            "programs": 0, "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "cache_hits": 0, "cache_misses": 0,
            "cache_load_s": 0.0, "cache_saved_s": 0.0,
        }
        #: Per compiling thread: ``depth`` of open stage spans and the
        #: open backend span's ``cache`` outcome.
        self._tls = threading.local()

    def register(
        self,
        name: str,
        fn=None,
        *,
        size_fn: Callable[[], int] | None = None,
        names: tuple[str, ...] = (),
    ) -> None:
        """Watch ``name``. Re-registering (same or different fn) re-arms
        the warmup window — constructors re-register their class-level
        jits precisely because a fresh ``self`` legitimately compiles
        fresh cache entries. ``names``: the functions whose compile
        events the account books under this watch, beside ``fn``'s own
        (a ``size_fn=`` family of closures has no ``fn`` to ask)."""
        if fn is not None and getattr(fn, "__name__", None):
            names = (fn.__name__, *names)
        if size_fn is None:
            if fn is None or not hasattr(fn, "_cache_size"):
                raise TypeError(
                    f"{name}: need a jit-wrapped fn (with _cache_size) "
                    "or an explicit size_fn"
                )
            size_fn = fn._cache_size
        with self._lock:
            w = _Watch(size_fn)
            prev = self._watches.get(name)
            if prev is not None:
                # An outstanding expected-compile allowance (rearm)
                # survives re-registration: a second instance's
                # construction must not erase the first one's pending
                # planned re-lowering and turn it into a false alarm.
                w.expected = prev.expected
            self._watches[name] = w
            self._pruned.discard(name)
            for fun_name in names:
                self._programs[fun_name] = name

    def unregister(self, name: str) -> None:
        with self._lock:
            if self._watches.pop(name, None) is not None:
                self._pruned.add(name)

    def rearm(self, name: str, expect: int = 1) -> None:
        """Grant ``name`` an allowance of ``expect`` EXPECTED compiles
        — for planned re-lowering events. Elastic mesh recovery
        re-lowers every program family against the shrunk mesh, but
        lazily (stage_slot on the next admission, a prefill bucket on
        its next use — possibly long after any warmup window would
        have re-closed), so the allowance is consumed whenever the
        growth actually lands: the next ``expect`` new executables are
        absorbed without an event, and anything beyond them is the
        phantom-variant alarm the sentinel exists for. Unknown names
        are a no-op (a spec-less batcher re-arms no draft watch).

        Caveat, same as re-registration's warmup re-arm: watches on
        class-level shared jits see every live instance, so an
        allowance granted for one batcher's recovery can absorb
        another's growth until consumed — grant only compiles the
        caller is confident will land (the batcher scopes its grants
        to the program families it actually dispatches)."""
        with self._lock:
            w = self._watches.get(name)
            if w is not None:
                w.expected += expect

    def disarm(self, name: str, expect: int = 1) -> None:
        """Revoke up to ``expect`` units of ``name``'s outstanding
        allowance (clamped at zero; unknown names are a no-op). A
        granter that retires before its planned re-lowering lands MUST
        call this with its full grant — consumed units are already
        subtracted, so the clamp removes exactly the leftover — or the
        slack survives on the shared class-level watch and silently
        absorbs another instance's REAL phantom variant. With
        concurrent granters the clamp can bite into another's pending
        allowance (same shared-watch caveat as :meth:`rearm`): the
        failure direction is a spurious alarm, never a masked one."""
        with self._lock:
            w = self._watches.get(name)
            if w is not None:
                w.expected = max(0, w.expected - expect)

    def watched(self) -> list[str]:
        with self._lock:
            return list(self._watches)

    def compiles(self, name: str) -> int:
        """Current executable-cache size of one watched program — the
        public replacement for poking ``fn._cache_size()`` in tests."""
        with self._lock:
            size = self._watches[name].size_fn()
        if size is None:
            raise KeyError(f"{name}: watched program's owner is gone")
        return int(size)

    def counts(self) -> dict[str, int]:
        """Current cache size of every watched program (one consistent
        read pass; programs whose size_fn raises — or whose owner is
        gone — are skipped)."""
        out = {}
        with self._lock:
            for name, w in self._watches.items():
                try:
                    size = w.size_fn()
                except Exception:  # noqa: BLE001 — a probe must not raise
                    continue
                if size is not None:
                    out[name] = int(size)
        return out

    @property
    def events(self) -> int:
        """Lifetime count of unexpected post-warmup compiles (summed
        new executables across all programs) — the cumulative value
        every sampling registry's ``engine.compile_events`` counter
        converges to."""
        with self._lock:
            return self._events

    # -- the compile account ---------------------------------------------
    # The four ``jax.monitoring`` callbacks run on the compiling thread
    # (the server thread under ``start()``), where the work happens.

    def _on_stage_open(self, event: str, value, **_) -> None:
        """Scalar listener: JAX stamps a stage's START under the
        stage's own event name."""
        if event in _STAGE_OF:
            tls = self._tls
            tls.depth = getattr(tls, "depth", 0) + 1
            if _STAGE_OF[event] == "backend":
                tls.cache = "off"

    def _on_stage_close(
        self, event: str, start: float, end: float, fun_name: str = "", **_
    ) -> None:
        """Time-span listener: the stage's end, with JAX's own two
        ``time.time()`` readings."""
        stage = _STAGE_OF.get(event)
        if stage is None:
            return
        tls = self._tls
        tls.depth = depth = max(getattr(tls, "depth", 1) - 1, 0)
        if depth == 0:
            self._book(stage, _bare_name(str(fun_name)), start, end)
        elif stage == "backend":  # nested: a program, its seconds the outer's
            with self._lock:
                self._totals["programs"] += 1
                programs = self._totals["programs"]
            global_metrics().set_gauge("engine.compile.programs", programs)

    def _on_cache_seconds(self, event: str, seconds: float, **_) -> None:
        """Duration listener: the persistent cache's two timings."""
        key = _CACHE_SECONDS_OF.get(event)
        if key is not None:
            self._book_cache(key, seconds)

    def _on_cache_event(self, event: str, **_) -> None:
        key = _CACHE_COUNT_OF.get(event)
        if key is not None:
            self._tls.cache = "hit" if key == "cache_hits" else "miss"
            self._book_cache(key, 1)

    def _book_cache(self, key: str, amount: float) -> None:
        with self._lock:
            self._totals[key] += amount
            # All of them from the first: a warm run reads 0 misses.
            gauges = {
                f"engine.compile.{k}": self._totals[k]
                for k in ("cache_hits", "cache_misses", "cache_load_s")
            }
        reg = global_metrics()
        for k, v in gauges.items():
            reg.set_gauge(k, float(v))

    def _book(
        self, stage: str, program: str, start: float, end: float
    ) -> None:
        """One outermost stage span into the account, the gauges and
        (tracer on) the ring."""
        seconds = end - start
        cache = getattr(self._tls, "cache", "off")
        with self._lock:
            watch = self._programs.get(program, OTHER)
            rows = [self._rows.setdefault(watch, _new_row())]
            if watch == OTHER:
                rows.append(self._other_row(program))
            for row in rows:
                row[f"{stage}_s"] += seconds
                row[_COUNT_OF[stage]] += 1
                if stage == "trace":
                    row["last"] = _new_last()
                row["last"][f"{stage}_s"] = seconds
                if stage == "backend":
                    row["last"]["cache"] = cache
                    if cache != "off":
                        row["cache_hits" if cache == "hit"
                            else "cache_misses"] += 1
            tot = self._totals
            tot[f"{stage}_s"] += seconds
            if stage == "backend":
                tot["programs"] += 1
            gauges = {
                f"engine.compile.{k}": tot[k]
                for k in ("programs", "trace_s", "lower_s", "backend_s")
            }
            if watch != OTHER:
                gauges[f"engine.compile.seconds.{watch}"] = _row_seconds(
                    rows[0]
                )
                gauges[f"engine.compile.variants.{watch}"] = rows[0][
                    "variants"
                ]
            variant = rows[0][_COUNT_OF[stage]]
        reg = global_metrics()
        for k, v in gauges.items():
            reg.set_gauge(k, float(v))
        tracer = global_tracer()
        if tracer.enabled:
            attrs = {"program": program, "watch": watch, "variant": variant}
            if stage == "backend":
                attrs["cache"] = cache
            # JAX read the epoch clock; the ring is on the perf clock.
            tracer.add_span(
                f"engine.compile.{stage}", start=start - _EPOCH_OFFSET,
                end=end - _EPOCH_OFFSET, **attrs,
            )

    def _other_row(self, program: str) -> dict:
        """The named row of a program no watch names; past
        ``_OTHER_KEPT`` names the smallest folds into ``(rest)``.
        Caller holds the lock."""
        row = self._other.get(program)
        if row is None:
            if len(self._other) >= _OTHER_KEPT:
                small = min(
                    (n for n in self._other if n != "(rest)"),
                    key=lambda n: _row_seconds(self._other[n]),
                )
                _add_row(
                    self._other.setdefault("(rest)", _new_row()),
                    self._other.pop(small),
                )
            row = self._other[program] = _new_row()
        return row

    def account(self) -> dict:
        """The compile account, read only (nothing is lowered, traced
        or compiled to answer): ``{"totals": {programs, trace_s,
        lower_s, backend_s, cache_hits, cache_misses, cache_load_s,
        cache_saved_s}, "programs": {watch | "other": row}, "other":
        {function name: row}}`` with ``row = {trace_s, lower_s,
        backend_s, traces, lowerings, variants, cache_hits,
        cache_misses, last}``. ``other``
        holds the ``_OTHER_SHOWN`` largest names by seconds, largest
        first, and ``(rest)`` for all the others."""
        with self._lock:
            named = sorted(
                (n for n in self._other if n != "(rest)"),
                key=lambda n: -_row_seconds(self._other[n]),
            )
            other = {
                n: _copy_row(self._other[n]) for n in named[:_OTHER_SHOWN]
            }
            rest = _new_row()
            for n in named[_OTHER_SHOWN:] + ["(rest)"]:
                if n in self._other:
                    _add_row(rest, self._other[n])
            if rest["traces"] or rest["lowerings"] or rest["variants"]:
                other["(rest)"] = rest
            return {
                "totals": dict(self._totals),
                "programs": {
                    n: _copy_row(r) for n, r in self._rows.items()
                },
                "other": other,
            }

    def sample(
        self,
        registry: MetricsRegistry | None = None,
        *,
        write_gauges: bool = True,
    ) -> int:
        """One sentinel pass over every watched program. Returns the
        number of unexpected-recompile events fired. ``registry``
        defaults to the process-global one (the exporter passes the
        registry actually being scraped). ``write_gauges=False`` is the
        hot tick path's detection-only mode: it skips the per-program
        gauge writes and tombstone cleanup (one registry-lock acquire
        each), which every scrape refreshes anyway via
        :func:`engine_collector` — detection, the event counter sync
        and the flight/log/tracer side effects still run."""
        reg = registry if registry is not None else global_metrics()
        # (name, size, delta, what the watch's newest program cost)
        fired: list[tuple[str, int, int, dict]] = []
        sizes: list[tuple[str, int]] = []
        dead: list[str] = []
        with self._lock:
            for name, w in self._watches.items():
                try:
                    raw = w.size_fn()
                except Exception:  # noqa: BLE001 — a sick probe is skipped
                    continue
                if raw is None:  # owner retired: prune the watch
                    dead.append(name)
                    continue
                size = int(raw)
                sizes.append((name, size))
                # Warmup advances only while the program is ACTIVE
                # (compiled at least once): idle-process scrapes must
                # not burn the grace window before the first request.
                warmed = w.samples >= self.warmup_samples
                if size > 0:
                    w.samples += 1
                if w.last is None or size == w.last:
                    w.last = size
                    continue
                delta = size - w.last
                w.last = size
                if delta > 0 and warmed and w.expected > 0:
                    # Planned re-lowering (rearm): absorb the expected
                    # executables; only the excess can fire. Warmup-
                    # covered growth is already silent and must NOT
                    # spend the allowance — the planned compile it was
                    # banked for may land later, post-warmup.
                    absorbed = min(delta, w.expected)
                    w.expected -= absorbed
                    delta -= absorbed
                if delta > 0 and warmed:
                    row = self._rows.get(name)
                    cost = dict(row["last"]) if row else {}
                    fired.append((name, size, delta, cost))
                    self._events += delta
            for name in dead:
                del self._watches[name]
            self._pruned.update(dead)
            tombstones = list(self._pruned)
            # Sync this registry's counter to the cumulative event
            # count: detection is sentinel-global, so a registry that
            # was not the one sampling when an event fired still
            # converges to the same engine.compile_events total.
            behind = self._events - self._synced.get(reg, 0)
            if behind > 0:
                self._synced[reg] = self._events
        # Registry / recorder / tracer writes happen outside the
        # sentinel lock (each has its own locking; no nesting). Gauges
        # are written unconditionally: a registry that samples less
        # often than the ticking one must still serve current values.
        if behind > 0:
            reg.inc("engine.compile_events", float(behind))
        if write_gauges:
            for name, size in sizes:
                reg.set_gauge(f"engine.compiles.{name}", float(size))
            for name in tombstones:
                # A retired program must not scrape as still-compiled.
                reg.remove_gauge(f"engine.compiles.{name}")
        tracer = global_tracer()
        for name, size, delta, cost in fired:
            global_flight_recorder().record(
                "recompile", program=name, compiles=size, new=delta, **cost
            )
            log.warning(
                "unexpected recompile %s",
                kv(program=name, compiles=size, new=delta, **cost),
            )
            if tracer.enabled:
                tracer.instant("engine.recompile", program=name, new=delta)
        return len(fired)


def snapshot_weak(owners) -> list:
    """Snapshot a WeakSet that another thread may be ``add()``-ing to:
    WeakSet iteration is Python-level, so even ``list(owners)`` can
    raise ``RuntimeError: Set changed size during iteration`` when a
    constructor registers concurrently with an exporter scrape.
    Bounded retries; a PERSISTENT race re-raises — callers in sentinel
    size_fns deliberately let it escape, because the sentinel skips a
    watch whose probe raises (sample untouched, retried next pass),
    whereas returning an empty/zero snapshot would be misread as "no
    owners" (pruning a live watch) or "cache size 0" (arming a false
    recompile event on recovery)."""
    last_err = None
    for _ in range(4):
        try:
            return list(owners)
        except RuntimeError as e:
            last_err = e
    raise last_err


def aggregate_size_fn(owners, extract: Callable) -> Callable:
    """Build a sentinel ``size_fn`` that SUMS a per-owner cache size
    over a weakly-held owner collection (one shared watch per program
    name — a second live instance aggregates instead of silently
    replacing the first's watch, and a collected owner drops out).

    ``extract(owner) -> int | None`` returns the owner's cache size for
    the watched program, or None when the owner does not carry it
    (e.g. a pipeline with fewer stages). When NO live owner matches,
    the size_fn returns None and the sentinel prunes the watch."""

    def size_fn():
        sizes = [
            s
            for s in (extract(o) for o in snapshot_weak(owners))
            if s is not None
        ]
        if not sizes:
            return None
        return sum(sizes)

    return size_fn


_SENTINEL = CompileSentinel()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def global_compile_sentinel() -> CompileSentinel:
    """The process's sentinel. The first call hands its compile
    account to ``jax.monitoring``: one listener of each kind a
    process, however many batchers are built. The lists are shared
    (the benchmark's ``CompileCounter`` hangs on one), so nothing here
    ever clears them."""
    global _LISTENING
    if not _LISTENING:
        with _LISTEN_LOCK:
            first, _LISTENING = not _LISTENING, True
        if first:
            monitoring.register_scalar_listener(_SENTINEL._on_stage_open)
            monitoring.register_event_time_span_listener(
                _SENTINEL._on_stage_close
            )
            monitoring.register_event_duration_secs_listener(
                _SENTINEL._on_cache_seconds
            )
            monitoring.register_event_listener(_SENTINEL._on_cache_event)
    return _SENTINEL


# -- memory accounting ------------------------------------------------------


def device_local_nbytes(x) -> int:
    """PER-DEVICE bytes of one (possibly sharded) array: the shard
    shape's bytes, i.e. global nbytes divided by the mesh factors on
    every sharded axis. This is the number that matters for HBM
    capacity planning under tensor parallelism — a tp-sharded KV cache's
    ``nbytes`` is the LOGICAL size, which would read as if the whole
    cache lived on one chip. Plain numpy / unsharded arrays just return
    ``nbytes``."""
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return int(x.nbytes)
    try:
        shard = sharding.shard_shape(x.shape)
    except Exception:  # noqa: BLE001 — exotic shardings: logical bytes
        return int(x.nbytes)
    return int(math.prod(shard)) * x.dtype.itemsize

#: Weakly-held memory sources: (label, id) -> object exposing
#: ``_memory_stats() -> {metric_name: value}``. Weak values: a retired
#: batcher (and its device arrays) must never be pinned by telemetry.
_MEMORY_SOURCES: "weakref.WeakValueDictionary[tuple[str, int], object]" = (
    weakref.WeakValueDictionary()
)
_MEMORY_LOCK = threading.Lock()
#: Per-registry set of memory gauge names the collector wrote on its
#: previous pass: names that stop being produced (their sources
#: retired — e.g. a closed paged batcher's pool gauges) are REMOVED
#: from that registry instead of serving their last value forever.
_MEMORY_WRITTEN: "weakref.WeakKeyDictionary[MetricsRegistry, set]" = (
    weakref.WeakKeyDictionary()
)


def register_memory_source(label: str, obj) -> None:
    """Register ``obj`` (anything with ``_memory_stats() -> dict``) as a
    pull-style memory source. Held by weakref; keyed by ``(label,
    id(obj))`` so several batchers coexist and gauges SUM across the
    live ones. NOTE: a source whose own jit caches pin it (a batcher —
    ``static_argnums=(0,)`` holds ``self`` strongly) is never collected
    by GC, so retiring such a component must call
    :func:`unregister_memory_source` (``ContinuousBatcher.close``
    does), or the replaced instance keeps summing into the gauges."""
    if not hasattr(obj, "_memory_stats"):
        raise TypeError(f"{label}: source must expose _memory_stats()")
    with _MEMORY_LOCK:
        _MEMORY_SOURCES[(label, id(obj))] = obj


def unregister_memory_source(label: str, obj) -> None:
    """Drop ``obj`` from the gauge sums (idempotent). For components
    whose jit caches pin them alive — explicit retirement is the only
    way their bytes leave the gauges."""
    with _MEMORY_LOCK:
        _MEMORY_SOURCES.pop((label, id(obj)), None)


def _device_memory_stats() -> dict[str, float]:
    """``memory.hbm_*`` from the backend, summed over EVERY local
    device (one process drives all the chips of a host — reading chip 0
    alone would hide three quarters of a four-chip host), when the
    backend reports them (TPU/GPU backends do; CPU returns None/raises
    — then nothing is exported, rather than a lying zero)."""
    out: dict[str, float] = {}
    try:
        import jax

        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            for key in ("bytes_in_use", "bytes_limit"):
                if key in stats:
                    name = f"memory.hbm_{key}"
                    out[name] = out.get(name, 0.0) + float(stats[key])
    except Exception:  # noqa: BLE001 — no backend / no stats: no gauges
        return {}
    return out


def engine_collector(reg: MetricsRegistry) -> None:
    """The engine-tier pull hook (``register_collector`` style, like the
    codec copy-stats bridge): runs at every snapshot/scrape. Sums each
    registered memory source's ``_memory_stats()`` into gauges, adds
    backend HBM stats when available, and runs one compile-sentinel
    sample so a scrape sees fresh ``engine.compiles.*`` gauges even
    between ticks."""
    totals: dict[str, float] = {}
    with _MEMORY_LOCK:
        sources = list(_MEMORY_SOURCES.values())
    for obj in sources:
        try:
            stats = obj._memory_stats()
        except Exception:  # noqa: BLE001 — one sick source must not kill scrape
            continue
        for k, v in stats.items():
            totals[k] = totals.get(k, 0.0) + float(v)
    totals.update(_device_memory_stats())
    # Roofline gauges ride the same write/stale-cleanup pass: a
    # retired batcher's engine.flops.*/mbu/mfu entries disappear with
    # its memory gauges instead of scraping stale forever.
    totals.update(_roofline_gauges())
    # Kernel-vs-oracle dispatch gauges (ops/dispatch books every
    # dispatcher resolution at trace time), so a route to the XLA
    # oracle is never a perf cliff invisible in metrics. 1.0 = the
    # op's most recent lowering took the Pallas kernel, 0.0 = the
    # oracle; the per-path lifetime counts ride along so a mixed
    # history (some programs on each path) is visible too.
    try:
        from adapt_tpu.ops.dispatch import kernel_dispatch_stats

        for op, d in kernel_dispatch_stats().items():
            totals[f"engine.kernel_dispatch.{op}"] = d["last"]
            totals[f"engine.kernel_dispatch.{op}.pallas_total"] = (
                d["pallas"]
            )
            totals[f"engine.kernel_dispatch.{op}.xla_total"] = d["xla"]
    except Exception:  # noqa: BLE001 — never break a scrape
        pass
    for k, v in totals.items():
        reg.set_gauge(k, v)
    # Gauges whose every source retired since the last pass (a closed
    # paged batcher's pool gauges, a vanished draft cache) are removed,
    # not served stale forever.
    with _MEMORY_LOCK:
        stale = _MEMORY_WRITTEN.get(reg, set()) - set(totals)
        _MEMORY_WRITTEN[reg] = set(totals)
    for k in stale:
        reg.remove_gauge(k)
    _SENTINEL.sample(reg)


# Pull-side default: the process registry scrapes engine state without
# any component having to push (the exporter re-registers this on
# whichever registry it actually serves; register_collector is
# idempotent per function object).
global_metrics().register_collector(engine_collector)
# The compile account starts with the process, not with the first
# batcher: a deployment draws its weights (and the benchmark runs its
# builder) before one exists, and set-up pays those programs too.
global_compile_sentinel()


# -- roofline accounting ----------------------------------------------------

#: Peak (FLOP/s, HBM bytes/s) per device KIND (``device.device_kind``,
#: lowercased) — the denominators of MFU/MBU. Rows are the published
#: per-chip bf16 peak FLOP/s and HBM bandwidth (Google Cloud TPU
#: documentation): v4 275 TF / 1.23 TB/s, v5e 197 TF / 819 GB/s
#: (mirrored by ``benchmarks/tpu_models.py``), v5p 459 TF / 2.77 TB/s,
#: v6e (Trillium) 918 TF / 1.64 TB/s. A kind that is not listed (and
#: CPU!) gets NO mfu/mbu gauges — flops and bytes export alone, because
#: dividing by another chip's peak would manufacture a utilization
#: number; ``bench.py`` treats an unlisted kind as an error.
ROOFLINE_PEAKS: dict[str, tuple[float, float]] = {
    "tpu v4": (275e12, 1.2288e12),
    "tpu v5e": (197e12, 8.19e11),
    "tpu v5 lite": (197e12, 8.19e11),
    "tpu v5p": (459e12, 2.765e12),
    "tpu v5": (459e12, 2.765e12),
    "tpu v6e": (918e12, 1.64e12),
    "tpu v6 lite": (918e12, 1.64e12),
}


def roofline_peaks() -> tuple[float, float] | None:
    """(peak FLOP/s, peak bytes/s) for the current backend, or None
    when no honest peak is known. Resolution order: the
    ``ADAPT_TPU_PEAK_FLOPS`` / ``ADAPT_TPU_PEAK_BYTES_S`` env vars
    override everything (set BOTH — the knob for unlisted hardware,
    and what lets tests exercise the mfu/mbu math on the CPU backend
    with explicit, visible peaks); otherwise the device KIND row
    (``jax.local_devices()[0].device_kind``, lowercased — the chips of
    one host are one kind). An unlisted kind is None, like the CPU.
    Catalog: ``docs/OBSERVABILITY.md`` "Roofline gauges"."""
    env_f = os.environ.get("ADAPT_TPU_PEAK_FLOPS")
    env_b = os.environ.get("ADAPT_TPU_PEAK_BYTES_S")
    if env_f and env_b:
        try:
            return (float(env_f), float(env_b))
        except ValueError:
            return None
    try:
        import jax

        kind = str(jax.local_devices()[0].device_kind or "").lower()
    except Exception:  # noqa: BLE001 — no backend: no claims
        return None
    return ROOFLINE_PEAKS.get(kind)


#: Weakly-held roofline sources: (label, id) -> object exposing
#: ``_roofline_stats() -> {program: {"flops": F, "bytes": B,
#: "wall_s": seconds-per-execution | None}}``. Same lifetime rules as
#: the memory sources (a batcher's jit caches pin it — retire via
#: :func:`unregister_roofline_source`, ``ContinuousBatcher.close``
#: does).
_ROOFLINE_SOURCES: "weakref.WeakValueDictionary[tuple[str, int], object]" = (
    weakref.WeakValueDictionary()
)


def register_roofline_source(label: str, obj) -> None:
    """Register ``obj`` (anything with ``_roofline_stats() -> dict``)
    as a pull-style roofline source (weakref; several sources coexist,
    later-registered same-program entries win)."""
    if not hasattr(obj, "_roofline_stats"):
        raise TypeError(f"{label}: source must expose _roofline_stats()")
    with _MEMORY_LOCK:
        _ROOFLINE_SOURCES[(label, id(obj))] = obj


def unregister_roofline_source(label: str, obj) -> None:
    """Drop ``obj`` from the roofline gauges (idempotent)."""
    with _MEMORY_LOCK:
        _ROOFLINE_SOURCES.pop((label, id(obj)), None)


def program_cost_analysis(jit_fn, *args, **kwargs) -> dict[str, float]:
    """``{"flops": F, "bytes": B}`` for ONE execution of ``jit_fn`` at
    the given arguments, from XLA's own ``cost_analysis()`` on the
    LOWERED module — no compile, no execution, and crucially no growth
    of the jit's executable cache (sentinel-checked in tests: pulling
    roofline numbers must never itself read as a recompile). Arguments
    may be real arrays or ``jax.ShapeDtypeStruct``s — only shapes and
    dtypes matter. Raises on backends whose lowering or analysis is
    unavailable; callers cache and degrade."""
    ca = jit_fn.lower(*args, **kwargs).cost_analysis()
    if isinstance(ca, list):  # some backends return one dict per device
        ca = ca[0] if ca else {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def _roofline_gauges() -> dict[str, float]:
    """Compute the roofline gauge family from the registered sources:
    per-program flops/bytes always; per-program + headline MFU/MBU only
    when the platform peak is known AND the program has a measured wall
    time (``EngineObs`` phase timing — enable ``obs_engine`` to get
    utilization numbers)."""
    with _MEMORY_LOCK:
        sources = list(_ROOFLINE_SOURCES.values())
    out: dict[str, float] = {}
    peaks = roofline_peaks()
    best_bytes = -1.0
    for obj in sources:
        try:
            stats = obj._roofline_stats()
        except Exception:  # noqa: BLE001 — a sick source must not kill scrape
            continue
        for prog, st in stats.items():
            flops = float(st.get("flops", 0.0))
            nbytes = float(st.get("bytes", 0.0))
            out[f"engine.flops.{prog}"] = flops
            out[f"engine.bytes_accessed.{prog}"] = nbytes
            wall = st.get("wall_s")
            if peaks is None or not wall:
                continue
            peak_f, peak_b = peaks
            mfu = flops / wall / peak_f
            mbu = nbytes / wall / peak_b
            out[f"engine.mfu.{prog}"] = mfu
            out[f"engine.mbu.{prog}"] = mbu
            if nbytes > best_bytes:
                # Headline = the byte-heaviest program: its stream is
                # what the decode roofline is made of.
                best_bytes = nbytes
                out["engine.mfu"] = mfu
                out["engine.mbu"] = mbu
    return out


# -- tick-phase timing ------------------------------------------------------


class _Region:
    """An enabled :meth:`EngineObs.region`: the profiler annotation plus
    the :meth:`EngineObs.phase` record on exit."""

    __slots__ = ("_eo", "_name", "_ann", "_span", "_attrs", "_t0")

    def __init__(self, eo, name, ann, span, attrs):
        self._eo, self._name, self._ann = eo, name, ann
        self._span, self._attrs = span, attrs

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._eo.phase(
            self._name, self._t0, span=self._span, **self._attrs
        )


class EngineObs:
    """Process-global gate for per-phase engine timing.

    A phase site is ``with eo.region(name):``. It always enters a
    ``jax.profiler.TraceAnnotation("engine.<name>")`` — free without a
    profiler session, and under one (``Tracer.device_trace``) the span
    lands in ``/host:CPU`` on the clock of the device's ``XLA Ops``.
    ``enabled`` is the one branch every site pays beyond that (the
    ``obs_timeline`` pattern). On, the region also records one
    ``engine.phase.<name>_s`` histogram sample (one registry-lock hold)
    and, when the global tracer is enabled, an ``engine.<name>`` span —
    so tick phases land in the same Perfetto timeline as the request
    spans. Enable via ``ObservabilityConfig(obs_engine=True)`` (applied
    when a Dispatcher is constructed) or directly:
    ``global_engine_obs().enabled = True``."""

    __slots__ = ("enabled", "last_s")

    def __init__(self):
        self.enabled = False
        #: Most recent wall seconds per phase name — the per-execution
        #: denominator the roofline gauges divide flops/bytes by (a
        #: dict write per phase sample; no lock: single writer per
        #: phase, readers tolerate one-sample staleness).
        self.last_s: dict[str, float] = {}

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def region(self, name: str, *, span: bool = True, **attrs):
        """Context manager around phase ``name`` on one thread: the
        profiler annotation always, and when ``enabled`` (read once, at
        entry, so a mid-region toggle cannot pair a missing open with a
        close) what :meth:`phase` records. ``attrs`` become the
        annotation's event stats and the ring span's args.
        ``span=False`` for sites that already record their own tracer
        span (``batcher.prefill_chunk``, ``decode.draft``)."""
        ann = TraceAnnotation("engine." + name, **attrs)
        if not self.enabled:
            return ann
        return _Region(self, name, ann, span, attrs)

    def phase(
        self, name: str, t0: float, *, span: bool = True, **attrs
    ) -> float:
        """Close phase ``name`` opened at ``t0``; returns the close time
        (the next phase's open). For the tick's cross-half stamps
        (``decode`` / ``verify`` / ``dispatch`` / ``commit_lag``): they
        open in the dispatch half and close in the commit half, one
        ``tick()`` call later, so they overlap
        the other phases, cannot nest and cannot be profiler
        annotations. Every same-thread site of the tick uses
        :meth:`region`; ``LocalPipeline``'s stage/hop stay on this form
        until the stage tier has a cell that reads them. ``span=False``
        for sites that already record their own tracer span."""
        t1 = time.perf_counter()
        self.last_s[name] = t1 - t0
        global_metrics().observe(f"engine.phase.{name}_s", t1 - t0)
        if span:
            tracer = global_tracer()
            if tracer.enabled:
                tracer.add_span(
                    f"engine.{name}", start=t0, end=t1, **attrs
                )
        return t1


_ENGINE_OBS = EngineObs()


def global_engine_obs() -> EngineObs:
    return _ENGINE_OBS
