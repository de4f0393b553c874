"""One LM cell, start to finish, in one process: weights from the
seed by the builder the configuration file names, the program's
``ContinuousBatcher`` built from the file's serving block, a
correctness sample against the plain reference the file names, warm-up
of every shape the traffic sends, the standing population, then the
measured window. What the engine knows of the architecture is the
builder's ``shape`` (``builders.py``) and nothing else.

The program is driven through ``submit`` + ``tick`` from ONE thread
(the load generator and the server share the machine's cores; a second
thread would only add scheduling noise). Every number comes from
token events stamped in the ``on_token`` callback.
"""

from __future__ import annotations

import gc
import time
from typing import NamedTuple

import numpy as np

from chipbench import manifest as mf
from chipbench import stall
from chipbench import traffic as tg
from chipbench import window as win
from chipbench import xtrace

#: Seconds of the window that a ``--trace 1`` run records (its end).
TRACE_SECONDS = 8.0


class Phases:
    """Where set-up went: printed on a line of its own."""

    def __init__(self, clock0: float):
        self.t = clock0
        self.parts: list[tuple[str, float]] = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.parts.append((name, now - self.t))
        self.t = now

    def line(self) -> str:
        return "setup: " + "  ".join(f"{n} {s:.1f}s" for n, s in self.parts)


class CompileCounter:
    """Backend compiles (persistent-cache loads included) as
    ``jax.monitoring`` reports them: every jitted program of the
    process, registered with the program's ``CompileSentinel`` or
    not. One in the window makes the run incorrect."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds


class Driver:
    """Submits requests, ticks the server, and keeps the books the
    metrics are read from."""

    def __init__(self, srv, vocab: int, seed: int, annotate):
        self.srv = srv
        self.vocab = vocab
        self.seed = seed
        self.annotate = annotate
        self.events: list[tuple[float, int, int]] = []
        self.reqs: dict[int, dict] = {}
        self.live: dict[int, dict] = {}
        self.ticks: list[tuple[float, float, int, int]] = []
        #: Beside ``ticks``, index for index: the live rows' contexts
        #: (a window layer's bytes floor needs each, not their sum).
        self.tick_contexts: list[tuple[int, ...]] = []
        self.finished: list[dict] = []
        self.submitted = 0
        self.failed = 0
        #: Per-tick maximum of every ``stats()`` key that starts with
        #: ``pages_in_use`` (a pool in layer groups has one a group).
        self.pool_peaks: dict[str, int] = {}
        self.sample_pool = False
        #: Closed loop: the seed-permuted template walk the callers
        #: draw their next request from (None: open loop).
        self.stream = None
        self._refilled = 0
        #: Told when a tick starts and ends (``stall.StallWatch``).
        self.watch = None

    def submit(self, req: tg.Request, due: float, client=None):
        ids = tg.token_ids(self.seed, self.submitted, req.prompt_len,
                           self.vocab)
        self.submitted += 1
        t = time.perf_counter()
        try:
            with self.annotate("chipbench.submit"):
                rid = self.srv.submit(ids, req.out_len, on_token=self._token)
        except Exception as e:  # noqa: BLE001 — refused counts as failed
            print(f"submit refused: {type(e).__name__}: {e}", flush=True)
            self.failed += 1
            return None
        info = dict(
            rid=rid, prompt_len=req.prompt_len, out_len=req.out_len,
            due=due, t_submit=t, emitted=0, client=client, ids=ids,
            tokens=[],
        )
        self.reqs[rid] = self.live[rid] = info
        return rid

    def _token(self, rid: int, token: int, index: int) -> None:
        t = time.perf_counter()
        self.events.append((t, rid, index))
        info = self.reqs[rid]
        info["emitted"] = index + 1
        info["tokens"].append(token)
        if not 0 <= token < self.vocab:
            info["bad"] = True
        if index + 1 >= info["out_len"]:
            info["t_done"] = t
            self.live.pop(rid, None)
            self.finished.append(info)

    def refill(self) -> None:
        """Closed loop: every caller whose request completed sends its
        next one, due at the instant the last one completed."""
        if self.stream is None:
            return
        done, self._refilled = (
            self.finished[self._refilled:], len(self.finished)
        )
        for info in done:
            if info["client"] is not None:
                self.submit(tg.Request(*next(self.stream)),
                            info["t_done"], client=info["client"])

    def tick(self) -> None:
        contexts = tuple(
            r["prompt_len"] + r["emitted"]
            for r in self.live.values() if r["emitted"]
        )
        t0 = time.perf_counter()
        if self.watch is not None:
            self.watch.tick_t0 = t0
        with self.annotate("chipbench.tick"):
            n = self.srv.tick()
        self.ticks.append((t0, time.perf_counter(), n, sum(contexts)))
        self.tick_contexts.append(contexts)
        if self.watch is not None:
            self.watch.tick_t0 = None
        if self.sample_pool:
            for key, pages in self.srv.stats().items():
                if key.startswith("pages_in_use"):
                    self.pool_peaks[key] = max(
                        self.pool_peaks.get(key, 0), pages
                    )

    def run_until(self, done, limit_s: float = 600.0) -> None:
        t_end = time.perf_counter() + limit_s
        while not done():
            if time.perf_counter() > t_end:
                raise TimeoutError("set-up phase did not finish")
            self.refill()
            self.tick()


#: Served logprobs compared a sampled request, unless the
#: configuration's ``correct`` block states ``sample_steps``.
SAMPLE_STEPS = 8


def _sample_steps(correct: dict) -> int:
    return int(correct.get("sample_steps", SAMPLE_STEPS))


def _sample_prompts(chunk: int, max_len: int,
                    steps: int = SAMPLE_STEPS) -> list[int]:
    """Prompt lengths of the correctness sample: two whole-prompt
    prefills and one that goes through chunked prefill."""
    return [40, chunk - 17, min(chunk + 45, max_len - steps)]


class Compared(NamedTuple):
    """What ``correctness_sample`` read: every number beside its limit."""

    ok: bool
    worst: float  # largest error among the vouched positions
    tol: float
    vouched: int
    compared: int
    least: int  # vouched positions needed (``correct.min_vouched``)
    kept_out: float | None  # largest error not vouched; None: no mask

    def line(self) -> str:
        mask = "" if self.kept_out is None else (
            f" (at least {self.least}), largest error not vouched "
            f"{self.kept_out:.4f}"
        )
        return (
            f"correctness: served logprobs vs plain reference, max|err| "
            f"{self.worst:.4f} (tolerance {self.tol}), vouched "
            f"{self.vouched} of {self.compared}{mask} -> "
            f"{'ok' if self.ok else 'WRONG'}"
        )


def correctness_sample(drv: Driver, variables, serving: dict, max_len: int,
                       reference, correct: dict, fault: str = "") -> Compared:
    """Three seeded requests served outside the window, one of them
    through chunked prefill, all decoding through the paged kernel;
    their served logprobs (``SAMPLE_STEPS`` each, or the block's
    ``sample_steps``) against those of ``reference`` (the plain
    reference the configuration names), held to the file's ``correct``
    block. ``fault`` goes to the reference, which knows its own tree.

    ONE rule for every architecture. The reference returns the
    ``(b, s - 1)`` logprobs, or a pair ``(logprobs, vouched)`` with a
    bool array of that shape: false where its own float32 pass came
    within its stated margin of another discrete choice (an expert
    near a tie), so that a served model in a lower precision may
    rightly have chosen otherwise there. The number compared is the
    largest error among the vouched positions; with no mask every
    position is vouched. Not correct: that number over
    ``logprob_tol``; fewer than ``min_vouched`` (a share) of the
    compared positions vouched; any value not finite among the served
    logprobs, or in the reference's at a vouched position."""
    import jax.numpy as jnp

    tol = correct["logprob_tol"]
    nan = float("nan")
    nothing = Compared(False, nan, tol, 0, 0, 0, None)  # no comparison made
    steps = _sample_steps(correct)
    lens = _sample_prompts(serving["prefill_chunk"], max_len, steps)
    rids = [
        drv.submit(tg.Request(n, steps), due=time.perf_counter())
        for n in lens
    ]
    if None in rids:
        return nothing
    drv.run_until(lambda: all(r not in drv.live for r in rids))
    width = max(lens) + steps
    ids = np.zeros((len(rids), width), np.int32)
    for row, rid in enumerate(rids):
        info = drv.reqs[rid]
        seq = np.concatenate([info["ids"], np.asarray(info["tokens"])])
        ids[row, : len(seq)] = seq  # causal: the padding is never read
    want = reference(variables, jnp.asarray(ids), fault=fault)
    masked = isinstance(want, tuple)
    if masked:
        if "min_vouched" not in correct:
            raise KeyError(
                "the reference vouches position by position, so the "
                "configuration's `correct` block must state min_vouched"
            )
        want, mask = (np.asarray(a) for a in want)
    else:
        want = np.asarray(want)
        mask = np.ones(want.shape, bool)
    err, sure = [], []
    for row, rid in enumerate(rids):
        n = lens[row]
        got = np.asarray(drv.srv.logprobs(rid), np.float32)
        at = slice(n - 1, n - 1 + steps)
        if got.shape != want[row, at].shape:
            return nothing
        err.append(np.abs(got - want[row, at]))
        # A served value that is not finite is vouched for by nobody's
        # leave: it counts wherever it stands.
        sure.append(mask[row, at] | ~np.isfinite(got))
    err, sure = np.concatenate(err), np.concatenate(sure)
    least = int(np.ceil(correct.get("min_vouched", 0.0) * err.size))
    # NaN-propagating maxima: one value not finite makes the number
    # compared not finite, and `nan <= tol` is false.
    worst = float(np.max(err[sure])) if sure.any() else nan
    kept_out = None
    if masked:
        kept_out = float(np.max(err[~sure])) if not sure.all() else 0.0
    ok = bool(worst <= tol and sure.sum() >= least)
    return Compared(ok, worst, tol, int(sure.sum()), err.size, least, kept_out)


def warm_up(drv: Driver, pairs) -> int:
    """Send every distinct (prompt, output) shape the window will send,
    to its first token, then cancel it. Empirical on purpose: the
    benchmark does not mirror the program's bucketing rules, so a
    change to them cannot leave a shape cold."""
    slots = len(drv.srv.slots)
    todo = sorted(set(pairs))
    for i in range(0, len(todo), slots):
        rids = [
            drv.submit(tg.Request(p, o), due=time.perf_counter())
            for p, o in todo[i: i + slots]
        ]
        rids = [r for r in rids if r is not None]
        drv.run_until(
            lambda: all(drv.reqs[r]["emitted"] for r in rids)
        )
        for r in rids:
            drv.srv.cancel(r)
            drv.live.pop(r, None)
        drv.run_until(lambda: drv.srv.stats()["active"] == 0)
    return len(todo)


def measure(drv: Driver, traffic: dict, pairs, seconds: float, seed: int,
            trace_dir: str | None):
    """The measured window. Opens right after a tick has committed and
    closes right after the first tick that ends past ``seconds``: both
    edges sit on commit instants, so a rate counts whole ticks over
    exactly the time they took. A traced pass reads ``t0`` and ``t1``
    inside a span each, which puts both instants on the profiler's
    clock (``xtrace.device_window`` cuts the device's busy time at
    them); an untraced pass reaches neither."""
    import jax

    arrivals = (
        [] if traffic["loop"] == "closed"
        else tg.open_schedule(traffic, pairs, seed, seconds + 1.0)
    )
    late_ms: list[float] = []
    trace = dict(on=False, t0=0.0, t1=0.0, prefill0=0, prefill1=0)
    t_trace = seconds - min(seconds, TRACE_SECONDS)
    i = 0
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t_open >= seconds:
            break
        if trace_dir and not trace["on"] and now - t_open >= t_trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with drv.annotate(xtrace.WINDOW_OPEN):
                trace.update(
                    on=True, t0=time.perf_counter(),
                    prefill0=drv.srv.stats()["prefill_tokens"],
                )
        while i < len(arrivals) and t_open + arrivals[i].due_s <= now:
            due = t_open + arrivals[i].due_s
            drv.submit(arrivals[i], due)
            late_ms.append((time.perf_counter() - due) * 1e3)
            i += 1
        drv.refill()
        if drv.live:
            drv.tick()
        else:
            time.sleep(0.001)  # idle server: wait for the next arrival
    t_close = time.perf_counter()
    if trace["on"]:
        with drv.annotate(xtrace.WINDOW_CLOSE):
            trace.update(
                t1=t_close, prefill1=drv.srv.stats()["prefill_tokens"]
            )
        jax.profiler.stop_trace()
    return dict(t_open=t_open, t_close=t_close, late_ms=late_ms, trace=trace)


def _mute(*_, **__):
    pass


def pool_pages(serving: dict, pairs, max_len: int,
               steps: int = SAMPLE_STEPS) -> int:
    """The pool rule: every slot can hold the longest request this
    traffic sends (or the correctness sample's, in set-up), plus the
    trash page, and not a page more. No request ever waits on pages,
    and no page is reserved that the deployment's own traffic could
    not fill. The prompt buckets are multiples of the page size, so a
    request's reservation, max(bucket, prompt + answer), rounds to the
    same pages as prompt + answer."""
    longest = max(
        max(p + o for p, o in pairs), serving["prompt_buckets"][0],
        max(_sample_prompts(serving["prefill_chunk"], max_len, steps))
        + steps,
    )
    return serving["slots"] * -(-longest // serving["page_size"]) + 1


def sweep(drv: Driver, traffic: dict, pairs, opts) -> None:
    """Find the knee once, when the cell is defined: one window per
    rate in one process, the server's state carried from one to the
    next. A rate is sustained while nothing is queued at the close and
    the requests in flight do not grow from window to window."""
    for k, rate in enumerate(opts.sweep):
        m = measure(drv, {**traffic, "rate_per_s": rate}, pairs,
                    opts.seconds, opts.seed + k, None)
        t0, t1 = m["t_open"], m["t_close"]
        due = {rid: info["due"] for rid, info in drv.reqs.items()}
        ttft = win.first_token_ms(drv.events, due, t0, t1)
        gaps = win.token_gaps_ms(drv.events, t0, t1)
        print(
            f"sweep rate {rate:.2f}/s: in flight {len(drv.live)} queued "
            f"{drv.srv.stats()['queued']} ttft p50 "
            f"{win.percentile(ttft, 50):.0f} p95 "
            f"{win.percentile(ttft, 95):.0f} itl p50 "
            f"{win.percentile(gaps, 50):.1f} p95 "
            f"{win.percentile(gaps, 95):.1f} tok/s "
            f"{win.tokens_in_window(drv.events, t0, t1) / (t1 - t0):.1f} "
            f"late p99 {win.percentile(m['late_ms'], 99):.1f}",
            flush=True,
        )
    drv.srv.close()
    return None


def run_cell(cell: dict, config: dict, traffic: dict, opts) -> dict:
    """Engine entry point: ``opts`` has seed, seconds, trace (bool),
    trace_dir, rehearse (bool), clock0 and annotate."""
    import jax

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from adapt_tpu.utils.metrics import global_metrics

    phases = Phases(opts.clock0)
    phases.mark("imports")
    correct = config.get("correct", {})
    if correct.get("logprob_tol") is None:
        raise KeyError(
            f"configuration {config.get('name')!r} states no "
            "correct.logprob_tol: the tolerance belongs to the architecture "
            "and its precision, so the file gives it, with its reason"
        )
    reference = mf.part_of(config, "reference")
    model = dict(config["model"])
    # The deployment: the configuration's serving block, then what the
    # traffic mix says of it (a mix is served by as many slots as its
    # callers need), then the rehearsal's tiny sizes.
    serving = {**config["serving"], **traffic.get("serving", {})}
    if opts.rehearse:
        model.update(config["rehearse"]["model"])
        serving.update(config["rehearse"]["serving"])
    compiles = CompileCounter()
    lm, variables, shape = mf.part_of(config, "builder")(
        model, config["dtype"], opts.seed
    )
    phases.mark("weights")
    max_total = min(shape["max_len"], serving["prompt_buckets"][-1])
    pairs = tg.templates(traffic, max_total)
    serving["pool_pages"] = pool_pages(
        serving, pairs, shape["max_len"], _sample_steps(correct)
    )
    itemsize = jax.numpy.dtype(config["dtype"]).itemsize
    srv = ContinuousBatcher(
        lm, variables,
        slots=serving["slots"], chunk=serving["chunk"],
        kv_layout=serving["kv_layout"], page_size=serving["page_size"],
        pool_pages=serving["pool_pages"],
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    phases.mark("batcher")
    drv = Driver(srv, shape["vocab"], opts.seed, opts.annotate)
    clients = traffic.get("clients")
    if clients == "slots":
        clients = serving["slots"]
    closed = traffic["loop"] == "closed"
    n_standing = clients if closed else traffic["standing"]["count"]
    n_standing = min(n_standing, serving["slots"])
    standing = tg.standing_population(pairs, n_standing)

    compared = correctness_sample(
        drv, variables, serving, shape["max_len"], reference, correct,
        opts.fault,
    )
    print(compared.line(), flush=True)
    phases.mark("correctness")
    n_shapes = warm_up(drv, pairs)
    phases.mark(f"warm-up({n_shapes} shapes)")
    setup_submitted = drv.submitted
    setup_failed = drv.failed

    if closed:
        drv.stream = tg.template_stream(pairs, opts.seed)
    t_admit = time.perf_counter()
    st_rids = [
        drv.submit(r, t_admit, client=(k if closed else None))
        for k, r in enumerate(standing)
    ]
    st_rids = [r for r in st_rids if r is not None]
    drv.run_until(lambda: all(drv.reqs[r]["emitted"] for r in st_rids))
    phases.mark(f"standing({len(st_rids)})")
    drv.sample_pool = opts.trace
    compiles_before = compiles.count
    snap = global_metrics().snapshot(window=True)
    setup_s = time.perf_counter() - opts.clock0
    # The pool as the batcher holds it (its memory gauge, summed over
    # the pool's own arrays), not an arithmetic of the architecture's:
    # a grouped, latent or windowed cache has other bytes to a page.
    pool_bytes = int(snap["gauges"].get("memory.pool_bytes", 0))
    print(
        f"deployment: slots {serving['slots']}  pool {serving['pool_pages']}"
        f" pages = {pool_bytes} B  weights "
        f"{sum(x.nbytes for x in jax.tree.leaves(variables))} B",
        flush=True,
    )
    say = _mute if opts.rehearse else print  # a CPU wall is no result
    say(phases.line() + f"  | compiles {compiles.count} "
        f"({compiles.seconds:.1f}s in backend compile or cache load)",
        flush=True)
    if opts.sweep:
        return sweep(drv, traffic, pairs, opts)

    gc_before = [g["collections"] for g in gc.get_stats()]
    drv.watch = stall.StallWatch()
    drv.watch.start()
    m = measure(drv, traffic, pairs, opts.seconds, opts.seed,
                opts.trace_dir if opts.trace else None)
    drv.watch.stop()
    gc_runs = [
        g["collections"] - n for g, n in zip(gc.get_stats(), gc_before)
    ]
    hist = global_metrics().snapshot(since=snap, reservoirs=True)
    compiled_in_window = compiles.count - compiles_before
    stats = srv.stats()
    srv.close()

    t_open, t_close = m["t_open"], m["t_close"]
    length = t_close - t_open
    gaps = win.token_gaps_ms(drv.events, t_open, t_close)
    due = {rid: info["due"] for rid, info in drv.reqs.items()}
    ttft = win.first_token_ms(drv.events, due, t_open, t_close)
    tokens = win.tokens_in_window(drv.events, t_open, t_close)
    say(win.histogram_line("ttft_ms", ttft), flush=True)
    say(win.histogram_line("itl_ms", gaps), flush=True)
    say(win.histogram_line("generator_late_ms", m["late_ms"]), flush=True)

    wrong = sum(
        1 for info in drv.finished
        if info.get("bad") or info["emitted"] != info["out_len"]
    ) + sum(1 for info in drv.live.values() if info.get("bad"))
    attempted = len(st_rids) + sum(
        1 for info in drv.reqs.values()
        if t_open < info["t_submit"] <= t_close
    )
    failed = (drv.failed - setup_failed) + wrong
    if compiled_in_window:
        print(f"INCORRECT: {compiled_in_window} program(s) compiled inside "
              "the window", flush=True)
    say(
        f"window {length:.3f}s  ticks "
        f"{sum(1 for t in drv.ticks if t_open < t[1] <= t_close)}  tokens "
        f"{tokens}  first tokens {len(ttft)}  finished "
        f"{sum(1 for f in drv.finished if t_open < f['t_done'] <= t_close)}"
        f"  queued at close {stats['queued']}  set-up requests "
        f"{setup_submitted} (failed {setup_failed})",
        flush=True,
    )
    # A far-off run is as a rule ONE tick that stalled for seconds
    # (PERF.md section 7): say how long the longest took and when, and
    # what the interpreter's collector did meanwhile.
    t0, t1 = max(
        (t for t in drv.ticks if t_open < t[1] <= t_close),
        key=lambda t: t[1] - t[0],
    )[:2]
    say(
        f"longest tick {(t1 - t0) * 1e3:.1f} ms at window+{t0 - t_open:.1f}s"
        f"  | gc collections in the window by generation {gc_runs}",
        flush=True,
    )
    for line in drv.watch.lines(t0, t1, t_open):
        say(line, flush=True)
    e2e = {
        "ttft_p50_ms": win.percentile(ttft, 50),
        "itl_p95_ms": win.percentile(gaps, 95),
        "out_tok_per_s": tokens / length,
        "setup_s": setup_s,
    }
    # Every candidate on an earlier line, judged in this cell or not:
    # how a cell's metrics are chosen from its spread runs.
    say("e2e " + "  ".join(
        f"{k} {v:.4f}" for k, v in e2e.items() if v is not None
    ), flush=True)
    t0 = time.perf_counter()
    gc.collect()
    say(f"gc: a full collection of this process takes "
        f"{time.perf_counter() - t0:.3f}s (after the window)", flush=True)
    records = dict(
        events=drv.events, ticks=drv.ticks, t_open=t_open, t_close=t_close,
        gaps_ms=gaps, ttft_ms=ttft, late_ms=m["late_ms"], trace=m["trace"],
        reqs=drv.reqs, tick_contexts=drv.tick_contexts,
        # The window's view of the program's own metrics: histograms
        # of the window's samples, counters as deltas since it opened,
        # gauges as they stood at its close.
        histograms=hist.get("histograms", {}),
        counters=hist.get("counters", {}), gauges=hist.get("gauges", {}),
        stats=stats, pool_peaks=drv.pool_peaks,
        pool_peak_pages=drv.pool_peaks.get("pages_in_use", 0),
        model=model, shape=shape, serving=serving, itemsize=itemsize,
    )
    return dict(
        correct=bool(
            compared.ok and not compiled_in_window and setup_failed == 0
        ),
        attempted=attempted, failed=failed, e2e=e2e, records=records,
        # Each number compared beside its limit: run.py repeats these
        # as the run's last lines on standard error.
        compared=[
            compared.line(),
            f"compiled inside the window: {compiled_in_window} (limit 0)",
            f"set-up requests refused: {setup_failed} (limit 0)",
        ],
    )
