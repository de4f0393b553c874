"""``EngineObs.region``: one site form for three sinks. Under a
profiler session the tick's phases land in the trace as the span tree
the benchmark's readers key on; with ``obs_engine`` on the same sites
record the ``engine.phase.<name>_s`` samples and ring spans; off and
unprofiled a site records nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from adapt_tpu.models.transformer_lm import lm_tiny
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import EngineObs, global_engine_obs
from adapt_tpu.utils.tracing import global_tracer
from chipbench import xtrace

#: child -> the spans it must lie inside (any one of them).
TREE = {
    "engine.admit": ("engine.tick",),
    "engine.prefill": ("engine.tick",),
    "engine.prefill_chunk": ("engine.prefill",),
    "engine.first_token": ("engine.admit", "engine.prefill_chunk"),
    "engine.launch": ("engine.tick",),
    "engine.fetch": ("engine.tick",),
    "engine.commit": ("engine.tick",),
    "engine.update": ("engine.tick",),
}


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=37, max_len=64)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


@pytest.fixture
def obs():
    """The process-global gate and tracer, restored afterwards."""
    eo, tracer = global_engine_obs(), global_tracer()
    was = eo.enabled, tracer.enabled
    yield eo, tracer
    eo.enabled, tracer.enabled = was


def _paged_batcher(lm_setup):
    lm, variables = lm_setup
    return ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=8,
        pool_pages=20, prefill_chunk=8, prompt_buckets=(8, 16, 32),
    )


def _submit_chunked_and_whole(bat):
    """20 tokens: three chunk passes of 8, 8 and 4; 5 tokens: one
    whole-prompt prefill. Eight steps each at ``chunk`` 2: some slot
    decodes in every one of the first five ticks."""
    bat.submit(np.arange(1, 21, dtype=np.int32), 8)
    bat.submit(np.arange(3, 8, dtype=np.int32), 8)


def _samples(name):
    hist = global_metrics().snapshot()["histograms"]
    return hist.get(f"engine.phase.{name}_s", {}).get("count", 0)


def _inside(child, parents):
    return [p for p in parents if p[0] <= child[0] and child[1] <= p[1]]


def test_traced_ticks_hold_the_span_tree(lm_setup, tmp_path):
    bat = _paged_batcher(lm_setup)
    # The operator's one switch; obs_engine and the ring stay off.
    with global_tracer().device_trace(str(tmp_path)):
        _submit_chunked_and_whole(bat)
        for _ in range(5):
            bat.tick()
    bat.drain()  # the fifth tick's commit half: outside the trace
    trace = xtrace.load(xtrace.find_xplane(str(tmp_path)))
    spans = {}
    for s, e, name in trace.host:
        if name.startswith("engine."):
            spans.setdefault(name, []).append((s, e))
    assert set(spans) == set(TREE) | {"engine.tick"}
    assert len(spans["engine.tick"]) == 5
    # The phases a tick always has, once each; a launch in every tick
    # that decoded, and the commit half of the tick before it.
    for name in ("engine.admit", "engine.prefill", "engine.launch"):
        assert len(spans[name]) == 5, name
    for name in ("engine.fetch", "engine.commit", "engine.update"):
        assert len(spans[name]) == 4, name
    assert len(spans["engine.prefill_chunk"]) == 3
    for child, parents in TREE.items():
        for span in spans[child]:
            found = [p for n in parents for p in _inside(span, spans[n])]
            assert len(found) == 1, (child, span)
    # One blocking first-token read per admission: the whole prompt's
    # in admit, the chunked one's at the end of its last pass.
    firsts = spans["engine.first_token"]
    assert len(firsts) == 2
    assert sum(bool(_inside(f, spans["engine.admit"])) for f in firsts) == 1
    assert sum(bool(_inside(f, spans["engine.prefill"])) for f in firsts) == 1
    # The phases of one tick follow each other; none overlaps the next.
    # The second tick: its own dispatch, then the first one's commit.
    tick = spans["engine.tick"][1]
    order = [
        spans[n][1] for n in (
            "engine.admit", "engine.prefill", "engine.launch",
        )
    ] + [
        spans[n][0] for n in (
            "engine.fetch", "engine.commit", "engine.update",
        )
    ]
    assert all(_inside(s, [tick]) for s in order)
    assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))


def test_region_is_the_annotation_alone_when_off(obs):
    eo = EngineObs()
    _, tracer = obs
    tracer.enabled = True
    # The snapshot first: a batcher's first scrape asks for its step
    # program's lowering again, an ``engine.compile.trace`` ring span.
    before = _samples("probe")
    seq = tracer.spans_since(0)[1]
    site = eo.region("probe", request=7)
    assert type(site) is TraceAnnotation  # no wrapper around it
    with site:
        pass
    assert _samples("probe") == before and "probe" not in eo.last_s
    assert not tracer.spans_since(seq)[0]


def test_region_records_what_phase_records_when_on(obs):
    eo = EngineObs()
    eo.enabled = True
    _, tracer = obs
    tracer.enabled = True
    before = _samples("probe")
    seq = tracer.spans_since(0)[1]
    with eo.region("probe", request=7):
        eo.enabled = False  # read once, at entry: the close still records
    with eo.region("probe"):
        pass  # ...and this one, entered off, does not
    assert _samples("probe") == before + 1
    assert eo.last_s["probe"] >= 0.0
    (span,) = tracer.spans_since(seq)[0]
    assert span.name == "engine.probe" and span.attrs == {"request": 7}
    assert span.end - span.start == pytest.approx(eo.last_s["probe"])
    eo.enabled = True
    with eo.region("probe", span=False):
        pass  # a site with a tracer row of its own: the sample alone
    assert _samples("probe") == before + 2
    assert len(tracer.spans_since(seq)[0]) == 1


def test_batcher_phases_reach_histograms_and_ring_through_region(
    lm_setup, obs
):
    eo, tracer = obs
    bat = _paged_batcher(lm_setup)
    names = (
        "tick", "admit", "prefill", "prefill_chunk", "first_token",
        "launch", "fetch", "commit", "update",
    )
    _submit_chunked_and_whole(bat)
    before = {n: _samples(n) for n in names}
    seq = tracer.spans_since(0)[1]
    bat.tick()  # gate and tracer off: nothing recorded
    bat.drain()
    assert {n: _samples(n) for n in names} == before
    assert not tracer.spans_since(seq)[0]
    eo.enabled = tracer.enabled = True
    for _ in range(4):
        bat.tick()
    bat.drain()  # the fourth tick's commit half
    eo.enabled = tracer.enabled = False
    got = {n: _samples(n) - before[n] for n in names}
    # The first tick above took the whole-prompt admission and the first
    # chunk pass; these four saw the other two passes and one first token.
    assert got == {
        "tick": 4, "admit": 4, "prefill": 4, "prefill_chunk": 2,
        "first_token": 1, "launch": 4, "fetch": 4, "commit": 4, "update": 4,
    }
    ring = [s.name for s in tracer.spans_since(seq)[0]]
    for n in names:
        # prefill_chunk's ring row is batcher.prefill_chunk, as before.
        want = 0 if n == "prefill_chunk" else got[n]
        assert ring.count(f"engine.{n}") == want, n
    assert ring.count("batcher.prefill_chunk") == 2
    assert ring.count("batcher.decode_chunk") == 4
