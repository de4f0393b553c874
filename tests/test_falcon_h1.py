"""Falcon-H1's mechanisms at a small size on the CPU, against the plain
reference the benchmark's configuration names
(``chipbench/falcon_h1_reference.py``): a Mamba-2 mixer in parallel
with attention in every block, its three schedules, the recurrent
state a slot beside the pages in the batcher, and everything that
moves pages only refusing such a model."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import (
    CacheTierConfig,
    ParallelConfig,
    PrefillConfig,
    SchedulerConfig,
)
from adapt_tpu.models.ssm import Mamba2Mixer, SsmSpec
from adapt_tpu.models.transformer_lm import (
    generate,
    logits_full,
    transformer_lm,
)
from adapt_tpu.ops.ssm_step import ssm_step, ssm_step_reference
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils.metrics import global_metrics
from chipbench import falcon_h1
from chipbench import falcon_h1_reference as ref
from chipbench import falcon_h1_yardstick as fy

ROOT = Path(__file__).parents[1]
CONFIG = json.loads(
    (ROOT / "chipbench/configs/falcon-h1-34b-instruct.json").read_text()
)
PAGE, CHUNK, PREFILL = 16, 4, 32


def _model(**over):
    """The published keys (every multiplier as published) at toy
    widths: 2 layers, 4 mixer heads of 16 with a state of 32 in 2
    groups, chunks of 16 positions."""
    model = dict(CONFIG["model"])
    model.update(
        vocab_size=128, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_ssm=64, mamba_d_state=32,
        mamba_chunk_size=16, num_hidden_layers=2, positions_served=128,
    )
    model.update(over)
    return model


@pytest.fixture(scope="module")
def built():
    return falcon_h1.build(_model(), "float32", 7)


def _batcher(lm, variables, slots=4, **kw):
    return ContinuousBatcher(
        lm, variables, slots=slots, chunk=CHUNK, page_size=PAGE,
        prefill_chunk=PREFILL, prompt_buckets=(32, 64, 128), **kw
    )


# -- (a) the mixer's three schedules ------------------------------------------

SPEC = SsmSpec(
    heads=4, head_dim=16, d_state=32, groups=2, chunk=16, in_mult=0.25,
    out_mult=0.5, mup=(0.35, 0.25, 0.18, 0.5, 0.35),
)


@pytest.fixture(scope="module")
def mixer():
    """(module, variables, u, the position-by-position float32 oracle
    of the whole sequence)."""
    m = Mamba2Mixer(SPEC, 64)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    variables = m.init(jax.random.PRNGKey(0), u)
    p = dict(variables["params"])
    # A bias and a skip that matter.
    p["conv_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), p["conv_bias"].shape
    )
    p["D"] = 1.0 + 0.1 * jnp.arange(4.0)
    variables = {"params": p}
    want = ref._mixer(
        p, u, SPEC.groups, SPEC.in_mult, SPEC.mup, SPEC.norm_eps
    )
    return m, variables, u, want


def _scan(m, variables, u, carried=None, length=None):
    return m.apply(variables, u, carried, length, method="scan")


def _whole(m, variables, u):
    return m.apply(variables, u)


def _three_passes(m, variables, u):
    outs, carried = [], None
    for lo, hi in ((0, 16), (16, 48), (48, 64)):
        out, carried = _scan(m, variables, u[:, lo:hi], carried)
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


def _prefill_then_decode(m, variables, u, prefer=None):
    out, carried = _scan(m, variables, u[:, :48])
    outs = [out]
    for t in range(48, 64):
        out, carried = m.apply(
            variables, u[:, t: t + 1], carried, jnp.ones((2,), bool),
            prefer, method="step",
        )
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("schedule", [
    _whole, _three_passes, _prefill_then_decode,
    lambda *a: _prefill_then_decode(*a, prefer="pallas"),
], ids=["whole-prompt", "three-chunk-passes", "prefill-16-decode-steps",
        "prefill-16-kernel-steps"])
def test_a_schedule_agrees_with_the_recurrence_by_position(mixer, schedule):
    m, variables, u, want = mixer
    np.testing.assert_allclose(
        schedule(m, variables, u), want, atol=2e-5, rtol=1e-4
    )


@pytest.mark.parametrize("length", [1, 2, 17, 40])
def test_padding_does_not_step_the_state(mixer, length):
    """A prompt shorter than its bucket leaves the state and the
    convolution tail of its LAST REAL position."""
    m, variables, u, _ = mixer
    _, (state, tail) = _scan(m, variables, u[:, :length])
    out, (p_state, p_tail) = _scan(m, variables, u, None, length)
    np.testing.assert_allclose(p_state, state, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(p_tail, tail, atol=1e-6)
    want, _ = _scan(m, variables, u[:, :length])
    np.testing.assert_allclose(out[:, :length], want, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("prefer", ["xla", "pallas"])
def test_the_step_leaves_a_dead_row_as_it_was(mixer, prefer):
    """dt = 0 is how a row is dead: state and tail bit for bit."""
    m, variables, u, _ = mixer
    _, carried = _scan(m, variables, u[:, :20])
    live = jnp.array([True, False])
    _, (state, tail) = m.apply(
        variables, u[:, 20:21], carried, live, prefer, method="step"
    )
    assert np.array_equal(state[1], carried[0][1])
    assert np.array_equal(tail[1], carried[1][1])
    assert not np.array_equal(state[0], carried[0][0])


def test_the_kernel_matches_the_plain_arm():
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    state = jax.random.normal(k[0], (3, 4, 32, 16))
    x = jax.random.normal(k[1], (3, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(k[2], (3, 4)))
    a = -jnp.arange(1.0, 5.0)
    b = jax.random.normal(k[3], (3, 2, 32))
    c = jax.random.normal(k[4], (3, 2, 32))
    want = ssm_step_reference(state, x, dt, a, b, c)
    got = ssm_step(state, x, dt, a, b, c, prefer="pallas")
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


# -- (b) served logits against the reference's full pass ----------------------


def test_full_forward_matches_the_plain_reference(built):
    lm, variables, _ = built
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 50), 0, 128)
    lp = jax.nn.log_softmax(logits_full(lm, variables, ids)[:, :-1], -1)
    got = jnp.take_along_axis(lp, ids[:, 1:, None], -1)[..., 0]
    np.testing.assert_allclose(
        got, ref.next_token_logprobs(variables, ids), atol=2e-4
    )


def _serve(srv, prompts, steps):
    rids = [srv.submit(p, steps) for p in prompts]
    out = srv.run()
    return [(out[r], srv.logprobs(r)) for r in rids]


def _against_reference(variables, prompt, tokens, lps, atol=3e-4):
    ids = jnp.asarray(np.concatenate([prompt, tokens]))[None]
    want = np.asarray(ref.next_token_logprobs(variables, ids))[0]
    n = len(prompt)
    np.testing.assert_allclose(
        lps, want[n - 1: n - 1 + len(tokens)], atol=atol
    )


@pytest.mark.parametrize("length", [9, 32, 45, 90], ids=[
    "whole-prompt", "whole-bucket", "two-chunk-passes", "three-chunk-passes",
])
def test_served_logprobs_match_the_reference(built, length):
    """Prefill (whole prompt, or chunk passes that carry the state) and
    paged decode through the batcher's normal path."""
    lm, variables, _ = built
    srv = _batcher(lm, variables)
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(length), (length,), 0, 128),
        np.int32,
    )
    before = global_metrics().snapshot()["counters"]
    [(tokens, lps)] = _serve(srv, [prompt], 12)
    after = global_metrics().snapshot()["counters"]
    stats = srv.stats()
    srv.close()
    _against_reference(variables, prompt, tokens, lps)
    passes = -(-length // PREFILL) if length > PREFILL else 1

    def moved(key):
        return after.get(key, 0) - before.get(key, 0)

    assert moved("ssm.state_writes") == passes
    assert moved("ssm.chunks_carried") == passes - 1
    assert not stats["inflight"]  # run() landed the last tick
    assert stats["state_slots"] == 4
    # 2 layers x 4 slots x (a state of 4 x 32 x 16 + a tail of 3
    # positions x (64 + 2 x 2 x 32) channels), float32 here
    assert stats["state_bytes"] == 2 * 4 * (4 * 32 * 16 + 3 * 192) * 4
    assert stats["prefix_cache"] == "off: recurrent state"


# -- (c) a slot retired and refilled under the overlapped order ---------------


def test_a_refilled_slot_serves_as_a_fresh_batcher_does(built):
    """Two slots, five requests of uneven length: every slot is
    retired and refilled while the other decodes, a tick in flight (a
    retired row is stepped once more before its slot is cleared). The
    next tenant's state is written whole at admission, so it reads
    nothing of that."""
    lm, variables, _ = built
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, 128, n).astype(np.int32) for n in (20, 40, 7, 33, 50)
    ]
    steps = [5, 14, 9, 3, 11]
    srv = _batcher(lm, variables, slots=2)
    rids = [srv.submit(p, s) for p, s in zip(prompts, steps)]
    srv.tick()
    srv.tick()
    assert srv.stats()["inflight"]
    out = srv.run()
    got = [(out[r], srv.logprobs(r)) for r in rids]
    srv.close()
    for prompt, n, (tokens, lps) in zip(prompts, steps, got):
        alone = _batcher(lm, variables, slots=2)
        [(want_t, want_lp)] = _serve(alone, [prompt], n)
        alone.close()
        assert np.array_equal(tokens, want_t)
        np.testing.assert_allclose(lps, want_lp, atol=1e-6)


# -- (d) what moves pages only refuses a model with recurrent state -----------


def _tiny_draft():
    lm = transformer_lm(128, 32, 1, 2, 64, max_len=128)
    return lm, lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )


def _refuse_draft(lm, variables):
    dlm, dvars = _tiny_draft()
    _batcher(lm, variables, draft_lm=dlm, draft_variables=dvars)


def _refuse_mesh(lm, variables):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    _batcher(lm, variables, mesh=mesh, parallel=ParallelConfig(tp=2))


def _refuse_health(lm, variables):
    from adapt_tpu.control.registry import DeviceHealthMonitor

    _batcher(lm, variables, health=DeviceHealthMonitor())


def _refuse_verify(lm, variables):
    block = lm.graph.node("decoder_block_0").module
    x = jnp.zeros((1, 2, 64))
    pool = jnp.zeros((3, 2, PAGE, 32))
    block.apply(
        variables["decoder_block_0"], x, pool,
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        method="verify_chunk_paged",
    )


_REFUSALS = [
    ("a draft model", _refuse_draft, "a draft model.*recurrent state"),
    ("a tp mesh", _refuse_mesh, "state-space mixer does not split over tp"),
    ("a host cache tier",
     lambda lm, v: _batcher(lm, v, cache_tier=CacheTierConfig()),
     "host cache tier.*recurrent state"),
    ("sp prefill",
     lambda lm, v: _batcher(lm, v, prefill=PrefillConfig(sp_threshold=64)),
     "sequence-parallel prefill.*recurrent state"),
    ("cache-aware admission",
     lambda lm, v: _batcher(lm, v, scheduler=SchedulerConfig(cache_aware=True)),
     "radix prefix cache.*recurrent state"),
    ("elastic recovery", _refuse_health, "elastic recovery.*recurrent state"),
    ("a quantized pool",
     lambda lm, v: _batcher(lm, v, kv_cache_dtype="int8"),
     "quantized KV pool.*recurrent state"),
    ("the radix prefix cache",
     lambda lm, v: _batcher(lm, v).prefix_cached(np.arange(40, dtype=np.int32)),
     "radix prefix cache.*recurrent state"),
    ("fan-out",
     lambda lm, v: _batcher(lm, v).submit_fanout(
         np.arange(40, dtype=np.int32), 2, 4),
     "fan-out.*recurrent state"),
    ("a handoff",
     lambda lm, v: _batcher(lm, v).adopt_prefill_pages(
         np.arange(40, dtype=np.int32), [], PAGE, False),
     "handoff of prefilled pages.*recurrent state"),
    ("generate()",
     lambda lm, v: generate(lm, v, jnp.zeros((1, 4), jnp.int32), 2),
     "carry no\\s+recurrent state"),
    ("verify_chunk_paged", _refuse_verify, "cannot be un-stepped"),
]


@pytest.mark.parametrize(
    "how,says", [r[1:] for r in _REFUSALS], ids=[r[0] for r in _REFUSALS]
)
def test_what_moves_pages_only_refuses_recurrent_state(built, how, says):
    lm, variables, _ = built
    with pytest.raises((ValueError, NotImplementedError), match=says):
        how(lm, variables)


# -- (e) the yardstick's counts on a hand-worked shape ------------------------


def test_the_yardstick_counts_a_hand_worked_shape():
    # 3 rows, 2 heads of 4 with a state of 8 in 1 group, bfloat16:
    # state 2 x 4 x 8 = 64 numbers; bytes a row = 2 x 64 x 4 (state in
    # and out) + 2 x 8 x 2 (x, y) + 2 x 8 x 2 (B, C) + 2 x 4 (dt) = 584.
    flops, nbytes = fy.ssm_step_cost(3, 2, 4, 8, 1, 2)
    assert nbytes == 3 * 584
    assert flops == 3 * 5 * 64
    # the cell's shape: a row's state twice is all but 0.3% of it
    _, row = fy.ssm_step_cost(1, 32, 128, 256, 2, 2)
    assert row == 2 * 4_194_304 + 2 * 8192 + 2 * 1024 + 128
    assert fy.ssm_step_cost(0, 32, 128, 256, 2, 2) == (0, 0)


# -- (g) the configuration file and the cell's rehearsal ----------------------


def test_the_configuration_file_holds_the_published_keys_twice_and_equal():
    model = CONFIG["model"]
    assert set(model) - set(CONFIG) == {"positions_served"}
    for key in set(model) - {"positions_served"}:
        assert CONFIG[key] == model[key], key
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 72}
    assert model["num_hidden_layers"] == 4
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        [entry] = [
            e for e in map(json.loads, catalog.read_text().splitlines())
            if e["name"] == "Falcon-H1-34B-Instruct"
        ]
        assert CONFIG["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
    # what the reference states as constants is what the file says
    for key, value in ref.ARCH.items():
        if key in model:
            assert tuple(np.atleast_1d(model[key])) == tuple(
                np.atleast_1d(value)
            ), key
    assert ref.ARCH["rope_base"] == model["rope_theta"]
    assert ref.ARCH["eps"] == model["rms_norm_eps"]
    assert tuple(CONFIG["correct"]["controls"]) == ref.CONTROLS


def test_every_branch_is_of_order_one_after_its_multiplier(built):
    """What ``leaf_std`` is for: logits of order one, so that a
    logprob is readable over a tolerance (N(0, 1/fan_in) alone leaves
    them at 0.008)."""
    lm, variables, _ = built
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, 40), 0, 128)
    logits = logits_full(lm, variables, ids)
    assert 0.3 < float(jnp.std(logits)) < 3.0


def test_the_rehearsal_walks_the_cell_in_both_trace_modes(capsys):
    """``tests/chipbench/test_chipbench_run_loop.py`` picks this
    configuration's three controls up from ``BENCHMARK.json``; its
    list of cells to walk is its own, so the walk is here."""
    from chipbench import run as bench_run

    assert bench_run.main(
        ["--rehearse", "--seconds", "1.5", "--workload", "falconh1_longgen"]
    ) == 0
    plain, traced = [
        ln for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("rehearsal ")
    ]
    assert "correct=True" in plain and "failed=0" in plain
    assert "would report ['out_tok_per_s', 'setup_s']" in plain
    # No device plane on the CPU: the readers of the counters report,
    # the device readers (the two this cell brings among them) return
    # nothing and do not raise.
    assert "correct=True" in traced
    for name in ("kv.pool_peak_pct.batch", "sched.slots_active_mean"):
        assert name in traced
    assert "roofline" not in traced and "ssm." not in traced
