"""A block that holds a delta-rule state and NO pages (``models/kda``,
``ops/kda_step``, ``BlockSpec.linear``) and a gated GQA block beside it,
as Solar-Open2 has them: the chunked scan and the decode kernel against
the position-by-position recurrence, the served model (whole-prompt
prefill, chunked prefill, decode through ``ContinuousBatcher``) against
the plain reference's full forward pass, and what the batcher keeps and
refuses for such a model. CPU, at the configuration's ``rehearse``
sizes; the kernel runs interpreted."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.kda import (
    KdaMixer,
    KdaSpec,
    _unit_lower_inverse,
    kda_chunked,
    kda_recurrent,
)
from adapt_tpu.models.moe import ExpertSpec, RoutedExperts
from adapt_tpu.models.transformer_lm import (
    BlockSpec,
    generate,
    logits_full,
    validate_tp,
)
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.kda_step import (
    heads_per_step,
    kda_step,
    kda_step_reference,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import cache_groups
from adapt_tpu.utils.metrics import global_metrics
from conftest import drained

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 16, 4


def _operands(s, heads, d, seed=0):
    """q, k normalised a head as the layer makes them, ``g`` negative,
    ``beta`` in (1, 2): the half of its range that flips a direction."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (s, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (s, heads, d)))
    v = jax.random.normal(ks[2], (s, heads, d))
    g = -jax.random.uniform(ks[3], (s, heads, d), minval=1e-3, maxval=0.5)
    beta = jax.random.uniform(ks[4], (s, heads), minval=1.0, maxval=2.0)
    state = jax.random.normal(ks[5], (heads, d, d))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("s,chunk", [(150, 64), (64, 64), (5, 8)])
def test_the_chunked_scan_is_the_recurrence(s, chunk):
    """From a carried state, over a length that is no whole number of
    chunks: outputs and the state left agree with the recurrence taken
    position by position."""
    q, k, v, g, beta, state = _operands(s, 3, 16)
    want_o, want_s = kda_recurrent(q, k, v, g, beta, state)
    got_o, got_s = kda_chunked(q, k, v, g, beta, state, chunk)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_a_strong_decay_does_not_overflow_the_chunked_scan():
    """``exp(G_r - G_i)`` is formed pairwise and only for ``i <= r``: a
    decay of e^-20 a step (``1 / exp(G_i)`` alone would be e^1280
    inside a chunk of 64) leaves everything finite and right."""
    q, k, v, g, beta, state = _operands(70, 2, 8, seed=1)
    g = g * 40.0
    want_o, want_s = kda_recurrent(q, k, v, g, beta, state)
    got_o, got_s = kda_chunked(q, k, v, g, beta, state, 64)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def _solve_unit_lower(a, rhs):
    """``(I + tril(a, -1)) w = rhs`` by forward substitution, a row a
    step: ``a`` (H, C, C), ``rhs`` (H, C, d) -> ``w`` (H, C, d). What
    the chunked prefill ran inside every chunk until the blocked
    inverse took its place; kept as what the inverse is held to."""
    a = jnp.tril(a, -1)

    def row(w, r):
        new = rhs[:, r] - jnp.einsum(
            "hi,hid->hd", a[:, r], w, precision=jax.lax.Precision.HIGHEST
        )
        return w.at[:, r].set(new), None

    return jax.lax.scan(
        row, jnp.zeros_like(rhs), jnp.arange(a.shape[1])
    )[0]


@pytest.mark.parametrize("beta_range", [(0.0, 0.05), (1.95, 2.0)])
@pytest.mark.parametrize("block", [16, 32])
def test_the_blocked_inverse_solves_a_chunks_system(block, beta_range):
    """``T = (I + Diag(beta) tril(A, -1))^-1`` at C = 64, diagonal
    blocks of 16 or 32 by substitution and merged by products, for
    several chunks and heads at once: ``T rhs`` is what a triangular
    solve of the same system gives, with ``beta`` near zero (``T`` near
    the identity) and near two (its largest entries)."""
    from jax.scipy.linalg import solve_triangular

    n, heads, c, d = 3, 2, 64, 16
    _, k, v, g, _, _ = _operands(n * c, heads, d, seed=4)
    kh = jnp.swapaxes(k.reshape(n, c, heads, d), 1, 2)
    gh = jnp.swapaxes(jnp.cumsum(g.reshape(n, c, heads, d), 1), 1, 2)
    decay = jnp.exp(jnp.minimum(gh[:, :, :, None] - gh[:, :, None, :], 0.0))
    kk = jnp.sum(decay * kh[:, :, :, None] * kh[:, :, None, :], -1)
    beta = jax.random.uniform(
        jax.random.PRNGKey(5), (n, heads, c, 1),
        minval=beta_range[0], maxval=beta_range[1],
    )
    a = beta * kk
    rhs = jnp.swapaxes(v.reshape(n, c, heads, d), 1, 2)
    t = jax.jit(_unit_lower_inverse, static_argnums=1)(a, block)
    got = jnp.einsum(
        "nhri,nhid->nhrd", t, rhs, precision=jax.lax.Precision.HIGHEST
    )
    system = jnp.eye(c) + jnp.tril(a, -1)
    want = solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    scale = max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    rows = jax.vmap(_solve_unit_lower)(a, rhs)
    np.testing.assert_allclose(got, rows, atol=2e-5 * scale)
    # the strict upper triangle is exactly zero, the diagonal exactly one
    t = np.asarray(t)
    np.testing.assert_array_equal(np.triu(t, 1), 0.0)
    np.testing.assert_array_equal(np.diagonal(t, axis1=-2, axis2=-1), 1.0)


def test_the_blocked_inverse_refuses_blocks_it_cannot_pair():
    with pytest.raises(ValueError, match="no power of two of blocks"):
        _unit_lower_inverse(jnp.zeros((1, 48, 48)), 16)


@pytest.mark.parametrize("s", [1, 63, 65, 200, 1000])
def test_the_hoisted_scan_is_the_recurrence_from_a_carried_state(s):
    """At the module's chunk of 64 from a NON-zero state: less than a
    chunk, a position either side of one, several chunks in one group,
    and 16 chunks in two groups of 8 (the scan over groups)."""
    q, k, v, g, beta, state = _operands(s, 3, 16, seed=s)
    want_o, want_s = kda_recurrent(q, k, v, g, beta, state)
    got_o, got_s = jax.jit(kda_chunked)(q, k, v, g, beta, state)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    books = kernel_dispatch_stats()["kda_prefill"]
    chunks = -(-s // 64)
    groups = -(-chunks // 8)
    assert books["chunk"] == 64 and books["solve_block"] == 16
    assert books["solve_steps"] == 16 * groups
    assert books["group"] == -(-chunks // groups)


def test_the_decode_kernel_is_one_step_of_the_recurrence():
    """Interpreted: four rows at once, one of them dead (``alpha`` one,
    ``beta`` zero), whose state comes back bit for bit."""
    rows, heads, d = 4, 3, 16
    q, k, v, g, beta, _ = _operands(rows, heads, d, seed=2)
    state = jax.random.normal(jax.random.PRNGKey(9), (rows, heads, d, d))
    live = jnp.array([True, False, True, True])
    alpha = jnp.where(live[:, None, None], jnp.exp(g), 1.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    want_o, want_s = kda_step_reference(state, q, k, v, alpha, beta)
    got_o, got_s = kda_step(state, q, k, v, alpha, beta, prefer="pallas")
    assert kernel_dispatch_stats()["kda_step"]["last"] == 1.0
    np.testing.assert_allclose(got_o, want_o, atol=1e-6)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6)
    np.testing.assert_array_equal(got_s[1], state[1])
    for r in (0, 2, 3):  # and a live row's is the recurrence's
        o_r, s_r = kda_recurrent(
            q[r: r + 1], k[r: r + 1], v[r: r + 1], g[r: r + 1],
            beta[r: r + 1], state[r],
        )
        np.testing.assert_allclose(got_o[r], o_r[0], atol=1e-6)
        np.testing.assert_allclose(got_s[r], s_r, atol=1e-6)
    # 16 heads of 128 x 128 float32 a grid step: 1 MiB
    assert heads_per_step(64, 128, 128) == 16
    with pytest.raises(ValueError, match="float32"):
        kda_step(state.astype(jnp.bfloat16), q, k, v, alpha, beta,
                 prefer="pallas")


def test_the_mixers_schedules_agree():
    """One parameter structure, three schedules: a prompt in a longer
    bucket (``length``) leaves the state and tail of its last real
    position; a second pass from them and then single steps give what
    the whole sequence gives at once; a dead row's state and tail are
    untouched by a step."""
    spec = KdaSpec(heads=2, head_dim=8, rank=4)
    mixer = KdaMixer(spec, 16)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 146, 16))
    params = mixer.init(jax.random.PRNGKey(1), u)
    whole = mixer.apply(params, u)
    # 70 real positions in a bucket of 80: two chunks of 64, one padded
    padded = jnp.concatenate([u[:, :70], jnp.ones((1, 10, 16))], axis=1)
    out, carried = mixer.apply(params, padded, None, 70, method="scan")
    np.testing.assert_allclose(out[:, :70], whole[:, :70], atol=1e-5)
    out, carried = mixer.apply(
        params, u[:, 70:140], carried, None, method="scan"
    )
    np.testing.assert_allclose(out, whole[:, 70:140], atol=1e-5)
    carried = jax.tree.map(  # a second, dead row beside the live one
        lambda t: jnp.concatenate([t, jnp.full_like(t, 0.5)]), carried
    )
    live = jnp.array([True, False])
    for t in range(140, 146):
        x_t = jnp.broadcast_to(u[:, t: t + 1], (2, 1, 16))
        out, carried = mixer.apply(
            params, x_t, carried, live, "pallas", method="step"
        )
        np.testing.assert_allclose(out[0], whole[0, t: t + 1], atol=1e-5)
    for leaf in carried:
        np.testing.assert_array_equal(leaf[1], jnp.full_like(leaf[1], 0.5))


def test_a_pass_of_padding_alone_leaves_state_and_tail_as_they_were():
    """``length`` 0: every position of the pass steps nothing (``g`` =
    0, ``beta`` = 0), so the state and the convolution's tail come back
    bit for bit."""
    mixer = KdaMixer(KdaSpec(heads=2, head_dim=8, rank=4), 16)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 80, 16))
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), u)
    scan = jax.jit(functools.partial(mixer.apply, method="scan"))
    empty = tuple(
        jnp.zeros(t.shape, t.dtype)
        for t in mixer.spec.state_shapes(1, jnp.float32)
    )
    _, carried = scan(params, u, empty, 70)
    _, after = scan(params, u, carried, 0)
    for was, now in zip(carried, after):
        assert np.abs(np.asarray(was)).max() > 0
        np.testing.assert_array_equal(now, was)


# -- the served model against the plain reference ------------------------------


@pytest.fixture(scope="module")
def built():
    """The configuration at its rehearsal sizes, in float32 (so that
    served and reference differ by schedule alone), built ONCE."""
    from chipbench import solar_open2

    config = json.loads(
        (ROOT / "chipbench/configs/solar-open2-250b.json").read_text()
    )
    model = {**config["model"], **config["rehearse"]["model"],
             "positions_served": 256}
    return solar_open2.build(model, "float32", 7)


def _batcher(lm, variables, **kw):
    return ContinuousBatcher(
        lm, variables, slots=3, chunk=CHUNK, kv_layout="paged",
        page_size=PAGE, prefill_chunk=2 * PAGE,
        prompt_buckets=(32, 64, 128), **kw,
    )


def test_a_block_without_pages_gets_no_pool_and_the_model_one_group(built):
    lm, variables, shape = built
    specs = [lm.graph.node(n).module.spec for n in lm.block_names]
    assert [s.linear is None for s in specs] == [True, False]
    assert specs[0].attn_gate and specs[0].rope_base is None
    groups = cache_groups(specs)
    assert [(g.name, g.blocks) for g in groups] == [("full", (0,))]
    assert shape["layers"] == 1 and shape["kda_layers"] == 1
    srv = _batcher(lm, variables)
    assert srv._caches[1] is None and srv._caches[0] is not None
    stats = srv.stats()
    lin = specs[1].linear
    state = 3 * (
        lin.heads * lin.head_dim ** 2 * 4 + 3 * lin.conv_dim * 4
    )
    assert stats["state_bytes"] == state and stats["state_slots"] == 3
    assert stats["prefix_cache"] == "off: recurrent state"
    assert global_metrics().snapshot()["gauges"].get(
        "memory.state_bytes", state
    ) == state
    srv.close()
    # a model of such blocks alone has no pager to run its requests by
    with pytest.raises(ValueError, match="no block of this model keeps pages"):
        from adapt_tpu.models.transformer_lm import transformer_lm

        only = transformer_lm(64, blocks=[specs[1]], pos="none", max_len=64)
        ContinuousBatcher(
            only, only.graph.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            ), slots=2, kv_layout="paged", page_size=PAGE,
        )


PROMPTS = (20, 50, 75)


@pytest.fixture(scope="module")
def served(built):
    """ONE batcher (its programs compile once) serves three prompts one
    after another: a whole-prompt prefill (20 in a bucket of 32:
    ``length`` masks the padding), a chunked prefill of two passes and
    one of three (the state and tail carried pass to pass in the slot's
    row), each followed by decode steps through the kernel (interpreted)
    beside dead rows. A prompt: the ids served, their logprobs, the
    counters the request moved."""
    lm, variables, shape = built
    # No scan past a request's end: the counters below are its own.
    srv = drained(_batcher(lm, variables))
    out = {}
    for n in PROMPTS:
        snap = global_metrics().snapshot(window=True)
        prompt = np.random.default_rng(n).integers(
            0, shape["vocab"], size=n
        ).astype(np.int32)
        toks = []
        rid = srv.submit(prompt, 10, on_token=lambda r, t, i: toks.append(t))
        srv.run()
        out[n] = (
            np.concatenate([prompt, np.asarray(toks, np.int32)])[None],
            np.asarray(srv.logprobs(rid)),
            global_metrics().snapshot(since=snap)["counters"],
        )
    srv.close()
    return out


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_served_logprobs_are_the_plain_references(built, served, prompt_len):
    """The served logprobs are the plain float32 reference's (and the
    program's own full forward's), and the counters book the state's
    writes."""
    from chipbench import solar_open2_reference as ref

    lm, variables, _ = built
    ids, got, c = served[prompt_len]
    want, gaps = ref.logprobs_and_gaps(variables, ids)
    np.testing.assert_allclose(
        got, np.asarray(want)[0, prompt_len - 1:], atol=2e-4
    )
    assert gaps.shape == (2, 1, ids.shape[1] - 1)
    lp = jax.nn.log_softmax(logits_full(lm, variables, jnp.asarray(ids)), -1)
    full = np.take_along_axis(
        np.asarray(lp[0, :-1]), ids[0, 1:, None], -1
    )[:, 0]
    np.testing.assert_allclose(full, np.asarray(want)[0], atol=2e-4)
    passes = -(-prompt_len // (2 * PAGE)) if prompt_len > 2 * PAGE else 1
    assert c["kda.state_writes"] == passes
    assert c.get("kda.chunks_carried", 0) == passes - 1
    assert c["kda.steps"] == 3 * CHUNK  # one KDA layer, three ticks
    # Zero, or absent where no state-space model ran in this process.
    assert not c.get("ssm.state_writes")
    assert c["moe.steps"] == 3 * CHUNK


def test_the_references_controls_read_wrong(built):
    from chipbench import solar_open2_reference as ref

    lm, variables, shape = built
    ids = np.random.default_rng(0).integers(
        0, shape["vocab"], size=(2, 48)
    ).astype(np.int32)
    sound, _ = ref.logprobs_and_gaps(variables, ids)
    for fault in ref.FAULTS:
        wrong, _ = ref.logprobs_and_gaps(
            variables, ids, fault, reset_at=(30, 40)
        )
        assert float(jnp.abs(wrong - sound).max()) > 0.05, fault
    with pytest.raises(ValueError, match="unknown fault"):
        ref.logprobs_and_gaps(variables, ids, "drop_rope")
    logp, sure = ref.next_token_logprobs(variables, ids)
    assert sure.dtype == bool and sure.shape == logp.shape
    # the precision readings move it, and by less than a fault does
    for name in ref.PRECISION:
        low, _ = ref.logprobs_and_gaps(variables, ids, name)
        assert 0 < float(jnp.abs(low - sound).max()) < 0.5, name


def test_the_reference_in_blocks_is_the_reference(built, monkeypatch):
    """A long row goes through the reference's attention and expert
    layer ``BLOCK`` positions at a time: the same numbers."""
    from chipbench import solar_open2_reference as ref

    _, variables, shape = built
    ids = np.random.default_rng(1).integers(
        0, shape["vocab"], size=(1, 40)
    ).astype(np.int32)
    whole = ref.logprobs_and_gaps(variables, ids)
    monkeypatch.setattr(ref, "BLOCK", 16)
    ref._attention.clear_cache()
    try:
        blocks = ref.logprobs_and_gaps(variables, ids)
    finally:
        ref._attention.clear_cache()
    for got, want in zip(blocks, whole):
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_what_cannot_carry_the_state_refuses_by_name(built):
    from adapt_tpu.models.transformer_lm import lm_tiny

    lm, variables, _ = built
    draft = lm_tiny(vocab=512, max_len=256)
    dvars = draft.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    with pytest.raises(ValueError, match="recurrent state"):
        _batcher(lm, variables, draft_lm=draft, draft_variables=dvars)
    with pytest.raises(ValueError, match="recurrent state"):
        _batcher(lm, variables, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="routed experts do not split"):
        validate_tp(lm, 2)
    from adapt_tpu.models.transformer_lm import transformer_lm

    dense = transformer_lm(64, blocks=[
        BlockSpec(32, 4, 64),
        BlockSpec(32, 4, 64, linear=KdaSpec(heads=4, head_dim=8, rank=4)),
    ], pos="none", max_len=64)
    with pytest.raises(ValueError, match="linear-attention mixer does not"):
        validate_tp(dense, 2)
    with pytest.raises(ValueError, match="carry no\\s+recurrent state"):
        generate(lm, variables, jnp.zeros((1, 4), jnp.int32), 2)
    srv = _batcher(lm, variables)
    with pytest.raises(ValueError, match="recurrent state"):
        srv.prefix_cached(np.arange(40, dtype=np.int32))
    with pytest.raises(ValueError, match="recurrent state"):
        srv.adopt_prefill_pages(np.arange(40, dtype=np.int32), [], PAGE, False)
    srv.close()
    block = lm.graph.node(lm.block_names[1]).module
    x = jnp.zeros((1, 4, block.dim))
    for method, args in (
        ("decode_step", (x[:, :1], None, None, 0)),
        ("verify_chunk", (x, None, None, 0)),
        ("prefill_sp", (x, None)),
    ):
        with pytest.raises(NotImplementedError, match="no recurrent state"):
            block.apply(variables[lm.block_names[1]], *args, method=method)


@pytest.mark.parametrize("field,value", [
    ("kv_heads", 2), ("head_dim", 8), ("window", 16), ("rope_base", 1e4),
    ("qk_norm", True), ("attn_gate", True), ("post_norm", True),
])
def test_a_linear_block_takes_no_attention_field(field, value):
    lin = KdaSpec(heads=4, head_dim=8, rank=4)
    BlockSpec(32, 4, 64, linear=lin)
    with pytest.raises(ValueError, match="linear-attention"):
        BlockSpec(32, 4, 64, linear=lin, **{field: value})


def test_the_shares_of_a_layer_add_up_to_the_whole():
    """8 chips, 5 of 40 experts each, route over all 40 at top-8 with
    no scaling factor: the routed parts of the 8 shares plus the shared
    expert ONCE are the uncut layer, and the uncut layer is the
    reference's (Solar-Open2's cut, at small widths)."""
    from chipbench import solar_open2_reference as ref

    d, hid, n_exp, k, held = 24, 16, 40, 8, 5
    kw = dict(score="sigmoid", normalize=True, scale=1.0, select_bias=True,
              shared_dim=hid)
    whole = ExpertSpec(n_exp, hid, k, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, d))
    params = RoutedExperts(whole).init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (n_exp,)
    )
    full = RoutedExperts(whole).apply({"params": params}, x)
    routed_only = {
        **params,
        **{n: jax.tree.map(jnp.zeros_like, params[n])
           for n in ("shared_gate", "shared_up", "shared_down")},
    }
    shared = full - RoutedExperts(whole).apply({"params": routed_only}, x)
    total = shared
    for chip in range(n_exp // held):
        lo = held * chip
        mine = {
            **params,
            **{n: params[n][lo: lo + held]
               for n in ("w_gate", "w_up", "w_down")},
        }
        total = total + (
            RoutedExperts(ExpertSpec(n_exp, hid, k, held=(lo, held), **kw))
            .apply({"params": mine}, x) - shared
        )
    np.testing.assert_allclose(total, full, atol=1e-5)
    want, _ = ref._experts(params, x, ref.ARCH, False)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(full, want, atol=1e-5)
