"""Three Gated DeltaNet blocks (a scalar decay a head, fewer key heads
than value heads: ``KdaSpec.head_decay`` / ``key_heads``) beside one
GATED latent-attention block, norms on both sides of every sub-layer
and a clamped SwiGLU, as GigaChat3.5 has them: the scalar-decay chunked
scan and the decode step against the position-by-position recurrence,
Solar-Open2's mixer against what it was, the gate on every schedule of
the latent block, the served model (whole-prompt prefill, chunked
prefill, decode through ``ContinuousBatcher``) against the plain
reference's full forward pass, and what the batcher keeps and refuses
for a request that owns states AND latent pages. CPU, at the
configuration's ``rehearse`` sizes; the kernels run interpreted."""

import functools
import json
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.kda import (
    KdaMixer,
    KdaSpec,
    kda_chunked,
    kda_chunked_head,
    kda_recurrent,
)
from adapt_tpu.models.mla import LatentSpec
from adapt_tpu.models.moe import ExpertSpec, RoutedExperts, limited
from adapt_tpu.models.transformer_lm import (
    BlockSpec,
    DecoderBlock,
    logits_full,
)
from adapt_tpu.ops.kda_step import kda_step
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import cache_layout
from adapt_tpu.utils.metrics import global_metrics
from conftest import drained

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 16, 4
GDN = KdaSpec(heads=4, head_dim=16, rank=None, neg_eigval=False,
              norm_eps=1e-6, key_heads=2, head_decay=True, gate_scale=2.0)


def _operands(s, heads, d, seed=0):
    """q, k normalised a head, ONE negative ``g`` a head, ``beta`` in
    (0, 1), a carried state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (s, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (s, heads, d)))
    v = jax.random.normal(ks[2], (s, heads, d))
    g = -jax.random.uniform(ks[3], (s, heads), minval=1e-3, maxval=0.5)
    beta = jax.random.uniform(ks[4], (s, heads))
    state = jax.random.normal(ks[5], (heads, d, d))
    return q, k, v, g, beta, state


def _channels(g, d):
    return jnp.broadcast_to(g[..., None], (*g.shape, d))


@pytest.mark.parametrize("s,chunk,decay", [
    (150, 64, 1.0), (5, 8, 1.0), (70, 64, 40.0),
])
def test_the_scalar_decay_scan_is_the_recurrence(s, chunk, decay):
    """From a carried state, over a length that is no whole number of
    chunks, and under a decay of e^-20 a step (the pairwise form must
    not overflow): outputs and the state left agree with the recurrence
    taken position by position, and with the per-channel form given the
    same decay on every channel."""
    q, k, v, g, beta, state = _operands(s, 3, 16)
    g = g * decay
    want_o, want_s = kda_recurrent(q, k, v, _channels(g, 16), beta, state)
    got_o, got_s = kda_chunked_head(q, k, v, g, beta, state, chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    other_o, other_s = kda_chunked(
        q, k, v, _channels(g, 16), beta, state, chunk
    )
    np.testing.assert_allclose(got_o, other_o, atol=2e-5)
    np.testing.assert_allclose(got_s, other_s, atol=2e-5)


def _mixer(spec=GDN, dim=32, seed=0):
    mixer = KdaMixer(spec, dim)
    u = jax.random.normal(jax.random.PRNGKey(seed), (2, 21, dim))
    params = mixer.init(jax.random.PRNGKey(seed + 1), u)
    return mixer, params, u


@pytest.mark.parametrize("s", [1, 63, 65, 200, 1000])
def test_the_hoisted_scalar_scan_is_the_recurrence_from_a_carried_state(s):
    """At the module's chunk of 64 from a NON-zero state: less than a
    chunk, a position either side of one, several chunks in one group,
    and 16 chunks in two groups of 8 (the scan over groups)."""
    q, k, v, g, beta, state = _operands(s, 3, 16, seed=s)
    want_o, want_s = kda_recurrent(q, k, v, _channels(g, 16), beta, state)
    got_o, got_s = jax.jit(kda_chunked_head)(q, k, v, g, beta, state)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_a_pass_of_padding_alone_leaves_state_and_tail_as_they_were():
    """``length`` 0 under a decay a head and shared key heads: the pass
    steps nothing, state and tail come back bit for bit."""
    mixer, params, u = _mixer()
    scan = jax.jit(functools.partial(mixer.apply, method="scan"))
    empty = tuple(
        jnp.zeros((2, *t.shape[1:]), t.dtype)
        for t in mixer.spec.state_shapes(1, jnp.float32)
    )
    _, carried = scan(params, u, empty, 13)
    _, after = scan(params, u, carried, 0)
    for was, now in zip(carried, after):
        assert np.abs(np.asarray(was)).max() > 0
        np.testing.assert_array_equal(now, was)


def test_the_mixers_schedules_agree_under_shared_key_heads():
    """The whole prompt at once; two chunk passes with the state and
    tail carried and a short ``length``; then token by token through
    the decode kernel (interpreted: ``alpha`` laid over the channels,
    q and k over the two value heads of a key head), beside a dead row
    that keeps its state bit for bit."""
    mixer, params, u = _mixer()
    assert params["params"]["qkv"]["kernel"].shape == (32, GDN.conv_dim)
    assert GDN.conv_dim == (2 * 2 + 4) * 16
    assert params["params"]["A_log"].shape == (4,)
    assert params["params"]["dt_bias"].shape == (4,)
    assert params["params"]["g_proj"]["kernel"].shape == (32, 64)
    whole, (s_whole, t_whole) = mixer.apply(
        params, u, None, None, method="scan"
    )
    # two passes: 16 positions, then a bucket of 8 holding 5 real ones
    a, carried = mixer.apply(params, u[:, :16], None, None, method="scan")
    pad = jnp.concatenate([u[:, 16:], jnp.zeros((2, 3, 32))], axis=1)
    b, (s_two, t_two) = mixer.apply(params, pad, carried, 5, method="scan")
    np.testing.assert_allclose(a, whole[:, :16], atol=2e-5)
    np.testing.assert_allclose(b[:, :5], whole[:, 16:], atol=2e-5)
    np.testing.assert_allclose(s_two, s_whole, atol=2e-5)
    np.testing.assert_allclose(t_two, t_whole, atol=1e-6)
    # steps from the state after 16 positions; row 1 is dead
    live = jnp.asarray([True, False])
    state, tail = carried
    step = jax.jit(lambda u_t, carried: mixer.apply(
        params, u_t, carried, live, "pallas", method="step"
    ))
    for t in range(16, 21):
        o, (state, tail) = step(u[:, t: t + 1], (state, tail))
        np.testing.assert_allclose(o[0], whole[0, t: t + 1], atol=2e-5)
    np.testing.assert_allclose(state[0], s_whole[0], atol=2e-5)
    np.testing.assert_array_equal(state[1], carried[0][1])
    np.testing.assert_array_equal(tail[1], carried[1][1])


def test_the_step_with_broadcast_operands_is_one_step_of_the_recurrence():
    """``kda_step`` (interpreted) given a head's ``alpha`` on all its
    channels and a key head's q and k under both of its value heads."""
    q, k, v, g, beta, state = _operands(1, 2, 16, seed=3)
    q, k = (jnp.repeat(t, 2, axis=1) for t in (q, k))  # 2 key, 4 value heads
    v, state = jnp.tile(v, (1, 2, 1)) * 0.5, jnp.tile(state, (2, 1, 1))
    g, beta = jnp.tile(g, (1, 2)) * jnp.asarray([1., 1., 2., 3.]), jnp.tile(
        beta, (1, 2)
    )
    want_o, want_s = kda_recurrent(q, k, v, _channels(g, 16), beta, state)
    got_o, got_s = kda_step(
        state[None], q, k, v, _channels(jnp.exp(g), 16), beta,
        prefer="pallas",
    )
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s[0], want_s, atol=2e-5)


def test_solar_open2s_mixer_is_what_it_was():
    """A spec as Solar-Open2's (key heads = value heads, a decay a key
    channel from a low-rank pair, a low-rank plain-sigmoid gate, beta
    to 2) builds the parameters it built and computes, operation for
    operation, what the mixer computed before it learnt the other
    forms: bit for bit against that forward written out here over the
    same parameters and ``kda_chunked``."""
    spec = KdaSpec(heads=4, head_dim=16, rank=8)
    mixer, params, u = _mixer(spec)
    p = params["params"]
    assert {n: jax.tree.map(jnp.shape, p[n]) for n in p} == {
        "qkv": {"kernel": (32, 192)}, "conv_kernel": (4, 192),
        "f_down": {"kernel": (32, 8)}, "f_up": {"kernel": (8, 64)},
        "A_log": (4,), "dt_bias": (64,), "b_proj": {"kernel": (32, 4)},
        "g_down": {"kernel": (32, 8)}, "g_up": {"kernel": (8, 64)},
        "norm_scale": (16,), "out_proj": {"kernel": (64, 32)},
    }
    F32 = jnp.float32

    def dense(name, x):
        return nn.Dense(
            p[name]["kernel"].shape[1], use_bias=False
        ).apply({"params": p[name]}, x)

    b, s, _ = u.shape
    qkv = dense("qkv", u)
    full = jnp.concatenate([jnp.zeros((b, 3, 192), qkv.dtype), qkv], axis=1)
    w = p["conv_kernel"].astype(F32)
    out = nn.silu(sum(full[:, j: j + s].astype(F32) * w[j] for j in range(4)))
    q, k, v = (t.reshape(b, s, 4, 16) for t in jnp.split(out, 3, axis=-1))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q, k = unit(q) * 16 ** -0.5, unit(k)
    f = (
        dense("f_up", dense("f_down", u)).astype(F32)
        + p["dt_bias"].astype(F32)
    ).reshape(b, s, 4, 16)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(f)
    live = jnp.ones((1, s), bool)
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(
        live[..., None], 2.0 * jax.nn.sigmoid(dense("b_proj", u).astype(F32)),
        0.0,
    )
    o, state = jax.vmap(kda_chunked)(
        q, k, v, g, beta, jnp.zeros((b, 4, 16, 16), F32)
    )
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + spec.norm_eps
    ) * p["norm_scale"].astype(F32)
    gate = jax.nn.sigmoid(dense("g_up", dense("g_down", u)).astype(F32))
    want = dense("out_proj", o.reshape(b, s, 64) * gate)
    got, (got_state, _) = mixer.apply(params, u, None, None, method="scan")
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_state, state)


# -- the block: gate, sandwich norms, clamp ------------------------------------

LATENT = LatentSpec(q_rank=24, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16)


def test_the_gate_is_on_every_schedule_of_a_latent_block():
    """Expanded attention (the full forward and ``prefill``) and the
    absorbed forms (a chunk pass and a decode step over the latent
    pool) agree with the gate on, and the gate is there: a zero
    ``W_g`` halves the attention's output."""
    spec = BlockSpec(64, 4, 128, norm="rmsnorm", bias=False,
                     rope_base=1e4, latent=LATENT, attn_gate=True)
    block = DecoderBlock(spec)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * PAGE + 1, 64))
    v = block.init(jax.random.PRNGKey(1), x)
    assert v["params"]["attn"]["gate"]["kernel"].shape == (64, 4 * 16)
    full = jax.jit(block.apply)(v, x)
    pre, rows, _ = jax.jit(
        lambda v, x: block.apply(v, x, PAGE, method="prefill")
    )(v, x[:, :PAGE])
    np.testing.assert_allclose(pre, full[:, :PAGE], atol=2e-5)
    pool = jnp.zeros((5, LATENT.row, PAGE))
    pages = jnp.asarray([3, 1, 4])
    pool = pool.at[3].set(rows[0].T)
    got, pool = jax.jit(lambda v, x, pool, pages: block.apply(
        v, x, pool, pages, PAGE, method="prefill_chunk_paged"
    ))(v, x[:, PAGE: 2 * PAGE], pool, pages[:2])
    np.testing.assert_allclose(got, full[:, PAGE: 2 * PAGE], atol=2e-5)
    got, _ = jax.jit(lambda v, x, pool, pages, at: block.apply(
        v, x, pool, pages, at, method="decode_step_paged"
    ))(v, x[:, 2 * PAGE:], pool, pages[None], jnp.asarray([2 * PAGE]))
    np.testing.assert_allclose(got, full[:, 2 * PAGE:], atol=2e-5)
    attn = jax.jit(lambda p: block.apply(
        {"params": p}, x, method=lambda m, t: m.attn(m.ln1(t))
    ))
    shut = {**v["params"], "attn": {
        **v["params"]["attn"], "gate": {"kernel": jnp.zeros((64, 64))},
    }}
    ungated = DecoderBlock(BlockSpec(
        64, 4, 128, norm="rmsnorm", bias=False, rope_base=1e4, latent=LATENT,
    ))
    plain = {k: t for k, t in v["params"]["attn"].items() if k != "gate"}
    want = ungated.apply(
        {"params": {**v["params"], "attn": plain}}, x,
        method=lambda m, t: m.attn(m.ln1(t)),
    )
    np.testing.assert_allclose(attn(shut), 0.5 * want, atol=1e-6)
    assert float(jnp.abs(attn(v["params"]) - 0.5 * want).max()) > 1e-3


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


@pytest.mark.parametrize("mixer", ["attention", "latent", "linear"])
def test_norms_stand_on_both_sides_of_both_sub_layers(mixer):
    """``h = x + N2(mixer(N1(x)))``, ``y = h + N4(mlp(N3(h)))`` for
    every mixer kind, the clamp in the MLP biting (inputs scaled until
    ``gate`` and ``up`` pass the limit)."""
    kind = dict(
        attention={}, latent=dict(latent=LATENT, rope_base=1e4),
        linear=dict(linear=GDN),
    )[mixer]
    spec = BlockSpec(
        32 if mixer != "latent" else 64, 4, 48, norm="rmsnorm", bias=False,
        mlp="gated_silu", swiglu_limit=0.5, sandwich_norm=True, **kind,
    )
    block = DecoderBlock(spec)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 9, spec.dim))
    v = block.init(jax.random.PRNGKey(1), x)
    p = dict(v["params"])
    for n, name in enumerate(("ln1", "ln1_post", "ln2", "ln2_post")):
        p[name] = {"scale": 1.0 + 0.1 * (n + 1) * jnp.ones((spec.dim,))}
    p["mlp_gate"] = {"kernel": 4.0 * p["mlp_gate"]["kernel"]}
    got = block.apply({"params": p}, x)
    name = "mixer" if mixer == "linear" else "attn"
    m = block.apply(
        {"params": p}, _rms(x, p["ln1"]["scale"]),
        method=lambda mod, u: getattr(mod, name)(u),
    )
    h = x + _rms(m, p["ln1_post"]["scale"])
    u = _rms(h, p["ln2"]["scale"])
    gate, up = u @ p["mlp_gate"]["kernel"], u @ p["mlp_in"]["kernel"]
    assert float((gate > 0.5).mean()) > 0.1  # the clamp bites
    assert float((jnp.abs(up) > 0.5).mean()) > 0.1
    f = (
        nn.silu(jnp.minimum(gate, 0.5)) * jnp.clip(up, -0.5, 0.5)
    ) @ p["mlp_out"]["kernel"]
    want = h + _rms(f, p["ln2_post"]["scale"])
    np.testing.assert_allclose(got, want, atol=2e-5)
    unclamped = (nn.silu(gate) * up) @ p["mlp_out"]["kernel"]
    assert float(jnp.abs(f - unclamped).max()) > 0.1


def test_no_limit_is_no_clamp_and_the_spec_says_where_one_applies():
    t = jnp.asarray([20.0, -20.0, 3.0])
    assert limited(t, None) is t and limited(t, None, both=True) is t
    np.testing.assert_array_equal(limited(t, 10.0), [10.0, -20.0, 3.0])
    np.testing.assert_array_equal(
        limited(t, 10.0, both=True), [10.0, -10.0, 3.0]
    )
    with pytest.raises(ValueError, match="swiglu_limit clamps a gated_silu"):
        BlockSpec(32, 4, 64, swiglu_limit=10.0)
    with pytest.raises(ValueError, match="BOTH sides"):
        BlockSpec(32, 4, 64, sandwich_norm=True, post_norm=True)
    with pytest.raises(ValueError, match="linear-attention"):
        BlockSpec(32, 4, 64, linear=GDN, post_norm=True)
    with pytest.raises(ValueError, match="low-rank pair"):
        KdaSpec(heads=4, head_dim=8, rank=None)
    with pytest.raises(ValueError, match="not divisible by key_heads"):
        KdaSpec(heads=4, head_dim=8, rank=4, key_heads=3)


def test_the_32_shares_of_a_clamped_layer_add_up_to_the_whole():
    """32 chips, 2 of 64 experts each, route over all 64 at top-8 times
    2.5: the routed parts of the 32 shares plus the shared expert ONCE
    are the uncut layer, the clamp biting in every expert, and the
    uncut layer is the plain reference's (GigaChat3.5's cut, at small
    widths)."""
    from chipbench import gigachat35_reference as ref

    d, hid, n_exp, k, held, limit = 24, 16, 64, 8, 2, 0.5
    kw = dict(score="sigmoid", normalize=True, scale=2.5, select_bias=True,
              shared_dim=hid, swiglu_limit=limit)
    whole = ExpertSpec(n_exp, hid, k, **kw)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 9, d))
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
    np.testing.assert_allclose(total, full, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(params, x, k, 2.5, 0, limit)
        loose, _ = ref._experts(params, x, k, 2.5, 0, None)
    np.testing.assert_allclose(full, want, atol=2e-5)
    assert float(jnp.abs(want - loose).max()) > 0.1  # the clamp bit


# -- the served model ----------------------------------------------------------


@pytest.fixture(scope="module")
def built():
    """The configuration's rehearsal model (a dense GDN block, a sparse
    gated MLA block, a sparse GDN block) in float32."""
    from chipbench import gigachat35

    config = json.loads(
        (ROOT / "chipbench/configs/gigachat3.5-432b-a28b.json").read_text()
    )
    model = {**config["model"], **config["rehearse"]["model"],
             "positions_served": 256}
    return gigachat35.build(model, "float32", 7)


def _batcher(lm, variables, **kw):
    return ContinuousBatcher(
        lm, variables, slots=3, chunk=CHUNK, kv_layout="paged",
        page_size=PAGE, prefill_chunk=2 * PAGE,
        prompt_buckets=(32, 64, 128), **kw,
    )


def test_a_request_owns_states_and_latent_pages(built):
    lm, variables, shape = built
    specs = [lm.graph.node(n).module.spec for n in lm.block_names]
    assert [s.linear is not None for s in specs] == [True, False, True]
    assert [s.mlp for s in specs] == ["gated_silu", "experts", "experts"]
    assert all(s.sandwich_norm for s in specs) and specs[1].attn_gate
    assert specs[0].swiglu_limit == 10.0
    assert specs[1].experts.swiglu_limit == 10.0
    layout = cache_layout(specs)
    assert len(layout.groups) == 1 and layout.groups[0].blocks == (1,)
    assert layout.groups[0].row == specs[1].latent.row
    assert layout.state_blocks == (0, 2) and layout.latent_blocks == (1,)
    assert shape["layers"] == 1 and shape["kda_layers"] == 2
    srv = _batcher(lm, variables)
    assert srv._caches[0] is None and srv._caches[2] is None
    assert srv._caches[1].shape[1:] == (specs[1].latent.row, PAGE)
    per_slot = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in GDN.state_shapes(1, jnp.float32)
    )
    assert srv.stats()["state_bytes"] == 2 * 3 * per_slot
    assert srv.stats()["prefix_cache"] == "off: recurrent state"
    srv.close()


PROMPTS = (20, 50, 75)


@pytest.fixture(scope="module")
def served(built):
    """ONE batcher serves three prompts one after another: a
    whole-prompt prefill (20 in a bucket of 32), a chunked prefill of
    two passes and one of three (state and tail carried pass to pass,
    latent rows written into the slot's pages), each followed by decode
    steps beside dead rows (a page of 16 is no kernel's: the plain arms;
    the kernels are held above and in ``test_mla_mhc.py``)."""
    lm, variables, shape = built
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
    from chipbench import gigachat35_reference as ref

    lm, variables, _ = built
    ids, got, c = served[prompt_len]
    n = ids.shape[1]
    # causal: padded to one length, the three prompts share one
    # compiled reference and one full forward
    ids = np.pad(ids, ((0, 0), (0, max(PROMPTS) + 10 - n)))
    want, gaps = ref.logprobs_and_gaps(variables, ids)
    np.testing.assert_allclose(
        got, np.asarray(want)[0, prompt_len - 1: n - 1], atol=2e-4
    )
    assert gaps.shape == (2, 1, ids.shape[1] - 1)  # two sparse layers
    lp = jax.nn.log_softmax(logits_full(lm, variables, jnp.asarray(ids)), -1)
    full = np.take_along_axis(
        np.asarray(lp[0, :-1]), ids[0, 1:, None], -1
    )[:, 0]
    np.testing.assert_allclose(
        full[: n - 1], np.asarray(want)[0, : n - 1], atol=2e-4
    )
    passes = -(-prompt_len // (2 * PAGE)) if prompt_len > 2 * PAGE else 1
    assert c["kda.state_writes"] == passes
    assert c.get("kda.chunks_carried", 0) == passes - 1
    assert c["kda.steps"] == 2 * 3 * CHUNK  # two GDN layers, three ticks
    assert c["mla.steps"] == 3 * CHUNK  # one latent layer
    assert c["moe.steps"] == 3 * CHUNK


def test_the_references_faults_read_wrong(built):
    from chipbench import gigachat35_reference as ref

    _, variables, shape = built
    ids = np.random.default_rng(0).integers(
        0, shape["vocab"], size=(2, 48)
    ).astype(np.int32)
    sound, _ = ref.logprobs_and_gaps(variables, ids)
    for fault in (*ref.CONTROLS, "no_gate"):
        wrong, _ = ref.logprobs_and_gaps(
            variables, ids, fault, reset_at=(30, 40)
        )
        assert float(jnp.abs(wrong - sound).max()) > 0.05, fault
    # at seeded weights the clamp never bites: gate and up are N(0, 1)
    loose, _ = ref.logprobs_and_gaps(variables, ids, "no_clamp")
    np.testing.assert_array_equal(loose, sound)
    with pytest.raises(ValueError, match="unknown fault"):
        ref.logprobs_and_gaps(variables, ids, "drop_rope")
    logp, sure = ref.next_token_logprobs(variables, ids)
    assert sure.dtype == bool and sure.shape == logp.shape


def test_preemption_re_prefills_states_and_latent_pages(built):
    """One slot: a low-priority request mid-decode is preempted for a
    high-priority one and served again by whole re-prefill (states
    written anew, latent rows into fresh pages); both streams are what
    each gets alone."""
    from adapt_tpu.config import SchedulerConfig, SLOSpec

    lm, variables, shape = built
    low_p, hi_p = (
        np.random.default_rng(s).integers(0, shape["vocab"], 40)
        .astype(np.int32) for s in (1, 2)
    )
    kw = dict(slots=1, chunk=2, kv_layout="paged", page_size=PAGE,
              prefill_chunk=2 * PAGE, prompt_buckets=(32, 64, 128))
    ref = ContinuousBatcher(lm, variables, **kw)
    alone = []
    for p, n in ((low_p, 16), (hi_p, 6)):
        rid = ref.submit(p, n)
        alone.append(ref.run()[rid])
    ref.close()
    srv = ContinuousBatcher(lm, variables, scheduler=SchedulerConfig(
        preempt=True, preempt_ttft_fraction=0.5, degrade=False
    ), **kw)
    low = srv.submit(low_p, 16, slo=SLOSpec(tenant="free", priority=0))
    for _ in range(3):
        srv.tick()
    hi = srv.submit(hi_p, 6, slo=SLOSpec(
        ttft_budget_s=1e-4, tenant="gold", priority=10
    ))
    out = srv.run()
    assert srv.stats()["preempted"] == 1
    np.testing.assert_array_equal(out[low], alone[0])
    np.testing.assert_array_equal(out[hi], alone[1])
    srv.close()


def test_a_refusal_names_both_caches(built):
    from adapt_tpu.models.transformer_lm import lm_tiny

    lm, variables, _ = built
    draft = lm_tiny(vocab=512, max_len=256)
    dvars = draft.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    both = "recurrent state \\(2 blocks.*\\) and a latent cache \\(1 blocks"
    with pytest.raises(ValueError, match="a draft model.*" + both):
        _batcher(lm, variables, draft_lm=draft, draft_variables=dvars)
    with pytest.raises(ValueError, match="quantized KV pool.*" + both):
        _batcher(lm, variables, kv_cache_dtype="int8")
    srv = _batcher(lm, variables)
    with pytest.raises(ValueError, match="handoff.*" + both):
        srv.adopt_prefill_pages(np.arange(40, dtype=np.int32), [], PAGE, False)
    # a feature that needs pages only is refused for the state alone
    with pytest.raises(ValueError, match="recurrent state") as e:
        srv.prefix_cached(np.arange(40, dtype=np.int32))
    assert "latent cache" not in str(e.value)
    srv.close()
