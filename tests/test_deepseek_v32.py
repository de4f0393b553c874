"""Latent attention that SELECTS what it reads (a lightning indexer
with a key cache of its own, DeepSeek-V3.2-Exp) and the group-limited
router beside it: the selecting decode kernel against the dense latent
oracle over the positions written out, the four schedules (full
forward, whole-prompt prefill, chunked prefill, paged decode through
both planes) against the plain reference with ``top_k`` under, at and
over the context, a preempted and re-admitted request, what the shares
of 32 chips add up to under the group limit, what the batcher keeps,
counts and refuses for a cache of two planes. CPU, at toy widths; the
kernels run interpreted."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.mla import IndexSpec, LatentSpec
from adapt_tpu.models.moe import ExpertSpec, RoutedExperts, route
from adapt_tpu.models.transformer_lm import generate, logits_full
from adapt_tpu.ops import sparse_latent_attention as sp
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.latent_attention import (
    latent_attention_reference,
    pages_to_rows,
    rows_to_pages,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import alloc_kv_pools, cache_layout
from adapt_tpu.utils.metrics import global_metrics
from conftest import drained

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 16, 4


# -- the kernel ----------------------------------------------------------------


def _kernel_operands(slots, pps, page=128, heads=4, row=40, j=16, d=16, seed=0):
    # 16 index heads: a score is exactly 0 (a tie) one position in 65,536
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = slots * pps + 1
    table = (
        jax.random.permutation(ks[0], n - 1)[: slots * pps].reshape(slots, pps)
        + 1
    ).astype(jnp.int32)
    return (
        jax.random.normal(ks[1], (slots, heads, row)),
        jax.random.normal(ks[2], (slots, j, d)),
        jax.random.normal(ks[3], (slots, j)),
        jax.random.normal(ks[4], (n, row, page)),
        jax.random.normal(ks[5], (n, d, page)),
        table,
    )


@pytest.mark.parametrize("pps,index,top_k", [
    (1, (5, 127, -1), 64),  # one page: under, over, a dead row
    (2, (255, 129, 0), 100),
    (3, (200, -1, 383), 300),  # a ragged last iteration of the walk
    (4, (511, 300, 100), 128),
    (4, (511, 300, 100), 1000),  # no context reaches the top-k
])
def test_the_selecting_decode_is_the_dense_oracle_over_the_listed_positions(
    pps, index, top_k
):
    """The interpreted kernel against ``latent_attention_reference``
    with the selection WRITTEN OUT: each slot's ``top_k`` best-scoring
    live positions (a plain ``lax.top_k`` over the index scores) copied
    into fresh pages, in order, and attended whole."""
    q, q_i, w, pool, ipool, table = _kernel_operands(3, pps)
    idx = jnp.asarray(index, jnp.int32)
    got = sp.sparse_latent_paged_attention(
        q, q_i, w, pool, ipool, table, idx, sm_scale=0.3, v_width=32,
        top_k=top_k, prefer="pallas",
    )
    assert kernel_dispatch_stats()["sparse_latent_decode"][
        "positions_minor"] == 1.0
    rows = np.asarray(pages_to_rows(pool[table]).reshape(3, pps * 128, -1))
    keys = np.asarray(pages_to_rows(ipool[table]).reshape(3, pps * 128, -1))
    listed = np.zeros((3, pps * 128, rows.shape[-1]), np.float32)
    count = []
    for s in range(3):
        n = index[s] + 1
        scores = np.maximum(
            np.einsum("jd,ld->jl", np.asarray(q_i[s]), keys[s, :n]), 0.0
        ).T @ np.asarray(w[s])
        chosen = np.sort(np.argsort(-scores)[:top_k])
        listed[s, : len(chosen)] = rows[s, chosen]
        count.append(len(chosen) - 1)
    assert count == [min(i + 1, top_k) - 1 for i in index]
    own = np.arange(3 * pps).reshape(3, pps) + 1
    fresh = jnp.concatenate([
        jnp.zeros((1, rows.shape[-1], 128)),
        rows_to_pages(jnp.asarray(listed).reshape(-1, rows.shape[-1]), 128),
    ])
    want = latent_attention_reference(
        q, fresh, jnp.asarray(own), jnp.asarray(count), 0.3, 32
    )
    np.testing.assert_allclose(got, want, atol=2e-5)
    oracle = sp.sparse_latent_paged_attention(
        q, q_i, w, pool, ipool, table, idx, sm_scale=0.3, v_width=32,
        top_k=top_k, prefer="xla",
    )
    np.testing.assert_allclose(got, oracle, atol=2e-5)
    assert not np.asarray(got)[np.asarray(index) < 0].any()  # dead: zeros


def test_the_threshold_is_the_kth_largest_and_ties_at_it_are_kept():
    scores = jnp.asarray([
        [3.0, -1.0, 7.0, 0.0, -0.0, 2.5, -jnp.inf, 2.5],
        [1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0],
    ])
    keys = sp.order_keys(scores)
    a, ka = np.asarray(scores[0]), np.asarray(keys[0])
    assert ((a[:, None] < a[None, :]) <= (ka[:, None] < ka[None, :])).all()
    for k, want in ((1, 7.0), (3, 2.5), (4, 2.5), (5, 0.0), (8, -np.inf)):
        t = sp.kth_largest_key(keys[:1], k)
        assert float(scores[0][keys[0] >= t[0]].min()) == want, k
    live = jnp.ones_like(scores, bool)
    assert np.asarray(sp.select(scores, live, 3)).sum(1).tolist() == [4, 8]
    few = live.at[:, 2:].set(False)  # two live positions, top-3: both
    assert np.asarray(sp.select(scores, few, 3)).sum(1).tolist() == [2, 2]


def test_the_masked_form_in_blocks_is_the_plain_selection(monkeypatch):
    """Several blocks of queries and keys, a window longer than the
    last query, against one dense pass."""
    monkeypatch.setattr(sp, "_Q_BLOCK", 8)
    monkeypatch.setattr(sp, "_K_BLOCK", 16)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    h, c, n, row, v, top_k = 3, 20, 50, 12, 8, 7
    q = jax.random.normal(ks[0], (h, c, row))
    q_i = jax.random.normal(ks[1], (c, 16, 6))  # 16 heads: no score ties at 0
    w = jax.random.normal(ks[2], (c, 16))
    rows = jax.random.normal(ks[3], (n, row))
    keys = jax.random.normal(ks[4], (n, 6))
    q_pos = 17 + jnp.arange(c)
    got = sp.selected_latent_attention(
        q, q_i, w, rows, keys, q_pos, 0.4, v, top_k
    )
    chosen = sp.select(
        sp.index_scores(q_i, w, keys), jnp.arange(n)[None] <= q_pos[:, None],
        top_k,
    )
    assert np.asarray(chosen).sum(1).tolist() == [top_k] * c
    s = jnp.where(chosen[None], jnp.einsum("hcw,lw->hcl", q, rows) * 0.4,
                  -jnp.inf)
    want = jnp.einsum("hcl,lv->hcv", jax.nn.softmax(s, -1), rows[:, :v])
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the router ----------------------------------------------------------------


def test_the_group_limited_choice_is_the_one_written_out():
    n_exp, k, groups = 32, 4, (8, 3)
    logits = jax.random.normal(jax.random.PRNGKey(0), (50, n_exp)) * 2
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (n_exp,))
    kw = dict(score="sigmoid", normalize=True, scale=2.5, select_bias=True)
    idx, w = route(ExpertSpec(n_exp, 8, k, groups=groups, **kw), logits, bias)
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    chosen = s + np.asarray(bias, np.float64)
    for t in range(50):
        per = chosen[t].reshape(8, 4)
        score = np.sort(per, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score)[:3]
        allowed = np.full(n_exp, -np.inf)
        for g in kept:
            allowed[4 * g: 4 * g + 4] = chosen[t, 4 * g: 4 * g + 4]
        want = np.argsort(-allowed)[:k]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(want.tolist())
        np.testing.assert_allclose(
            np.sort(np.asarray(w[t])),
            np.sort(2.5 * s[t, want] / s[t, want].sum()), rtol=1e-5,
        )
    # no limit, said either way, is the router that was
    plain = route(ExpertSpec(n_exp, 8, k, **kw), logits, bias)
    one = route(ExpertSpec(n_exp, 8, k, groups=(1, 1), **kw), logits, bias)
    all_kept = route(ExpertSpec(n_exp, 8, k, groups=(8, 8), **kw), logits, bias)
    for other in (one, all_kept):
        np.testing.assert_array_equal(other[0], plain[0])
        np.testing.assert_array_equal(other[1], plain[1])
    limited = np.asarray(idx) != np.asarray(plain[0])
    assert limited.any()  # the limit bit somewhere
    with pytest.raises(ValueError, match="groups="):
        ExpertSpec(n_exp, 8, k, groups=(8, 0))
    with pytest.raises(ValueError, match="groups="):
        ExpertSpec(n_exp, 8, 8, groups=(8, 1))  # 4 experts cannot hold top-8


def test_the_32_shares_of_a_group_limited_layer_add_up_to_the_whole():
    """32 chips, 2 of 64 experts each, route over all 64 (8 groups, 4
    kept) at top-8 times 2.5: the routed parts of the 32 shares plus the
    shared expert ONCE are the uncut layer, and the uncut layer is the
    plain reference's (DeepSeek-V3.2's cut, at small widths)."""
    from chipbench import deepseek_v32_reference as ref

    d, hid, n_exp, k, held = 24, 16, 64, 8, 2
    kw = dict(score="sigmoid", normalize=True, scale=2.5, select_bias=True,
              shared_dim=hid, groups=(8, 4))
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
    np.testing.assert_allclose(total, full, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        want, gap = ref._experts(params, x, k, 2.5, 0, 8, 4)
        loose, _ = ref._experts(params, x, k, 2.5, 0, 1, 1)
    np.testing.assert_allclose(full, want, atol=2e-5)
    assert gap.shape == x.shape[:2] and bool((gap > 0).all())
    assert float(jnp.abs(want - loose).max()) > 1e-4  # the limit bit


# -- the served model ----------------------------------------------------------


def _model(top_k, **over):
    config = json.loads(
        (ROOT / "chipbench/configs/deepseek-v3.2-exp.json").read_text()
    )
    return {**config["model"], **config["rehearse"]["model"],
            "num_hidden_layers": 3,
            "mlp_layer_types": ["dense", "sparse", "sparse"],
            "positions_served": 256, "index_topk": top_k, **over}


def _arch(top_k):
    return dict(index_topk=top_k)


@functools.lru_cache(maxsize=None)
def _built(top_k):
    from chipbench import deepseek_v32

    return (top_k, *deepseek_v32.build(_model(top_k), "float32", 7))


@pytest.fixture(scope="module", params=[24, 85, 200])
def built(request):
    """The configuration's rehearsal model (a dense block and two sparse
    ones, every one selecting) in float32, at a ``top_k`` under the
    contexts served (20 to 85), at the longest of them, and over it."""
    return _built(request.param)


@pytest.fixture(scope="module")
def under():
    """The model whose ``top_k`` every served context passes."""
    return _built(24)


def _batcher(lm, variables, **kw):
    kw = {**dict(slots=3, chunk=CHUNK, kv_layout="paged", page_size=PAGE,
                 prefill_chunk=2 * PAGE, prompt_buckets=(32, 64, 128)), **kw}
    return ContinuousBatcher(lm, variables, **kw)


PROMPTS = (20, 75)


@pytest.fixture(scope="module")
def served(built):
    """ONE batcher serves a whole-prompt prefill (20 in a bucket of 32)
    and a chunked one of three passes (rows and index keys written into
    the slot's pages of both planes pass by pass), each followed by ten
    decode steps beside dead rows (a page of 16 is no kernel's: the
    plain arms; the kernel is held above)."""
    _, lm, variables, shape = built
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
    """Prefill then decode through both planes, the program's full
    forward and the plain reference agree; the counters say what was
    scored and what was read."""
    from chipbench import deepseek_v32_reference as ref

    top_k, lm, variables, _ = built
    ids, got, c = served[prompt_len]
    n = ids.shape[1]
    ids = np.pad(ids, ((0, 0), (0, max(PROMPTS) + 10 - n)))
    want, gaps = ref.logprobs_and_gaps(variables, ids, arch=_arch(top_k))
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
    ticks = 3  # ten tokens: the prefill's, then three scans of 4
    assert c["dsa.steps"] == c["mla.steps"] == 3 * ticks * CHUNK
    ctx = prompt_len + 1 + np.arange(ticks * CHUNK)
    assert c["dsa.positions_scored"] == 3 * ctx.sum()
    assert c["dsa.positions_selected"] == 3 * np.minimum(ctx, top_k).sum()
    if top_k < prompt_len:  # the selection dropped positions: seen
        dense, _ = ref.logprobs_and_gaps(
            variables, ids, "drop_selection", arch=_arch(top_k)
        )
        assert float(jnp.abs(dense - want)[0, : n - 1].max()) > 0.01


def test_a_chunk_pass_is_the_whole_prompt_program(built, served):
    """The prompt of 75 served in three passes (above) and by ONE
    whole-prompt program (a bucket of 128 under a chunk of 128)."""
    _, lm, variables, _ = built
    ids, chunked, _ = served[75]
    srv = drained(_batcher(lm, variables, prefill_chunk=8 * PAGE))
    rid = srv.submit(ids[0, :75], 10)
    out = srv.run()[rid]
    np.testing.assert_array_equal(out, ids[0, 75:])
    np.testing.assert_allclose(
        np.asarray(srv.logprobs(rid)), chunked, atol=2e-5
    )
    srv.close()


def test_a_request_owns_pages_of_two_planes(under):
    top_k, lm, variables, shape = under
    specs = [lm.graph.node(n).module.spec for n in lm.block_names]
    lat = specs[0].latent
    assert lat.index == IndexSpec(heads=4, dim=16, rope_dim=8, top_k=top_k)
    assert [s.mlp for s in specs] == ["gated_silu", "experts", "experts"]
    assert specs[1].experts.groups == (8, 4)
    layout = cache_layout(specs)
    (group,) = layout.groups
    assert (group.row, group.index_row) == (lat.row, 16)
    assert group.position_values == lat.row + 16
    assert layout.latent_blocks == layout.selecting_blocks == (0, 1, 2)
    assert layout.lacks("one_plane")[0] == "a selecting cache"
    srv = _batcher(lm, variables)
    for rows, keys in srv._caches:
        assert rows.shape[1:] == (lat.row, PAGE)
        assert keys.shape == (rows.shape[0], 16, PAGE)
    stats = srv.stats()
    assert stats["pool_row_values"] == lat.row + 16
    assert stats["pool_row_bytes"] == 4 * (lat.row + 16)
    pool = sum(x.nbytes for x in jax.tree.leaves(srv._caches))
    assert pool == 3 * stats["pool_pages"] * PAGE * 4 * (lat.row + 16)
    assert stats["prefix_cache"] == "off: a selecting cache"
    srv.close()
    assert shape["index_row"] == 16 and shape["index_topk"] == top_k
    # the block that was: no index, one plane, no second row in the key
    plain = alloc_kv_pools(5, 1, PAGE, 8, jnp.float32, row=lat.row)
    assert plain.shape == (5, lat.row, PAGE)
    with pytest.raises(ValueError, match="index.rope_dim"):
        LatentSpec(24, 32, 16, 8, 16, index=IndexSpec(4, 16, 4, 8))


def test_preemption_re_prefills_both_planes(under):
    """One slot: a low-priority request mid-decode is preempted for a
    high-priority one and served again by whole re-prefill (rows and
    index keys into fresh pages); both streams are what each gets
    alone."""
    from adapt_tpu.config import SchedulerConfig, SLOSpec

    _, lm, variables, shape = under
    low_p, hi_p = (
        np.random.default_rng(s).integers(0, shape["vocab"], 40)
        .astype(np.int32) for s in (1, 2)
    )
    kw = dict(slots=1, chunk=2)
    alone_srv = _batcher(lm, variables, **kw)
    alone = []
    for p, n in ((low_p, 16), (hi_p, 6)):
        rid = alone_srv.submit(p, n)
        alone.append(alone_srv.run()[rid])
    alone_srv.close()
    srv = _batcher(lm, variables, scheduler=SchedulerConfig(
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


def test_what_a_selecting_cache_cannot_do_is_refused_by_name(under):
    from adapt_tpu.config import SchedulerConfig
    from adapt_tpu.models.transformer_lm import lm_tiny

    _, lm, variables, _ = under
    draft = lm_tiny(vocab=512, max_len=256)
    dvars = draft.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    both = ("a latent cache \\(3 blocks.*\\) and a selecting cache \\(3 "
            "blocks keep a 16-value index key")
    with pytest.raises(ValueError, match="a draft model.*" + both):
        _batcher(lm, variables, draft_lm=draft, draft_variables=dvars)
    with pytest.raises(ValueError, match="quantized KV pool.*" + both):
        _batcher(lm, variables, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="cache-aware admission.*selecting"):
        _batcher(lm, variables, scheduler=SchedulerConfig(cache_aware=True))
    srv = _batcher(lm, variables)
    ids = np.arange(40, dtype=np.int32)
    with pytest.raises(ValueError, match="handoff.*" + both):
        srv.adopt_prefill_pages(ids, [], PAGE, False)
    # what shares a prompt page is refused for the second plane alone
    with pytest.raises(ValueError, match="radix prefix cache.*selecting") as e:
        srv.prefix_cached(ids)
    assert "latent cache" not in str(e.value)
    with pytest.raises(ValueError, match="copy-on-write fan-out.*selecting"):
        srv.submit_fanout(ids, 4, 2)
    srv.close()
    with pytest.raises(ValueError, match="an index key beside it"):
        generate(lm, variables, jnp.zeros((1, 4), jnp.int32), 2)
