"""Xing4.0-29B-A4B's two mechanisms at a tiny size on the CPU: a latent
(MLA) paged cache with no head axis served in the absorbed form, and a
four-stream mHC residual; the block against the benchmark's plain
reference; and everything that moves per-head pages refusing the
model."""

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import (
    CacheTierConfig,
    KernelConfig,
    ParallelConfig,
    PrefillConfig,
)
from adapt_tpu.models.mhc import HyperConnection, HyperSpec, sinkhorn
from adapt_tpu.models.mla import LatentSpec
from adapt_tpu.models.moe import ExpertSpec, RoutedExperts
from adapt_tpu.models.rope import YarnSpec, yarn_frequencies
from adapt_tpu.models.transformer_lm import (
    BlockSpec,
    chosen_logprob,
    generate,
    logits_full,
    transformer_lm,
)
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.latent_attention import (
    append_latent_paged,
    latent_attention_reference,
    latent_paged_attention,
    latent_pages_per_step,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import alloc_kv_pools, cache_groups
from chipbench import xing4_reference as ref
from chipbench import xing4_yardstick as xy

PAGE = 128
YARN = YarnSpec(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
LATENT = LatentSpec(24, 32, 16, 8, 16, yarn=YARN)
STREAMS = HyperSpec(4)
#: top-4 and scaling 2: the constants ``xing4_reference.ARCH`` holds.
EXPERTS = ExpertSpec(
    16, 16, 4, score="sigmoid", normalize=True, scale=2.0, select_bias=True,
    shared_dim=16, held=(0, 4),
)


def _spec(sparse, latent=LATENT, streams=STREAMS):
    return BlockSpec(
        32, 4, 64, norm="rmsnorm", bias=False,
        mlp="experts" if sparse else "gated_silu",
        experts=EXPERTS if sparse else None, rope_base=10000.0,
        latent=latent, streams=streams,
    )


@pytest.fixture(scope="module")
def built():
    lm = transformer_lm(
        128, blocks=[_spec(False), _spec(True)], pos="none", max_len=512
    )
    key = jax.random.PRNGKey(0)
    variables = lm.graph.init(key, jnp.zeros((1, 8), jnp.int32))
    # Selection biases and mHC's b away from zero: every term is read.
    variables = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(
            jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 997),
            x.shape,
        ) if jax.tree_util.keystr(path).endswith(("['b']", "['router_bias']"))
        else x,
        variables,
    )
    return lm, variables


def _batcher(lm, variables, **kw):
    return ContinuousBatcher(
        lm, variables, slots=4, chunk=4, page_size=PAGE, prefill_chunk=PAGE,
        prompt_buckets=(128, 256, 384), **kw,
    )


# -- (a) the absorbed decode through the latent paged cache -------------------


@pytest.mark.parametrize("prompt,impl", [
    (40, None), (300, None), (300, "pallas"),
], ids=["whole-prompt-xla", "chunked-prefill-xla", "chunked-prefill-kernels"])
def test_served_logprobs_equal_the_expanded_full_forward(built, prompt, impl):
    """Whole-prompt prefill (expanded), chunked prefill and decode
    (both absorbed, over the latent pool) under the overlapped tick,
    through ``ContinuousBatcher``, against ``logits_full``."""
    lm, variables = built
    srv = _batcher(lm, variables, kernel=KernelConfig(attn_impl=impl))
    ids = np.random.default_rng(prompt).integers(0, 128, prompt)
    rid = srv.submit(ids, 9)
    toks = srv.run()[rid]
    assert not srv.stats()["inflight"]
    full = logits_full(lm, variables, jnp.asarray(np.concatenate([ids, toks]))[None])[0]
    at = slice(prompt - 1, prompt - 1 + len(toks))
    assert (np.asarray(jnp.argmax(full[at], -1)) == toks).all()
    want = chosen_logprob(full[at], jnp.asarray(toks))
    np.testing.assert_allclose(srv.logprobs(rid), want, atol=2e-5)
    books = kernel_dispatch_stats()
    path = "pallas" if impl else "xla"
    assert books["latent_decode"][path] and books["latent_write"][path]
    srv.close()


def test_the_decode_kernel_reads_ragged_and_dead_rows_like_the_oracle():
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(9, 40, PAGE)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, 4, 40)), jnp.float32)
    table = jnp.asarray(
        [[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0], [8, 0, 0, 0]], jnp.int32
    )
    index = jnp.asarray([500, 130, 0, -5], jnp.int32)  # the last row is dead
    args = dict(sm_scale=0.3, v_width=32)
    got = latent_paged_attention(q, pool, table, index, prefer="pallas", **args)
    want = latent_attention_reference(q, pool, table, index, **args)
    np.testing.assert_allclose(got[:3], want[:3], atol=1e-5)
    assert not np.asarray(got[3]).any()  # a dead row reads nothing
    # the pages an iteration of the walk covers: what the budget holds,
    # at most a slot's own
    assert latent_pages_per_step(64, 128, 576, 2) == 8
    assert latent_pages_per_step(4, 128, 576, 2) == 4


def _ragged(rng, slots, pages_per_slot, index):
    """A table whose slot i owns the pages its index needs (the rest
    trash, page 0) and a pool of ``slots * pages_per_slot + 1``."""
    table = np.zeros((slots, pages_per_slot), np.int32)
    free = iter(rng.permutation(slots * pages_per_slot) + 1)
    for i, idx in enumerate(index):
        live = max(idx, -1) // PAGE + 1 if idx >= 0 else 0
        table[i, :live] = [next(free) for _ in range(live)]
    return jnp.asarray(table)


@pytest.mark.parametrize("pages_per_slot,pages", [
    (16, 4), (7, 2), (5, 4), (16, 1),
], ids=["4-steps", "ragged-last-step", "2-steps-3-pages-short", "a-page-a-step"])
def test_the_decode_kernel_carries_its_softmax_across_grid_steps(
    pages_per_slot, pages,
):
    """The path a long context takes and a short one does not (a
    "step" is an iteration of the kernel's walk since PR 44, ``pages``
    pages each): the online-softmax carry past iteration 0, the walk
    ending where the slot's pages end, and the last, shorter iteration
    taken in halving groups of its live pages. Indices that end in iteration 0, on an
    iteration's first and last position, mid-way and in the last one,
    beside a dead row."""
    from adapt_tpu.ops.latent_attention import _latent_impl

    rng = np.random.default_rng(pages_per_slot * 8 + pages)
    last = pages_per_slot * PAGE - 1
    step = pages * PAGE
    index = [0, 77, step - 1, step, step + PAGE + 5, last - step // 2,
             last, -1]
    index = [min(i, last) for i in index]
    table = _ragged(rng, len(index), pages_per_slot, index)
    pool = jnp.asarray(
        rng.normal(size=(len(index) * pages_per_slot + 1, 40, PAGE)),
        jnp.float32,
    )
    q = jnp.asarray(rng.normal(size=(len(index), 4, 40)), jnp.float32)
    idx = jnp.asarray(index, jnp.int32)
    got = _latent_impl(q, pool, table, idx, 0.3, 32, pages)
    want = latent_attention_reference(q, pool, table, idx, 0.3, 32)
    np.testing.assert_allclose(got[:-1], want[:-1], atol=2e-5)
    assert not np.asarray(got[-1]).any()
    # what a slot's dead pages hold is never read: the trash page can
    # be anything
    loud = pool.at[0].set(1e9)
    np.testing.assert_array_equal(
        _latent_impl(q, loud, table, idx, 0.3, 32, pages), got
    )


def test_the_decode_kernel_at_the_cells_row_takes_two_steps_of_eight_pages():
    """576 values a position in bfloat16, 16 pages a slot: the entry
    point itself picks 8 pages an iteration, so the second one runs."""
    rng = np.random.default_rng(5)
    index = [100, 1023, 1024, 1500, 2047, -3]
    table = _ragged(rng, len(index), 16, index)
    pool = jnp.asarray(
        rng.normal(size=(len(index) * 16 + 1, 576, PAGE)) * 0.7, jnp.bfloat16
    )
    q = jnp.asarray(rng.normal(size=(len(index), 4, 576)) * 0.7, jnp.bfloat16)
    idx = jnp.asarray(index, jnp.int32)
    assert latent_pages_per_step(16, PAGE, 576, 2) == 8
    args = dict(sm_scale=0.14468, v_width=512)
    got = latent_paged_attention(q, pool, table, idx, prefer="pallas", **args)
    want = latent_attention_reference(q, pool, table, idx, **args)
    np.testing.assert_allclose(
        np.asarray(got[:-1], np.float32), np.asarray(want[:-1], np.float32),
        atol=2e-2,
    )
    assert not np.asarray(got[-1], np.float32).any()


#: (the slots' newest positions, pages a slot, pages an iteration): what
#: the walk over LIVE pages has to get right beside the ragged cases
#: above, 4 pages = 512 positions an iteration where not said.
WALKS = {
    "dead-row-first": ([-1, 700, 3], 8, 4),
    "dead-row-last": ([700, 3, -1], 8, 4),
    "dead-rows-between-live": ([900, -1, -7, 130, -1, 1023], 8, 4),
    "dead-rows-only": ([-1, -4, -1], 8, 4),
    "one-position": ([0, 0, 600], 8, 4),
    "a-page-more-than-an-iteration": ([4 * PAGE, 5 * PAGE - 1, 640], 8, 4),
    "a-page-fewer-than-an-iteration": ([3 * PAGE - 1, 2 * PAGE, 383], 8, 4),
    "whole-iterations-to-the-tables-end": ([1023, 511, 512], 8, 4),
    "one-slot": ([777], 8, 4),
    "one-slot-one-page": ([5], 8, 2),
    "one-dead-slot": ([-1], 8, 4),
    "a-table-that-ends-mid-iteration": ([6 * PAGE - 1, 5 * PAGE, 0], 6, 4),
}


@pytest.mark.parametrize("walk", WALKS, ids=list(WALKS))
def test_the_walk_reads_a_slots_live_pages_and_nothing_else(walk):
    """Every page that is NOT live for its slot (the trash page, the
    pool's unowned pages, the pages other slots own past their newest
    position: none here) holds NaN: fetched-and-masked, a dead page
    still reaches the value product as 0 x NaN, so this shows a read
    that a loud finite number cannot. The oracle gathers whole windows
    and reads a clean pool. The look-ahead across slots (the first
    pages of the next LIVE slot are in flight before a slot's last are
    consumed) is what the dead rows first, last and between try."""
    from adapt_tpu.ops.latent_attention import _latent_impl

    index, pages_per_slot, pages = WALKS[walk]
    rng = np.random.default_rng(zlib.crc32(walk.encode()))
    table = _ragged(rng, len(index), pages_per_slot, index)
    clean = rng.normal(size=(len(index) * pages_per_slot + 1, 40, PAGE))
    owned = np.zeros(len(clean), bool)
    for row, idx in zip(np.asarray(table), index):
        owned[row[:max(idx, -1) // PAGE + 1]] = True
    assert not owned[0]
    pool = jnp.asarray(
        np.where(owned[:, None, None], clean, np.nan), jnp.float32
    )
    q = jnp.asarray(rng.normal(size=(len(index), 4, 40)), jnp.float32)
    idx = jnp.asarray(index, jnp.int32)
    got = np.asarray(_latent_impl(q, pool, table, idx, 0.3, 32, pages))
    want = np.asarray(latent_attention_reference(
        q, jnp.asarray(clean, jnp.float32), table, idx, 0.3, 32
    ))
    live = np.asarray(index) >= 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not got[~live].any()  # zeros, and no NaN


def test_the_books_say_which_walk_a_program_was_built_on():
    """``grid_steps`` a call is the slots (it was slots x page steps
    while the page axis was on the grid), beside the pages an
    iteration covers."""
    rng = np.random.default_rng(3)
    index = [300, -1, 1100, 90, 2047]
    table = _ragged(rng, len(index), 16, index)
    pool = jnp.asarray(
        rng.normal(size=(len(index) * 16 + 1, 576, PAGE)), jnp.bfloat16
    )
    q = jnp.asarray(rng.normal(size=(len(index), 4, 576)), jnp.bfloat16)
    latent_paged_attention(
        q, pool, table, jnp.asarray(index, jnp.int32), sm_scale=0.1,
        v_width=512, prefer="pallas",
    )
    books = kernel_dispatch_stats()["latent_decode"]
    assert books["grid_steps"] == len(index)
    assert books["pages_per_step"] == 8


def test_the_write_kernel_lays_a_row_over_one_position():
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(5, 40, PAGE)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(3, 40)), jnp.float32)
    phys, off = jnp.asarray([2, 4, 0]), jnp.asarray([7, 127, 3])
    got = append_latent_paged(pool, new, phys, off, prefer="pallas")
    want = append_latent_paged(pool, new, phys, off, prefer="xla")
    np.testing.assert_array_equal(got, want)
    assert (np.asarray(got) != np.asarray(pool)).sum() == 3 * 40


# -- (b) the block against the benchmark's plain reference --------------------


def test_the_block_equals_the_plain_reference(built):
    lm, variables = built
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, 128)
    with jax.default_matmul_precision("highest"):
        full = logits_full(lm, variables, ids)
    got = chosen_logprob(
        full[:, :-1].reshape(-1, 128), ids[:, 1:].reshape(-1)
    ).reshape(2, -1)
    want, gaps = ref.logprobs_and_gaps(variables, ids)
    assert gaps.shape == (1, 2, 47)  # one sparse layer
    sure = np.asarray(ref.vouched(gaps))
    assert sure.mean() > 0.5
    np.testing.assert_allclose(
        np.asarray(got)[sure], np.asarray(want)[sure], atol=2e-4
    )
    for fault in ref.CONTROLS:  # each control moves the answer
        moved = ref.logprobs_and_gaps(variables, ids, fault)[0]
        assert np.abs(np.asarray(moved) - np.asarray(want))[sure].max() > 0.01
    low = ref.logprobs_and_gaps(variables, ids, arch={"round_to": "float8_e4m3fn"})[0]
    assert np.abs(np.asarray(low) - np.asarray(want))[sure].max() > 0.01


# -- (c) mHC's coefficients ---------------------------------------------------


def test_h_res_is_doubly_stochastic_and_the_clamp_holds():
    spec = HyperSpec(4, clamp=(-3.0, 3.0))
    hc = HyperConnection(spec, 8)
    x = 50.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 8))
    v = hc.init(jax.random.PRNGKey(1), x)
    u, (h_post, h_res) = hc.apply(v, x)
    assert u.shape == (2, 5, 8) and h_post.shape == (2, 5, 4)
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-4)
    # a huge scale on Hres~: without the clamp exp() overflows; at the
    # clamp's edges (entries e^6 apart) 20 iterations leave the
    # columns within 2% of 1, the rows (normalised last) exact
    v = {"params": {**v["params"], "a": jnp.asarray([1.0, 1.0, 1e4])}}
    u, (h_post, h_res) = hc.apply(v, x)
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=0.02)
    assert np.isfinite(np.asarray(h_res)).all()
    # clamped at +-3, an entry is at least e^-6 of its row's largest
    assert float(h_res.min()) > 0.0
    assert float((h_post > 0).all() & (h_post < 2).all())
    m = sinkhorn(jnp.exp(jnp.asarray([[3.0, -3.0], [-3.0, 3.0]])), 20, 1e-6)
    np.testing.assert_allclose(m.sum(0), 1.0, atol=1e-5)


# -- (d) YaRN and the softmax scale against the closed form -------------------


def test_yarn_frequencies_and_the_scale_against_the_closed_form():
    dim, base = 64, 10000.0
    got = np.asarray(yarn_frequencies(dim, base, YARN))

    def correction(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(correction(32.0)), math.ceil(correction(1.0))
    assert (low, high) == (10, 23)
    plain = base ** (-np.arange(0, dim, 2) / dim)
    np.testing.assert_allclose(got[: low + 1], plain[: low + 1], rtol=1e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / 64.0, rtol=1e-6)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    np.testing.assert_allclose(
        got[mid], plain[mid] * (1 - ramp) + plain[mid] / 64.0 * ramp, rtol=1e-6
    )
    np.testing.assert_allclose(
        got, np.asarray(ref.yarn_inv_freq(dim, base, ref.ARCH["yarn"])),
        rtol=1e-6,
    )
    full = LatentSpec(768, 512, 128, 64, 128, yarn=YARN)
    assert full.row == 576 and full.qk_dim == 192
    want = 192 ** -0.5 * (0.1 * math.log(64.0) + 1.0) ** 2
    assert abs(full.softmax_scale - want) < 1e-12
    assert abs(full.softmax_scale - 0.14468) < 1e-5
    assert abs(ref.softmax_scale(192, ref.ARCH["yarn"]) - want) < 1e-12


# -- (e) the eight shares of a sparse layer -----------------------------------


def test_the_eight_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    def spec(held):
        return ExpertSpec(
            64, 16, 4, score="sigmoid", normalize=True, scale=2.0,
            select_bias=True, shared_dim=16, held=held,
        )

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    whole = RoutedExperts(spec(None))
    v = whole.init(jax.random.PRNGKey(1), x)
    p = dict(v["params"])
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    want = whole.apply({"params": p}, x)
    shared = RoutedExperts(spec((0, 8))).apply({"params": {
        **p, **{k: jnp.zeros_like(p[k][:8]) for k in ("w_gate", "w_up", "w_down")}
    }}, x)  # a share whose experts add nothing: the shared expert alone
    total = 0.0
    for chip in range(8):
        mine = {k: p[k][8 * chip: 8 * chip + 8]
                for k in ("w_gate", "w_up", "w_down")}
        total = total + RoutedExperts(spec((8 * chip, 8))).apply(
            {"params": {**p, **mine}}, x
        )
    np.testing.assert_allclose(total - 7 * shared, want, atol=2e-5)


# -- (f) the pool-bytes rule --------------------------------------------------


def test_a_latent_pool_holds_one_row_a_position_and_no_head_axis(built):
    lm, variables = built
    full = _spec(True, latent=LatentSpec(768, 512, 128, 64, 128, yarn=YARN))
    (group,) = cache_groups([full] * 3)
    assert group.row == 576 and group.position_values == 576
    pool = alloc_kv_pools(
        33, group.kv_heads, PAGE, group.head_dim, jnp.bfloat16, row=group.row
    )
    # pages x page x 576 x itemsize: expanded K and V would be 14x it
    assert pool.nbytes == 33 * PAGE * 576 * 2
    assert pool.shape == (33, 576, PAGE)
    srv = _batcher(lm, variables, pool_pages=17)
    stats = srv.stats()
    assert stats["pool_row_values"] == LATENT.row == 40
    assert stats["pool_row_bytes"] == 40 * 4
    assert all(c.nbytes == 17 * PAGE * 40 * 4 for c in srv._caches)
    assert len(srv._pagers) == 1
    srv.close()
    mha = BlockSpec(32, 4, 64, kv_heads=2)
    assert cache_groups([mha])[0].position_values == 2 * 2 * 8
    with pytest.raises(NotImplementedError, match="latent pool is not quantized"):
        alloc_kv_pools(3, 1, PAGE, 8, jnp.float32, "int8", row=40)


def test_the_yardstick_counts_a_hand_worked_shape():
    # 2 rows of 300 positions in all, 32 heads, a 576-value row of which
    # 512 are weighted, bfloat16
    flops, nbytes = xy.latent_decode_cost(300, 2, 32, 576, 512, 2)
    assert flops == 2 * 32 * (576 + 512) * 300
    assert nbytes == 300 * 576 * 2 + 2 * 32 * (576 + 512) * 2
    assert xy.latent_decode_cost(0, 0, 32, 576, 512, 2) == (0, 0)


# -- (g) what moves per-head pages refuses a latent block ---------------------


def _refuse_draft(lm, variables):
    dlm = transformer_lm(128, 32, 1, 2, 64, max_len=512)
    dvars = dlm.graph.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
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
    block.apply(
        variables["decoder_block_0"], jnp.zeros((1, 2, 4, 32)),
        jnp.zeros((3, 40, PAGE)), jnp.zeros((1, 2), jnp.int32),
        jnp.zeros((1,), jnp.int32), method="verify_chunk_paged",
    )


_REFUSALS = [
    ("a tp mesh", _refuse_mesh, "latent-attention block does not split over tp"),
    ("a handoff",
     lambda lm, v: _batcher(lm, v).adopt_prefill_pages(
         np.arange(40, dtype=np.int32), [], PAGE, False),
     "handoff of prefilled pages.*latent cache"),
    ("a host cache tier",
     lambda lm, v: _batcher(lm, v, cache_tier=CacheTierConfig()),
     "host cache tier.*latent cache"),
    ("sp prefill",
     lambda lm, v: _batcher(lm, v, prefill=PrefillConfig(sp_threshold=64)),
     "sequence-parallel prefill.*latent cache"),
    ("a quantized pool",
     lambda lm, v: _batcher(lm, v, kv_cache_dtype="int8"),
     "quantized KV pool.*latent cache"),
    ("speculation", _refuse_draft, "a draft model.*latent cache"),
    ("generate()",
     lambda lm, v: generate(lm, v, jnp.zeros((1, 4), jnp.int32), 2),
     "latent-attention model keeps one row a position"),
    ("elastic recovery", _refuse_health, "elastic recovery.*latent cache"),
    ("verify_chunk_paged", _refuse_verify, "moves per-head K and V"),
]


@pytest.mark.parametrize(
    "how,says", [r[1:] for r in _REFUSALS], ids=[r[0] for r in _REFUSALS]
)
def test_what_moves_per_head_pages_refuses_a_latent_block(built, how, says):
    lm, variables = built
    with pytest.raises((ValueError, NotImplementedError), match=says):
        how(lm, variables)


@pytest.mark.parametrize("field,value", [
    ("kv_heads", 2), ("window", 64), ("qk_norm", True), ("rope_base", None),
])
def test_a_latent_spec_refuses_what_does_not_apply(field, value):
    kw = dict(norm="rmsnorm", bias=False, rope_base=10000.0, latent=LATENT)
    kw[field] = value
    with pytest.raises(ValueError, match="latent"):
        BlockSpec(32, 4, 64, **kw)


def test_streams_are_one_number_a_model():
    with pytest.raises(ValueError, match="disagree on the residual streams"):
        transformer_lm(
            128, blocks=[_spec(False), _spec(False, streams=None)], pos="none"
        )
    with pytest.raises(ValueError, match="normed INPUT"):
        BlockSpec(32, 4, 64, post_norm=True, streams=STREAMS)


def test_the_streams_are_counted_beside_the_expert_steps(built):
    from adapt_tpu.utils.metrics import global_metrics

    lm, variables = built
    srv = _batcher(lm, variables)
    before = global_metrics().snapshot()["counters"].get("mhc.mixes", 0.0)
    rid = srv.submit(np.arange(20, dtype=np.int32), 9)
    srv.run()
    after = global_metrics().snapshot()["counters"]["mhc.mixes"]
    # two blocks x two sub-layers a step, a chunk of 4 steps a tick
    assert after > before and (after - before) % (2 * 2 * 4) == 0
    assert len(srv.logprobs(rid)) == 9
    srv.close()


def test_a_prefix_hit_shares_latent_pages(built):
    """A page is a page: the radix cache shares a latent pool's full
    prompt pages, and the suffix pass (absorbed, over the shared
    pages) serves the same logprobs as the first request's."""
    lm, variables = built
    srv = _batcher(lm, variables)
    ids = np.random.default_rng(7).integers(0, 128, 300)
    first = srv.submit(ids, 6)
    srv.run()
    again = srv.submit(ids, 6)
    out = srv.run()
    stats = srv.stats()
    assert stats["prefix_hits"] >= 2 and "prefix_cache" not in stats
    np.testing.assert_allclose(
        srv.logprobs(again), srv.logprobs(first), atol=2e-5
    )
    assert len(out[again]) == 6
    srv.close()
