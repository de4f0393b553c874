"""Paged KV cache: allocator, kernel parity, and batcher equivalence.

The contract stack, bottom-up: the ``Pager`` free-list bookkeeping, the
``ops/paged_attention`` kernel against its gather oracle (which itself
reduces to the contiguous decode oracle), the pool's format
(``alloc_kv_pools`` / ``pool_geometry``, the one definition three
callers share), and the ``ContinuousBatcher`` emitting token-for-token
what ``generate()`` emits for each request alone — including under a
pool small enough to force requests to wait for pages."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import (
    BlockSpec,
    CausalSelfAttention,
    generate,
    lm_tiny,
)
from adapt_tpu.ops.paged_attention import (
    append_kv_paged,
    fuse_kv,
    paged_attention,
    paged_attention_reference,
    split_kv,
)
from adapt_tpu.ops.quantize import quantize_kv_vectors
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import (
    CACHE_PROPERTIES,
    CacheGroup,
    Pager,
    alloc_kv_pools,
    cache_layout,
    group_pool_pages,
    insert_prefill_pages,
    kv_value_width,
    pool_geometry,
    window_hold_pages,
)


# -- allocator ---------------------------------------------------------------


def test_pager_alloc_free_cycle():
    p = Pager(num_pages=8, slots=3, pages_per_slot=4)
    assert p.alloc(0, 3) and p.alloc(1, 4)
    assert p.stats().in_use == 7 and p.stats().free == 0
    assert not p.alloc(2, 1)  # exhausted (page 0 never handed out)
    assert 0 not in p.owned(0) + p.owned(1)
    t = p.table()
    assert t.shape == (3, 4)
    assert set(t[0, :3]) == set(p.owned(0)) and t[0, 3] == 0
    assert (t[2] == 0).all()
    p.free_slot(1)
    assert p.stats().free == 4
    assert p.alloc(2, 4)  # reuses freed pages


def test_pager_validation():
    with pytest.raises(ValueError, match="num_pages"):
        Pager(1, 1, 1)
    p = Pager(8, 2, 2)
    with pytest.raises(ValueError, match="table width"):
        p.alloc(0, 3)


def test_hold_past_a_rows_end_releases_and_grants_nothing():
    """``hi < lo``: a pass dispatched for a row whose request ended
    inside the tick in flight, its window wholly past the last
    position. What fell behind ``lo`` goes back, nothing is granted
    (not even from an empty pool), and the row's table points at the
    trash page from there on."""
    p = Pager(num_pages=5, slots=2, pages_per_slot=6)
    p.hold(0, 1, 4)
    assert len(p.owned(0)) == 3 and p.base(0) == 1
    assert p.alloc(1, 1) and p.stats().free == 0
    p.hold(0, 3, 2)  # keeps ordinal 3, which is not behind lo
    assert len(p.owned(0)) == 1 and p.base(0) == 3
    p.hold(0, 5, 4)
    assert p.owned(0) == [] and p.base(0) == 5
    assert (p.table()[0] == 0).all() and p.stats().free == 3
    p.hold(0, 9, 7)  # past the table's width: capped, still nothing
    assert p.owned(0) == [] and p.base(0) == 6 and p.stats().free == 3
    p.free_slot(0)
    assert p.base(0) == 0


def _toy_specs(kind):
    from adapt_tpu.models.kda import KdaSpec
    from adapt_tpu.models.mla import IndexSpec, LatentSpec, YarnSpec
    from adapt_tpu.models.ssm import SsmSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    def spec(**kw):
        return BlockSpec(32, 4, 64, **kw)

    return {
        "one_group": [spec(), spec()],
        "two_groups": [spec(window=8), spec(), spec(window=8)],
        "state": [spec(ssm=SsmSpec(
            heads=4, head_dim=16, d_state=32, groups=2, chunk=16
        ))] * 2,
        "linear": [
            spec(), spec(linear=KdaSpec(heads=4, head_dim=8, rank=4))
        ],
        "latent": [spec(rope_base=1e4, latent=LatentSpec(
            24, 32, 16, 8, 16, yarn=YarnSpec(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
        ))] * 2,
        "selecting": [spec(rope_base=1e4, latent=LatentSpec(
            24, 32, 16, 8, 16, index=IndexSpec(4, 16, 8, 24)
        ))] * 2,
    }[kind]


#: What PR 47's constructor refused in each of its three tables
#: (several cache groups, recurrent state, a latent row), in that order,
#: and what a cache of two planes (a selecting latent block, PR 54) is
#: refused: everything that shares, moves, verifies or re-encodes a page.
_REFUSED_AT_PR47 = dict(
    one_group=(
        "a draft model", "a tp mesh", "a host cache tier",
        "sequence-parallel prefill",
        "cache-aware admission (the radix prefix cache)",
        "a handoff of prefilled pages", "the radix prefix cache",
        "copy-on-write fan-out",
    ),
    pages_only=(
        "a draft model", "a host cache tier", "sequence-parallel prefill",
        "cache-aware admission (the radix prefix cache)",
        "elastic recovery (health=)", "a quantized KV pool",
        "a handoff of prefilled pages", "the radix prefix cache",
        "copy-on-write fan-out",
    ),
    per_head_pages=(
        "a draft model", "a tp mesh", "a host cache tier",
        "sequence-parallel prefill", "elastic recovery (health=)",
        "a quantized KV pool", "a handoff of prefilled pages",
    ),
    one_plane=(
        "a draft model", "a tp mesh", "a host cache tier",
        "sequence-parallel prefill",
        "cache-aware admission (the radix prefix cache)",
        "elastic recovery (health=)", "a quantized KV pool",
        "a handoff of prefilled pages", "the radix prefix cache",
        "copy-on-write fan-out",
    ),
)


@pytest.mark.parametrize("kind, groups, group_of, state, latent, lacks", [
    ("one_group", ["full"], (0, 0), (), (), ()),
    ("two_groups", ["full", "window"], (1, 0, 1), (), (), ("one_group",)),
    ("state", ["full"], (0, 0), (0, 1), (), ("pages_only",)),
    ("linear", ["full"], (0, 0), (1,), (), ("pages_only",)),
    ("latent", ["full"], (0, 0), (), (0, 1), ("per_head_pages",)),
    ("selecting", ["full"], (0, 0), (), (0, 1),
     ("per_head_pages", "one_plane")),
])
def test_the_cache_layout_is_what_the_batcher_derived_piecemeal(
    kind, groups, group_of, state, latent, lacks
):
    """``cache_layout`` says of a model's blocks what the batcher's
    constructor worked out in five loops (the groups with the one that
    reserves whole first, a block's group, the blocks with a state and
    the latent ones), and the one table refuses, for each kind of
    cache, exactly the features the three tables did, with that kind's
    sentence."""
    from adapt_tpu.runtime import continuous

    layout = cache_layout(_toy_specs(kind))
    assert [g.name for g in layout.groups] == groups
    assert layout.group_of == group_of
    assert layout.state_blocks == state
    assert layout.latent_blocks == latent
    assert layout.selecting_blocks == (latent if kind == "selecting" else ())
    assert tuple(
        p for p in CACHE_PROPERTIES if layout.lacks(p) is not None
    ) == lacks
    assert set(continuous._CACHE_NEEDS) == set().union(
        *_REFUSED_AT_PR47.values()
    )
    for feature, needs in continuous._CACHE_NEEDS.items():
        assert set(needs) == {
            p for p, told in _REFUSED_AT_PR47.items() if feature in told
        }, feature
        refused = [p for p in lacks if p in needs]
        unmet = continuous._unmet(layout, feature)
        if not refused:
            assert unmet is None, feature
            continue
        assert unmet[0].startswith(feature)
        # every lack the feature trips over is named, the last one last
        what, detail = layout.lacks(refused[-1])
        assert unmet[1].endswith(what) and unmet[2] == detail
        for p in refused[:-1]:
            assert layout.lacks(p)[0] in unmet[1]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("order", ["synchronous", "overlapped"])
def test_a_walk_of_holds_stays_within_window_hold_pages(order, seed):
    """The batcher's protocol over a window group's pager, at random
    (window, page, chunk, prefill chunk): admission holds the prompt's
    tail (or a chunked-prefill pass its pages), a decode dispatch holds
    ``[pos, pos + chunk)`` from the position the row has been
    DISPATCHED to (a chunk past the committed one under the overlapped
    order, where a row that ended is dispatched once more and freed a
    tick late). No slot ever holds more than ``window_hold_pages`` and
    no grant fails from a pool of ``group_pool_pages``."""
    rng = np.random.default_rng(seed)
    lag = order == "overlapped"
    slots, max_len = 3, 160
    for _ in range(25):
        page = int(rng.choice([2, 4, 8, 16]))
        window = int(rng.integers(1, 40))
        chunk = int(rng.integers(1, 12))
        pchunk = page * int(rng.integers(1, 4)) if rng.random() < 0.5 else None
        pps = -(-max_len // page)
        bound = min(pps, window_hold_pages(window, page, chunk, pchunk))
        pager = Pager(
            group_pool_pages(
                CacheGroup("window", window, 2, 8, (0,)),
                slots, pps, page, chunk, pchunk,
            ),
            slots, pps, page_tokens=page,
        )
        assert pager.num_pages == slots * bound + 1

        def hold(slot, lo_pos, hi_pos):  # ContinuousBatcher._hold_groups
            pager.hold(
                slot, max(0, lo_pos - window + 1) // page, -(-hi_pos // page)
            )
            assert len(pager.owned(slot)) <= bound

        rows = [None] * slots  # dict(s0, steps, emitted, pf) a request
        flight = []  # (slot, row) of the dispatch not yet committed
        for _ in range(60):
            dispatched = []
            for i in range(slots):
                if rows[i] is None:
                    s0 = int(rng.integers(1, 70))
                    rows[i] = dict(
                        s0=s0, steps=int(rng.integers(2, max_len - s0)),
                        emitted=1,
                        pf=0 if pchunk and s0 > pchunk else -1,
                    )
                    if rows[i]["pf"] < 0:
                        hold(i, s0, s0)
                r = rows[i]
                if r["pf"] >= 0:
                    clen = min(pchunk, r["s0"] - r["pf"])
                    hold(i, r["pf"], r["pf"] + -(-clen // page) * page)
                    r["pf"] += clen
                    if r["pf"] < r["s0"]:
                        continue
                    r["pf"] = -1
                ahead = any(q is r for _, q in flight)
                pos = r["s0"] + r["emitted"] - 1 + (chunk if ahead else 0)
                hold(i, pos, min(pos + chunk, r["s0"] + r["steps"]))
                dispatched.append((i, r))
            landing, flight = (flight, dispatched) if lag else (dispatched, [])
            for i, r in landing:
                if rows[i] is not r:
                    continue  # a row past its end: dropped
                r["emitted"] += chunk
                if r["emitted"] >= r["steps"] or rng.random() < 0.05:
                    rows[i] = None  # by step count, or an EOS
                    pager.free_slot(i)
        for i in range(slots):
            pager.free_slot(i)
        assert pager.stats().in_use == 0


# -- the pool's format: one owner --------------------------------------------

#: A block's pool, plane by plane: [(shape, dtype), ...] at 9 pages, 2
#: kv heads, page 16, head_dim 8. K and V of a position are ONE row of
#: the value plane (K's lanes, then V's); a quantized pool keeps the two
#: scale planes beside it, and int4 packs two nibbles a lane.
_POOL_FORMATS = {
    "native": [((9, 2, 16, 16), jnp.float32)],
    "int8": [((9, 2, 16, 16), jnp.int8), ((9, 2, 16, 1), jnp.float32),
             ((9, 2, 16, 1), jnp.float32)],
    "int4": [((9, 2, 16, 8), jnp.int8), ((9, 2, 16, 1), jnp.float32),
             ((9, 2, 16, 1), jnp.float32)],
}


@pytest.mark.parametrize("kv_dtype", sorted(_POOL_FORMATS))
def test_alloc_kv_pools_is_the_format_the_callers_built(kv_dtype):
    """A block's pool: ONE zeroed fused K|V plane — a native array, or
    (int8 values, float32 K scales, float32 V scales) with int4 at half
    the lane width — and the batcher's and the prefill worker's pools
    for the same arguments are that, leaf for leaf."""
    from adapt_tpu.runtime.disagg import PrefillWorker

    want = _POOL_FORMATS[kv_dtype]
    pool = alloc_kv_pools(9, 2, 16, 8, jnp.float32, kv_dtype)
    assert isinstance(pool, tuple) == (kv_dtype != "native")
    leaves = list(pool) if kv_dtype != "native" else [pool]
    assert [(x.shape, x.dtype) for x in leaves] == want
    assert all(not np.asarray(x).any() for x in leaves)
    assert 2 * kv_value_width(8, kv_dtype) == want[0][0][-1]
    # The same bytes as the two planes a block held until PR 30.
    two = 2 * 9 * 2 * 16 * kv_value_width(8, kv_dtype) * (
        4 if kv_dtype == "native" else 1
    ) + (0 if kv_dtype == "native" else 2 * 9 * 2 * 16 * 4)
    assert sum(x.nbytes for x in leaves) == two

    # The callers: 4 query heads, 2 kv heads, head_dim 8; 2 slots x 4
    # pages + the trash page = 9.
    from adapt_tpu.models.transformer_lm import transformer_lm

    lm = transformer_lm(vocab=31, dim=32, depth=2, heads=4, mlp_dim=48,
                        max_len=64, kv_heads=2)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    bat = ContinuousBatcher(
        lm, variables, slots=2, page_size=16, kv_cache_dtype=kv_dtype
    )
    worker = PrefillWorker(
        lm, variables, slots=2, page_size=16, kv_cache_dtype=kv_dtype
    )
    def fmt(tree):
        return [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]

    assert len(bat._caches) == len(worker._pools) == 2  # one per block
    assert jax.tree.structure(bat._caches) == jax.tree.structure(
        worker._pools
    )
    assert fmt(bat._caches) == fmt(worker._pools) == want * 2
    assert bat._pager.num_pages == worker._pager.num_pages == 9
    assert bat._pager.pages_per_slot == worker._pager.pages_per_slot == 4
    bat.close()


def test_int4_pool_needs_an_even_head_dim():
    with pytest.raises(ValueError, match="even head_dim"):
        alloc_kv_pools(3, 2, 8, 7, jnp.float32, "int4")
    with pytest.raises(ValueError, match="even head_dim"):
        kv_value_width(7, "int4")
    assert kv_value_width(7, "int8") == 7  # only the nibble pack cares


def test_pool_geometry_table_width_and_default_pool():
    """Table width = ceil((max_len + slack) / page); the default pool
    is every row full plus the trash page. The batcher's speculative
    slack (draft_k + tree_width) widens the table through it."""
    assert pool_geometry(8, 48, 128) == (1, 9)
    assert pool_geometry(3, 48, 8) == (6, 19)
    assert pool_geometry(3, 48, 8, slack=1) == (7, 22)
    assert pool_geometry(32, 1024, 128) == (8, 257)
    with pytest.raises(ValueError, match="page_size"):
        pool_geometry(2, 48, 0)
    from adapt_tpu.config import SpeculativeConfig

    lm = lm_tiny(vocab=37, max_len=48)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    bat = ContinuousBatcher(
        lm, variables, slots=3, page_size=8, draft_lm=lm,
        draft_variables=variables,
        speculative=SpeculativeConfig(draft_k=3, tree_width=2),
    )
    want = pool_geometry(3, 48, 8, slack=5)
    assert (bat._pager.pages_per_slot, bat._pager.num_pages) == want
    assert want == (7, 22)
    bat.close()


def test_pager_radix_probe_and_books():
    """``radix_probe`` walks the deepest resident token-block path
    READ-ONLY (no counters move, nothing acquired),
    ``record_prefix_match`` books token-weighted hits and flags
    matches ending strictly inside the prompt's shareable run as
    partial, ``lookup_share`` heat feeds back into the probe, and
    eviction prunes the radix in lockstep with the byte registry."""
    P = 4
    p = Pager(num_pages=8, slots=2, pages_per_slot=4, page_tokens=P)
    toks = np.arange(12, dtype=np.int32)
    assert p.alloc(0, 2)
    pages = p.owned(0)
    for j, page in enumerate(pages):
        p.register(page, Pager.prefix_key(toks, (j + 1) * P))
    assert p.stats().radix_nodes == 2
    # The walk caps at (len-1)//P pages: the last-token page is never
    # shareable, so a 12-token prompt matches at most 2 pages.
    assert p.radix_probe(toks) == (2, 8, 0)
    longer = np.concatenate([toks, np.arange(6, dtype=np.int32)])
    assert p.radix_probe(longer)[:2] == (2, 8)  # shared-prefix match
    assert p.radix_probe(np.ones(12, np.int32))[0] == 0  # diverges
    st = p.stats()
    assert (st.prefix_hits, st.radix_hit_tokens) == (0, 0)  # read-only
    # Full-cap match on the 12-token prompt: a hit, NOT partial.
    p.record_prefix_match(2, 12)
    st = p.stats()
    assert (st.radix_hit_tokens, st.radix_partial_hits) == (8, 0)
    # The same 2 pages against the longer prompt end strictly inside
    # its shareable run — the case whole-run keying scores as a miss.
    p.record_prefix_match(2, len(longer))
    st = p.stats()
    assert (st.radix_hit_tokens, st.radix_partial_hits) == (16, 1)
    # Heat: lookup_share bumps the node, the probe sums the path.
    p.free_slot(0)  # registered pages park rc=0 in the LRU
    assert p.lookup_share(1, Pager.prefix_key(toks, P)) == pages[0]
    assert p.radix_probe(toks)[2] == 1
    # Eviction drops radix nodes with their keys and counts it.
    p.free_slot(1)
    assert p.evict_cached() == 2
    assert p.stats().radix_nodes == 0 and p.radix_evictions == 2
    assert p.radix_probe(toks) == (0, 0, 0)


# -- kernel vs oracle --------------------------------------------------------


def _pool(key, npages, kvh, page, hd, quantized=False):
    """A random block pool: K and V drawn apart and fused — native, or
    quantized with THE shared per-vector scheme ((int8 values, K
    scales, V scales), scales (npages, kvh, page, 1))."""
    k = jax.random.normal(jax.random.fold_in(key, 1), (npages, kvh, page, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (npages, kvh, page, hd))
    if quantized:
        k, v = quantize_kv_vectors(k), quantize_kv_vectors(v)
    return fuse_kv(k, v)


# head_dim 64: the fused row is one lane tile, read whole; 128: cut at
# a tile edge (``paged_attention._acc_width``); 32: half a tile, read
# whole, and by the decode kernel transposed (the page on the lanes).
_HD = pytest.mark.parametrize("hd", [32, 64, 128])
_QUANT = pytest.mark.parametrize(
    "quantized", [False, True], ids=["native", "int8"]
)


@_QUANT
@_HD
def test_paged_kernel_matches_oracle(rng, hd, quantized):
    """``_paged_kernel`` against the gather oracle (which itself reduces
    to the contiguous decode oracle), with and without ragged
    valid_from; quantized, the scale tiles ride the scalar-prefetch
    pipeline (table-addressed like the int8 payload) into the shared
    ``_attend_tile`` quantized branch."""
    b, kvh, g, page, npages = 2, 2, 3, 128, 16
    q = jax.random.normal(rng, (b, kvh, g, hd))
    pool = _pool(rng, npages, kvh, page, hd, quantized)
    table = jnp.asarray([[3, 7, 1, 0], [5, 2, 9, 4]], jnp.int32)
    index = jnp.asarray([300, 200], jnp.int32)
    for vf in (None, jnp.asarray([10, 0], jnp.int32)):
        ref = paged_attention_reference(q, pool, table, index, vf)
        out = paged_attention(q, pool, table, index, vf, prefer="pallas")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


#: A chunk's passes as the batcher hands them: (pos0, page list) with
#: the power-of-two padding pointed at the trash page 0.
_CHUNK_PASSES = [(128, [3, 7, 0, 0]), (0, [5, 0]), (256, [2, 4, 9, 0])]

#: id -> (kv_heads, query heads a KV head, chunk, head_dim, pool,
#: window, passes). The first six are the kernel's cases since PR 21
#: (their ids and float32 tolerance kept); the rest are what one grid
#: step covers since PR 42: heads a step > 1 at a head count with a
#: proper divisor (25 -> 5, GPT-2-XL's own chunk shape) and at a prime
#: one, the groups of 5 and 8 query heads Falcon-H1 and K-EXAONE fold,
#: a window with pages wholly under it, and the pools whose operands
#: reach the MXU as they are (bfloat16) or keep the widening (int8
#: with scales, packed int4).
_CHUNK_CASES = {
    "32-native": (2, 3, 32, 32, "f32", None, _CHUNK_PASSES),
    "64-native": (2, 3, 32, 64, "f32", None, _CHUNK_PASSES),
    "128-native": (2, 3, 32, 128, "f32", None, _CHUNK_PASSES),
    "32-int8": (2, 3, 32, 32, "int8", None, _CHUNK_PASSES),
    "64-int8": (2, 3, 32, 64, "int8", None, _CHUNK_PASSES),
    "128-int8": (2, 3, 32, 128, "int8", None, _CHUNK_PASSES),
    "gpt2xl-25-heads-5-a-step-bf16": (
        25, 1, 256, 64, "bf16", None, [(256, [2, 4, 9, 1]), (0, [5, 3])],
    ),
    "prime-7-heads-f32": (7, 1, 32, 64, "f32", None, _CHUNK_PASSES),
    "prime-7-heads-bf16": (7, 2, 32, 128, "bf16", None, _CHUNK_PASSES),
    "gqa5-hd128-bf16": (4, 5, 32, 128, "bf16", None, _CHUNK_PASSES),
    "gqa5-hd128-f32": (4, 5, 32, 128, "f32", None, _CHUNK_PASSES),
    "gqa8-hd128-window-bf16": (
        2, 8, 32, 128, "bf16", 128,
        [(384, [3, 7, 9, 2]), (0, [5, 0]), (160, [2, 4, 0, 0])],
    ),
    "gqa8-hd128-window-f32": (
        2, 8, 32, 128, "f32", 100,
        [(384, [3, 7, 9, 2, 0, 0, 0, 0]), (96, [5, 0])],
    ),
    "window-shorter-than-chunk-f32": (
        3, 2, 32, 64, "f32", 20, [(256, [2, 4, 9, 0]), (0, [5, 0])],
    ),
    "64-int4": (2, 3, 32, 64, "int4", None, _CHUNK_PASSES),
    "128-int4": (2, 3, 32, 128, "int4", None, _CHUNK_PASSES),
    "64-int8-window": (5, 2, 32, 64, "int8", 130, [(384, [3, 7, 9, 2])]),
    "64-bf16": (2, 3, 32, 64, "bf16", None, _CHUNK_PASSES),
}

#: Float32 operands reach both products as float32, as they always
#: did. A bfloat16 pool's scores are the oracle's (a bf16 x bf16
#: product is exact in float32); its probabilities are rounded to
#: bfloat16 for the second product and the output is bfloat16, so it
#: sits within an ulp or two of the oracle's own rounding.
_CHUNK_TOL = {"bf16": dict(rtol=2e-2, atol=2e-2)}


def _chunk_operands(key, kvh, g, chunk, hd, pool, npages=12, page=128):
    q = jax.random.normal(key, (1, kvh, g * chunk, hd))
    if pool in ("int8", "int4"):
        k, v = (
            quantize_kv_vectors(
                jax.random.normal(
                    jax.random.fold_in(key, i), (npages, kvh, page, hd)
                ),
                pool,
            )
            for i in (1, 2)
        )
        return q, fuse_kv(k, v)
    kv = _pool(key, npages, kvh, page, hd)
    if pool == "bf16":
        return q.astype(jnp.bfloat16), kv.astype(jnp.bfloat16)
    return q, kv


@pytest.mark.parametrize("case", list(_CHUNK_CASES))
def test_paged_chunk_kernel_matches_oracle(rng, case):
    """Chunk-query kernel (per-row causal over a paged window) vs its
    gather oracle: GQA folding, non-zero pos0, and pow2 trash padding;
    quantized, the chunk's rows attend the int8 / int4 window with
    fused scale application. One grid step covers every KV head that
    fits (``chunk_heads_per_step``), and one head a step gives the same
    rows. The pages no row attends (the padding's, those wholly under
    the window) are poisoned: a dead step's block, fetched or not,
    never reaches the result."""
    from adapt_tpu.ops.dispatch import kernel_dispatch_stats
    from adapt_tpu.ops.paged_attention import (
        _chunk_impl,
        _chunk_rows,
        _pool_planes,
        chunk_heads_per_step,
        paged_chunk_attention,
        paged_chunk_attention_reference,
        pool_values,
    )

    kvh, g, chunk, hd, pool_kind, window, passes = _CHUNK_CASES[case]
    page = 128
    q, pool = _chunk_operands(rng, kvh, g, chunk, hd, pool_kind)
    vals = pool_values(pool)
    tol = _CHUNK_TOL.get(pool_kind, dict(rtol=2e-5, atol=2e-5))
    for pos0, pages in passes:
        n = len(pages)
        live = [
            j for j in range(n)
            if j * page <= pos0 + chunk - 1
            and (window is None or (j + 1) * page - 1 > pos0 - window)
        ]
        dead = sorted({pages[j] for j in range(n)} - {pages[j] for j in live})
        pages = jnp.asarray(pages, jnp.int32)
        ref = paged_chunk_attention_reference(
            q, pool, pages, pos0, chunk, window
        )
        poisoned = pool
        if dead and pool_kind in ("f32", "bf16"):
            poisoned = pool.at[jnp.asarray(dead)].set(jnp.nan)
        out = paged_chunk_attention(
            q, poisoned, pages, pos0, chunk, prefer="pallas", window=window
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            err_msg=f"pos0={pos0}", **tol,
        )
        quantized = isinstance(pool, tuple)
        heads = chunk_heads_per_step(
            kvh, _chunk_rows(g * chunk, not quantized), page, vals.shape[3],
            vals.dtype.itemsize, quantized, hd, q.dtype.itemsize,
        )
        books = kernel_dispatch_stats()["paged_chunk"]
        assert books["heads_per_step"] == heads and books["last"] == 1.0
        if heads > 1:
            one = _chunk_impl(
                q, *_pool_planes(poisoned), pages,
                jnp.asarray(pos0, jnp.int32), chunk=chunk, window=window,
                heads=1,
            )
            np.testing.assert_array_equal(np.asarray(one), np.asarray(out))


@_QUANT
@_HD
def test_paged_verify_kernel_matches_oracle(rng, hd, quantized):
    """Batched verify kernel (per-SLOT base positions, per-row causal
    diagonal — the speculative tick's attention) vs its gather oracle:
    desynchronized indices, GQA folding, and a sliding window."""
    from adapt_tpu.ops.paged_attention import (
        paged_verify_attention,
        paged_verify_attention_reference,
    )

    b, kvh, g, chunk, page, npages = 2, 2, 2, 5, 128, 16
    q = jax.random.normal(rng, (b, kvh, g * chunk, hd))
    pool = _pool(rng, npages, kvh, page, hd, quantized)
    table = jnp.asarray([[3, 7, 1, 0], [5, 2, 9, 4]], jnp.int32)
    index = jnp.asarray([301, 77], jnp.int32)  # rows desynchronized
    for window in (None, 130):
        ref = paged_verify_attention_reference(
            q, pool, table, index, chunk, window=window
        )
        out = paged_verify_attention(
            q, pool, table, index, chunk, prefer="pallas", window=window,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"window={window}",
        )


def test_fuse_and_split_kv_are_inverse_and_say_where_the_lanes_are(rng):
    """``fuse_kv``: K on lanes [0, w), V on [w, 2w) of one row; a
    quantized pair fuses its value rows and keeps both scale columns.
    ``split_kv`` gives the two operands back, bit for bit."""
    k = jax.random.normal(rng, (3, 2, 8, 4))
    v = -k
    fused = fuse_kv(k, v)
    assert fused.shape == (3, 2, 8, 8)
    np.testing.assert_array_equal(np.asarray(fused[..., :4]), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(fused[..., 4:]), np.asarray(v))
    for dt, w in (("int8", 4), ("int4", 2)):
        kq, vq = quantize_kv_vectors(k, dt), quantize_kv_vectors(v, dt)
        vals, ks, vs = fuse_kv(kq, vq)
        assert vals.shape == (3, 2, 8, 2 * w) and vals.dtype == jnp.int8
        assert ks.shape == vs.shape == (3, 2, 8, 1)
        for got, want in zip(
            jax.tree.leaves(split_kv((vals, ks, vs))),
            jax.tree.leaves((kq, vq)),
        ):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# One page of every head that fits: the folded decode kernel. Rows of
# one call: ragged, ending exactly on a page's last position, starting
# a page, one token, and dead (negative index; its table maps live
# pages of other rows, which must not leak in).
_FOLD_SHAPES = {  # kv_heads, g, head_dim
    "h16-hd128": (16, 1, 128),
    "h25-hd64": (25, 1, 64),
    "gqa4x4-hd128": (4, 4, 128),
}


@pytest.mark.parametrize("ragged_left", [False, True], ids=["vf0", "vf"])
@pytest.mark.parametrize("split", [1, 2, None], ids=["s1", "s2", "auto"])
@pytest.mark.parametrize("pool", ["native", "int8-p1024"])
@pytest.mark.parametrize("shape", sorted(_FOLD_SHAPES))
def test_folded_paged_kernel_matches_oracle(shape, pool, split, ragged_left):
    kvh, g, hd = _FOLD_SHAPES[shape]
    page, pps = (128, 4) if pool == "native" else (1024, 2)
    span = page * pps
    index = np.asarray(
        [span - page // 2 - 3, page - 1, page, 0, -1], np.int32
    )
    b = len(index)
    npages = b * pps + 1
    rs = np.random.RandomState(kvh + hd + pps)
    table = jnp.asarray(
        1 + rs.permutation(npages - 1).reshape(b, pps), jnp.int32
    )
    key = jax.random.PRNGKey(kvh * hd + pps)
    q = jax.random.normal(key, (b, kvh, g, hd))
    kv = _pool(key, npages, kvh, page, hd, pool != "native")
    # Row 0's window starts inside its second page (the first is dead
    # from the left), row 2's on its own last position.
    vf = jnp.asarray([page + 7, 5, page, 0, 0], jnp.int32) if ragged_left else None
    idx = jnp.asarray(index)
    ref = paged_attention_reference(q, kv, table, idx, vf)
    out = paged_attention(
        q, kv, table, idx, vf, prefer="pallas", split=split
    )
    live = index >= 0
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], rtol=2e-5, atol=2e-5
    )
    assert np.isfinite(np.asarray(out)).all()  # the dead row: finite


# The walk: the decode kernel's grid is (slots, head blocks) and a row's
# LIVE pages are walked inside it, ``pages`` an iteration. A case is
# (kv_heads, g, head_dim, pages a slot, each row's context in positions
# held (0: a dead row; a function is given the pages an iteration the
# entry point derives at these widths), the layer's window or None, and
# (heads, pages) where the case drives ``_paged_impl`` itself: the
# public entry takes every head that fits a step, so only there does
# the look-ahead cross a head block).
_WALK_CASES = {
    "dead-rows-first": (4, 3, 128, 4, [0, 0, 300, 129], None, None),
    "dead-rows-last": (4, 3, 128, 4, [300, 129, 0, 0], None, None),
    "dead-rows-between": (8, 3, 64, 4, [300, 0, 0, 129, 0, 512], None, None),
    "all-dead-but-one": (4, 3, 128, 4, [0, 0, 0, 200, 0], None, None),
    "all-dead": (4, 3, 128, 4, [0, 0, 0], None, None),
    "one-live-position": (8, 3, 64, 4, [1, 0, 1], None, None),
    "window-2-pages-of-15": (
        4, 8, 128, 15, [1920, 700, 129, 60, 0, 1500], 128, None,
    ),
    "window-3-pages-of-15": (
        4, 8, 128, 15, [1920, 1000, 300, 257, 0, 140], 258, None,
    ),
    "window-wider-than-an-iteration": (
        2, 2, 128, 15, [1920, 1300, 0, 1281], 1200, None,
    ),
    "a-page-over-and-under-an-iteration": (
        2, 3, 128, 12,
        [lambda P: (P + 1) * 128, lambda P: (P - 1) * 128,
         lambda P: P * 128, lambda P: P * 128 + 1], None, None,
    ),
    "whole-iterations-to-the-tables-end": (
        4, 3, 64, lambda P: 2 * P,
        [lambda P: 2 * P * 128, lambda P: P * 128, lambda P: 2 * P * 128],
        None, None,
    ),
    "table-ends-mid-iteration": (
        4, 3, 64, lambda P: P + P // 2,
        [lambda P: (P + P // 2) * 128, lambda P: (P + 1) * 128,
         lambda P: (P + P // 2) * 128 - 5], None, None,
    ),
    "one-row-live": (4, 3, 128, 4, [385], None, None),
    "one-row-dead": (4, 3, 128, 4, [0], None, None),
    "head-blocks-2-pages-1": (
        4, 2, 64, 4, [300, 0, 129, 512, 0], None, (2, 1),
    ),
    "head-blocks-2-pages-2": (
        4, 2, 64, 5, [0, 300, 0, 640, 129, 0], None, (2, 2),
    ),
    "head-blocks-4-pages-4-window": (
        4, 1, 128, 9, [1152, 0, 600, 1, 0], 300, (1, 4),
    ),
    # (widths at which an iteration covers 2 pages: every page more is
    # a body more for the interpreter to compile)
    "hd32-g5": (16, 5, 32, 6, [700, 0, 128, 40], None, None),
    "hd64-g5": (8, 5, 64, 6, [700, 0, 128, 40], None, None),
    "hd128-g5": (4, 5, 128, 6, [700, 0, 128, 40], None, None),
    "hd32-g8": (16, 8, 32, 6, [768, 0, 257, 40], 200, None),
    "hd64-g8": (8, 8, 64, 6, [768, 0, 257, 40], None, None),
    "hd128-g8": (4, 8, 128, 6, [768, 0, 257, 40], 200, None),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_walk_reads_live_pages_only(case, monkeypatch):
    """The walk against the gather oracle with NaN written on EVERY
    page no slot owns, the trash page (every dead table entry's)
    among them: a dead page that reaches a product, or a live one
    skipped, shows. A dead page copied and never scored shows nowhere
    in the result, so the copies the kernel STARTS are counted (the
    interpreter runs a callback placed in ``start``): one a live page
    and head block, not one more. Dead rows get zeros. The books say
    what engaged."""
    import sys

    from adapt_tpu.ops.dispatch import kernel_dispatch_stats
    from adapt_tpu.ops.paged_attention import (
        decode_heads_per_step,
        decode_pages_per_step,
    )

    # (the package re-exports the function under the module's name)
    pa = sys.modules["adapt_tpu.ops.paged_attention"]
    started = []
    make_copy = pa.pltpu.make_async_copy

    class Counted:
        def __init__(self, *refs):
            self.copy = make_copy(*refs)

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(pa.pltpu, "make_async_copy", Counted)
    # (the jitted entry may hold a trace from before the patch, and
    # jit's cache goes by the function: a fresh one a case)
    impl = pa._paged_impl.__wrapped__

    @functools.partial(jax.jit, static_argnames=("heads", "split", "pages"))
    def _paged_impl(*operands, heads=1, split=1, pages=1):
        return impl(*operands, heads=heads, split=split, pages=pages)

    monkeypatch.setattr(pa, "_paged_impl", _paged_impl)

    kvh, g, hd, pps, ctx, window, forced = _WALK_CASES[case]
    page = 128
    geometry = (page, 2 * hd, 4, False, g + (-g) % 8, hd)

    def derived(pps):
        return decode_pages_per_step(
            pps, decode_heads_per_step(kvh, *geometry), *geometry
        )

    if callable(pps):  # so many iterations of the derived P
        pps = pps(derived(64))
    pages = forced[1] if forced else derived(pps)
    if not forced:
        assert pages > 1  # these blocks are thin: the groups are driven
    ctx = np.asarray([c(pages) if callable(c) else c for c in ctx])
    assert ctx.max() <= pps * page
    b = len(ctx)
    index = ctx - 1
    vf = None if window is None else np.maximum(ctx - window, 0)
    first = np.zeros(b, int) if vf is None else vf // page
    live = np.where(ctx > 0, index // page - first + 1, 0)
    npages = int(live.sum()) + 3
    rs = np.random.RandomState(len(case))
    owned = 1 + rs.permutation(npages - 1)
    table = np.zeros((b, pps), np.int32)  # dead entries: the trash page
    at = np.concatenate([[0], np.cumsum(live)])
    for s in range(b):
        table[s, first[s]:first[s] + live[s]] = owned[at[s]:at[s + 1]]
    unowned = np.ones(npages, bool)
    unowned[owned[:at[-1]]] = False
    key = jax.random.PRNGKey(len(case))
    q = jax.random.normal(key, (b, kvh, g, hd))
    clean = _pool(key, npages, kvh, page, hd)
    pool = jnp.where(
        jnp.asarray(unowned)[:, None, None, None], jnp.nan, clean
    )
    table, idx = jnp.asarray(table), jnp.asarray(index, jnp.int32)
    vf = None if vf is None else jnp.asarray(vf, jnp.int32)
    # the oracle gathers whole windows (0 x NaN is NaN): the clean pool
    ref = np.asarray(paged_attention_reference(q, clean, table, idx, vf))
    if forced:
        out = _paged_impl(
            q, pool, None, None, table, idx, vf, heads=forced[0],
            split=1, pages=pages,
        )
    else:
        out = paged_attention(q, pool, table, idx, vf, prefer="pallas")
        books = kernel_dispatch_stats()["paged_decode"]
        assert books["pages_per_step"] == pages
        assert books["grid_steps"] == b * (kvh // books["heads_per_step"])
    out = np.asarray(out)
    jax.effects_barrier()
    heads = forced[0] if forced else kvh
    assert len(started) == live.sum() * (kvh // heads)
    rows = ctx > 0
    np.testing.assert_allclose(out[rows], ref[rows], rtol=2e-5, atol=2e-5)
    assert not out[~rows].any()  # a dead row reads nothing: zeros


#: kv_heads, page, the fused row's width (2 x head_dim), itemsize, scale
#: planes -> heads a grid step. The three cells' deployments (PERF.md
#: section 4) take every head; an int8 pool at 1024-position pages
#: halves by the same sum.
_HEADS_PER_STEP = {
    "cgpt1b3_batchgen": ((16, 128, 256, 2, False), 16),
    "gpt2xl_doc": ((25, 128, 128, 2, False), 25),
    "gpt2xl_chat": ((25, 128, 128, 2, False), 25),
    "int8-p1024-hd128": ((16, 1024, 256, 1, True), 8),
    "int8-p1024-hd64": ((25, 1024, 128, 1, True), 5),
    "tp4-shard-of-16": ((4, 128, 256, 2, False), 4),
    "prime-heads-too-wide": ((7, 4096, 256, 2, False), 1),
}


@pytest.mark.parametrize("case", sorted(_HEADS_PER_STEP))
def test_decode_heads_per_step_is_derived_from_the_operands(case):
    from adapt_tpu.ops.paged_attention import (
        DECODE_STEP_VMEM_BUDGET,
        decode_heads_per_step,
        decode_step_vmem_bytes,
    )

    (kvh, page, width, itemsize, scales), want = _HEADS_PER_STEP[case]
    heads = decode_heads_per_step(kvh, page, width, itemsize, scales)
    assert heads == want and kvh % heads == 0
    used = decode_step_vmem_bytes(heads, page, width, itemsize, scales)
    # Under the budget, which is itself half of Mosaic's scoped 16 MB —
    # unless not even one head fits, which the kernel then tries anyway.
    assert DECODE_STEP_VMEM_BUDGET == 8 * 2 ** 20
    assert used <= DECODE_STEP_VMEM_BUDGET or heads == 1
    # The fused block alone (double-buffered) is most of it.
    blocks = 2 * heads * page * width * itemsize
    assert blocks <= used
    # The next divisor up would not have fit.
    bigger = [h for h in range(heads + 1, kvh + 1) if kvh % h == 0]
    if bigger:
        assert decode_step_vmem_bytes(
            bigger[0], page, width, itemsize, scales
        ) > DECODE_STEP_VMEM_BUDGET


#: kv_heads, rows a KV head (group x chunk), page, the fused row's
#: width, the pool's itemsize, scale planes, head_dim -> heads a grid
#: step of the chunk kernel. A chunk's state is per query ROW, so the
#: answer differs by model: 5 of GPT-2-XL's 25 heads at 256 rows of
#: head_dim 64; one at K-EXAONE's 2,048 rows and at Falcon-H1's 1,280.
_CHUNK_HEADS_PER_STEP = {
    "gpt2xl": ((25, 256, 128, 128, 2, False, 64), 5),
    "gpt2xl-tp5-shard": ((5, 256, 128, 128, 2, False, 64), 5),
    "kexaone": ((8, 2048, 128, 256, 2, False, 128), 1),
    "falconh1": ((4, 1280, 128, 256, 2, False, 128), 1),
    "cgpt1b3": ((16, 256, 128, 256, 2, False, 128), 4),
    "prime-heads-all-fit": ((7, 64, 128, 128, 2, False, 64), 7),
    "prime-heads-too-many-rows": ((7, 1024, 128, 256, 2, False, 128), 1),
    "int8-p1024-hd64": ((25, 256, 1024, 128, 1, True, 64), 1),
    "small-chunk-every-head": ((12, 32, 128, 128, 4, False, 64), 12),
}


@pytest.mark.parametrize("case", sorted(_CHUNK_HEADS_PER_STEP))
def test_chunk_heads_per_step_is_derived_from_the_operands(case):
    from adapt_tpu.ops.paged_attention import (
        DECODE_STEP_VMEM_BUDGET,
        chunk_heads_per_step,
        chunk_step_vmem_bytes,
    )

    (kvh, gc, page, width, itemsize, scales, hd), want = (
        _CHUNK_HEADS_PER_STEP[case]
    )
    args = (gc, page, width, itemsize, scales, hd, 2)
    heads = chunk_heads_per_step(kvh, *args)
    assert heads == want and kvh % heads == 0
    used = chunk_step_vmem_bytes(heads, *args)
    # Under the decode kernel's budget (half of Mosaic's scoped 16 MB),
    # unless not even one head fits it, which the kernel then tries
    # anyway: K-EXAONE's 2,048 rows are 10.1 MB by this sum and compile
    # (``tests/test_chip_lowering.py``).
    assert used <= DECODE_STEP_VMEM_BUDGET or heads == 1
    # The (gc, 1) running max and denominator pad to a lane tile each.
    assert used >= heads * gc * 2 * 128 * 4
    bigger = [h for h in range(heads + 1, kvh + 1) if kvh % h == 0]
    if bigger:
        assert chunk_step_vmem_bytes(bigger[0], *args) > (
            DECODE_STEP_VMEM_BUDGET
        )


@pytest.mark.parametrize("window", [None, 1, 20, 128, 130, 300])
def test_chunk_live_pages_are_the_pages_some_row_attends(window):
    """``_chunk_live_pages``: the run of page-list entries the chunk
    kernel's body runs on and its index map names, against the mask
    itself — a page is live iff some row of the chunk attends some
    position of it."""
    from adapt_tpu.ops.paged_attention import _chunk_live_pages

    page = 128
    for chunk in (32, 256):
        for pos0 in (0, 96, 128, 256, 384, 512, 640):
            live_n = -(-(pos0 + chunk) // page)
            n = 1 << (live_n - 1).bit_length()
            rows = pos0 + np.arange(chunk)[:, None]
            cols = np.arange(n * page)[None, :]
            mask = cols <= rows
            if window is not None:
                mask &= cols > rows - window
            want = np.flatnonzero(mask.reshape(chunk, n, page).any((0, 2)))
            first, last = _chunk_live_pages(
                jnp.asarray(pos0, jnp.int32), chunk, page, n, window
            )
            assert (int(first), int(last)) == (want[0], want[-1]), (
                chunk, pos0,
            )
            assert len(want) == want[-1] - want[0] + 1  # one run


def test_paged_chunk_books_heads_per_step(rng):
    """``kernel_dispatch_stats()["paged_chunk"]["heads_per_step"]`` is
    what the VMEM sum gives for the newest resolution's operands, per
    shard under a head shard; the oracle's path books none."""
    from adapt_tpu.ops.dispatch import kernel_dispatch_stats
    from adapt_tpu.ops.paged_attention import (
        chunk_heads_per_step,
        paged_chunk_attention,
    )

    kvh, g, chunk, hd, page = 6, 2, 16, 64, 128
    q, pool = _chunk_operands(rng, kvh, g, chunk, hd, "bf16", npages=4)
    pages = jnp.asarray([2, 1], jnp.int32)
    paged_chunk_attention(q, pool, pages, 128, chunk, prefer="pallas")
    books = kernel_dispatch_stats()["paged_chunk"]
    assert books["heads_per_step"] == chunk_heads_per_step(
        kvh, g * chunk, page, 2 * hd, 2, False, hd, 2
    ) == 6
    assert books["last"] == 1.0
    paged_chunk_attention(q, pool, pages, 128, chunk, prefer="xla")
    books = kernel_dispatch_stats()["paged_chunk"]
    assert books["last"] == 0.0 and books["heads_per_step"] == 6.0


def test_paged_decode_books_heads_per_step_and_split(rng):
    """``kernel_dispatch_stats()["paged_decode"]`` says what engaged:
    the heads a grid step and the split of the newest resolution."""
    from adapt_tpu.ops.dispatch import kernel_dispatch_stats

    b, kvh, g, hd, page, npages = 1, 6, 1, 64, 128, 4
    q = jax.random.normal(rng, (b, kvh, g, hd))
    kv = _pool(rng, npages, kvh, page, hd)
    table = jnp.asarray([[2, 1]], jnp.int32)
    paged_attention(q, kv, table, 130, prefer="pallas", split=2)
    books = kernel_dispatch_stats()["paged_decode"]
    assert (books["heads_per_step"], books["split"]) == (6.0, 2.0)
    assert books["last"] == 1.0


def test_paged_kernel_unsupported_page_size_raises_when_forced(rng):
    # page 16 is not a lane multiple: auto dispatch serves the oracle, a
    # forced prefer="pallas" raises instead of serving it silently.
    b, kvh, g, hd, page, npages = 1, 2, 1, 64, 16, 8
    q = jax.random.normal(rng, (b, kvh, g, hd))
    kv = _pool(rng, npages, kvh, page, hd)
    table = jnp.asarray([[2, 5, 1]], jnp.int32)
    with pytest.raises(ValueError, match="page_size 16"):
        paged_attention(q, kv, table, 30, prefer="pallas")
    out = paged_attention(q, kv, table, 30)
    ref = paged_attention_reference(q, kv, table, 30)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_insert_prefill_pages_roundtrip(rng):
    kvh, page, hd, npages = 2, 16, 8, 10
    pool = jnp.zeros((npages, kvh, page, hd))
    kv = jax.random.normal(rng, (1, kvh, 40, hd))  # 40 -> 3 pages (pad 8)
    pages = jnp.asarray([4, 7, 2], jnp.int32)
    pool = insert_prefill_pages(pool, pages, kv)
    got = np.concatenate(
        [np.asarray(pool)[p] for p in [4, 7, 2]], axis=1
    )  # (kvh, 48, hd)
    np.testing.assert_allclose(got[:, :40], np.asarray(kv)[0], rtol=1e-6)
    assert (got[:, 40:] == 0).all()
    assert (np.asarray(pool)[[0, 1, 3, 5, 6, 8, 9]] == 0).all()
