"""Every Pallas entry the serving path can pick must LOWER for a TPU.

The CPU suite runs the kernels through the Pallas interpreter, which
checks none of Mosaic's rules — that is how a rank-1 ``(1,)`` SMEM
block (refused by the TPU lowering) and a ``pallas_call`` inside a
GSPMD-partitioned program ("Mosaic kernels cannot be automatically
partitioned") both shipped. Here the backend question is answered
"tpu" for the duration of a test, so every dispatcher picks the
compiled kernel, and the program is cross-lowered with
``lowering_platforms=("tpu",)`` from the CPU: the Pallas-to-Mosaic
lowering and the partitioning check run without a chip. What Mosaic's
own compiler accepts is ``chip_smoke.py``'s job.

The last section goes one step further and COMPILES, for a v5e that is
described and not attached, the decode step's, the verify chunk's and
the prefill chunk's writes into the paged pool, and counts the
whole-pool copies the compiler scheduled around them.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import log_softmax_score, pool_copies
from jax import lax
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
)

from adapt_tpu.models.transformer_lm import BlockSpec, DecoderBlock
from adapt_tpu.ops.attention import flash_attention, flash_attention_with_lse
from adapt_tpu.ops.decode_attention import decode_attention
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.paged_attention import (
    DECODE_STEP_VMEM_BUDGET,
    chunk_heads_per_step,
    chunk_step_vmem_bytes,
    decode_heads_per_step,
    fuse_kv,
    paged_attention,
    paged_attention_reference,
    paged_chunk_attention,
    paged_verify_attention,
)
from adapt_tpu.ops.quantize import QuantizedTensor, dequantize, quantize
from adapt_tpu.utils import compile_cache

B, KVH, HD, PPS, NPAGES = 8, 12, 64, 8, 65


@pytest.fixture
def as_tpu(monkeypatch):
    """Answer "tpu" wherever the ops ask for the backend, with clean
    jit caches on both sides (a trace cached under the interpreter
    would lower vacuously; one cached here would not run on CPU)."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def pool(page, dtype):
    """An abstract block pool: the fused K|V plane, or the quantized
    ``(values, k_scales, v_scales)`` triple (int4 packs two nibbles per
    lane)."""
    if dtype == "native":
        return sds((NPAGES, KVH, page, 2 * HD))
    width = HD // 2 if dtype == "int4" else HD
    return (
        sds((NPAGES, KVH, page, 2 * width), jnp.int8),
        sds((NPAGES, KVH, page, 1), jnp.float32),
        sds((NPAGES, KVH, page, 1), jnp.float32),
    )


def lower_for_tpu(fn, *args, kernels=1):
    text = (
        jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    )
    assert text.count("tpu_custom_call") >= kernels, (
        "no Mosaic kernel in the lowered program — the dispatcher "
        "routed away or the trace was interpreted"
    )


TABLE = sds((B, PPS), jnp.int32)
INDEX = sds((B,), jnp.int32)


@pytest.mark.parametrize("split", [1, 2, None])
@pytest.mark.parametrize("dtype,page", [("native", 128), ("int8", 1024)])
def test_paged_decode_lowers(as_tpu, split, dtype, page):
    lower_for_tpu(
        lambda q, kv, t, i, vf: paged_attention(q, kv, t, i, vf, split=split),
        sds((B, KVH, 1, HD)), pool(page, dtype), TABLE, INDEX, INDEX,
    )


#: slots, kv heads, head_dim, pages a slot, pool pages: the deployments
#: of ``cgpt1b3_batchgen`` and ``gpt2xl_chat`` (PERF.md section 4), the
#: two widths the decode kernel folds (16 heads of 128, 25 of 64).
_CELL_SHAPES = {
    "cgpt1b3_batchgen": (24, 16, 128, 7, 169),
    "gpt2xl_chat": (32, 25, 64, 3, 97),
}


def _cell_args(cell):
    b, kvh, hd, pps, npages = _CELL_SHAPES[cell]
    return (
        sds((b, kvh, 1, hd)), sds((npages, kvh, 128, 2 * hd)),
        sds((b, pps), jnp.int32), sds((b,), jnp.int32),
    )


@pytest.mark.parametrize("split", [1, None])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_paged_decode_lowers_at_cell_shapes(as_tpu, cell, split):
    """The folded kernel at the cells' own shapes: every KV head of a
    page in one grid step (16 and 25, the books say), ragged left or
    not."""
    args = _cell_args(cell)
    for vf in ((), args[-1:]):
        lower_for_tpu(
            lambda *a: paged_attention(*a, split=split), *args, *vf
        )
    books = kernel_dispatch_stats()["paged_decode"]
    assert books["heads_per_step"] == _CELL_SHAPES[cell][1]


#: cell (and cache group) -> slots, kv heads, query heads a KV head,
#: head_dim, pages a slot, pool pages, and what the walk derives there:
#: (heads a step, pages an iteration, grid steps a call). A block of
#: every head of a page is 1 MB in ``cgpt1b3_batchgen`` and 0.8 MB in
#: the GPT-2-XL cells and goes alone; K-EXAONE's and Solar-Open2's 512
#: KB go in twos and Falcon-H1's 256 KB in fours (measured on a v5e,
#: PERF.md section 6, PR 46). The grid is the rows: 9,728 steps a
#: decode step of ``kexaone_longgen`` while the page axis was on it
#: (128 x (16 + 4 x 15)), 640 now.
_WALK_CELLS = {
    "cgpt1b3_batchgen": (24, 16, 1, 128, 7, 169, (16, 1, 24)),
    "gpt2xl_doc": (8, 25, 1, 64, 7, 57, (25, 1, 8)),
    "gpt2xl_chat": (32, 25, 1, 64, 3, 97, (25, 1, 32)),
    "kexaone_longgen.full": (128, 8, 8, 128, 16, 2049, (8, 2, 128)),
    "kexaone_longgen.window": (128, 8, 8, 128, 15, 385, (8, 2, 128)),
    "falconh1_longgen": (128, 4, 5, 128, 16, 2049, (4, 4, 128)),
    "solaropen2_longgen": (256, 8, 8, 128, 15, 3841, (8, 2, 256)),
}


@pytest.mark.parametrize("cell", sorted(_WALK_CELLS))
def test_paged_decode_books_the_walk_at_cell_shapes(as_tpu, cell):
    """``kernel_dispatch_stats()["paged_decode"]`` at every cell's
    shape: the pages an iteration of the walk covers and the grid
    steps a call takes, derived from the operands and lowered for a
    TPU at the cell's own group of query heads (5 pads to 8)."""
    b, kvh, g, hd, pps, npages, want = _WALK_CELLS[cell]
    lower_for_tpu(
        paged_attention,
        sds((b, kvh, g, hd)), sds((npages, kvh, 128, 2 * hd)),
        sds((b, pps), jnp.int32), sds((b,), jnp.int32),
        sds((b,), jnp.int32),
    )
    books = kernel_dispatch_stats()["paged_decode"]
    assert (
        books["heads_per_step"], books["pages_per_step"],
        books["grid_steps"],
    ) == want
    assert books["split"] == 1


@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_cell_planes_take_every_head_a_step(cell):
    """16 of 16 and 25 of 25: the fused plane's block (one stream of
    the bytes the two planes' blocks were) leaves the derivation where
    PR 28 measured it."""
    _, kvh, hd, _, _ = _CELL_SHAPES[cell]
    assert decode_heads_per_step(
        kvh, 128, 2 * hd, 2, False, 8, hd
    ) == kvh


@pytest.mark.parametrize("tree_tail", [0, 2])
@pytest.mark.parametrize("dtype,page", [("native", 128), ("int8", 1024)])
def test_paged_verify_lowers(as_tpu, tree_tail, dtype, page):
    for split in (1, None):
        lower_for_tpu(
            lambda q, kv, t, i: paged_verify_attention(
                q, kv, t, i, 5, tree_tail=tree_tail, split=split
            ),
            sds((B, KVH, 5, HD)), pool(page, dtype), TABLE, INDEX,
        )


#: kv heads, query heads a KV head, head_dim, window, heads a step:
#: the chunk shapes the cells compile (chunk 256, page 128).
_CHUNK_CELL_SHAPES = {
    "gpt2xl": (25, 1, 64, None, 5),
    "kexaone-window": (8, 8, 128, 128, 1),
    "falconh1": (4, 5, 128, None, 1),
}


@pytest.mark.parametrize("dtype,page,window", [
    ("native", 128, None), ("native", 128, 300), ("int8", 1024, None),
])
def test_paged_chunk_lowers(as_tpu, dtype, page, window):
    chunk = max(256, page)
    lower_for_tpu(
        lambda q, kv, p, pos0: paged_chunk_attention(
            q, kv, p, pos0, chunk, window=window
        ),
        sds((1, KVH, chunk, HD)), pool(page, dtype), sds((4,), jnp.int32),
        sds((), jnp.int32),
    )


@pytest.mark.parametrize("cell", sorted(_CHUNK_CELL_SHAPES))
def test_paged_chunk_lowers_at_cell_shapes(as_tpu, cell):
    """The folded chunk kernel at the cells' own shapes (chunk 256,
    page 128), at each of a document's three pass widths."""
    kvh, g, hd, window, want = _CHUNK_CELL_SHAPES[cell]
    for n in (2, 4, 8):
        lower_for_tpu(
            lambda q, kv, p, pos0: paged_chunk_attention(
                q, kv, p, pos0, 256, window=window
            ),
            sds((1, kvh, g * 256, hd)), sds((65, kvh, 128, 2 * hd)),
            sds((n,), jnp.int32), sds((), jnp.int32),
        )
    assert kernel_dispatch_stats()["paged_chunk"]["heads_per_step"] == want


@pytest.mark.parametrize("quantized", [False, True])
def test_dense_decode_lowers(as_tpu, quantized):
    cache = sds((B, KVH, 2048, HD))
    if quantized:
        cache = (
            sds((B, KVH, 2048, HD), jnp.int8),
            sds((B, KVH, 2048, 1), jnp.float32),
        )
    for split in (1, None):
        lower_for_tpu(
            lambda q, k, v, i, vf: decode_attention(
                q, k, v, i, vf, prefer="pallas", split=split
            ),
            sds((B, KVH, 1, HD)), cache, cache, INDEX, INDEX,
        )


def test_flash_lowers(as_tpu):
    q = sds((2, 12, 512, HD))
    vf = sds((2,), jnp.int32)
    lower_for_tpu(
        lambda q: flash_attention(q, q, q, causal=True, prefer="pallas"), q
    )
    lower_for_tpu(
        lambda q, vf: flash_attention(
            q, q, q, causal=True, prefer="pallas", valid_from=vf, window=200
        ),
        q, vf,
    )
    lower_for_tpu(
        lambda q, s: flash_attention_with_lse(
            q, q, q, causal=True, causal_shift=s
        ),
        q, sds((), jnp.int32),
    )


def test_flash_streaming_grad_lowers(as_tpu):
    # Past FLASH_MIN_SEQ the backward streams too: forward + dQ + dK/dV.
    q = sds((1, 1, 32768, HD))
    vf = sds((1,), jnp.int32)

    def loss(q, vf):
        return flash_attention(
            q, q, q, causal=True, valid_from=vf
        ).astype(jnp.float32).sum()

    lower_for_tpu(jax.grad(loss), q, vf, kernels=3)


def test_quantize_roundtrip_lowers(as_tpu):
    x = sds((3, 64 * 128), jnp.float32)
    lower_for_tpu(quantize, x)
    qt = QuantizedTensor(
        sds((3 * 64, 128), jnp.int8), sds((3, 1), jnp.float32),
        (3, 64 * 128), jnp.float32,
    )
    lower_for_tpu(dequantize, qt)


@pytest.mark.parametrize("dtype,page,why", [
    # int8 scale tiles need 1024-position pages on hardware.
    ("int8", 128, "page_size 128"),
    # The int4 nibble unpack does not fit Mosaic's scoped VMEM (measured
    # on a v5e; ROADMAP A1).
    ("int4", 1024, "int4 pools"),
])
def test_stated_rules_route_to_xla_on_tpu(as_tpu, dtype, page, why):
    """What the kernels cannot serve on hardware is routed by a rule
    the books report; forcing the kernel raises instead of serving the
    oracle under its name."""
    args = (sds((B, KVH, 1, HD)), pool(page, dtype), TABLE, INDEX)
    before = kernel_dispatch_stats().get("paged_decode", {"xla": 0.0})
    text = jax.jit(paged_attention).trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" not in text
    assert kernel_dispatch_stats()["paged_decode"]["xla"] == before["xla"] + 1
    with pytest.raises(ValueError, match=why):
        jax.jit(
            lambda *a: paged_attention(*a, prefer="pallas")
        ).trace(*args)


def _tp_mesh(devices, n=4):
    return Mesh(np.asarray(devices[:n]), ("tp",))


def test_tp4_paged_decode_lowers_under_shard_map(as_tpu, devices):
    """The batcher's programs are GSPMD-partitioned over the tp mesh;
    the kernel must sit in a shard_map over the head axis or the TPU
    lowering refuses the program."""
    mesh = _tp_mesh(devices)
    heads = NamedSharding(mesh, P(None, "tp"))
    repl = NamedSharding(mesh, P())

    def step(q, kv, t, i, head_shard):
        return paged_attention(q, kv, t, i, head_shard=head_shard)

    args = (sds((B, KVH, 1, HD)), pool(128, "native"), TABLE, INDEX)
    shardings = (heads, heads, repl, repl)
    sharded = jax.jit(
        functools.partial(step, head_shard=(mesh, "tp")),
        in_shardings=shardings, out_shardings=heads,
    )
    text = sharded.trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text
    bare = jax.jit(
        functools.partial(step, head_shard=None),
        in_shardings=shardings, out_shardings=heads,
    )
    with pytest.raises(NotImplementedError, match="shard_map"):
        bare.trace(*args).lower(lowering_platforms=("tpu",))


def test_tp4_paged_decode_lowers_at_cell_shape(as_tpu, devices):
    """``cgpt1b3_batchgen``'s shape under tp=4: each shard's kernel sees
    4 of the 16 heads and folds those."""
    mesh = _tp_mesh(devices)
    heads = NamedSharding(mesh, P(None, "tp"))
    repl = NamedSharding(mesh, P())
    sharded = jax.jit(
        lambda q, kv, t, i: paged_attention(
            q, kv, t, i, head_shard=(mesh, "tp")
        ),
        in_shardings=(heads, heads, repl, repl), out_shardings=heads,
    )
    text = sharded.trace(*_cell_args("cgpt1b3_batchgen")).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text
    assert kernel_dispatch_stats()["paged_decode"]["heads_per_step"] == 4.0


def test_head_sharded_kernels_match_oracles(devices):
    """Interpreter parity of the shard_map route itself: per-shard
    kernels over a tp=4 head split equal the single-device oracles
    (decode with int8 pools and ragged rows, verify with a tree tail
    and a split, chunk)."""
    from adapt_tpu.ops.paged_attention import (
        paged_chunk_attention_reference,
        paged_verify_attention_reference,
    )
    from adapt_tpu.ops.quantize import quantize_kv_vectors

    shard = (_tp_mesh(devices), "tp")
    rng = np.random.RandomState(0)
    b, kvh, g, hd, page = 2, 4, 2, 64, 128
    kp = jnp.asarray(rng.randn(6, kvh, page, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(6, kvh, page, hd), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    index = jnp.asarray([200, 90], jnp.int32)

    def close(out, ref):
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    q = jnp.asarray(rng.randn(b, kvh, g, hd), jnp.float32)
    kv = fuse_kv(kp, vp)
    kvq = fuse_kv(quantize_kv_vectors(kp), quantize_kv_vectors(vp))
    vf = jnp.asarray([3, 0], jnp.int32)
    close(
        jax.jit(
            lambda *a: paged_attention(
                *a, prefer="pallas", split=2, head_shard=shard
            )
        )(q, kvq, table, index, vf),
        paged_attention_reference(q, kvq, table, index, vf),
    )
    qv = jnp.asarray(rng.randn(b, kvh, g * 5, hd), jnp.float32)
    close(
        jax.jit(
            lambda *a: paged_verify_attention(
                *a, 5, prefer="pallas", tree_tail=2, split=2,
                head_shard=shard,
            )
        )(qv, kv, table, index),
        paged_verify_attention_reference(
            qv, kv, table, index, 5, tree_tail=2
        ),
    )
    qc = jnp.asarray(rng.randn(1, kvh, g * page, hd), jnp.float32)
    pages = jnp.asarray([3, 5], jnp.int32)
    close(
        jax.jit(
            lambda *a: paged_chunk_attention(
                *a, page, prefer="pallas", head_shard=shard
            )
        )(qc, kv, pages, jnp.int32(page)),
        paged_chunk_attention_reference(qc, kv, pages, page, page),
    )


# -- the pool write compiles with no relayout of the pool ----------------------


def _pallas_calls(jaxpr, found=None):
    """Every ``pallas_call`` equation of a jaxpr, nested ones too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _pallas_calls(getattr(inner, "jaxpr", inner), found)
    return found


@pytest.mark.parametrize("split", [1, 2])
@pytest.mark.parametrize("shape", [
    (24, 16, 128, 7, 169, 128, "native"),  # cgpt1b3_batchgen
    (8, 25, 64, 7, 57, 128, "native"),  # gpt2xl_doc
    (32, 25, 64, 3, 97, 128, "native"),  # gpt2xl_chat
    (8, 16, 128, 2, 17, 1024, "int8"),  # 8 of 16 heads a step
    (8, 12, 32, 4, 33, 128, "native"),  # a row under a lane tile
], ids=["cgpt1b3_batchgen", "gpt2xl_doc", "gpt2xl_chat", "int8-p1024",
        "hd32-transposed"])
def test_folded_paged_decode_compiles_for_v5e(
    as_tpu, one_chip, no_persistent_cache, shape, split
):
    """Mosaic's own compile of the folded decode kernel (the lowering
    above stops before it): the block of every head that
    ``decode_heads_per_step`` derives, in the two buffers of the walk's
    ``decode_pages_per_step``, fits the scoped VMEM of a v5e, head_dim
    64 reads its fused row whole and emits the accumulator's upper
    lanes, and the operation keeps the name the benchmark's readers sum
    (``_paged_impl``). Unsplit, the kernel WALKS: the grid is the rows,
    every plane of the pool is handed to the call once, whole and
    where it lives (a blocked operand pinned to a memory space fails
    here), and the compiled program holds no copy or relayout of it; a
    row under a lane tile reaches the call through a bitcast."""
    b, kvh, hd, pps, npages, page, dtype = shape

    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kv = on_chip((npages, kvh, page, 2 * hd))
    if dtype == "int8":
        kv = (
            on_chip((npages, kvh, page, 2 * hd), jnp.int8),
            on_chip((npages, kvh, page, 1), jnp.float32),
            on_chip((npages, kvh, page, 1), jnp.float32),
        )
    args = (
        on_chip((b, kvh, 1, hd)), kv, on_chip((b, pps), jnp.int32),
        on_chip((b,), jnp.int32), on_chip((b,), jnp.int32),
    )

    def attend(q, kv, t, i, vf):
        return paged_attention(q, kv, t, i, vf, split=split)

    text = jax.jit(attend).lower(*args).compile().as_text()
    call = re.search(r"%_paged_impl[.\d]* = .*tpu_custom_call.*", text)
    assert call
    if split != 1:
        return
    books = kernel_dispatch_stats()["paged_decode"]
    jaxpr = jax.make_jaxpr(attend)(*args)
    (eqn,) = _pallas_calls(jaxpr.jaxpr)
    assert "memory_space_constraint" not in str(jaxpr)
    mapping = eqn.params["grid_mapping"]
    assert mapping.grid == (b, kvh // books["heads_per_step"])
    assert books["grid_steps"] == np.prod(mapping.grid)
    planes = [
        str(m.transformed_block_aval) for m in mapping.block_mappings
        if "any" in str(m.transformed_block_aval)
    ]
    assert len(planes) == len(jax.tree.leaves(kv)), planes
    row = (page, 2 * hd) if hd >= 64 else (2 * hd, page)
    held = ",".join(map(str, (npages, kvh) + row))
    assert f"[{held}]" in planes[0]  # the whole plane, not a block of it
    constraints = call[0].split("operand_layout_constraints=")[1]
    assert constraints.split("frontend_attributes")[0].count(f"[{held}]") == 1
    for plane in jax.tree.leaves(kv):
        assert pool_copies(text, plane.shape) == (0, 0)
    assert pool_copies(text, (npages, kvh) + row) == (0, 0)
    if hd < 64:
        assert re.search(
            rf"\[{held}\]\S* bitcast\(", text
        ), "the swapped view of the plane is no longer a bitcast"


@pytest.mark.parametrize("cell", sorted(_CHUNK_CELL_SHAPES))
def test_folded_paged_chunk_compiles_for_v5e(
    as_tpu, one_chip, no_persistent_cache, cell
):
    """Mosaic's own compile of the chunk kernel at the cells' shapes:
    the heads a step ``chunk_heads_per_step`` derives (the books say
    which) fit a v5e's scoped VMEM with bfloat16 operands on the MXU,
    the dead steps' clamped index map compiles, and the operation
    keeps the name the benchmark's readers sum (``_chunk_impl``). The
    step's VMEM sum is under the budget it was derived from wherever
    there was a choice; one head of K-EXAONE's 2,048 rows is over it
    and under Mosaic's 16 MB, which this compile is the proof of."""
    kvh, g, hd, window, want = _CHUNK_CELL_SHAPES[cell]
    chunk, page = 256, 128

    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    for n in (2, 8):
        text = jax.jit(
            lambda q, kv, p, pos0: paged_chunk_attention(
                q, kv, p, pos0, chunk, window=window
            )
        ).lower(
            on_chip((1, kvh, g * chunk, hd)),
            on_chip((65, kvh, page, 2 * hd)),
            on_chip((n,), jnp.int32), on_chip((), jnp.int32),
        ).compile().as_text()
        assert re.search(r"%_chunk_impl[.\d]* = .*tpu_custom_call", text)
    heads = kernel_dispatch_stats()["paged_chunk"]["heads_per_step"]
    args = (g * chunk, page, 2 * hd, 2, False, hd, 2)
    assert heads == chunk_heads_per_step(kvh, *args) == want
    used = chunk_step_vmem_bytes(want, *args)
    assert used <= (
        DECODE_STEP_VMEM_BUDGET if want > 1 else 2 * DECODE_STEP_VMEM_BUDGET
    )


_LAYERS = 2

#: (pool shape, model dim, heads, mlp, slots, pages per slot): the
#: benchmark's two deployments at their published widths. A block's
#: pool is ONE plane, K|V fused on the lanes.
_CGPT = ((169, 16, 128, 256), 2048, 16, 8192, 24, 7)  # cgpt1b3_batchgen
_XL = ((57, 25, 128, 128), 1600, 25, 6400, 8, 7)  # gpt2xl_doc


@pytest.mark.parametrize("deploy,form", [
    # head_dim 128: two lane tiles a row.
    (_CGPT, "step"),
    (_CGPT, "scan8"),
    (_CGPT, "verify"),
    # head_dim 64: the fused row is exactly ONE lane tile, so the plane
    # lives row-major like head_dim 128's, ``append_kv_paged`` takes its
    # head-indexed scatter and all three kernels read it as it lives
    # (until PR 30 a K and a V plane of 64-lane rows lived with the page
    # axis on the lanes: a row loop to write them, a transposed read in
    # the decode kernel, and one relayout a plane around the other two).
    (_XL, "step"),
    (_XL, "scan8"),
    (_XL, "verify"),
    (_XL, "chunk"),
], ids=["hd128-step", "hd128-scan8", "hd128-verify", "hd64-step",
        "hd64-scan8", "hd64-verify", "hd64-chunk"])
def test_pool_write_compiles_without_pool_relayout(
    as_tpu, one_chip, no_persistent_cache, deploy, form
):
    """Two real ``DecoderBlock``s, pools donated, compiled for the
    described chip: the write (per token; a chunk's pages in ``chunk``,
    through ``prefill_chunk_paged``) plus the Mosaic call schedule no
    whole-pool relayout and no staging move (the advanced-index scatter
    this replaced cost 2 per plane at head_dim 128, 3 inside a scan's
    body + entry + exit, and 3 at head_dim 64; the two-plane head_dim-64
    pool cost its chunk-prefill program 2 a plane a pass). Layout
    assignment is a heuristic — how the update operand is produced
    decides the pool's layout — so this count is the guard."""
    shape, dim, heads, mlp, slots, pps = deploy
    block = DecoderBlock(BlockSpec(dim, heads, mlp), dtype=jnp.bfloat16)
    rows, kc = slots, {"verify": 4, "chunk": 256}.get(form, 1)
    if form == "chunk":
        rows = 1  # prefill is per request: 256 positions, two pages

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(
            lambda: block.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1, dim), jnp.bfloat16)
            )
        ),
    )

    def step(params, x, pools, table, index):
        out = []
        for kv in pools:
            if form == "verify":
                x, kv = block.apply(
                    params, x, kv, table, index, "pallas",
                    method="verify_chunk_paged",
                )
            elif form == "chunk":  # table: the window's pages; index: pos0
                x, kv = block.apply(
                    params, x, kv, table, index, "pallas",
                    method="prefill_chunk_paged",
                )
            else:
                x, kv = block.apply(
                    params, x, kv, table, index, None, "pallas",
                    method="decode_step_paged",
                )
            out.append(kv)
        return x, out

    def program(params, x, pools, table, index):
        if form != "scan8":
            return step(params, x, pools, table, index)

        def body(carry, _):
            x, pools, index = carry
            x, pools = step(params, x, pools, table, index)
            return (x, pools, index + 1), None

        (x, pools, _), _ = lax.scan(
            body, (x, pools, index), None, length=8
        )
        return x, pools

    if form == "chunk":
        table, index = on_chip((4,), jnp.int32), on_chip((), jnp.int32)
    else:
        table = on_chip((slots, pps), jnp.int32)
        index = on_chip((slots,), jnp.int32)
    compiled = jax.jit(program, donate_argnums=(2,)).lower(
        params, on_chip((rows, kc, dim)),
        [on_chip(shape) for _ in range(_LAYERS)], table, index,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= _LAYERS
    assert pool_copies(text, shape) == (0, 0)
    # And no row loop: neither deployment's plane takes
    # ``append_kv_paged``'s narrow arm (one pool-shaped
    # ``dynamic-update-slice`` a token, 4-5 us each on a v5e).
    dims = re.escape(",".join(map(str, shape)))
    assert not re.search(
        r"= \w+\[" + dims + r"\]\S* dynamic-update-slice\(", text
    )


def test_pool_copies_counts_what_the_scatter_cost():
    """The counter itself, on lines of the shapes the compiler prints
    (the parent's program at head_dim 64 held all three kinds)."""
    text = """
  %copy.91 = bf16[57,25,128,64]{3,1,2,0:T(8,128)(2,1)S(1)} copy(%copy-done.1), sharding={replicated}
  %copy.112 = bf16[57,25,128,64]{2,3,1,0:T(8,128)(2,1)} copy(%fusion.1)
  %copy-start.1 = (bf16[57,25,128,64]{2,3,1,0:T(8,128)(2,1)S(1)}, bf16[57,25,128,64]{2,3,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%pools_0__0_.1)
  %copy-start.2 = (bf16[57,25,128,64]{3,2,1,0:T(8,128)(2,1)}, bf16[57,25,128,64]{2,3,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%p)
  %copy.3 = bf16[8,25,1,64]{3,0,1,2:T(8,128)(2,1)} copy(%x)
  %dus = bf16[57,25,128,64]{2,3,1,0:T(8,128)(2,1)} dynamic-update-slice(%a, %copy.3, %i, %z, %j, %z)
"""
    assert pool_copies(text, (57, 25, 128, 64)) == (3, 1)
    assert pool_copies(text, (8, 25, 1, 64)) == (1, 0)


# -- compile cache placement --------------------------------------------------


def test_compile_cache_env_wins(monkeypatch):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a: updates.append(a)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.ensure_compile_cache() == "/some/dir"
    assert updates == []


def test_compile_cache_default_is_fixed_under_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a: updates.append(a)
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compile_cache.ensure_compile_cache() == want
    assert compile_cache.ensure_compile_cache() == want  # no pid/time in it
    assert updates == [
        ("jax_compilation_cache_dir", want),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ] * 2


# -- what K-EXAONE's path reaches (PR 31): 8 query heads a KV head, a
# -- window, a page table a cache group, the grouped expert product ----

#: slots, kv heads, query heads a KV head, head_dim, pages a slot, the
#: full and the window group's pool pages: ``kexaone_longgen``.
_KEX = (128, 8, 8, 128, 16, 1921, 385)


def test_kexaone_attention_entries_lower(as_tpu):
    b, kvh, g, hd, pps, full, win = _KEX
    q = sds((b, kvh, g, hd))
    table, index = sds((b, pps), jnp.int32), sds((b,), jnp.int32)
    for pages in (full, win):  # a pool and a table a group
        lower_for_tpu(
            paged_attention, q, sds((pages, kvh, 128, 2 * hd)), table,
            index, index,
        )
    for window in (None, 128):
        lower_for_tpu(
            lambda q, kv, p, pos0: paged_chunk_attention(
                q, kv, p, pos0, 256, window=window
            ),
            sds((1, kvh, g * 256, hd)), sds((win, kvh, 128, 2 * hd)),
            sds((4,), jnp.int32), sds((), jnp.int32),
        )


def _expert_operands(on=sds):
    rows, d, h, held = 1024, 6144, 2048, 16  # 128 rows x top-8
    return (
        on((rows, d)), on((held, d, h)), on((held, d, h)), on((held, h, d)),
        on((held,), jnp.int32),
    )


def test_expert_product_lowers(as_tpu):
    from adapt_tpu.models.moe import expert_product

    # Gate and up share one lowered kernel (equal shapes), down its own.
    lower_for_tpu(expert_product, *_expert_operands(), kernels=2)
    assert kernel_dispatch_stats()["expert_product"]["last"] == 1.0


def test_expert_product_compiles_for_v5e(as_tpu, one_chip, no_persistent_cache):
    """Mosaic's own compile of the grouped matmul at the published
    widths and the tiles ``models/moe`` picked (a 1024 x 2048 weight
    tile, double-buffered, inside a v5e's scoped VMEM), under the name
    the benchmark's reader sums (``gmm``)."""
    from adapt_tpu.models.moe import expert_product

    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = expert_product.lower(*_expert_operands(on_chip)).compile().as_text()
    assert len(re.findall(r"%gmm[.\d]* = .*tpu_custom_call", text)) == 3


def test_step_chunk_with_a_table_a_group_lowers(as_tpu):
    """The batcher's own decode program for a two-group model (window
    and full layers, routed experts): every block's paged kernel takes
    its group's table, the expert blocks their grouped product."""
    from adapt_tpu.models.moe import ExpertSpec
    from adapt_tpu.models.transformer_lm import transformer_lm
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    experts = ExpertSpec(16, 128, 4, score="sigmoid", normalize=True,
                         select_bias=True, shared_dim=128, held=(0, 4))

    def spec(window, sparse):
        return BlockSpec(
            256, 8, 256, kv_heads=1, head_dim=128, norm="rmsnorm",
            post_norm=True, qk_norm=True, bias=False,
            mlp="experts" if sparse else "gated_silu",
            experts=experts if sparse else None,
            rope_base=1e6 if window else None, window=window,
        )

    lm = transformer_lm(
        512, blocks=[spec(128, False), spec(128, True), spec(None, True)],
        pos="none", max_len=512, dtype=jnp.bfloat16,
    )
    variables = jax.eval_shape(
        lm.graph.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    variables = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.bfloat16), variables
    )
    srv = ContinuousBatcher(lm, variables, slots=8, chunk=2, page_size=128)
    assert [g.name for g in srv._groups] == ["full", "window"]
    tables = srv._current_table()
    assert isinstance(tables, tuple) and len(tables) == 2
    # Rows of 256 are whole lane tiles: the engine holds the model's own
    # tree and applies the graph's own embedding (tests/test_embed_lanes).
    assert srv.stats()["embed_row_pad"] == 0
    assert srv._served is variables and srv.variables is variables
    assert srv._embed is lm.graph.node("embed").module
    text = type(srv)._step_chunk.trace(
        srv, srv._served, srv._caches, srv._dstate, tables,
        truncate=False, nucleus=False, epoch=0,
    ).lower(lowering_platforms=("tpu",)).as_text()
    srv.close()
    # The paged decode kernel (full and window tables) and the grouped
    # matmul (in and out widths): each lowered once, called a block.
    assert text.count("tpu_custom_call") >= 3


# -- Falcon-H1: the state-space mixer's decode step ----------------------------

# falconh1_longgen: 128 rows, 32 heads of (256, 128) in 2 groups.
_SSM = (128, 32, 256, 128, 2)


def _ssm_operands(on=sds):
    rows, heads, n, p, groups = _SSM
    return (
        on((rows, heads, n, p), jnp.float32), on((rows, heads, p)),
        on((rows, heads), jnp.float32), on((heads,), jnp.float32),
        on((rows, groups, n)), on((rows, groups, n)),
    )


def test_ssm_step_lowers(as_tpu):
    from adapt_tpu.ops.ssm_step import heads_per_step, ssm_step

    lower_for_tpu(ssm_step, *_ssm_operands())
    assert kernel_dispatch_stats()["ssm_step"]["last"] == 1.0
    # 8 of a group's 16 heads a grid step: 1 MiB of float32 state
    assert heads_per_step(16, 256, 128) == 8


def test_ssm_step_compiles_for_v5e_in_place(
    as_tpu, one_chip, no_persistent_cache
):
    """Mosaic's own compile at the published widths (the in-kernel
    transpose that turns B and C into columns, four 1 MiB blocks in
    VMEM), under the name the benchmark's reader sums
    (``_ssm_step_impl``), and the donated state aliased to the output:
    no second copy of 537 MB."""
    from adapt_tpu.ops.ssm_step import ssm_step

    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(ssm_step, donate_argnums=(0,)).lower(
        *_ssm_operands(on_chip)
    ).compile()
    assert re.search(
        r"%_ssm_step_impl[.\d]* = .*tpu_custom_call", compiled.as_text()
    )
    mem = compiled.memory_analysis()
    state = 128 * 32 * 256 * 128 * 4
    assert mem.alias_size_in_bytes == state
    assert mem.temp_size_in_bytes < state // 16


# -- Solar-Open2: the gated delta-rule layer's decode step ---------------------

# solaropen2_longgen: 256 rows, 64 heads of a (128, 128) state.
_KDA = (256, 64, 128)


def _kda_operands(on=sds):
    rows, heads, d = _KDA
    return (
        on((rows, heads, d, d), jnp.float32), on((rows, heads, d)),
        on((rows, heads, d)), on((rows, heads, d)),
        on((rows, heads, d), jnp.float32), on((rows, heads), jnp.float32),
    )


def test_kda_step_lowers_and_narrow_heads_route_to_xla(as_tpu):
    from adapt_tpu.ops.kda_step import kda_step

    lower_for_tpu(kda_step, *_kda_operands())
    assert kernel_dispatch_stats()["kda_step"]["last"] == 1.0
    narrow = (
        sds((4, 4, 16, 16), jnp.float32), sds((4, 4, 16)), sds((4, 4, 16)),
        sds((4, 4, 16)), sds((4, 4, 16), jnp.float32),
        sds((4, 4), jnp.float32),
    )
    jax.jit(kda_step).trace(*narrow).lower(lowering_platforms=("tpu",))
    assert kernel_dispatch_stats()["kda_step"]["last"] == 0.0
    with pytest.raises(ValueError, match="whole \\(128, 128\\) tiles"):
        jax.eval_shape(
            functools.partial(kda_step, prefer="pallas"), *narrow
        )


def test_kda_step_compiles_for_v5e_in_place(
    as_tpu, one_chip, no_persistent_cache
):
    """Mosaic's own compile at the published widths (three in-kernel
    transposes a head turn alpha, k and q into columns; four 1 MiB
    blocks of 16 heads in VMEM), under the name the benchmark's reader
    sums (``_kda_step_impl``), and the donated state aliased to the
    output: no second copy of 1.07 GB a layer."""
    from adapt_tpu.ops.kda_step import kda_step

    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(kda_step, donate_argnums=(0,)).lower(
        *_kda_operands(on_chip)
    ).compile()
    assert re.search(
        r"%_kda_step_impl[.\d]* = .*tpu_custom_call", compiled.as_text()
    )
    mem = compiled.memory_analysis()
    state = 256 * 64 * 128 * 128 * 4
    assert mem.alias_size_in_bytes == state
    assert mem.temp_size_in_bytes < state // 16


def test_ssm_step_on_narrow_heads_routes_to_xla_on_tpu(as_tpu):
    """A head's state that is not whole (128, 128) tiles cannot take
    the in-kernel transpose: auto dispatch books the plain arm, a
    forced kernel raises."""
    from adapt_tpu.ops.ssm_step import ssm_step

    args = (
        sds((4, 4, 32, 16), jnp.float32), sds((4, 4, 16)),
        sds((4, 4), jnp.float32), sds((4,), jnp.float32),
        sds((4, 2, 32)), sds((4, 2, 32)),
    )
    jax.jit(ssm_step).trace(*args).lower(lowering_platforms=("tpu",))
    assert kernel_dispatch_stats()["ssm_step"]["last"] == 0.0
    with pytest.raises(ValueError, match="whole .128, 128. tiles"):
        jax.jit(lambda *a: ssm_step(*a, prefer="pallas")).trace(*args)


def test_step_chunk_with_recurrent_state_lowers(as_tpu):
    """The batcher's own decode program for a hybrid model: every
    block's paged kernel (5 query heads a KV head) and its mixer's
    state update, the states carried through the scan."""
    from adapt_tpu.models.ssm import SsmSpec
    from adapt_tpu.models.transformer_lm import transformer_lm
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    spec = BlockSpec(
        256, 5, 256, kv_heads=1, head_dim=128, norm="rmsnorm", bias=False,
        mlp="gated_silu", rope_base=1e11, attn_out_mult=0.0375,
        ssm=SsmSpec(heads=16, head_dim=128, d_state=128, groups=2, chunk=128),
    )
    lm = transformer_lm(
        512, blocks=[spec] * 2, pos="none", max_len=512, dtype=jnp.bfloat16,
    )
    variables = jax.eval_shape(
        lm.graph.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    variables = jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.bfloat16), variables
    )
    srv = ContinuousBatcher(lm, variables, slots=8, chunk=2, page_size=128)
    text = type(srv)._step_chunk.trace(
        srv, srv._served, srv._caches, srv._dstate, srv._current_table(),
        srv._states, truncate=False, nucleus=False, epoch=0,
    ).lower(lowering_platforms=("tpu",)).as_text()
    srv.close()
    assert text.count("tpu_custom_call") >= 2


# -- the latent (MLA) cache: decode kernel and per-token write ----------------


#: (slots, heads, pool pages): xing4_longgen8k's kernel shape (a small
#: pool: its size is no kernel's operand), and gigachat35_longgen8k's
#: (64 heads, 192 slots, the cell's pool of 192 x 62 + 1 pages).
_LATENT_CELLS = {"xing4": (128, 32, 257), "gigachat35": (192, 64, 11905)}


def _latent_operands(on=sds, cell="xing4"):
    b, heads, pages = _LATENT_CELLS[cell]
    row, pps, page = 576, 64, 128
    return (
        on((b, heads, row)), on((pages, row, page)), on((b, row)),
        on((b,), jnp.int32), on((b,), jnp.int32), on((b, pps), jnp.int32),
        on((b,), jnp.int32),
    )


def _latent_step(q, pool, new, phys, off, table, index):
    from adapt_tpu.ops.latent_attention import (
        append_latent_paged,
        latent_paged_attention,
    )

    pool = append_latent_paged(pool, new, phys, off)
    return latent_paged_attention(
        q, pool, table, index, sm_scale=0.14468, v_width=512
    ), pool


def test_latent_decode_and_write_lower(as_tpu):
    """Xing4.0's shapes: 32 heads against one 576-value row a
    position, a slot a grid step and 8 pages an iteration of its walk;
    the write is a kernel too."""
    lower_for_tpu(_latent_step, *_latent_operands(), kernels=2)


@pytest.mark.parametrize("cell", sorted(_LATENT_CELLS))
def test_latent_decode_and_write_compile_for_v5e_in_place(
    as_tpu, one_chip, no_persistent_cache, cell
):
    """Mosaic's own compile at the cell's shapes, and no copy of the
    pool around either kernel: a page's positions lie on the lanes,
    which is the layout the plane has, and the per-token write is a
    kernel because a scatter of one lane a slot relaid the whole pool
    out, there and back (PERF.md section 6, PR 43). The decode kernel
    takes the pool ONCE and copies the pages it reads out of it itself
    (PR 44: it was handed the pool once a page of a grid step)."""
    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(_latent_step, donate_argnums=(1,)).lower(
        *_latent_operands(on_chip, cell)
    ).compile()
    text = compiled.as_text()
    pool = f"bf16[{_LATENT_CELLS[cell][2]},576,128]"
    call = re.search(r"%_latent_impl[.\d]* = .*tpu_custom_call.*", text)
    assert call and call.group(0).count(pool) == 1
    assert re.search(r"%_latent_write_impl[.\d]* = .*tpu_custom_call", text)
    assert not re.search(re.escape(pool) + r"\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def _sparse_latent_step(q, q_i, w, pool, ipool, new, key, phys, off, table,
                        index):
    from adapt_tpu.ops.latent_attention import append_latent_paged
    from adapt_tpu.ops.sparse_latent_attention import (
        sparse_latent_paged_attention,
    )

    pool = append_latent_paged(pool, new, phys, off)
    ipool = append_latent_paged(ipool, key, phys, off)
    return sparse_latent_paged_attention(
        q, q_i, w, pool, ipool, table, index, sm_scale=0.13523, v_width=512,
        top_k=2048,
    ), pool, ipool


def test_the_selecting_decode_compiles_for_v5e_in_place(
    as_tpu, one_chip, no_persistent_cache
):
    """``dsv32_longgen32k``'s step of one layer at its shapes (32 slots
    of 252 pages, 128 heads, 64 index heads of 128, both planes of the
    cell's pool): Mosaic's own compile of the ONE kernel that scores,
    bisects and reads, the write kernel once a plane, and no copy of
    either plane around them."""
    def on_chip(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, pages = 32, 8065
    compiled = jax.jit(_sparse_latent_step, donate_argnums=(3, 4)).lower(
        on_chip((b, 128, 576)), on_chip((b, 64, 128)),
        on_chip((b, 64), jnp.float32), on_chip((pages, 576, 128)),
        on_chip((pages, 128, 128)), on_chip((b, 576)), on_chip((b, 128)),
        on_chip((b,), jnp.int32), on_chip((b,), jnp.int32),
        on_chip((b, 252), jnp.int32), on_chip((b,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    call = re.search(r"%_sparse_latent_impl[.\d]* = .*tpu_custom_call.*", text)
    assert call
    for plane in (f"bf16[{pages},576,128]", f"bf16[{pages},128,128]"):
        assert call.group(0).count(plane) == 1
        assert not re.search(re.escape(plane) + r"\S* copy\(", text)
    assert len(re.findall(
        r"%_latent_write_impl[.\d]* = .*tpu_custom_call", text
    )) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("rows,vocab", [
    (128, 261120),  # falconh1_longgen: the whole published vocabulary
    (256, 24576),  # solaropen2_longgen
])
def test_the_greedy_tail_writes_no_rows_by_vocabulary_array(
    one_chip, no_persistent_cache, rows, vocab
):
    """A greedy row's tail over the vocabulary (the arg-max, then
    ``chosen_logprob``), compiled for a v5e at two cells' shapes: only
    reading passes over the logits. The log-softmax it replaced (PR 50)
    compiled to a fusion whose RESULT was a second float32
    ``(rows, vocabulary)`` array, 134 MB a step in Falcon-H1; the same
    function holds that array, so the count tells the two apart."""
    from adapt_tpu.models.transformer_lm import chosen_logprob

    def tail(score, logits):
        nxt = jnp.argmax(logits, axis=-1)
        return nxt, score(logits, nxt)

    logits = jax.ShapeDtypeStruct((rows, vocab), jnp.float32,
                                  sharding=one_chip)
    wide = re.compile(rf"= f32\[{rows},{vocab}\]\S* fusion\(")

    def written(score):
        text = jax.jit(functools.partial(tail, score)).lower(
            logits
        ).compile().as_text()
        return len(wide.findall(text))

    assert written(chosen_logprob) == 0
    assert written(log_softmax_score) == 1


def _while_loops(text):
    """The ``while`` operations of a compiled program's text as a tree:
    ``[(trip_count, loops_inside_its_body), ..]`` from the entry
    computation down (through fusions and calls). A trip count is the
    constant its condition compares the counter with (what a
    ``lax.scan`` compiles to); None where there is no such constant."""
    bodies, name = {}, None
    for line in text.splitlines():
        if m := re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line):
            name = "ENTRY" if m.group(1) else m.group(2)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)

    def trips(condition):
        lines = "\n".join(bodies[condition])
        if not re.search(r"compare\(.*direction=LT", lines):
            return None
        found = re.findall(r"= s32\[\]\S* constant\((\d+)\)", lines)
        return int(found[0]) if len(found) == 1 else None

    def inside(name):
        out = []
        for line in bodies[name]:
            if m := re.search(
                r" while\(.*condition=%?([\w.\-]+), body=%?([\w.\-]+)", line
            ):
                out.append((trips(m.group(1)), inside(m.group(2))))
            else:
                for callee in re.findall(
                    r"(?:calls|to_apply)=%?([\w.\-]+)", line
                ):
                    out += inside(callee)
        return out

    return inside("ENTRY")


@pytest.mark.parametrize("decay", ["channel", "head"])
def test_kda_prefill_compiles_for_v5e_with_the_solve_outside_the_state_scan(
    as_tpu, one_chip, no_persistent_cache, decay
):
    """Both chunked forms at the published widths (64 heads of a
    128 x 128 state; ``solar-open2-250b`` a decay a channel,
    ``gigachat3.5-432b-a28b`` one a head), compiled for a v5e. A pass of
    256 positions (every pass of both cells) is ONE group of 4 chunks:
    no loop inside another, the substitution's 16 steps beside the
    4-chunk state scan (and, a decay a channel, the 4-chunk map that
    keeps the pairwise decays to one chunk's multiply-and-reduce). A
    pass of 2,048 is a scan over 4 groups of 8 and nothing deeper, and
    what it holds beside its operands stays under 256 MB: neither the
    (64, 64, 64, 128) pairwise tensor (134 MB a chunk) nor the hoisted
    operands were formed for the whole pass."""
    from adapt_tpu.models import kda

    fn = kda.kda_chunked if decay == "channel" else kda.kda_chunked_head
    heads, d = 64, 128

    def compiled(s):
        def on_chip(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        g = (s, heads, d) if decay == "channel" else (s, heads)
        return jax.jit(fn).lower(
            *(on_chip((s, heads, d)) for _ in "qkv"),
            on_chip(g, jnp.float32), on_chip((s, heads), jnp.float32),
            on_chip((heads, d, d), jnp.float32),
        ).compile()

    block = kda._SOLVE_BLOCK
    pre_pass = [4] if decay == "channel" else []
    loops = _while_loops(compiled(256).as_text())
    assert sorted(loops) == sorted(
        (n, []) for n in [*pre_pass, block, 4]
    ), loops
    def booked():
        books = kernel_dispatch_stats()["kda_prefill"]
        return [
            books[k] for k in ("chunk", "group", "solve_block", "solve_steps")
        ]

    assert booked() == [64, 4, block, block]
    long = compiled(2048)
    (groups, inner), = _while_loops(long.as_text())
    pre_pass = [8] if decay == "channel" else []
    assert groups == 4 and sorted(inner) == sorted(
        (n, []) for n in [*pre_pass, block, 8]
    ), (groups, inner)
    assert booked() == [64, 8, block, 4 * block]
    assert long.memory_analysis().temp_size_in_bytes < 256e6
