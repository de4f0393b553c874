"""ISSUE-12 kernel push: flash-split decode, int4 KV, tree-draft verify.

Interpreter-mode parity for every new kernel branch (split decode
dense/paged x native/int8/int4, ragged last split, split=1 degenerate
== the unsplit kernel bit-exact; tree-mask verify vs a jnp oracle),
the batcher-level invariants under a forced-Pallas `KernelConfig`
(bit-identical greedy streams, 0 h2d/steady tick, frozen compile
footprint), tree-draft losslessness + the > 5.0 accepted-per-pass
claim, int4 composition (top-1 agreement vs int8, prefix cache, disagg
handoff, tp=2 sharding, recovery migration), the kernel-dispatch
gauges, and the per-generation roofline peak table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.config import (
    KernelConfig,
    ParallelConfig,
    SpeculativeConfig,
)
from adapt_tpu.models.transformer_lm import (
    generate,
    lm_tiny,
    transformer_lm,
)
from adapt_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    default_decode_split,
    verify_attention,
)
from adapt_tpu.ops.dispatch import kernel_dispatch_stats
from adapt_tpu.ops.paged_attention import (
    fuse_kv,
    paged_attention,
    paged_attention_reference,
    paged_verify_attention,
    paged_verify_attention_reference,
)
from adapt_tpu.ops.quantize import (
    pack_int4,
    quantize_kv_vectors,
    unpack_int4,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher

VOCAB = 37


def _solo(lm, variables, prompt, steps, **kw):
    return np.asarray(
        generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
    )[0]


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=VOCAB, max_len=96)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


# -- ops: packing ------------------------------------------------------------


def test_int4_pack_roundtrip():
    rng = np.random.RandomState(0)
    q = rng.randint(-8, 8, size=(3, 5, 16)).astype(np.int32)
    rt = np.asarray(unpack_int4(pack_int4(jnp.asarray(q))))
    np.testing.assert_array_equal(rt, q)


def test_int4_quantize_kv_vectors_shapes_and_error():
    t = jnp.asarray(np.random.RandomState(1).randn(2, 3, 16), jnp.float32)
    v8, s8 = quantize_kv_vectors(t, "int8")
    v4, s4 = quantize_kv_vectors(t, "int4")
    assert v8.shape == (2, 3, 16) and v4.shape == (2, 3, 8)
    assert s8.shape == s4.shape == (2, 3, 1)
    # int4 dequant stays within one lattice step of the input
    deq = np.asarray(unpack_int4(v4)) * np.asarray(s4)
    assert np.abs(deq - np.asarray(t)).max() <= np.asarray(s4).max() * 0.51
    with pytest.raises(ValueError, match="even head_dim"):
        quantize_kv_vectors(t[..., :15], "int4")


@pytest.mark.parametrize("cores,want", [
    # One core (a v5e): the chip's A/B kept the kernels unsplit (PERF.md
    # section 6, PR 28).
    (1, [1, 1, 1, 1, 1, 1, 1]),
    (2, [1, 1, 1, 2, 2, 2, 2]),
    # Cores to spare: every split keeps at least two blocks, 8 at most.
    (8, [1, 1, 1, 2, 4, 8, 8]),
    (16, [1, 1, 1, 2, 4, 8, 8]),
])
def test_default_decode_split_rule(cores, want):
    assert [
        default_decode_split(n, cores) for n in (1, 2, 3, 4, 8, 16, 64)
    ] == want


def test_resolve_decode_split_reads_blocks_and_cores(monkeypatch):
    """An explicit split wins; the automatic one is 1 off-TPU, and on a
    TPU ``default_decode_split`` of the block count and the cores the
    device reports (the benchmark's 7 pages a slot: 1 on one core)."""
    import importlib

    # ``adapt_tpu.ops`` exports the dispatcher under the module's name.
    da = importlib.import_module("adapt_tpu.ops.decode_attention")
    assert da.resolve_decode_split(7, 4) == 4
    assert da.resolve_decode_split(64, None) == 1  # the CPU backend
    monkeypatch.setattr(da, "on_tpu", lambda: True)
    for cores, want in ((1, 1), (2, 2), (8, 8)):
        monkeypatch.setattr(da, "device_cores", lambda cores=cores: cores)
        assert da.resolve_decode_split(64, None) == want
    monkeypatch.setattr(da, "device_cores", lambda: 1)
    assert da.resolve_decode_split(7, None) == 1
    assert da.resolve_decode_split(7, 2) == 2


# -- ops: interpreter parity, every new branch -------------------------------


def _quant(pool, dt):
    return quantize_kv_vectors(pool, dt)


@pytest.mark.parametrize("dtype", ["native", "int8", "int4"])
@pytest.mark.parametrize("split", [1, 2, 3, 4])
def test_split_decode_dense_parity(dtype, split):
    """Dense split kernel vs the einsum oracle, every dtype, including
    the RAGGED split=3 over 4 blocks and a ragged valid_from window."""
    rng = np.random.RandomState(0)
    b, kvh, g, hd, L = 2, 2, 4, 16, 1024
    q = jnp.asarray(rng.randn(b, kvh, g, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, kvh, L, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, kvh, L, hd), jnp.float32)
    idx = jnp.asarray([700, 130], jnp.int32)
    vf = jnp.asarray([3, 0], jnp.int32)
    if dtype == "native":
        ck, cv = k, v
    else:
        ck, cv = _quant(k, dtype), _quant(v, dtype)
    ref = decode_attention_reference(q, ck, cv, idx, vf)
    out = decode_attention(
        q, ck, cv, idx, vf, prefer="pallas", split=split
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5
    )


def test_split1_degenerate_bit_exact():
    """split=1 must be the ORIGINAL single-stream kernel bit-for-bit
    (it IS that code path; the combine never runs)."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 512, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 512, 16), jnp.float32)
    idx = jnp.asarray([200], jnp.int32)
    a = decode_attention(q, k, v, idx, prefer="pallas", split=1)
    b = decode_attention(q, k, v, idx, prefer="pallas")  # auto off-TPU -> 1
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", ["native", "int8", "int4"])
@pytest.mark.parametrize("split", [2, 3])
def test_split_decode_paged_parity(dtype, split):
    rng = np.random.RandomState(1)
    b, kvh, g, hd, P, pps = 2, 2, 4, 16, 128, 5
    npages = b * pps + 1
    kp = jnp.asarray(rng.randn(npages, kvh, P, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(npages, kvh, P, hd), jnp.float32)
    table = jnp.asarray(
        np.arange(1, 1 + b * pps).reshape(b, pps), jnp.int32
    )
    q = jnp.asarray(rng.randn(b, kvh, g, hd), jnp.float32)
    idx = jnp.asarray([500, 60], jnp.int32)
    if dtype != "native":
        kp, vp = _quant(kp, dtype), _quant(vp, dtype)
    pool = fuse_kv(kp, vp)
    ref = paged_attention_reference(q, pool, table, idx)
    out = paged_attention(
        q, pool, table, idx, prefer="pallas", split=split
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5
    )


@pytest.mark.parametrize("dtype", ["native", "int8", "int4"])
@pytest.mark.parametrize("split,tree_tail", [(1, 0), (2, 0), (1, 2), (3, 2)])
def test_split_verify_paged_parity(dtype, split, tree_tail):
    """Batched paged verify: split x tree-mask x dtype vs the gather
    oracle, with a DEAD (negative-index) row in the batch (compared on
    the live row only — dead rows emit finite garbage by contract)."""
    rng = np.random.RandomState(4)
    b, kvh, g, hd, P, pps, K = 2, 2, 4, 16, 128, 5, 6
    npages = b * pps + 1
    kp = jnp.asarray(rng.randn(npages, kvh, P, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(npages, kvh, P, hd), jnp.float32)
    table = jnp.asarray(
        np.arange(1, 1 + b * pps).reshape(b, pps), jnp.int32
    )
    q = jnp.asarray(rng.randn(b, kvh, g * K, hd), jnp.float32)
    idx = jnp.asarray([300, -7], jnp.int32)  # row 1 dead
    if dtype != "native":
        kp, vp = _quant(kp, dtype), _quant(vp, dtype)
    pool = fuse_kv(kp, vp)
    ref = paged_verify_attention_reference(
        q, pool, table, idx, K, tree_tail=tree_tail
    )
    out = paged_verify_attention(
        q, pool, table, idx, K, prefer="pallas",
        tree_tail=tree_tail, split=split,
    )
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), atol=2e-5
    )


def test_tree_mask_verify_vs_jnp_oracle():
    """The tree mask's semantics pinned against a hand-built oracle:
    chain rows keep their diagonal, each leaf row attends the chain
    plus ONLY its own slot (never a sibling's)."""
    rng = np.random.RandomState(5)
    b, kvh, g, hd, L, K, w = 2, 2, 2, 16, 64, 6, 2
    ck = jnp.asarray(rng.randn(b, kvh, L, hd), jnp.float32)
    cv = jnp.asarray(rng.randn(b, kvh, L, hd), jnp.float32)
    q = jnp.asarray(rng.randn(b, kvh, g * K, hd), jnp.float32)
    idx = np.asarray([10, 20], np.int32)
    out = np.asarray(
        verify_attention(q, ck, cv, jnp.asarray(idx), K, tree_tail=w)
    )
    s = np.einsum(
        "bhqd,bhkd->bhqk", np.asarray(q), np.asarray(ck)
    ) / np.sqrt(hd)
    chain = K - 1 - w
    rows = np.arange(g * K) % K
    man = np.zeros_like(out)
    for bi in range(b):
        for r in range(g * K):
            t = rows[r]
            live = np.arange(L) <= idx[bi] + min(t, chain)
            live |= np.arange(L) == idx[bi] + t
            srow = np.where(live, s[bi, :, r, :], -1e30)
            e = np.exp(srow - srow.max(-1, keepdims=True))
            p = e / e.sum(-1, keepdims=True)
            man[bi, :, r, :] = np.einsum(
                "hk,hkd->hd", p, np.asarray(cv)[bi]
            )
    np.testing.assert_allclose(out, man, atol=2e-5)


# -- kernel-dispatch gauges --------------------------------------------------


def test_kernel_dispatch_gauges_surface_fallback():
    """Every dispatcher records pallas-vs-oracle at trace time and the
    engine collector exports the gauges — the silent `_kernel_supported`
    fallback is now observable."""
    from adapt_tpu.utils.metrics import global_metrics

    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 2, 4, 16), jnp.float32)
    kp = jnp.asarray(rng.randn(5, 2, 8, 16), jnp.float32)  # page 8:
    vp = jnp.asarray(rng.randn(5, 2, 8, 16), jnp.float32)  # unsupported
    table = jnp.asarray([[1, 2]], jnp.int32)
    paged_attention(q, fuse_kv(kp, vp), table, jnp.asarray([9], jnp.int32))
    st = kernel_dispatch_stats()
    assert st["paged_decode"]["last"] == 0.0  # oracle (page not lane-mult)
    assert st["paged_decode"]["xla"] >= 1
    kp2 = jnp.asarray(rng.randn(3, 2, 128, 16), jnp.float32)
    vp2 = jnp.asarray(rng.randn(3, 2, 128, 16), jnp.float32)
    paged_attention(
        q, fuse_kv(kp2, vp2), jnp.asarray([[1, 2]], jnp.int32),
        jnp.asarray([100], jnp.int32), prefer="pallas",
    )
    st = kernel_dispatch_stats()
    assert st["paged_decode"]["last"] == 1.0
    assert st["paged_decode"]["pallas"] >= 1
    snap = global_metrics().snapshot()
    gauges = snap["gauges"]
    assert gauges["engine.kernel_dispatch.paged_decode"] == 1.0
    assert gauges["engine.kernel_dispatch.paged_decode.xla_total"] >= 1


def test_roofline_peaks_per_generation(monkeypatch):
    """The peak table resolves by device KIND (v4/v5e/v5p/v6e rows) and
    the env override beats everything — the documented knob order."""
    from adapt_tpu.utils import profiling

    assert {"tpu v4", "tpu v5e", "tpu v5p", "tpu v6e"} <= set(
        profiling.ROOFLINE_PEAKS
    )
    # distinct generations carry distinct peaks
    assert (
        profiling.ROOFLINE_PEAKS["tpu v4"]
        != profiling.ROOFLINE_PEAKS["tpu v5p"]
    )
    monkeypatch.setenv("ADAPT_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("ADAPT_TPU_PEAK_BYTES_S", "1e11")
    assert profiling.roofline_peaks() == (1e12, 1e11)
    monkeypatch.delenv("ADAPT_TPU_PEAK_FLOPS")
    monkeypatch.delenv("ADAPT_TPU_PEAK_BYTES_S")
    # CPU backend, no override: no honest peak
    assert profiling.roofline_peaks() is None


# -- batcher: split dispatch invariants --------------------------------------


@pytest.mark.slow
def test_batcher_split_streams_bit_identical():
    """Greedy streams are BIT-IDENTICAL between the forced Pallas
    kernels and the default XLA path, across staggered
    admits/retires/cancels; 0 h2d per steady tick and a frozen compile
    footprint hold under the kernels (sentinel-pinned)."""
    from adapt_tpu.utils.profiling import global_compile_sentinel

    lm = transformer_lm(VOCAB, 32, 2, 2, 64, max_len=256,
                        name="split_paged")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in (4, 7, 3)]
    sentinel = global_compile_sentinel()
    streams = {}
    for tag, kern in (
        ("xla", None),
        ("pallas", KernelConfig(attn_impl="pallas")),
    ):
        bat = ContinuousBatcher(
            lm, variables, slots=2, kernel=kern, chunk=2,
            page_size=128, pool_pages=9,
        )
        # staggered admits, then a steady-state window with BOTH slots
        # mid-flight (steps sized to outlive it — a retirement's
        # row-clear is a legitimate +1, not a violation)
        r1 = bat.submit(prompts[0], 20)
        bat.tick()
        r2 = bat.submit(prompts[1], 20)
        bat.tick()
        bat.tick()
        h2d0 = bat.stats()["h2d_transfers"]
        c0 = sentinel.compiles("continuous.step_chunk")
        bat.tick()
        assert bat.stats()["h2d_transfers"] == h2d0  # 0 h2d/steady tick
        assert sentinel.compiles("continuous.step_chunk") == c0
        # a queued cancel rides the drain, exercising the churn path
        rc = bat.submit(prompts[2], 8)
        bat.cancel(rc)
        out = bat.run()
        streams[tag] = {0: out[r1], 1: out[r2]}
        bat.close()
    for i in (0, 1):
        np.testing.assert_array_equal(
            streams["pallas"][i], streams["xla"][i],
            err_msg=f"pallas req {i} diverged",
        )


@pytest.mark.slow
def test_batcher_split_speculative_int8():
    """The forced Pallas kernels compose with speculative mode over
    int8 pools: their spec stream equals the XLA-path spec stream AND
    solo generate(int8)."""
    lm = transformer_lm(VOCAB, 32, 2, 2, 64, max_len=256,
                        name="split_spec")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    p = np.asarray([1, 2, 3, 4, 5], np.int32)
    outs = {}
    for tag, kern in (
        ("xla", None),
        ("pallas", KernelConfig(attn_impl="pallas")),
    ):
        bat = ContinuousBatcher(
            lm, variables, slots=2, kv_layout="paged", page_size=128,
            kv_cache_dtype="int8", draft_lm=lm, draft_variables=variables,
            speculative=SpeculativeConfig(draft_k=3), kernel=kern,
        )
        r = bat.submit(p, 10)
        outs[tag] = bat.run()[r]
        bat.close()
    solo = _solo(lm, variables, p, 10, kv_cache_dtype="int8")
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    np.testing.assert_array_equal(outs["pallas"], solo)


# -- tree drafts -------------------------------------------------------------


@pytest.mark.parametrize("page_size", [8, 128])
def test_tree_spec_lossless_and_beats_chain(lm_setup, page_size):
    """tree_width=1: the emitted stream is STILL exactly the target's
    greedy stream (lossless, staggered admits + a cancel), and the
    perfect-draft arm commits > 5.0 tokens per verify pass at
    draft_k=4 (the chain's ceiling) — with the leaf rows and the
    accepted leaf's move inside one page (128) and across page
    boundaries (8)."""
    lm, variables = lm_setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in (4, 6)]
    bat = ContinuousBatcher(
        lm, variables, slots=2, draft_lm=lm, draft_variables=variables,
        speculative=SpeculativeConfig(draft_k=4, tree_width=1),
        page_size=page_size,
    )
    r1 = bat.submit(prompts[0], 40)
    bat.tick()
    r2 = bat.submit(prompts[1], 30)
    rc = bat.submit(prompts[0], 5)
    bat.cancel(rc)
    bat.tick()
    # steady-state acceptance window (both slots decoding)
    e0 = sum(len(s.tokens) for s in bat.slots if s.req is not None)
    for _ in range(3):
        bat.tick()
    e1 = sum(len(s.tokens) for s in bat.slots if s.req is not None)
    per_pass = (e1 - e0) / (3 * 2)
    out = bat.run()
    np.testing.assert_array_equal(out[r1], _solo(lm, variables, prompts[0], 40))
    np.testing.assert_array_equal(out[r2], _solo(lm, variables, prompts[1], 30))
    assert out[rc].size == 0 or len(out[rc]) < 5  # cancelled
    assert per_pass > 5.0, per_pass
    assert bat.stats()["spec_acceptance"] == 1.0
    bat.close()


def test_tree_spec_adversarial_draft_still_lossless(lm_setup):
    """A wrong draft (acceptance ~1/vocab) with tree_width=2: the tree
    machinery must never corrupt the stream — worst case it commits 1
    token per round like chain speculation."""
    lm, variables = lm_setup
    adv = transformer_lm(VOCAB, 16, 1, 1, 32, max_len=96,
                         name="tree_adv")
    avars = adv.graph.init(
        jax.random.PRNGKey(9), jnp.zeros((1, 4), jnp.int32)
    )
    p = np.asarray([5, 6, 7], np.int32)
    bat = ContinuousBatcher(
        lm, variables, slots=2, draft_lm=adv, draft_variables=avars,
        speculative=SpeculativeConfig(draft_k=3, tree_width=2),
    )
    r = bat.submit(p, 16)
    out = bat.run()
    np.testing.assert_array_equal(out[r], _solo(lm, variables, p, 16))
    bat.close()


# -- int4 composition --------------------------------------------------------


def test_int4_logits_close_to_int8():
    """Teacher-forced next-token LOGITS of the int4 and int8 caches
    stay within a stated tolerance: both caches serve the SAME
    committed stream (the int8 greedy stream), so the numbers compared
    carry the quantization perturbation alone, no free-running
    divergence. Logits, not argmax tokens: on random weights the top
    two logits sit 0.15-0.19 apart (median), closer than int4's honest
    error, so top-1 agreement (0.85 here) measures the toy model's
    luck and not the scheme (``model-configs`` guide: compare logits
    within a tolerance).

    The tolerance: int4's absmax step is a vector's largest entry over
    7, int8's over 127 — 18 times coarser. int8 reads within 0.019 of
    the native cache here (asserted under 0.05), so int4 is allowed
    0.5 of int8 (read: 0.24 and 0.33 on logits of spread 1.0-1.1 and
    range 4.8-5.3). A packing or scale regression (nibbles swapped, a
    scale of the wrong vector) moves logits by their whole range."""
    lm = transformer_lm(13, 64, 2, 2, 128, max_len=96, name="i4_agree")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.RandomState(11)
    g = lm.graph
    embed = g.node("embed").module
    head = g.node("head").module
    blocks = [g.node(n).module for n in lm.block_names]

    def logits(dt, prompt, stream):
        quant = dt if dt != "native" else False
        h = embed.apply(variables["embed"], prompt)
        caches = []
        for name, block in zip(lm.block_names, blocks):
            h, ck, cv = block.apply(
                variables[name], h, lm.max_len, None, quant,
                method="prefill",
            )
            caches.append((ck, cv))
        out = [head.apply(variables["head"], h[:, -1:, :])[0, 0]]
        idx = prompt.shape[1]
        for t in stream:
            x = embed.apply(
                variables["embed"], jnp.asarray([[t]], jnp.int32), idx,
                method="embed_at",
            )
            new = []
            for name, block, (ck, cv) in zip(
                lm.block_names, blocks, caches
            ):
                x, ck, cv = block.apply(
                    variables[name], x, ck, cv, idx, None, False,
                    method="decode_step",
                )
                new.append((ck, cv))
            caches = new
            out.append(head.apply(variables["head"], x)[0, 0])
            idx += 1
        return np.stack([np.asarray(o) for o in out])

    for trial in range(2):
        p = jnp.asarray(rng.randint(0, lm.vocab, (1, 6)), jnp.int32)
        stream = [int(t) for t in np.asarray(
            generate(lm, variables, p, 20, kv_cache_dtype="int8")
        )[0][:-1]]
        native = logits("native", p, stream)
        int8 = logits("int8", p, stream)
        int4 = logits("int4", p, stream)
        assert np.isfinite(int4).all()
        err8 = float(np.abs(int8 - native).max())
        err4 = float(np.abs(int4 - int8).max())
        assert err8 <= 0.05, f"trial {trial}: int8 vs native {err8}"
        assert err4 <= 0.5, f"trial {trial}: int4 vs int8 {err4}"


def test_int4_batcher_lossless_and_prefix_cache(lm_setup):
    """int4 batcher streams equal solo generate(kv_cache_dtype='int4')
    inside one page (128) and across pages (8), and a re-submitted
    prompt with full pages enters through the prefix cache (its int4
    pages + scale planes are reused)."""
    lm, variables = lm_setup
    p = np.asarray(list(range(1, 19)), np.int32)  # 2 full 8-pages
    solo = _solo(lm, variables, p, 6, kv_cache_dtype="int4")
    for page_size in (128, 8):
        bat = ContinuousBatcher(
            lm, variables, slots=2, kv_cache_dtype="int4",
            page_size=page_size,
        )
        r1 = bat.submit(p, 6)
        out1 = bat.run()[r1]
        np.testing.assert_array_equal(out1, solo)
        if page_size == 8:
            hits0 = bat._pager.prefix_hits
            r2 = bat.submit(p, 6)
            out2 = bat.run()[r2]
            assert bat._pager.prefix_hits > hits0
            np.testing.assert_array_equal(out2, solo)
        bat.close()


@pytest.mark.slow
def test_int4_disagg_handoff():
    """A disaggregated prefill over int4 pools streams packed pages +
    scale planes over the wire (kv_dtype in the annex) and the decode
    side's stream equals the collocated int4 stream."""
    from adapt_tpu.config import DisaggConfig
    from adapt_tpu.runtime.disagg import DisaggServer, PrefillWorker

    lm = transformer_lm(61, 32, 2, 2, 64, max_len=96, name="i4_disagg")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    PAGE = 8
    prompt = np.arange(1, 2 * PAGE + 4, dtype=np.int32)  # > threshold

    def decode_bat():
        return ContinuousBatcher(
            lm, variables, slots=2, chunk=4, kv_layout="paged",
            page_size=PAGE, kv_cache_dtype="int4",
        )

    solo_bat = decode_bat()
    r = solo_bat.submit(prompt, 6)
    collocated = solo_bat.run()[r]
    solo_bat.close()

    decode = decode_bat()
    worker = PrefillWorker(
        lm, variables, page_size=PAGE, prefill_chunk=2 * PAGE,
        kv_cache_dtype="int4",
    )
    srv = DisaggServer(
        decode, worker,
        DisaggConfig(prompt_threshold=2 * PAGE,
                     busy_prompt_threshold=2 * PAGE),
    )
    rid = srv.submit(prompt, 6)
    out = srv.run()
    np.testing.assert_array_equal(out[rid], collocated)
    assert srv.stats()["disaggregated"] == 1
    assert decode._pager.prefix_hits > 0  # landed through the cache
    decode.close()


@pytest.mark.slow
def test_int4_tp2_and_recovery_migration(sim_mesh):
    """int4 pools head-shard under tp=2 (both pytree members at
    logical/2 per device) and a chip loss migrates them live: the
    post-kill stream equals solo generate(kv_cache_dtype='int4')."""
    from adapt_tpu.control.registry import DeviceHealthMonitor
    from adapt_tpu.utils.profiling import device_local_nbytes

    lm = transformer_lm(37, 32, 2, 8, 64, max_len=48, kv_heads=4,
                        name="i4_rec")
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    p = np.asarray([1, 2, 3], np.int32)
    solo = _solo(lm, variables, p, 10, kv_cache_dtype="int4")
    mon = DeviceHealthMonitor()
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, mesh=sim_mesh(2),
        parallel=ParallelConfig(tp=2), kv_cache_dtype="int4",
        kv_layout="paged", page_size=8, health=mon,
    )
    # sharded: both members at logical/2 per device
    for pool in bat._caches:
        for member in pool:
            assert device_local_nbytes(member) * 2 == member.nbytes
    r = bat.submit(p, 10)
    bat.tick()
    mon.kill(list(bat._mesh.devices.flat)[1])
    out = bat.run()
    st = bat.stats()
    assert st["tp"] == 1 and st["recoveries"] == 1
    np.testing.assert_array_equal(out[r], solo)
    bat.close()
