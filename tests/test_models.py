"""Model zoo tests: shapes, partitionability at the BASELINE cut lists, and
stage-composition equivalence on small inputs (the SURVEY §4 oracle applied
to real model graphs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.graph import partition, valid_cut_points
from adapt_tpu.models.efficientnet import efficientnet_b0
from adapt_tpu.models.resnet import RESNET50_3STAGE_CUTS, resnet50
from adapt_tpu.models.vit import vit_block_cuts, vit_tiny


@pytest.fixture(scope="module")
def small_image():
    # 64x64 keeps CPU-test conv time low; graphs are resolution-agnostic.
    return jnp.ones((1, 64, 64, 3), jnp.float32)


def test_resnet50_graph_structure():
    g = resnet50()
    # 16 blocks -> 16 merge nodes; merges + stem are the valid cuts.
    cuts = valid_cut_points(g)
    assert "stem" in cuts
    assert "conv3_block1_out" in cuts
    assert "conv3_block1_branch" not in cuts
    merges = [n for n in g.topo_order() if n.endswith("_out")]
    assert len(merges) == 16


def test_resnet50_partition_and_compose(small_image):
    g = resnet50(num_classes=10)
    variables = g.init(jax.random.PRNGKey(0), small_image)
    y_full = g.apply(variables, small_image)
    assert y_full.shape == (1, 10)
    plan = partition(g, list(RESNET50_3STAGE_CUTS))
    assert plan.num_stages == 3
    sv = plan.extract_variables(variables)
    y = plan.compose(sv, small_image)
    np.testing.assert_array_equal(np.asarray(y_full), np.asarray(y))


def test_resnet152_cuts_exist():
    from adapt_tpu.models.resnet import RESNET152_8STAGE_CUTS, resnet152

    g = resnet152(num_classes=10)
    plan = partition(g, list(RESNET152_8STAGE_CUTS))
    assert plan.num_stages == 8


def test_vit_tiny_partition_and_compose():
    g = vit_tiny()
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    y_full = g.apply(variables, x)
    assert y_full.shape == (2, 10)
    cuts = vit_block_cuts(4, 2)
    assert cuts == ["encoder_block_1"]
    plan = partition(g, cuts)
    sv = plan.extract_variables(variables)
    np.testing.assert_array_equal(
        np.asarray(y_full), np.asarray(plan.compose(sv, x))
    )


def test_efficientnet_b0_dag_partition(small_image):
    g = efficientnet_b0(num_classes=10)
    variables = jax.jit(g.init)(jax.random.PRNGKey(1), small_image)
    y_full = g.apply(variables, small_image)
    assert y_full.shape == (1, 10)
    # Multi-branch DAG: identity-residual blocks create joins; partition at
    # a couple of add-merge points.
    cuts = [c for c in valid_cut_points(g) if c.endswith("_add")]
    assert len(cuts) >= 4  # several residual merges exist
    plan = partition(g, cuts[:2])
    sv = plan.extract_variables(variables)
    np.testing.assert_array_equal(
        np.asarray(y_full), np.asarray(plan.compose(sv, small_image))
    )


def test_bfloat16_resnet(small_image):
    g = resnet50(num_classes=10, dtype=jnp.bfloat16)
    variables = g.init(jax.random.PRNGKey(0), small_image)
    y = g.apply(variables, small_image)
    assert y.dtype == jnp.float32  # head casts logits back to f32
    assert np.isfinite(np.asarray(y)).all()


def test_vit_block_cuts_validation():
    from adapt_tpu.models.vit import vit_block_cuts

    with pytest.raises(ValueError, match="cannot split"):
        vit_block_cuts(4, 8)
    assert vit_block_cuts(4, 4) == [
        "encoder_block_0",
        "encoder_block_1",
        "encoder_block_2",
    ]
    assert vit_block_cuts(12, 3) == ["encoder_block_3", "encoder_block_7"]


def test_vit_attention_flash_matches_oracle(rng):
    """The product-path attention (MultiHeadSelfAttention on the Pallas
    flash kernel) must match the same module running the jnp oracle with
    identical params — the flax-parity check for the kernel wiring."""
    import numpy as np

    from adapt_tpu.models.vit import MultiHeadSelfAttention
    from adapt_tpu.ops.attention import attention_reference

    x = jax.random.normal(rng, (2, 65, 64))
    # Pin the Pallas path: the measured dispatch would route this small
    # shape to the XLA oracle, making the comparison vacuous.
    m_flash = MultiHeadSelfAttention(heads=4, attn_prefer="pallas")
    m_ref = MultiHeadSelfAttention(heads=4, attn_fn=attention_reference)
    variables = m_flash.init(jax.random.PRNGKey(7), x)
    y_flash = m_flash.apply(variables, x)
    y_ref = m_ref.apply(variables, x)
    np.testing.assert_allclose(
        np.asarray(y_flash), np.asarray(y_ref), rtol=1e-2, atol=1e-2
    )
    # And gradients flow through the kernel (custom VJP): training-path
    # usability, not just inference.
    g = jax.grad(lambda v: jnp.sum(m_flash.apply(v, x) ** 2))(variables)
    assert all(
        bool(jnp.all(jnp.isfinite(leaf))) for leaf in jax.tree.leaves(g)
    )


def test_resnet_space_to_depth_stem(rng):
    """The MXU-friendly s2d stem must produce the same output shape as the
    7x7/s2 stem, keep every cut name valid, and reject odd inputs."""
    from adapt_tpu.models.resnet import RESNET50_3STAGE_CUTS, resnet50

    g = resnet50(num_classes=10, stem="s2d")
    x = jnp.ones((1, 64, 64, 3))
    v = jax.jit(g.init)(rng, x)
    y = jax.jit(g.apply)(v, x)
    assert y.shape == (1, 10)
    # Cut names unchanged: the baseline 3-stage plan still partitions.
    plan = partition(g, list(RESNET50_3STAGE_CUTS))
    assert plan.num_stages == 3
    with pytest.raises(ValueError, match="unknown stem"):
        resnet50(stem="bogus")
    with pytest.raises(ValueError, match="even"):
        g.apply(v, jnp.ones((1, 63, 63, 3)))


# -- transformer LM ---------------------------------------------------------


def test_lm_cached_decode_matches_full_forward():
    """Teacher-forced incremental decoding (prefill + per-token cached
    steps) must reproduce the full causal forward's logits position for
    position — the KV cache is a schedule change, not a model change."""
    from adapt_tpu.models.transformer_lm import lm_tiny, logits_full

    lm = lm_tiny(vocab=97, max_len=32)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 0, 97)
    variables = jax.jit(lm.graph.init)(jax.random.PRNGKey(1), ids)
    full = np.asarray(logits_full(lm, variables, ids))  # (2, 12, 97)

    # Prefill on the first 5 tokens, then feed ground-truth tokens 5..11
    # through decode_step; logits must match the full forward at every
    # position.
    from conftest import logits_by_cached_decode

    s0 = 5
    prefill_logits, step_logits, _ = logits_by_cached_decode(
        lm, variables, ids, s0
    )
    np.testing.assert_allclose(
        prefill_logits, full[:, :s0], rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        step_logits, full[:, s0:], rtol=2e-4, atol=2e-4
    )


def test_lm_generate_matches_uncached_greedy():
    """generate() (compiled prefill + scan decode) must emit exactly the
    tokens an uncached greedy loop over the full forward would."""
    from adapt_tpu.models.transformer_lm import generate, lm_tiny, logits_full

    lm = lm_tiny(vocab=61, max_len=24)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 61)
    variables = lm.graph.init(jax.random.PRNGKey(3), prompt)
    steps = 6

    from conftest import greedy_by_full_forward

    out = np.asarray(generate(lm, variables, prompt, steps))
    np.testing.assert_array_equal(
        out, greedy_by_full_forward(lm, variables, prompt, steps)
    )


def test_lm_pipeline_partition_parity():
    """The LM graph cuts at decoder blocks like ViT: composed stages ==
    full model."""
    from adapt_tpu.graph.partition import partition
    from adapt_tpu.models.transformer_lm import lm_tiny, logits_full

    lm = lm_tiny(vocab=41, max_len=16)
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0, 41)
    variables = lm.graph.init(jax.random.PRNGKey(5), ids)
    full = np.asarray(logits_full(lm, variables, ids))

    plan = partition(lm.graph, ["decoder_block_1", "decoder_block_3"])
    svars = plan.extract_variables(variables)
    composed = np.asarray(plan.compose(svars, ids))
    np.testing.assert_allclose(composed, full, rtol=2e-4, atol=2e-4)


def test_lm_generate_rejects_overflow():
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=17, max_len=8)
    prompt = jnp.zeros((1, 6), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        generate(lm, prompt=prompt, variables={}, steps=4)


def test_lm_serves_through_pipeline(devices):
    """The LM graph family works with the serving machinery end-to-end:
    partitioned at block cuts, pipelined over devices via LocalPipeline,
    streaming token batches — same contract as the CNN families."""
    from adapt_tpu.graph.partition import partition
    from adapt_tpu.models.transformer_lm import lm_tiny, logits_full
    from adapt_tpu.runtime.pipeline import LocalPipeline

    lm = lm_tiny(vocab=53, max_len=16)
    ids = [
        jax.random.randint(jax.random.PRNGKey(i), (2, 9), 0, 53)
        for i in range(4)
    ]
    variables = lm.graph.init(jax.random.PRNGKey(99), ids[0])
    plan = partition(lm.graph, ["decoder_block_1", "decoder_block_3"])
    pipe = LocalPipeline(
        plan, variables, devices=devices[: plan.num_stages]
    )
    outs = pipe.stream(ids)
    for x, y in zip(ids, outs):
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(logits_full(lm, variables, x)),
            rtol=2e-4,
            atol=2e-4,
        )


def test_lm_generate_rejects_zero_steps():
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=17, max_len=8)
    with pytest.raises(ValueError, match="steps"):
        generate(lm, {}, jnp.zeros((1, 2), jnp.int32), 0)


def test_lm_generate_sampling_and_eos():
    """Serving knobs: top_k=1 sampling degenerates to greedy whatever the
    temperature; eos_id pads a finished row with EOS forever after."""
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=31, max_len=24)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 4), 0, 31)
    variables = lm.graph.init(jax.random.PRNGKey(7), prompt)

    greedy = np.asarray(generate(lm, variables, prompt, 8))
    topk1 = np.asarray(
        generate(
            lm, variables, prompt, 8,
            temperature=1.7, top_k=1, rng=jax.random.PRNGKey(8),
        )
    )
    np.testing.assert_array_equal(greedy, topk1)

    # Same key -> same sample; different key -> (here) a different draw.
    s1 = np.asarray(
        generate(lm, variables, prompt, 8, temperature=1.0,
                 rng=jax.random.PRNGKey(9))
    )
    s2 = np.asarray(
        generate(lm, variables, prompt, 8, temperature=1.0,
                 rng=jax.random.PRNGKey(9))
    )
    np.testing.assert_array_equal(s1, s2)

    # EOS: declare the greedy path's first emission to be EOS — every
    # subsequent token on that row must be EOS too.
    eos = int(greedy[0, 0])
    out = np.asarray(generate(lm, variables, prompt, 8, eos_id=eos))
    assert (out[0] == eos).all()

    with pytest.raises(ValueError, match="rng"):
        generate(lm, variables, prompt, 4, temperature=0.5)


def test_lm_generate_ragged_prompts_match_per_row():
    """Batched ragged generation (right-padded prompts + prompt_lengths)
    must emit, per row, exactly what generating that row alone emits —
    left-alignment, per-row position ids, and padding masks are internal
    bookkeeping, never visible in the output."""
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=43, max_len=24)
    lens = [3, 7, 5]
    s0 = max(lens)
    rows = [
        jax.random.randint(jax.random.PRNGKey(10 + i), (1, n), 0, 43)
        for i, n in enumerate(lens)
    ]
    variables = lm.graph.init(jax.random.PRNGKey(20), rows[1])

    batched = jnp.zeros((len(lens), s0), jnp.int32)
    for i, r in enumerate(rows):
        batched = batched.at[i, : lens[i]].set(r[0])
    out = np.asarray(
        generate(
            lm, variables, batched, 6,
            prompt_lengths=jnp.asarray(lens),
        )
    )
    for i, r in enumerate(rows):
        solo = np.asarray(generate(lm, variables, r, 6))
        np.testing.assert_array_equal(out[i], solo[0], err_msg=f"row {i}")


def test_lm_generate_rejects_bad_prompt_lengths():
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=11, max_len=16)
    prompt = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="prompt_lengths"):
        generate(lm, {}, prompt, 2, prompt_lengths=jnp.asarray([2, 6]))
    with pytest.raises(ValueError, match="prompt_lengths"):
        generate(lm, {}, prompt, 2, prompt_lengths=jnp.asarray([0, 3]))
    with pytest.raises(ValueError, match="shape"):
        generate(lm, {}, prompt, 2, prompt_lengths=jnp.asarray([3]))


def test_lm_generate_int8_kv_cache():
    """kv_cache_dtype='int8' stores (int8 values, f32 scales) caches.
    Teacher-forced logits through the quantized cache must track the
    native-cache logits closely (absmax-per-vector int8, ~0.4% scale
    granularity), and greedy generation runs end to end."""
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=37, max_len=24)
    prompt = jax.random.randint(jax.random.PRNGKey(30), (2, 6), 0, 37)
    variables = jax.jit(lm.graph.init)(jax.random.PRNGKey(31), prompt)

    # One FIXED token sequence feeds both runs (true teacher forcing):
    # a quantization-induced argmax flip must not send the two runs down
    # different decode paths, or the logits comparison is meaningless.
    from conftest import logits_by_cached_decode

    forced = jax.random.randint(jax.random.PRNGKey(32), (4, 2), 0, 37)
    ids = jnp.concatenate([prompt, forced.T], axis=1)

    def run(quant):
        first, steps, caches = logits_by_cached_decode(
            lm, variables, ids, prompt.shape[1], quant
        )
        return np.concatenate([first[:, -1:], steps], axis=1), caches

    lg_native, _ = run(False)
    lg_int8, caches = run(True)
    assert caches[0][0][0].dtype == jnp.int8
    assert caches[0][0][1].dtype == jnp.float32
    scale = np.abs(lg_native).max()
    np.testing.assert_allclose(
        lg_int8 / scale, lg_native / scale, atol=0.05
    )

    out = np.asarray(
        generate(lm, variables, prompt, 6, kv_cache_dtype="int8")
    )
    assert out.shape == (2, 6) and (out >= 0).all() and (out < 37).all()

    with pytest.raises(ValueError, match="kv_cache_dtype"):
        generate(lm, variables, prompt, 2, kv_cache_dtype="fp8")


def test_lm_generate_top_p():
    """Nucleus sampling: top_p=1.0 filters nothing (stream identical to
    the unfiltered sampler), top_p→0 degenerates to greedy (only the
    top-1 token survives the nucleus), and mid-range p is deterministic
    per key."""
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=29, max_len=24)
    prompt = jax.random.randint(jax.random.PRNGKey(15), (2, 4), 0, 29)
    variables = lm.graph.init(jax.random.PRNGKey(16), prompt)

    base = np.asarray(
        generate(lm, variables, prompt, 8, temperature=1.0,
                 rng=jax.random.PRNGKey(17))
    )
    all_mass = np.asarray(
        generate(lm, variables, prompt, 8, temperature=1.0, top_p=1.0,
                 rng=jax.random.PRNGKey(17))
    )
    np.testing.assert_array_equal(base, all_mass)

    greedy = np.asarray(generate(lm, variables, prompt, 8))
    tiny_p = np.asarray(
        generate(lm, variables, prompt, 8, temperature=1.7, top_p=1e-6,
                 rng=jax.random.PRNGKey(18))
    )
    np.testing.assert_array_equal(greedy, tiny_p)

    s1 = np.asarray(generate(lm, variables, prompt, 8, temperature=1.0,
                             top_p=0.7, rng=jax.random.PRNGKey(19)))
    s2 = np.asarray(generate(lm, variables, prompt, 8, temperature=1.0,
                             top_p=0.7, rng=jax.random.PRNGKey(19)))
    np.testing.assert_array_equal(s1, s2)

    with pytest.raises(ValueError, match="top_p"):
        generate(lm, variables, prompt, 2, temperature=1.0, top_p=1.5,
                 rng=jax.random.PRNGKey(20))


# -- grouped-query attention (GQA / MQA) ------------------------------------


def _gqa_lm(vocab=47, heads=4, kv_heads=2, max_len=24):
    from adapt_tpu.models.transformer_lm import transformer_lm

    return transformer_lm(
        vocab=vocab, dim=32, depth=2, heads=heads, mlp_dim=48,
        max_len=max_len, kv_heads=kv_heads,
    )


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_lm_gqa_cached_decode_matches_full_forward(kv_heads):
    """GQA's cached decode (grouped q rows over the small kv_heads cache)
    must reproduce the full-forward logits position for position, exactly
    like MHA — the cache layout is a schedule change, not a model change.
    Also pins the capacity claim: the cache's head axis is kv_heads."""
    from adapt_tpu.models.transformer_lm import logits_full

    vocab = 47
    lm = _gqa_lm(vocab=vocab, heads=4, kv_heads=kv_heads)
    ids = jax.random.randint(jax.random.PRNGKey(40), (2, 10), 0, vocab)
    variables = jax.jit(lm.graph.init)(jax.random.PRNGKey(41), ids)
    full = np.asarray(logits_full(lm, variables, ids))

    from conftest import logits_by_cached_decode

    s0 = 4
    prefill_logits, step_logits, caches = logits_by_cached_decode(
        lm, variables, ids, s0
    )
    for ck, cv in caches:
        # The whole point of GQA: the cache head axis is kv_heads, not
        # heads — 4/kv_heads x less HBM per decoded context.
        assert ck.shape == cv.shape == (2, kv_heads, lm.max_len, 32 // 4)
    np.testing.assert_allclose(
        prefill_logits, full[:, :s0], rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        step_logits, full[:, s0:], rtol=2e-4, atol=2e-4,
        err_msg=f"kv_heads={kv_heads}",
    )


def test_lm_gqa_generate_matches_uncached_greedy():
    """generate() on a GQA model == uncached greedy loop, token for
    token (same contract the MHA test pins)."""
    from adapt_tpu.models.transformer_lm import generate, logits_full

    vocab = 43
    lm = _gqa_lm(vocab=vocab, heads=4, kv_heads=2)
    prompt = jax.random.randint(jax.random.PRNGKey(42), (2, 5), 0, vocab)
    variables = lm.graph.init(jax.random.PRNGKey(43), prompt)
    steps = 6

    from conftest import greedy_by_full_forward

    out = np.asarray(generate(lm, variables, prompt, steps))
    np.testing.assert_array_equal(
        out, greedy_by_full_forward(lm, variables, prompt, steps)
    )


def test_lm_gqa_int8_cache_composes():
    """GQA x int8: the quantized cache keeps the kv_heads layout (the
    two capacity knobs multiply) and generation runs end to end."""
    from adapt_tpu.models.transformer_lm import generate

    vocab = 41
    lm = _gqa_lm(vocab=vocab, heads=4, kv_heads=1, max_len=24)  # MQA
    prompt = jax.random.randint(jax.random.PRNGKey(44), (2, 6), 0, vocab)
    variables = lm.graph.init(jax.random.PRNGKey(45), prompt)

    g = lm.graph
    embed = g.node("embed").module
    block = g.node(lm.block_names[0]).module
    h = embed.apply(variables["embed"], prompt)
    _, (kv, ks), _ = block.apply(
        variables[lm.block_names[0]], h, lm.max_len, None, True,
        method="prefill",
    )
    assert kv.dtype == jnp.int8 and kv.shape == (2, 1, lm.max_len, 8)
    assert ks.shape == (2, 1, lm.max_len, 1)

    out = np.asarray(
        generate(lm, variables, prompt, 6, kv_cache_dtype="int8")
    )
    native = np.asarray(generate(lm, variables, prompt, 6))
    assert out.shape == (2, 6) and (out >= 0).all() and (out < vocab).all()
    # int8 rounding may legitimately flip an argmax, so token equality
    # is not the contract here — the int8 logits-tracking contract is
    # pinned by test_lm_generate_int8_kv_cache.
    assert native.shape == out.shape


def test_lm_gqa_validation():
    """kv_heads must divide heads and sit in [1, heads]; kv_heads ==
    heads (or None) keeps the fused-QKV MHA parameter structure."""
    from adapt_tpu.models.transformer_lm import transformer_lm

    with pytest.raises(ValueError, match="kv_heads"):
        lm = _gqa_lm(heads=4, kv_heads=3)
        lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )
    with pytest.raises(ValueError, match="kv_heads"):
        lm = _gqa_lm(heads=4, kv_heads=8)
        lm.graph.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )

    mha = transformer_lm(vocab=11, dim=16, depth=1, heads=4, mlp_dim=16,
                         max_len=8)
    explicit = transformer_lm(vocab=11, dim=16, depth=1, heads=4,
                              mlp_dim=16, max_len=8, kv_heads=4)
    ids = jnp.zeros((1, 4), jnp.int32)
    v1 = mha.graph.init(jax.random.PRNGKey(7), ids)
    v2 = explicit.graph.init(jax.random.PRNGKey(7), ids)
    assert jax.tree.structure(v1) == jax.tree.structure(v2)


def test_generate_logprobs_match_full_forward():
    """return_logprobs: the reported score of each emitted token equals
    log_softmax of the full causal forward's logits at that position —
    and tokens are unchanged vs the plain call."""
    from adapt_tpu.models.transformer_lm import (
        generate, lm_tiny, logits_full,
    )

    lm = lm_tiny(vocab=37, max_len=32)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 37)
    plain = np.asarray(generate(lm, variables, prompt, 6))
    toks, lps = generate(lm, variables, prompt, 6, return_logprobs=True)
    toks, lps = np.asarray(toks), np.asarray(lps)
    np.testing.assert_array_equal(toks, plain)
    assert lps.shape == (2, 6) and (lps <= 0).all()
    ids = np.concatenate([np.asarray(prompt), toks], axis=1)
    for t in range(6):
        lg = logits_full(lm, variables, jnp.asarray(ids[:, : 5 + t]))[:, -1]
        want = np.asarray(jax.nn.log_softmax(lg, axis=-1))
        got_tok = toks[:, t]
        np.testing.assert_allclose(
            lps[:, t], want[np.arange(2), got_tok], rtol=2e-4, atol=2e-4,
            err_msg=f"step {t}",
        )


def test_generate_logprobs_sampled_score_is_models_own():
    """Sampled generation with temperature/top-k still reports the RAW
    model log-softmax of the chosen token (not the tempered/filtered
    distribution)."""
    from adapt_tpu.models.transformer_lm import generate, lm_tiny

    lm = lm_tiny(vocab=37, max_len=32)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 37)
    toks, lps = generate(
        lm, variables, prompt, 5, temperature=1.3, top_k=5,
        rng=jax.random.PRNGKey(3), return_logprobs=True,
    )
    plain = generate(
        lm, variables, prompt, 5, temperature=1.3, top_k=5,
        rng=jax.random.PRNGKey(3),
    )
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(plain))
    lps = np.asarray(lps)
    assert (lps <= 0).all() and np.isfinite(lps).all()
