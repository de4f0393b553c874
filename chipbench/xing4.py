"""Builder of Xing4.0-29B-A4B (``xing4_0``) as
``configs/xing4.0-29b-a4b.json`` cuts it: the program's decoder from a
list of block specs read off the published keys (multi-head latent
attention, a four-stream mHC residual, DeepSeek-V3's ``noaux_tc``
router over the experts held here), and weights drawn from ``--seed``
leaf by leaf (:func:`init_weights`: matrices N(0, 1 / fan_in), the
embedding N(0, 1), scales 1; mHC's ``phi`` is a matrix like any other,
so each pre-activation is N(0, 1) over the normed streams, its ``a``
ones and ``b`` zeros: every coefficient is O(1) and ``H_res`` a doubly
stochastic matrix far from the identity).
"""

from __future__ import annotations

import functools


def specs(model: dict):
    """The published keys -> one ``BlockSpec`` a layer kept."""
    from adapt_tpu.models.mhc import HyperSpec
    from adapt_tpu.models.mla import LatentSpec
    from adapt_tpu.models.moe import ExpertSpec
    from adapt_tpu.models.rope import YarnSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    rs = model["rope_scaling"]
    latent = LatentSpec(
        q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
        nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        yarn=YarnSpec(
            factor=float(rs["factor"]),
            original_max=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
        ),
    )
    streams = HyperSpec(
        streams=model["hc_mult"], sinkhorn_iters=model["hc_sinkhorn_iters"],
        eps=model["hc_eps"],
        clamp=(float(model["mhc_h_res_clamp_min"]),
               float(model["mhc_h_res_clamp_max"])),
    )
    experts = ExpertSpec(
        num_experts=model["n_routed_experts_published"],
        hidden_dim=model["moe_intermediate_size"],
        top_k=model["num_experts_per_tok"], score=model["scoring_func"],
        normalize=model["norm_topk_prob"],
        scale=float(model["routed_scaling_factor"]), select_bias=True,
        shared_dim=model["n_shared_experts"] * model["moe_intermediate_size"],
        held=(0, model["n_routed_experts"]),
    )
    out = []
    for i in range(model["num_hidden_layers"]):
        sparse = i >= model["first_k_dense_replace"]
        out.append(BlockSpec(
            model["hidden_size"], model["num_attention_heads"],
            model["intermediate_size"], norm="rmsnorm",
            norm_eps=model["rms_norm_eps"], bias=False,
            mlp="experts" if sparse else "gated_silu",
            experts=experts if sparse else None,
            rope_base=float(model["rope_theta"]), latent=latent,
            streams=streams,
        ))
    return out


@functools.lru_cache(maxsize=None)
def _draw(shape, std, dtype):
    """One compiled draw a (shape, std): the layers repeat both."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    )


def init_weights(lm, dtype, seed: int):
    """``k_exaone.init_weights``' rule, one draw a leaf on the device,
    cast there: a norm's scale and mHC's ``a`` are ones, the routers'
    selection biases and mHC's ``b`` zeros (the biases are set
    afterwards, :func:`balance_routers`), the embedding N(0, 1), every
    matrix N(0, 1 / fan_in)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )
    abstract = jax.eval_shape(
        lm.graph.init, key, jnp.zeros((1, 8), jnp.int32)
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for n, (path, leaf) in enumerate(leaves):
        name, shape = jax.tree_util.keystr(path), leaf.shape
        if "scale" in name or name.endswith("['a']"):
            out.append(jnp.ones(shape, dtype))
        elif "router_bias" in name or name.endswith("['b']"):
            out.append(jnp.zeros(shape, dtype))
        else:
            stacked = "'experts'" in name and len(shape) == 3
            std = 1.0 if "embedding" in name else (
                shape[1 if stacked else 0] ** -0.5
            )
            out.append(_draw(shape, std, dtype)(jax.random.fold_in(key, n)))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))


@functools.lru_cache(maxsize=None)
def _forward(spec, dtype):
    """ONE compiled forward a block spec (the dense layers share one,
    the sparse layers another): ``(variables, x) -> (y, h)``, ``h``
    the second norm's output, which a sparse layer's router reads."""
    import jax

    from adapt_tpu.models.transformer_lm import DecoderBlock

    block = DecoderBlock(spec, dtype=dtype)

    @jax.jit
    def forward(v, x):
        y, seen = block.apply(
            v, x, capture_intermediates=lambda m, _: m.name == "ln2",
            mutable=["intermediates"],
        )
        return y, seen["intermediates"]["ln2"]["__call__"][0]

    return forward


def balance_routers(lm, variables, seed: int, rows: int = 32):
    """``k_exaone.balance_routers`` over this decoder: each sparse
    layer's selection bias set so that every expert clears the bar on
    the same share of ``rows`` x 256 random tokens from the seed. The
    router reads the second norm's output (the normed mix of the
    streams the MLP sub-layer is given), captured from the block's own
    forward; a layer whose bias changed is run again."""
    import jax
    import jax.numpy as jnp

    g = lm.graph
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), 1 << 20)
    ids = jax.random.randint(key, (rows, 256), 0, lm.vocab)
    x = g.node("embed").module.apply(variables["embed"], ids)
    out = dict(variables)
    for name in lm.block_names:
        block = g.node(name).module
        forward = _forward(block.spec, block.dtype)
        y, h = forward(out[name], x)
        if block.spec.mlp == "experts":
            spec = block.spec.experts
            p = dict(out[name]["params"]["experts"])
            scores = jax.nn.sigmoid(
                h.reshape(-1, h.shape[-1]).astype(jnp.float32)
                @ p["router"].astype(jnp.float32)
            )
            bar = jnp.quantile(
                scores, 1.0 - spec.top_k / spec.num_experts, axis=0
            )
            p["router_bias"] = (-bar).astype(p["router_bias"].dtype)
            out[name] = {"params": {**out[name]["params"], "experts": p}}
            y, _ = forward(out[name], x)
        x = y
    return jax.block_until_ready(out)


def build(model: dict, dtype_name: str, seed: int):
    """``(lm, variables, shape)`` for ``lm_engine``. ``shape`` also
    carries what this architecture's readers need of it: the latent
    row and the part of it the probabilities weight."""
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm

    dtype = jnp.dtype(dtype_name)
    blocks = specs(model)
    lm = transformer_lm(
        model["vocab_size"], blocks=blocks, pos="none",
        max_len=model["positions_served"], dtype=dtype,
    )
    latent = blocks[0].latent
    shape = dict(
        vocab=model["vocab_size"], max_len=model["positions_served"],
        layers=len(blocks), heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=latent.qk_dim,
        latent_row=latent.row, latent_values=latent.kv_rank,
    )
    variables = balance_routers(lm, init_weights(lm, dtype, seed), seed)
    return lm, variables, shape
