"""Builder of DeepSeek-V3.2-Exp (``deepseek_v32``) as
``configs/deepseek-v3.2-exp.json`` cuts it: the program's decoder from
a list of block specs read off the published keys (every layer latent
attention with a lightning indexer that picks ``index_topk`` cached
positions a query; a dense gated-SiLU MLP in the leading layers, the
group-limited sigmoid router over ``n_routed_experts`` with one shared
expert in the rest), and weights drawn from ``--seed`` leaf by leaf.

The draw is Xing4.0's rule (``xing4._draw``: a norm's scale ones, a
bias zeros, the embedding N(0, 1), every matrix N(0, 1 / fan_in): no
leaf is drawn wider, DEEPSEEK_V32.md says why none had to be), and the
routers are balanced by
Xing4.0's rule (``xing4.balance_routers``: dense layers passed
through, each sparse layer's selection bias set on 32 x 256 random
tokens), taken ``_BALANCE_ROWS`` sequences at a time as Solar-Open2
takes it: all 32 at 128 heads are 3.5 GB of temporaries, which a chip
that already holds a pool (``scripts/solar_open2_limits.py``: one
batcher, the weights swapped seed by seed) has no room for.
"""

from __future__ import annotations

#: Sequences of 256 tokens a forward of :func:`balance_routers`.
_BALANCE_ROWS = 4


def specs(model: dict):
    """The published keys -> one ``BlockSpec`` a layer kept. Layer
    ``i`` kept is the published layer ``first_layer + i``."""
    from adapt_tpu.models.mla import IndexSpec, LatentSpec
    from adapt_tpu.models.moe import ExpertSpec
    from adapt_tpu.models.rope import YarnSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    if model["scoring_func"] != "sigmoid" or model["topk_method"] != "noaux_tc":
        raise ValueError("the router is sigmoid scores under a selection "
                         "bias (noaux_tc); nothing else is built")
    if model["moe_layer_freq"] != 1 or model["hidden_act"] != "silu":
        raise ValueError("every layer past the dense ones is sparse and "
                         "the MLPs are gated SiLU; nothing else is built")
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
        index=IndexSpec(
            heads=model["index_n_heads"], dim=model["index_head_dim"],
            rope_dim=model["qk_rope_head_dim"], top_k=model["index_topk"],
        ),
    )
    experts = ExpertSpec(
        num_experts=model["n_routed_experts_published"],
        hidden_dim=model["moe_intermediate_size"],
        top_k=model["num_experts_per_tok"], score="sigmoid",
        normalize=model["norm_topk_prob"],
        scale=float(model["routed_scaling_factor"]), select_bias=True,
        shared_dim=model["n_shared_experts"] * model["moe_intermediate_size"],
        held=(0, model["n_routed_experts"]),
        groups=(model["n_group"], model["topk_group"]),
    )
    out = []
    for i in range(model["num_hidden_layers"]):
        sparse = model["first_layer"] + i >= model["first_k_dense_replace"]
        out.append(BlockSpec(
            model["hidden_size"], model["num_attention_heads"],
            model["intermediate_size"], norm="rmsnorm",
            norm_eps=model["rms_norm_eps"], bias=False,
            mlp="experts" if sparse else "gated_silu",
            experts=experts if sparse else None,
            rope_base=float(model["rope_theta"]), latent=latent,
        ))
    return out


def init_weights(lm, dtype, seed: int):
    """``xing4.init_weights``' rule, one draw a leaf on the device, cast
    there: a norm's scale ones, every bias (the routers' selection
    biases, set afterwards, and the index key norm's) zeros, the
    embedding N(0, 1), every matrix N(0, 1 / fan_in)."""
    import jax
    import jax.numpy as jnp

    from chipbench.xing4 import _draw

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
        if "scale" in name:
            out.append(jnp.ones(shape, dtype))
        elif "bias" in name:
            out.append(jnp.zeros(shape, dtype))
        else:
            stacked = "'experts'" in name and len(shape) == 3
            std = 1.0 if "embedding" in name else (
                shape[1 if stacked else 0] ** -0.5
            )
            out.append(_draw(shape, std, dtype)(jax.random.fold_in(key, n)))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))


def balance_routers(lm, variables, seed: int, rows: int = 32):
    """``xing4.balance_routers``' rule over this decoder, a few
    sequences a forward: each sparse layer's selection bias set so that
    every expert clears the bar on the same share of ``rows`` x 256
    random tokens from the seed (before the group limit); a dense layer
    is passed through. The router reads the second norm's output,
    captured from the block's own forward (``xing4._forward``); a layer
    whose bias changed is run again."""
    import jax
    import jax.numpy as jnp

    from chipbench.xing4 import _forward

    g = lm.graph
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), 1 << 20)
    ids = jax.random.randint(key, (rows, 256), 0, lm.vocab)
    xs = [
        g.node("embed").module.apply(
            variables["embed"], ids[i: i + _BALANCE_ROWS]
        )
        for i in range(0, rows, _BALANCE_ROWS)
    ]
    out = dict(variables)
    for name in lm.block_names:
        block = g.node(name).module
        forward = _forward(block.spec, block.dtype)
        seen = [forward(out[name], x) for x in xs]
        if block.spec.mlp == "experts":
            spec = block.spec.experts
            h = jnp.concatenate([h for _, h in seen])
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
            seen = [forward(out[name], x) for x in xs]
        xs = [y for y, _ in seen]
    return jax.block_until_ready(out)


def build(model: dict, dtype_name: str, seed: int):
    """``(lm, variables, shape)`` for ``lm_engine``. ``shape`` carries
    what the latent readers take (``xing4_readers``: ``latent_row`` /
    ``latent_values``) and what this architecture's own do: the index
    key's width and how many positions a query reads at most."""
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
        index_row=latent.index.dim, index_topk=latent.index.top_k,
    )
    variables = balance_routers(lm, init_weights(lm, dtype, seed), seed)
    return lm, variables, shape
