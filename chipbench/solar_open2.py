"""Builder of Solar-Open2-250B (``solar_open2``) as
``configs/solar-open2-250b.json`` cuts it: the program's decoder from a
list of block specs read off the published keys (a gated NoPE GQA layer
where the layer's index is in ``gqa_layers``, a Kimi-Delta-Attention
layer that holds a state and no pages elsewhere, every layer's MLP the
sigmoid-routed experts held here plus the shared one), and weights
drawn from ``--seed`` leaf by leaf (:func:`init_weights`).
"""

from __future__ import annotations

#: Sequences the routers are balanced on at a time (a KDA layer's
#: chunked forward forms ``exp(G_r - G_i)`` pairwise: 134 MB a
#: sequence and chunk at the published widths).
_BALANCE_ROWS = 4


def specs(model: dict):
    """The published keys -> one ``BlockSpec`` a layer kept."""
    from adapt_tpu.models.kda import KdaSpec
    from adapt_tpu.models.moe import ExpertSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    lin = model["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("linear_attn_config.num_kv_heads: q, k and v "
                         "have num_heads heads each here")
    if model["kda_use_full_proj"] or model["use_rope"]:
        raise ValueError("kda_use_full_proj and use_rope are false in "
                         "the published config; nothing else is built")
    linear = KdaSpec(
        heads=lin["num_heads"], head_dim=lin["head_dim"],
        rank=model["kda_low_rank"], d_conv=lin["short_conv_kernel_size"],
        neg_eigval=model["kda_allow_neg_eigval"],
        norm_eps=model["rms_norm_eps"],
    )
    experts = ExpertSpec(
        num_experts=model["n_routed_experts_published"],
        hidden_dim=model["moe_intermediate_size"],
        top_k=model["num_experts_per_tok"], score="sigmoid",
        normalize=model["norm_topk_prob"],
        scale=float(model["routed_scaling_factor"]), select_bias=True,
        shared_dim=model["n_shared_experts"] * model["moe_intermediate_size"],
        held=(0, model["n_routed_experts"]),
    )
    common = dict(
        norm="rmsnorm", norm_eps=model["rms_norm_eps"], bias=False,
        mlp="experts", experts=experts,
    )
    out = []
    for i in range(model["num_hidden_layers"]):
        if i < model["first_k_dense_replace"]:
            raise ValueError("first_k_dense_replace is 0 in the published "
                             "config: no dense layer is built")
        if i in model["gqa_layers"]:
            out.append(BlockSpec(
                model["hidden_size"], model["num_attention_heads"],
                model["intermediate_size"],
                kv_heads=model["num_key_value_heads"],
                head_dim=model["head_dim"],
                attn_gate=model["use_gqa_gate"], **common,
            ))
        else:
            out.append(BlockSpec(
                model["hidden_size"], linear.heads,
                model["intermediate_size"], linear=linear, **common,
            ))
    return out


def init_weights(lm, dtype, seed: int):
    """``xing4.init_weights``' rule, one draw a leaf on the device,
    cast there: a norm's scale is ones, the routers' selection biases
    zeros (set afterwards, :func:`balance_routers`), the embedding
    N(0, 1), every matrix N(0, 1 / fan_in), the convolution's taps
    among them (fan-in 4); a KDA layer's ``A_log`` and ``dt_bias`` by
    ``models/kda``'s own initialisers. q and k are normalised a head,
    so every branch is O(1) with no further scale."""
    import jax
    import jax.numpy as jnp

    from adapt_tpu.models.kda import init_a_log, init_dt_bias
    from chipbench.xing4 import _draw

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )
    abstract = jax.eval_shape(
        lm.graph.init, key, jnp.zeros((1, 8), jnp.int32)
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    own = {"['A_log']": init_a_log, "['dt_bias']": init_dt_bias}
    out = []
    for n, (path, leaf) in enumerate(leaves):
        name, shape = jax.tree_util.keystr(path), leaf.shape
        k = jax.random.fold_in(key, n)
        drawn = [f for end, f in own.items() if name.endswith(end)]
        if drawn:
            out.append(drawn[0](k, shape).astype(dtype))
        elif "scale" in name:
            out.append(jnp.ones(shape, dtype))
        elif "router_bias" in name:
            out.append(jnp.zeros(shape, dtype))
        else:
            stacked = "'experts'" in name and len(shape) == 3
            std = 1.0 if "embedding" in name else (
                shape[1 if stacked else 0] ** -0.5
            )
            out.append(_draw(shape, std, dtype)(k))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))


def balance_routers(lm, variables, seed: int, rows: int = 32):
    """``k_exaone.balance_routers`` over this decoder: each layer's
    selection bias set so that every expert clears the bar on the same
    share of ``rows`` x 256 random tokens from the seed. The router
    reads the second norm's output, captured from the block's own
    forward (``xing4._forward``), ``_BALANCE_ROWS`` sequences at a
    time; a layer whose bias changed is run again."""
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
        spec = block.spec.experts
        h = jnp.concatenate([forward(out[name], x)[1] for x in xs])
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
        xs = [forward(out[name], x)[0] for x in xs]
    return jax.block_until_ready(out)


def build(model: dict, dtype_name: str, seed: int):
    """``(lm, variables, shape)`` for ``lm_engine``. ``shape["layers"]``
    counts the layers whose PAGES a decode step reads (the GQA layers
    kept: ``builders.py``); the ``kda_*`` entries are what this
    architecture's readers need of the layers that keep a state."""
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm

    dtype = jnp.dtype(dtype_name)
    blocks = specs(model)
    lm = transformer_lm(
        model["vocab_size"], blocks=blocks, pos="none",
        max_len=model["positions_served"], dtype=dtype,
    )
    linear = [b.linear for b in blocks if b.linear is not None]
    shape = dict(
        vocab=model["vocab_size"], max_len=model["positions_served"],
        layers=len(blocks) - len(linear),
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        kda_layers=len(linear), kda_heads=linear[0].heads,
        kda_head_dim=linear[0].head_dim,
    )
    variables = balance_routers(lm, init_weights(lm, dtype, seed), seed)
    return lm, variables, shape
