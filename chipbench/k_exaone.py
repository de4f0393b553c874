"""Builder of K-EXAONE-236B-A23B (``exaone_moe``) as
``configs/k-exaone-236b-a23b.json`` cuts it: the program's decoder
from a list of block specs read off the published keys, and weights
drawn from ``--seed`` LEAF BY LEAF, each cast to the served type as it
is made (``builders.init_weights`` draws the whole tree in float32
first: 14.8 GB here).
"""

from __future__ import annotations


def specs(model: dict):
    """The published keys -> one ``BlockSpec`` a layer kept."""
    from adapt_tpu.models.moe import ExpertSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    experts = ExpertSpec(
        num_experts=model["num_experts_published"],
        hidden_dim=model["moe_intermediate_size"],
        top_k=model["num_experts_per_tok"],
        score=model["scoring_func"],
        normalize=model["norm_topk_prob"],
        scale=model["routed_scaling_factor"],
        select_bias=True,
        shared_dim=model["num_shared_experts"]
        * model["moe_intermediate_size"],
        held=(0, model["num_experts"]),
    )
    out = []
    for i in range(model["num_hidden_layers"]):
        window = model["sliding_windows"][i] or None
        sparse = model["mlp_layer_types"][i] == "sparse"
        out.append(BlockSpec(
            model["hidden_size"], model["num_attention_heads"],
            model["intermediate_size"],
            kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"], norm="rmsnorm",
            norm_eps=model["rms_norm_eps"], post_norm=True, qk_norm=True,
            bias=False, mlp="experts" if sparse else "gated_silu",
            experts=experts if sparse else None,
            # rotary on the window layers only
            rope_base=float(model["rope_parameters"]["rope_theta"])
            if window else None,
            window=window,
        ))
    return out


def init_weights(lm, dtype, seed: int):
    """One jitted draw a leaf, on the device, cast there: a norm's
    scale is ones, the embedding N(0, 1), every matrix N(0, 1 /
    fan_in); the router's selection bias is set afterwards
    (:func:`balance_routers`)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )
    abstract = jax.eval_shape(
        lm.graph.init, key, jnp.zeros((1, 8), jnp.int32)
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def draw(shape, std):
        @jax.jit
        def f(k):
            x = jax.random.normal(k, shape, jnp.float32) * std
            return x.astype(dtype)
        return f

    out = []
    for n, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        shape = leaf.shape
        if "scale" in name:
            out.append(jnp.ones(shape, dtype))
            continue
        if "router_bias" in name:
            out.append(jnp.zeros(shape, dtype))
            continue
        if "embedding" in name:
            std = 1.0
        else:  # (in, ...out) kernels; (experts, in, out) stacks
            stacked = "'experts'" in name and len(shape) == 3
            std = shape[1 if stacked else 0] ** -0.5
        out.append(draw(shape, std)(jax.random.fold_in(key, n)))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))


def balance_routers(lm, variables, seed: int, rows: int = 32):
    """Set every sparse layer's selection bias as the balancing it is
    trained with leaves it (DeepSeek-V3's, without an auxiliary loss):
    each expert chosen equally often. On ``rows`` sequences of 256
    random ids from the seed (many sequences: each has a common
    direction of its own in its hidden states), layer by layer, expert e's bias becomes minus the
    (1 - top_k / experts) quantile of its score, so every expert
    clears the bar on the same share of tokens. Random matrices alone
    send most tokens to a few experts (5 times the mean, my chip runs,
    PR 31), and WHICH few, held here or not, changes with the seed: a
    step's work, and the cell's rate, would then be the seed's."""
    import jax
    import jax.numpy as jnp

    g = lm.graph
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), 1 << 20)
    ids = jax.random.randint(key, (rows, 256), 0, lm.vocab)
    x = g.node("embed").module.apply(variables["embed"], ids)
    out = dict(variables)
    for name in lm.block_names:
        block = g.node(name).module
        if block.spec.mlp == "experts":
            spec = block.spec.experts
            h = jax.jit(lambda v, x, block=block: block.apply(
                v, x,
                method=lambda m, x: m._attn_res(x, m.attn(m._attn_in(x))),
            ))(out[name], x)
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
        x = jax.jit(block.apply)(out[name], x)
    return jax.block_until_ready(out)


def build(model: dict, dtype_name: str, seed: int):
    """``(lm, variables, shape)`` for ``lm_engine``. ``shape`` also
    carries what this module's readers need of the architecture."""
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm

    dtype = jnp.dtype(dtype_name)
    blocks = specs(model)
    lm = transformer_lm(
        model["vocab_size"], blocks=blocks, pos="none",
        max_len=model["positions_served"], dtype=dtype,
    )
    shape = dict(
        vocab=model["vocab_size"], max_len=model["positions_served"],
        layers=len(blocks), heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
    )
    variables = balance_routers(lm, init_weights(lm, dtype, seed), seed)
    return lm, variables, shape
