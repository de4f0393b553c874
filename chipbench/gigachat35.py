"""Builder of GigaChat3.5-432B-A28B (``gigachat3_5``) as
``configs/gigachat3.5-432b-a28b.json`` cuts it: the program's decoder
from a list of block specs read off the published keys (a gated
latent-attention layer where the layer's published index is in
``full_attention_layers``, a Gated DeltaNet layer that holds a state
and no pages elsewhere: a scalar decay a value head, 32 key heads under
64 value heads; norms on both sides of every sub-layer; a clamped
SwiGLU in the dense MLP of the leading layers and in the experts of the
rest), and weights drawn from ``--seed`` leaf by leaf.

Nothing here is new machinery: the draw is Solar-Open2's
(``solar_open2.init_weights``: ``A_log`` and ``dt_bias`` by
``models/kda``'s own initialisers, a norm's scale ones, matrices
N(0, 1 / fan_in)) and the routers are balanced as Xing4.0's are
(``xing4.balance_routers``: dense layers passed through, each sparse
layer's selection bias set on 32 x 256 random tokens).
"""

from __future__ import annotations


def specs(model: dict):
    """The published keys -> one ``BlockSpec`` a layer kept. Layer
    ``i`` kept is the published layer ``first_layer + i``."""
    from adapt_tpu.models.kda import KdaSpec
    from adapt_tpu.models.mla import LatentSpec
    from adapt_tpu.models.moe import ExpertSpec
    from adapt_tpu.models.rope import YarnSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    if model["linear_attention_type"] != "GigaChat35GatedDeltaNet" or (
        model["linear_gating_type"] != "gated_rmsnorm_sigmoid_zero_centered"
    ):
        raise ValueError("the linear layer is GigaChat35GatedDeltaNet under "
                         "a zero-centred sigmoid gate; nothing else is built")
    if model["linear_key_head_dim"] != model["linear_value_head_dim"]:
        raise ValueError("linear_key_head_dim != linear_value_head_dim: a "
                         "head's state is square here")
    if model["layernorm_type"] != "pre_post" or model["n_group"] != 1:
        raise ValueError("layernorm_type pre_post and n_group 1 are what "
                         "the published config says; nothing else is built")
    limit = float(model["swiglu_limit"])
    linear = KdaSpec(
        heads=model["linear_num_value_heads"],
        head_dim=model["linear_value_head_dim"], rank=None,
        d_conv=model["linear_conv_kernel_dim"], neg_eigval=False,
        norm_eps=model["linear_attn_o_norm_eps"],
        key_heads=model["linear_num_key_heads"], head_decay=True,
        gate_scale=float(model["linear_sigmoid_gate_scale"]),
    )
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
    experts = ExpertSpec(
        num_experts=model["n_routed_experts_published"],
        hidden_dim=model["moe_intermediate_size"],
        top_k=model["num_experts_per_tok"], score="sigmoid",
        normalize=model["norm_topk_prob"],
        scale=float(model["routed_scaling_factor"]), select_bias=True,
        shared_dim=model["n_shared_experts"] * model["moe_intermediate_size"],
        held=(0, model["n_routed_experts"]), swiglu_limit=limit,
    )
    out = []
    for i in range(model["num_hidden_layers"]):
        layer = model["first_layer"] + i
        mlp = dict(mlp="experts", experts=experts) if (
            layer >= model["first_k_dense_replace"]
        ) else dict(mlp="gated_silu", swiglu_limit=limit)
        common = dict(
            norm="rmsnorm", norm_eps=model["rms_norm_eps"], bias=False,
            sandwich_norm=True, **mlp,
        )
        if layer in model["full_attention_layers"]:
            out.append(BlockSpec(
                model["hidden_size"], model["num_attention_heads"],
                model["intermediate_size"], latent=latent,
                rope_base=float(model["rope_theta"]),
                attn_gate=model["gated_attention"], **common,
            ))
        else:
            out.append(BlockSpec(
                model["hidden_size"], linear.heads,
                model["intermediate_size"], linear=linear, **common,
            ))
    return out


def build(model: dict, dtype_name: str, seed: int):
    """``(lm, variables, shape)`` for ``lm_engine``. ``shape`` carries
    what BOTH families of readers take: ``layers`` counts the layers
    whose pages a decode step reads (the latent ones, with
    ``latent_row`` / ``latent_values``: ``xing4_readers``), the
    ``kda_*`` entries the layers that keep a state
    (``solar_open2_readers``)."""
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm
    from chipbench.solar_open2 import init_weights
    from chipbench.xing4 import balance_routers

    dtype = jnp.dtype(dtype_name)
    blocks = specs(model)
    lm = transformer_lm(
        model["vocab_size"], blocks=blocks, pos="none",
        max_len=model["positions_served"], dtype=dtype,
    )
    linear = [b.linear for b in blocks if b.linear is not None]
    latent = [b.latent for b in blocks if b.latent is not None]
    shape = dict(
        vocab=model["vocab_size"], max_len=model["positions_served"],
        layers=len(latent), heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=latent[0].qk_dim,
        latent_row=latent[0].row, latent_values=latent[0].kv_rank,
        kda_layers=len(linear), kda_heads=linear[0].heads,
        kda_head_dim=linear[0].head_dim,
    )
    variables = balance_routers(lm, init_weights(lm, dtype, seed), seed)
    return lm, variables, shape
