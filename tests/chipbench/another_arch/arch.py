"""Builder of a decoder the GPT-2 builder cannot say: grouped-query
attention, rotary positions, a top-2 mixture of experts. The key names
are a Mixtral-style ``config.json``'s, none of them GPT-2's."""


def build(model: dict, dtype_name: str, seed: int):
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm
    from chipbench.builders import init_weights

    dtype = jnp.dtype(dtype_name)
    heads = model["num_attention_heads"]
    lm = transformer_lm(
        model["vocab_size"], model["hidden_size"],
        model["num_hidden_layers"], heads, model["intermediate_size"],
        max_len=model["max_position_embeddings"], dtype=dtype,
        kv_heads=model["num_key_value_heads"],
        moe_experts=model["num_local_experts"],
        moe_top_k=model["num_experts_per_tok"], pos="rope",
    )
    shape = dict(
        vocab=model["vocab_size"], max_len=model["max_position_embeddings"],
        layers=model["num_hidden_layers"], heads=heads,
        kv_heads=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // heads,
    )
    return lm, init_weights(lm, dtype, seed), shape
