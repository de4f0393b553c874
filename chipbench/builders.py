"""Builders: what a configuration file names under ``builder`` as
``module:function``. A builder is called as ``builder(model,
dtype_name, seed)`` with the file's ``model`` block (the rehearsal's
tiny sizes laid over it in a rehearsal) and returns ``(lm, variables,
shape)``: the program's model object, its weights on the device in
the served type, and the architecture as the engine and the readers
may know it:

``vocab``     token ids are drawn below it and served tokens held to it
``max_len``   positions the model serves (pool rule, longest prompt)
``layers``    attention layers whose cache a decode step reads
``heads``, ``kv_heads``, ``head_dim``   the attention kernels' shapes

Nothing outside a builder reads a ``model`` key by name, so a new
architecture is a new builder (here or in a directory of its own
listed in ``paths``), never an edit to the engine. A builder imports
the program's model constructors and this module's ``init_weights``;
it reads nothing else of the benchmark.
"""

from __future__ import annotations


def init_weights(lm, dtype, seed: int):
    """Weights from ``--seed``: one jitted ``graph.init`` on the
    device, cast there to the served type."""
    import jax
    import jax.numpy as jnp

    # --seed may exceed 31 bits: fold the high part in.
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )

    @jax.jit
    def init(key):
        tree = lm.graph.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree.map(lambda x: x.astype(dtype), tree)

    return jax.block_until_ready(init(key))


def gpt2(model: dict, dtype_name: str, seed: int):
    """The GPT-2 block (pre-LayerNorm, fused QKV, tanh-GELU two-matrix
    MLP, learned positions, untied float32 head) at the sizes of a
    published GPT-2 ``config.json``."""
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm

    dtype = jnp.dtype(dtype_name)
    heads = model["n_head"]
    lm = transformer_lm(
        model["vocab_size"], model["n_embd"], model["n_layer"], heads,
        model["n_inner"], max_len=model["n_positions"], dtype=dtype,
    )
    shape = dict(
        vocab=model["vocab_size"], max_len=model["n_positions"],
        layers=model["n_layer"], heads=heads, kv_heads=heads,
        head_dim=model["n_embd"] // heads,
    )
    return lm, init_weights(lm, dtype, seed), shape
