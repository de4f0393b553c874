"""Builder of Falcon-H1-34B-Instruct (``falcon_h1``) as
``configs/falcon-h1-34b-instruct.json`` cuts it: the program's decoder
from one block spec (every layer alike: attention and a Mamba-2 mixer
in parallel), and weights drawn from ``--seed`` LEAF BY LEAF, each cast
to the served type as it is made.

The family puts a scalar multiplier on every branch (muP), trained
weights being the size that makes the product O(1). Random matrices at
N(0, 1 / fan_in) alone would leave the attention's output at 0.04, the
mixer's at 0.09, the MLP's at 0.01 and every logit at 0.008: logprobs
near uniform, and no control readable over a tolerance. So each matrix
is drawn at ``1 / (multiplier * sqrt(fan_in))``, ``multiplier`` the
product of what stands between it and the branch it feeds
(:func:`leaf_std`).
"""

from __future__ import annotations

import functools
import math

#: Rows a leaf is drawn in where it is large (the head and the table
#: are 1.3e9 numbers each: a float32 draw of one whole is 5.35 GB).
_PIECES = 8


def specs(model: dict):
    """The published keys -> one ``BlockSpec`` a layer kept."""
    from adapt_tpu.models.ssm import SsmSpec
    from adapt_tpu.models.transformer_lm import BlockSpec

    ssm = SsmSpec(
        heads=model["mamba_n_heads"], head_dim=model["mamba_d_head"],
        d_state=model["mamba_d_state"], groups=model["mamba_n_groups"],
        d_conv=model["mamba_d_conv"], chunk=model["mamba_chunk_size"],
        in_mult=model["ssm_in_multiplier"],
        out_mult=model["ssm_out_multiplier"],
        mup=tuple(model["ssm_multipliers"]), norm_eps=model["rms_norm_eps"],
    )
    if ssm.d_inner != model["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm != mamba_n_heads * mamba_d_head")
    block = BlockSpec(
        model["hidden_size"], model["num_attention_heads"],
        model["intermediate_size"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        norm="rmsnorm", norm_eps=model["rms_norm_eps"], bias=False,
        mlp="gated_silu", rope_base=float(model["rope_theta"]), ssm=ssm,
        attn_in_mult=model["attention_in_multiplier"],
        key_mult=model["key_multiplier"],
        attn_out_mult=model["attention_out_multiplier"],
        mlp_gate_mult=model["mlp_multipliers"][0],
        mlp_out_mult=model["mlp_multipliers"][1],
    )
    return [block] * model["num_hidden_layers"]


def leaf_std(name: str, shape, model: dict):
    """The standard deviation a leaf is drawn at: a float, or for a
    matrix whose columns feed differently scaled branches an array
    that broadcasts against the leaf. ``name`` is the leaf's path in
    the parameter tree."""
    import numpy as np

    def fan(n):
        return 1.0 / math.sqrt(n)

    if "embedding" in name:  # h0 = rows * embedding_multiplier ~ N(0, 1)
        return 1.0 / model["embedding_multiplier"]
    if "'head'" in name:  # logits ~ N(0, 1) after lm_head_multiplier
        return fan(shape[0]) / model["lm_head_multiplier"]
    if "'kv'" in name:  # (dim, 2, kv_heads, hd): K carries key_multiplier
        std = np.full((1, 2, 1, 1), fan(shape[0]), np.float32)
        std[0, 0] /= model["key_multiplier"]
        return std
    if "'out'" in name:  # (heads * hd, dim) -> * attention_out_multiplier
        return fan(shape[0]) / model["attention_out_multiplier"]
    if "in_proj" in name:  # z | x | B | C | dt, a multiplier a segment
        s = model["mamba_d_ssm"]
        gn = model["mamba_n_groups"] * model["mamba_d_state"]
        seg = (s, s, gn, gn, model["mamba_n_heads"])
        mup = np.concatenate([
            np.full((n,), m, np.float32)
            for n, m in zip(seg, model["ssm_multipliers"])
        ])
        std = fan(shape[0]) / (model["ssm_in_multiplier"] * mup)
        std[-seg[-1]:] *= 0.5  # dt's own noise, beside dt_bias's range
        return std[None, :]
    if "out_proj" in name:
        return fan(shape[0]) / model["ssm_out_multiplier"]
    if "conv_kernel" in name:  # (width, channels), depthwise
        return fan(shape[0])
    if "conv_bias" in name:
        return 0.1
    if "mlp_gate" in name:
        return fan(shape[0]) / model["mlp_multipliers"][0]
    if "mlp_out" in name:
        return fan(shape[0]) / model["mlp_multipliers"][1]
    return fan(shape[0])  # q, mlp_in: no multiplier of their own


def init_weights(lm, dtype, seed: int, model: dict):
    """One jitted draw a leaf, on the device, cast there. Norm scales
    and ``D`` are ones; ``A_log``, ``dt_bias`` and ``D`` keep float32
    and Mamba-2's own ranges (``A`` in [1, 16], ``dt`` in [0.001,
    0.1]: the module's initialisers), so that a state neither vanishes
    in a step nor never decays; every other leaf is N(0, leaf_std^2)
    in the served type."""
    import jax
    import jax.numpy as jnp

    from adapt_tpu.models.ssm import init_a_log, init_dt_bias

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )
    shapes = jax.eval_shape(
        lm.graph.init, key, jnp.zeros((1, 8), jnp.int32)
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @functools.partial(jax.jit, static_argnums=(0,))
    def draw(shape, std, k):  # one program a SHAPE, not a leaf
        if math.prod(shape) < 1 << 28 or shape[0] % _PIECES:
            x = jax.random.normal(k, shape, jnp.float32) * std
            return x.astype(dtype)
        rows = shape[0] // _PIECES  # a piece's float32 at a time
        return jax.lax.map(
            lambda k: (jax.random.normal(
                k, (rows,) + shape[1:], jnp.float32
            ) * std).astype(dtype),
            jax.random.split(k, _PIECES),
        ).reshape(shape)

    out = []
    for n, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, n)
        if "scale" in name:
            out.append(jnp.ones(leaf.shape, dtype))
        elif name.endswith("['D']"):
            out.append(jnp.ones(leaf.shape, jnp.float32))
        elif "A_log" in name:
            out.append(init_a_log(k, leaf.shape))
        elif "dt_bias" in name:
            out.append(init_dt_bias(k, leaf.shape))
        else:
            std = jnp.asarray(leaf_std(name, leaf.shape, model), jnp.float32)
            out.append(draw(leaf.shape, std, k))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))


def build(model: dict, dtype_name: str, seed: int):
    """``(lm, variables, shape)`` for ``lm_engine``. ``shape`` names
    the attention kernels' sizes (every layer alike); the mixer's are
    in ``records["model"]`` for this module's readers."""
    import jax.numpy as jnp

    from adapt_tpu.models.transformer_lm import transformer_lm

    dtype = jnp.dtype(dtype_name)
    blocks = specs(model)
    lm = transformer_lm(
        model["vocab_size"], blocks=blocks, pos="none",
        max_len=model["positions_served"], dtype=dtype,
        embed_scale=model["embedding_multiplier"],
        head_scale=model["lm_head_multiplier"],
    )
    shape = dict(
        vocab=model["vocab_size"], max_len=model["positions_served"],
        layers=len(blocks), heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
    )
    return lm, init_weights(lm, dtype, seed, model), shape
