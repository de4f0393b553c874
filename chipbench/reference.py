"""Plain reference of the decoder both GPT-2 configurations are: a GPT-2
block (pre-LayerNorm, fused QKV, tanh-GELU two-matrix MLP, learned
positions, untied head) in straightforward ``jax.numpy`` at float32
and ``highest`` matmul precision. No kernels, no cache, no batching
tricks. It reads the program's parameter tree and nothing else of the
program. A configuration names it under ``reference``
(``chipbench.reference:next_token_logprobs``); another architecture
brings a reference module of its own with the same signature.

Run block by block from Python: one small jitted block serves every
layer (same shapes), and only one layer's float32 copy of the weights
is alive at a time, so it fits beside a full KV pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32) + p[
        "bias"
    ].astype(F32)


def _dense(x, p):
    return jnp.tensordot(x, p["kernel"].astype(F32), axes=1) + p[
        "bias"
    ].astype(F32)


@jax.jit
def _block(p, h):
    with jax.default_matmul_precision("highest"):
        b, s, _ = h.shape
        x = _ln(h, p["ln1"])
        qkv = _dense(x, p["attn"]["qkv"])  # (b, s, 3, heads, hd)
        q, k, v = (qkv[:, :, i] for i in range(3))
        hd = q.shape[-1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        h = h + _dense(o.reshape(b, s, -1), p["attn"]["out"])
        x = _ln(h, p["ln2"])
        x = jax.nn.gelu(_dense(x, p["mlp_in"]), approximate=True)
        return h + _dense(x, p["mlp_out"])


@jax.jit
def _embed(p, ids):
    tok = p["tok"]["embedding"].astype(F32)[ids]
    return tok + p["pos_embed"].astype(F32)[: ids.shape[1]]


@jax.jit
def _head_logprobs(p, h, targets):
    """log P(targets[t] | ids[:t+1]) at every position."""
    with jax.default_matmul_precision("highest"):
        logits = _dense(_ln(h, p["ln"]), p["logits"])
    logp = jax.nn.log_softmax(logits, -1)
    return jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def next_token_logprobs(variables, ids, fault=""):
    """(b, s - 1): log-probability the model gives ``ids[:, t + 1]``
    after reading ``ids[:, : t + 1]``. ``variables`` is the program's
    parameter tree (``{"params": {...}}`` per graph node).

    ``fault="drop_block"`` is the self-test of the comparison built on
    this: the first block is left out, which is what a served model
    one block short looks like from here, and a sound comparison then
    answers not correct. Each reference knows its own tree, so the
    fault lives here and not in the engine."""
    if fault not in ("", "drop_block"):
        raise ValueError(f"unknown fault {fault!r}")
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(variables["embed"]["params"], ids)
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    for name in names[1:] if fault == "drop_block" else names:
        h = _block(variables[name]["params"], h)
    return _head_logprobs(
        variables["head"]["params"], h[:, :-1], ids[:, 1:]
    )
