"""Plain reference of ``arch.build``'s decoder, in straightforward
``jax.numpy`` at float32 and ``highest`` precision: pre-LayerNorm,
grouped-query attention (query head i reads KV head i // group) with
rotate-half rotary positions on q and k, and a mixture of experts in
which every token adds its two largest gate probabilities' experts,
each weighted by its own probability (not renormalised). It reads the
program's parameter tree and nothing else of the program, and nothing
of ``chipbench/reference.py``."""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(x):
    return x.astype(F32)


def _ln(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f(p["scale"]) + _f(p["bias"])


def _rope(x, base=10000.0):
    """(b, s, heads, hd), positions 0..s-1."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(s, dtype=F32)[:, None] * freqs  # (s, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, _f(p["q"]["kernel"])) + _f(p["q"]["bias"])
    kv = jnp.einsum("bsd,dchk->bschk", x, _f(p["kv"]["kernel"])) + _f(p["kv"]["bias"])
    k, v = kv[:, :, 0], kv[:, :, 1]  # (b, s, kv_heads, hd)
    q, k = _rope(q), _rope(k)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(b, s, -1) @ _f(p["out"]["kernel"]) + _f(p["out"]["bias"])


def _mixture(p, x):
    gates = jax.nn.softmax(x @ _f(p["gate"]), -1)  # (b, s, e)
    top, idx = jax.lax.top_k(gates, 2)
    out = jnp.zeros_like(x)
    for e in range(gates.shape[-1]):
        h = jax.nn.gelu(x @ _f(p["w1"][e]) + _f(p["b1"][e]), approximate=True)
        y = h @ _f(p["w2"][e]) + _f(p["b2"][e])
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)
        out = out + weight * y
    return out


@jax.jit
def _block(p, h):
    with jax.default_matmul_precision("highest"):
        h = h + _attention(p["attn"], _ln(h, p["ln1"]))
        return h + _mixture(p["moe"], _ln(h, p["ln2"]))


@jax.jit
def _head_logprobs(p, h, targets):
    with jax.default_matmul_precision("highest"):
        logits = _ln(h, p["ln"]) @ _f(p["logits"]["kernel"]) + _f(
            p["logits"]["bias"])
    logp = jax.nn.log_softmax(logits, -1)
    return jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def next_token_logprobs(variables, ids, fault=""):
    """(b, s - 1) float32; ``fault="drop_block"`` leaves the first
    block out (the comparison's self-test)."""
    if fault not in ("", "drop_block"):
        raise ValueError(f"unknown fault {fault!r}")
    ids = jnp.asarray(ids, jnp.int32)
    h = _f(variables["embed"]["params"]["tok"]["embedding"])[ids]
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    for name in names[1:] if fault == "drop_block" else names:
        h = _block(variables[name]["params"], h)
    return _head_logprobs(variables["head"]["params"], h[:, :-1], ids[:, 1:])
