"""Plain reference of ``arch.build``'s decoder, in straightforward
``jax.numpy`` at float32 and ``highest`` precision: pre-LayerNorm,
grouped-query attention (query head i reads KV head i // group) with
rotate-half rotary positions on q and k, and a mixture of experts in
which every token adds its two largest gate probabilities' experts,
each weighted by its own probability (not renormalised). It reads the
program's parameter tree and nothing else of the program, and nothing
of ``chipbench/reference.py``.

Two entry points over one forward pass. ``next_token_logprobs`` returns
the plain array (the float32 configuration's reference).
``next_token_logprobs_vouched`` returns the pair ``(logprobs,
vouched)``: the router's choice is hard, so where this float32 pass
finds the second and third gate probabilities within ``MARGIN`` of each
other, in either layer, a model served in bfloat16 may rightly send the
token to the other expert, and the reference does not vouch for that
position (``lm_engine.correctness_sample`` leaves it out of the number
it compares, and counts it)."""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: A position is vouched for when, in every mixture layer, the gate
#: probability of the last expert chosen exceeds that of the first one
#: left out by this much. Readings: configs/tiny-moe-bf16.json.
MARGIN = 0.005

FAULTS = ("", "drop_block", "drop_expert")


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


def _mixture(p, x, skip):
    """The layer's output and, per position, the gap between the last
    gate probability chosen and the first left out. ``skip`` is an
    expert left out of the sum (the ``drop_expert`` control), or None."""
    gates = jax.nn.softmax(x @ _f(p["gate"]), -1)  # (b, s, e)
    top, idx = jax.lax.top_k(gates, 3)
    gap = top[..., 1] - top[..., 2]
    top, idx = top[..., :2], idx[..., :2]
    out = jnp.zeros_like(x)
    for e in range(gates.shape[-1]):
        if e == skip:
            continue
        h = jax.nn.gelu(x @ _f(p["w1"][e]) + _f(p["b1"][e]), approximate=True)
        y = h @ _f(p["w2"][e]) + _f(p["b2"][e])
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)
        out = out + weight * y
    return out, gap


@functools.partial(jax.jit, static_argnames="skip")
def _block(p, h, skip=None):
    with jax.default_matmul_precision("highest"):
        h = h + _attention(p["attn"], _ln(h, p["ln1"]))
        out, gap = _mixture(p["moe"], _ln(h, p["ln2"]), skip)
        return h + out, gap


@jax.jit
def _head_logprobs(p, h, targets):
    with jax.default_matmul_precision("highest"):
        logits = _ln(h, p["ln"]) @ _f(p["logits"]["kernel"]) + _f(
            p["logits"]["bias"])
    logp = jax.nn.log_softmax(logits, -1)
    return jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def _forward(variables, ids, fault):
    """(logprobs, smallest gap over the layers), both (b, s - 1)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ids = jnp.asarray(ids, jnp.int32)
    h = _f(variables["embed"]["params"]["tok"]["embedding"])[ids]
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    gaps = []
    for k, name in enumerate(names[1:] if fault == "drop_block" else names):
        skip = 0 if fault == "drop_expert" and k == 0 else None
        h, gap = _block(variables[name]["params"], h, skip=skip)
        gaps.append(gap[:, :-1])
    logp = _head_logprobs(variables["head"]["params"], h[:, :-1], ids[:, 1:])
    return logp, jnp.min(jnp.stack(gaps), 0)


def next_token_logprobs(variables, ids, fault=""):
    """(b, s - 1) float32; ``fault="drop_block"`` leaves the first
    block out, ``"drop_expert"`` expert 0 of the first mixture layer
    (the comparison's self-tests)."""
    return _forward(variables, ids, fault)[0]


def next_token_logprobs_vouched(variables, ids, fault=""):
    """The pair ``(logprobs, vouched)``, both (b, s - 1): ``vouched``
    is false where a router came within ``MARGIN`` of another choice."""
    logp, gap = _forward(variables, ids, fault)
    return logp, gap >= MARGIN
