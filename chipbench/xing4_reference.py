"""Plain reference of Xing4.0-29B-A4B's decoder (``xing4_0``) as
``configs/xing4.0-29b-a4b.json`` cuts it: every layer's equations
(``XING4.md``) in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision, the whole sequence at once under a plain
causal mask. No kernels, no cache, no absorbed form (K and V are
EXPANDED a head), no sorting, no grouped product, Sinkhorn as written.
It reads the program's parameter tree and nothing else of the program;
what a tree cannot say (the rotation's base and YaRN's constants, the
norms' eps, mHC's constants, the router's) is ``ARCH`` below, the
published values. The expert layer is K-EXAONE's, function for
function (``k_exaone_reference._experts``: DeepSeek-V3's router with
the held experts' gap to the choice).

Per layer, x in R^(n x C), every projection without bias:

    mHC around each sub-layer F (attention, then MLP):
      r = RMSNorm_nC(vec(x));  h = r phi                  (n + n + n n)
      H_pre = sigmoid(a0 h_pre + b_pre);  H_post = 2 sigmoid(a1 h_post + b_post)
      H_res = Sinkhorn(exp(clamp(a2 h_res + b_res)))  (columns, then
              rows, ``hc_iters`` times, eps in each denominator)
      u = H_pre x;   x' = H_res x + H_post^T F(RMSNorm_C(u))
    attention:
      c_q = RMSNorm(u W_qa);  q_h = c_q W_qb = [q_nope | q_rope]
      [c_kv | k_r] = u W_kva;  c_kv <- RMSNorm(c_kv)
      q_rope, k_r rotated (rotate-half, YaRN's frequencies)
      k_h = [c_kv W_UK,h | k_r];  v_h = c_kv W_UV,h
      a = concat_h softmax(q_h k_h^T scale) v_h W_o
    MLP: gated SiLU (dense layers) or the routed experts + the shared.

The pass returns ``(logprobs, vouched)``: a position is NOT vouched
where, in some sparse layer, an expert held here came within that
layer's margin (``MARGINS``) of changing sides.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.k_exaone_reference import _experts, _gated, _rms

F32 = jnp.float32

#: What the parameter tree cannot say: the published constants.
ARCH = dict(
    rope_base=10000.0,  # rope_theta
    yarn=dict(factor=64.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
              mscale=1.0, mscale_all_dim=1.0),  # rope_scaling
    eps=1e-6,  # rms_norm_eps
    hc_eps=1e-6, hc_iters=20, hc_clamp=(-30.0, 30.0),
    top_k=4,  # num_experts_per_tok
    scale=2.0,  # routed_scaling_factor
    held_first=0,  # the first expert of this chip's share
    #: A dtype name: what the served model keeps in its own type
    #: between operations (``_layer``) is rounded to it. None in the
    #: reference proper; the precision reading of
    #: ``scripts/xing4_limits.py``.
    round_to=None,
    scores=None,  # ``k_exaone_reference._experts`` reads the key
)

#: One margin a sparse layer, by its ordinal, in the units of
#: ``_experts``' gap (the configuration's ``correct.why`` has the
#: readings they are set from).
MARGINS = (0.02, 0.06, 0.135, 0.18)

CONTROLS = ("drop_block", "drop_expert", "drop_rope", "one_stream")


def margins(layers: int):
    return tuple(MARGINS[min(n, len(MARGINS) - 1)] for n in range(layers))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(qk_dim: int, yarn: dict) -> float:
    """``qk_dim ** -0.5 x yarn_mscale(factor, mscale_all_dim) ** 2``
    (DeepSeek-V3's): 0.14468 at 192, factor 64."""
    return qk_dim ** -0.5 * yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]
    ) ** 2


def yarn_inv_freq(dim: int, base: float, yarn: dict):
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` frequencies,
    line for line."""

    def correction_dim(rotations):
        return (dim * math.log(
            yarn["original_max"] / (rotations * 2 * math.pi)
        )) / (2 * math.log(base))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inter = extra / yarn["factor"]
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _rotate(x, inv_freq, mscale):
    """Rotate-half over (b, s, ..., d) at positions 0..s-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq  # (s, half)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=("arch", "drop_rope", "cache_as"))
def _attention(p, u, arch, drop_rope, cache_as=None):
    arch = dict(arch)
    yarn, eps = dict(arch["yarn"]), arch["eps"]
    c_q = _rms(u @ p["q_a"]["kernel"].astype(F32), p["q_norm"]["scale"], eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, p["q_b"]["kernel"].astype(F32))
    kv = u @ p["kv_a"]["kernel"].astype(F32)
    w = p["kv_b"].astype(F32)  # (kv_rank, heads, nope + v)
    rank = w.shape[0]
    # The out projection reads heads x v values: what is left of
    # kv_b's width is the part of q and k that does not rotate.
    nope = w.shape[2] - p["out"]["kernel"].shape[0] // w.shape[1]
    c_kv = _rms(kv[..., :rank], p["kv_norm"]["scale"], eps)
    rope = q.shape[-1] - nope
    inv = yarn_inv_freq(rope, arch["rope_base"], yarn)
    m = yarn_mscale(yarn["factor"], yarn["mscale"]) / yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]
    )
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], inv, m)
    k_r = _rotate(kv[..., rank:], inv, m)  # (b, s, rope): one for all heads
    if cache_as:  # the precision reading: the cache's rows in that type
        c_kv = c_kv.astype(cache_as).astype(F32)
        k_r = k_r.astype(cache_as).astype(F32)
    k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w[..., :nope])
    v = jnp.einsum("bsr,rhv->bshv", c_kv, w[..., nope:])
    scores = jnp.einsum("bqhn,bjhn->bhqj", q_nope, k_nope)
    if not drop_rope:  # the control: the shared rotated part left out
        scores = scores + jnp.einsum("bqhr,bjr->bhqj", q_rope, k_r)
    scores = scores * softmax_scale(q.shape[-1], yarn)
    s = u.shape[1]
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    o = jnp.einsum("bhqj,bjhv->bqhv", jax.nn.softmax(scores, -1), v)
    return o.reshape(*o.shape[:2], -1) @ p["out"]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=("arch", "one_stream"))
def _hyper(p, x, arch, one_stream):
    """-> (u, H_post, H_res) of one sub-layer over x (b, s, n, C)."""
    arch = dict(arch)
    n, eps = x.shape[-2], arch["hc_eps"]
    r = _rms(x.reshape(*x.shape[:2], -1), p["norm"]["scale"], eps)
    h = r @ p["phi"].astype(F32)
    a, b = p["a"].astype(F32), p["b"].astype(F32)
    pre = a[0] * h[..., :n] + b[:n]
    post = a[1] * h[..., n: 2 * n] + b[n: 2 * n]
    res = jnp.clip(a[2] * h[..., 2 * n:] + b[2 * n:], *arch["hc_clamp"])
    m = jnp.exp(res.reshape(*res.shape[:-1], n, n))
    if one_stream:  # the control: Sinkhorn skipped, H_res the identity
        m = jnp.broadcast_to(jnp.eye(n, dtype=F32), m.shape)
    else:
        for _ in range(arch["hc_iters"]):
            m = m / (m.sum(-2, keepdims=True) + eps)  # columns
            m = m / (m.sum(-1, keepdims=True) + eps)  # rows
    u = jnp.einsum("bsn,bsnc->bsc", jax.nn.sigmoid(pre), x)
    return u, 2.0 * jax.nn.sigmoid(post), m


def _merge(x, f, h_post, h_res):
    return jnp.einsum("bsij,bsjc->bsic", h_res, x) + (
        h_post[..., None] * f[..., None, :]
    )


def _frozen(arch: dict):
    """``arch`` as a static argument: hashable, dicts and all."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in arch.items() if k not in ("scores", "round_to")
    ))


def _layer(p, x, arch, fault):
    """-> (y, gap): ``gap`` (b, s) of ``_experts``; None from a dense
    layer. ``arch["round_to"]``: what the served model keeps in its
    own type between operations (a sub-layer's input and output, the
    cache rows) is rounded to that type instead; the streams stay
    float32, as served."""
    with jax.default_matmul_precision("highest"):
        key, eps = _frozen(arch), arch["eps"]
        one = fault == "one_stream"

        def lower(t):
            to = arch["round_to"]
            return t.astype(to).astype(F32) if to else t

        u, h_post, h_res = _hyper(p["hc_attn"], x, key, one)
        a = _attention(
            p["attn"], _rms(lower(u), p["ln1"]["scale"], eps), key,
            fault == "drop_rope", arch["round_to"],
        )
        x = _merge(x, lower(a), h_post, h_res)
        u, h_post, h_res = _hyper(p["hc_mlp"], x, key, one)
        h = _rms(lower(u), p["ln2"]["scale"], eps)
        if "experts" in p:
            f, gap = _experts(p["experts"], h, arch, fault == "drop_expert")
        else:
            f = _gated(
                h, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                p["mlp_out"]["kernel"],
            )
            gap = None
        return _merge(x, lower(f), h_post, h_res), gap


def hidden_states(variables, ids, fault="", arch=None):
    """The streams (b, s, n, C) after the last block, and (sparse
    layers, b, s) the gap of ``_experts`` in each sparse layer by its
    ordinal (infinite in a layer a fault left out)."""
    if fault not in ("",) + CONTROLS:
        raise ValueError(f"unknown fault {fault!r}")
    arch = {**ARCH, **(arch or {})}
    ids = jnp.asarray(ids, jnp.int32)
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    x = variables["embed"]["params"]["tok"]["embedding"].astype(F32)[ids]
    n = variables[names[0]]["params"]["hc_attn"]["norm"]["scale"].shape[0] // (
        x.shape[-1]
    )
    x = jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], n, x.shape[-1]))
    sparse = [n_ for n_ in names if "experts" in variables[n_]["params"]]
    gaps = []
    for name in names:
        p = variables[name]["params"]
        if fault == "drop_block" and name == sparse[0]:
            gaps.append(jnp.full(ids.shape, jnp.inf))
            continue  # a served model one (sparse) block short
        x, g = _layer(p, x, arch, fault)
        if g is not None:
            gaps.append(g)
    return x, jnp.stack(gaps)


def vouched(gaps):
    """(sparse layers, b, s) gaps -> (b, s) bool: no sparse layer had
    a held expert within that layer's margin of changing sides."""
    held_to = jnp.asarray(margins(gaps.shape[0]), F32)[:, None, None]
    return (gaps >= held_to).all(0)


def logprobs_and_gaps(variables, ids, fault="", arch=None):
    """``(logprobs, gaps)``: (b, s - 1) the log-probability the model
    gives ``ids[:, t + 1]`` after reading ``ids[:, : t + 1]`` over the
    vocabulary slice held here, and (sparse layers, b, s - 1) position
    t's gap in each sparse layer. ``fault`` is the self-test of the
    comparison built on this, each a served model gone wrong as it
    looks from here: ``drop_block`` (the first sparse block left out),
    ``drop_expert`` (of every sparse block, the busiest held expert
    left out), ``drop_rope`` (the shared rotated key part left out of
    the scores: a cache that kept ``c_kv`` alone), ``one_stream``
    (``H_res`` the identity: Sinkhorn skipped, the streams never
    mixed). ``arch`` overrides entries of ``ARCH`` (tests at small
    sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    x, gaps = hidden_states(variables, ids, fault, arch)
    p = variables["head"]["params"]
    with jax.default_matmul_precision("highest"):
        x = _rms(
            x.sum(-2)[:, :-1], p["ln"]["scale"],
            {**ARCH, **(arch or {})}["eps"],
        )
        logits = x @ p["logits"]["kernel"].astype(F32)
    logp = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return logp, gaps[..., :-1]


def next_token_logprobs(variables, ids, fault="", arch=None):
    """``(logprobs, vouched)``: :func:`logprobs_and_gaps` with each
    sparse layer's gap held to its margin."""
    logp, gaps = logprobs_and_gaps(variables, ids, fault, arch)
    return logp, vouched(gaps)
