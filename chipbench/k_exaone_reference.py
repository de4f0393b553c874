"""Plain reference of K-EXAONE-236B-A23B's decoder (``exaone_moe``) as
``configs/k-exaone-236b-a23b.json`` cuts it: every layer's equations in
straightforward ``jax.numpy`` at float32 and ``highest`` matmul
precision, the whole sequence at once under plain masks. No kernels,
no cache, no sorting, no grouped product: every expert held meets
every token and a mask picks. It reads the program's parameter tree
and nothing else of the program; what a tree cannot say (the window,
the layer pattern, the rotation's base, the router's constants) is
``ARCH`` below, the published values.

Per layer, x in R^d, every projection without bias, RMSNorm with a
learned scale:

    q, k, v = W_q x, W_k x, W_v x        (heads x hd; kv_heads x hd)
    q, k    = RMSNorm_hd(q), RMSNorm_hd(k)
    window layer: q, k rotated (rotate-half, base 1e6); a position
                  attends itself and the ``window - 1`` before it
    full layer:   no rotation; a position attends all before it
    a  = W_o softmax(q k^T / sqrt(hd)) v     (query head i, KV head i // g)
    h  = x + RMSNorm(a)
    y  = h + RMSNorm(F(h))
    F  = dense layer:  W_down(silu(W_gate h) * W_up h)
         sparse layer: s = sigmoid(W_r h); I = the top_k of s + b;
                       w_e = scale * s_e / sum_I s; F = sum over the
                       experts of I HELD HERE of w_e E_e(h), plus the
                       shared expert. What the absent experts would
                       add is left out, as in the program.

The pass returns ``(logprobs, vouched)``. A position is NOT vouched
where, in any sparse layer, an expert held here stands within
``MARGIN`` of the bar between chosen and left out: a served model in
bfloat16 may rightly have put it on the other side (two or three
experts can be level there, so every held expert is looked at, not
the last in and the first out alone). The distance is of selection
scores (with bias) in units of the ROUTER'S LOGITS, over the sigmoid's
slope s(1 - s): rounding moves a logit by about the same amount
whatever the expert, and a score near 1 by much less than one near
1/2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: What the parameter tree cannot say: the published constants.
ARCH = dict(
    window=128,  # sliding_window
    pattern="LLLG",  # sliding_window_pattern: G = full attention
    rope_base=1_000_000.0,  # rope_parameters.rope_theta
    eps=1e-5,  # rms_norm_eps
    top_k=8,  # num_experts_per_tok
    scale=2.5,  # routed_scaling_factor
    held_first=0,  # the first expert of this chip's share
    #: A dtype name: every layer's output is rounded to it. None in
    #: the reference proper; the precision reading of
    #: ``scripts/kexaone_limits.py`` (what a served model in a
    #: precision below the stated one looks like from here).
    round_to=None,
)

#: Two experts closer than this, in router logits, are a tie a lower
#: precision may break the other way (set on the chip: the
#: configuration's ``correct.why`` has the readings).
MARGIN = 0.04

CONTROLS = ("drop_block", "drop_expert", "full_window")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (
        scale.astype(F32)
    )


def _rotate(x, base):
    """Rotate-half over (b, s, heads, hd) at positions 0..s-1."""
    half = x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv  # (s, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=("window", "rotate", "eps", "base"))
def _attention(p, x, window, rotate, eps, base):
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["kernel"].astype(F32))
    kv = jnp.einsum("bsd,dthk->tbshk", x, p["kv"]["kernel"].astype(F32))
    k, v = kv[0], kv[1]
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)
    if rotate:  # rotary on window layers only
        q, k = _rotate(q, base), _rotate(k, base)
    s, group = x.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bjhk->bhqj", q, k) / jnp.sqrt(F32(q.shape[-1]))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    scores = jnp.where(seen, scores, -jnp.inf)
    o = jnp.einsum("bhqj,bjhk->bqhk", jax.nn.softmax(scores, -1), v)
    return o.reshape(*o.shape[:2], -1) @ p["out"]["kernel"].astype(F32)


@jax.jit
def _gated(h, gate, up, down):
    g = h @ gate.astype(F32)
    return (jax.nn.silu(g) * (h @ up.astype(F32))) @ down.astype(F32)


def _experts(p, h, arch, drop_expert):
    """-> (F(h), gap): ``gap`` (b, s) is how far, in router logits,
    the nearest expert HELD HERE stood from the bar between chosen and
    left out (midway between the last in and the first out), twice
    that distance: the gap between two experts level on either side of
    it. ``drop_expert``: the busiest held expert is left out."""
    k = arch["top_k"]
    s = jax.nn.sigmoid(h @ p["router"].astype(F32))  # (b, s, E)
    chosen_by = s + p["router_bias"].astype(F32)
    order = jnp.argsort(-chosen_by, axis=-1)
    ranked = jnp.take_along_axis(chosen_by, order, -1)
    picked = order[..., :k]  # (b, s, k)
    w = jnp.take_along_axis(s, picked, -1)
    w = arch["scale"] * w / w.sum(-1, keepdims=True)
    first, held = arch["held_first"], p["w_gate"].shape[0]

    def here(e):
        return (e >= first) & (e < first + held)

    bar = (ranked[..., k - 1: k] + ranked[..., k: k + 1]) / 2
    held_here = here(jnp.arange(s.shape[-1]))
    gap = jnp.where(
        held_here, 2 * jnp.abs(chosen_by - bar) / (s * (1 - s)), jnp.inf
    ).min(-1)
    out = _gated(
        h, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )
    mine = picked[..., None] == first + jnp.arange(held)  # (b, s, k, held)
    dropped = int(mine.sum((0, 1, 2)).argmax()) if drop_expert else -1
    for e in range(held):  # one upcast at a time
        if e == dropped:
            continue
        w_e = jnp.where(mine[..., e], w, 0.0).sum(-1, keepdims=True)
        out = out + w_e * _gated(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        )
    return out, gap


def _layer(p, x, window, rotate, arch, drop_expert):
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]
        a = _attention(
            p["attn"], x, window, rotate, eps, arch["rope_base"]
        )
        h = x + _rms(a, p["ln1"]["scale"], eps)
        if "experts" in p:
            f, gap = _experts(p["experts"], h, arch, drop_expert)
        else:
            f = _gated(
                h, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                p["mlp_out"]["kernel"],
            )
            gap = jnp.full(x.shape[:2], jnp.inf)
        y = h + _rms(f, p["ln2"]["scale"], eps)
        if arch["round_to"]:
            y = y.astype(arch["round_to"]).astype(F32)
        return y, gap


def hidden_states(variables, ids, fault="", arch=None):
    """The final hidden states (b, s, d) before the head's norm, and
    (b, s) the smallest gap of ``_experts`` over the sparse layers."""
    if fault not in ("",) + CONTROLS:
        raise ValueError(f"unknown fault {fault!r}")
    arch = {**ARCH, **(arch or {})}
    ids = jnp.asarray(ids, jnp.int32)
    x = variables["embed"]["params"]["tok"]["embedding"].astype(F32)[ids]
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    gap = jnp.full(ids.shape, jnp.inf)
    pattern = arch["pattern"]
    for i, name in enumerate(names):
        if fault == "drop_block" and i == 1:
            continue  # a served model one (sparse) block short
        full = pattern[i % len(pattern)] == "G"
        x, g = _layer(
            variables[name]["params"], x,
            None if full or fault == "full_window" else arch["window"],
            not full, arch,
            fault == "drop_expert",  # of every sparse layer, one held
        )
        gap = jnp.minimum(gap, g)
    return x, gap


def next_token_logprobs(variables, ids, fault="", arch=None):
    """``(logprobs, vouched)``: :func:`logprobs_and_gap` with the gap
    held to ``MARGIN``."""
    logp, gap = logprobs_and_gap(variables, ids, fault, arch)
    return logp, gap >= MARGIN


def logprobs_and_gap(variables, ids, fault="", arch=None):
    """``(logprobs, gap)``, each (b, s - 1): the log-probability
    the model gives ``ids[:, t + 1]`` after reading ``ids[:, : t + 1]``
    over the vocabulary slice held here, and position t's smallest
    gap over the sparse layers (``_experts``). ``variables`` is the program's parameter tree.

    ``fault`` is the self-test of the comparison built on this, each a
    served model gone wrong as it looks from here: ``drop_block`` (the
    second block, a sparse one, left out), ``drop_expert`` (of every sparse
    block, the held expert that got the most tokens left out), ``full_window`` (the window layers
    attend everything, and still rotate: a model that forgot its
    window, or whose window layers kept too few pages). ``arch``
    overrides entries of ``ARCH`` (tests at small sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    x, gap = hidden_states(variables, ids, fault, arch)
    p = variables["head"]["params"]
    with jax.default_matmul_precision("highest"):
        x = _rms(x[:, :-1], p["ln"]["scale"], {**ARCH, **(arch or {})}["eps"])
        logits = x @ p["logits"]["kernel"].astype(F32)
    logp = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return logp, gap[:, :-1]
