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
where, in some sparse layer, an expert held here came within that
layer's margin (``margins``) of changing sides: a served model in
bfloat16 may rightly have put it on the other side. How near is
``_experts``' gap: the difference of selection scores (with bias)
between the held expert and any expert across the choice, over what
unit noise on both their router LOGITS moves that difference by
(rounding moves a logit by about the same amount whatever the expert,
a score near 1 by much less than one near 1/2, and the difference by
both). The margin grows with the sparse layer's ordinal, as the served
bfloat16 hidden state drifts from the float32 one with depth (the
readings: the configuration's ``correct.why``).
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
    #: ``{ordinal: mask}``: in the sparse layer of that ordinal, at the
    #: positions of the (b, s) bool mask, the held expert nearest the
    #: bar is put on its other side (``_experts``). None in the
    #: reference proper; how ``kexaone_flips.py`` and the tests make
    #: the served model's flipped choice.
    flip=None,
    #: A list: every sparse layer appends its ``(scores, scores +
    #: bias)``, each (b, s, experts). None in the reference proper;
    #: what ``kexaone_flips.py`` keeps of a run.
    scores=None,
)

#: One margin a sparse layer, by its ordinal, in the units of
#: ``_experts``' gap (set on the chip, PR 41: the configuration's
#: ``correct.why`` has the readings). A held expert nearer than this
#: to changing sides is one a lower precision may put across.
MARGINS = (0.05, 0.09, 0.09, 0.17)
#: What ``scripts/kexaone_limits.py`` holds its one smallest gap to:
#: the widest (goes with that script and ``logprobs_and_gap``).
MARGIN = max(MARGINS)


def margins(layers: int):
    """One margin a sparse layer; a layer deeper than the readings
    went takes the deepest one's."""
    return tuple(MARGINS[min(n, len(MARGINS) - 1)] for n in range(layers))


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


def _experts(p, h, arch, drop_expert, flip=None):
    """-> (F(h), gap): ``gap`` (b, s) is how near a held expert came
    to changing sides (below). ``drop_expert``: the busiest held
    expert is left out. ``flip`` (b, s) bool: where true, the held
    expert nearest to changing sides is put on the other side (chosen
    -> left out and the first out taken in; left out -> chosen and the
    last in dropped), as a served model does whose rounding moved it
    across.

    The gap. A held expert e changes sides when some expert c on the
    other side of the choice passes it: when the sign of ``chosen_by_e
    - chosen_by_c`` turns. Rounding moves every router LOGIT by about
    the same amount whatever the expert, so it moves that difference
    by about ``hypot(s_e (1 - s_e), s_c (1 - s_c))`` times as much
    (the sigmoid's slope at each; the two moves independent). The gap
    of e is the smallest, over the c across, of the difference over
    that factor: the distance in units of the logits' own noise. It
    counts BOTH slopes: a held expert whose sigmoid is saturated
    barely moves, and is still passed by a neighbour at the middle of
    its sigmoid that does. (Until PR 41 the distance was to the bar,
    over e's own slope alone: the flips read on the chip in the last
    sparse layer then reached 0.157 and 0.131 with the rest under 0.1,
    and reach 0.062 here, the rest under 0.055: this distance ranks
    them better, so a margin keeps fewer sound positions out.)"""
    k = arch["top_k"]
    s = jax.nn.sigmoid(h @ p["router"].astype(F32))  # (b, s, E)
    chosen_by = s + p["router_bias"].astype(F32)
    ranked = -jnp.sort(-chosen_by, axis=-1)
    first, held = arch["held_first"], p["w_gate"].shape[0]
    experts = jnp.arange(s.shape[-1])
    held_here = (experts >= first) & (experts < first + held)
    inside = chosen_by >= ranked[..., k - 1: k]  # the chosen
    slope = s * (1 - s)
    apart = jnp.abs(chosen_by[..., :, None] - chosen_by[..., None, :]) / (
        jnp.hypot(slope[..., :, None], slope[..., None, :])
    )  # (b, s, e, c)
    across = inside[..., :, None] != inside[..., None, :]
    dist = jnp.where(
        across & held_here[:, None], apart, jnp.inf
    ).min(-1)  # (b, s, E): infinite for an expert not held
    gap = dist.min(-1)
    if arch["scores"] is not None:
        arch["scores"].append((s, chosen_by))
    if flip is not None:
        nearest = experts == dist.argmin(-1)[..., None]
        chosen_by = jnp.where(
            nearest & jnp.asarray(flip)[..., None],
            jnp.where(inside, -jnp.inf, jnp.inf), chosen_by,
        )
    picked = jnp.argsort(-chosen_by, axis=-1)[..., :k]  # (b, s, k)
    w = jnp.take_along_axis(s, picked, -1)
    w = arch["scale"] * w / w.sum(-1, keepdims=True)
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


def _layer(p, x, window, rotate, arch, drop_expert, flip=None):
    """-> (y, gap): ``gap`` (b, s) of ``_experts``; None from a dense
    layer."""
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]
        a = _attention(
            p["attn"], x, window, rotate, eps, arch["rope_base"]
        )
        h = x + _rms(a, p["ln1"]["scale"], eps)
        if "experts" in p:
            f, gap = _experts(p["experts"], h, arch, drop_expert, flip)
        else:
            f = _gated(
                h, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                p["mlp_out"]["kernel"],
            )
            gap = None
        y = h + _rms(f, p["ln2"]["scale"], eps)
        if arch["round_to"]:
            y = y.astype(arch["round_to"]).astype(F32)
        return y, gap


def hidden_states(variables, ids, fault="", arch=None):
    """The final hidden states (b, s, d) before the head's norm, and
    (sparse layers, b, s) the gap of ``_experts`` in each sparse layer
    by its ordinal among them (infinite in a layer a fault left out)."""
    if fault not in ("",) + CONTROLS:
        raise ValueError(f"unknown fault {fault!r}")
    arch = {**ARCH, **(arch or {})}
    ids = jnp.asarray(ids, jnp.int32)
    x = variables["embed"]["params"]["tok"]["embedding"].astype(F32)[ids]
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    gaps = []
    pattern = arch["pattern"]
    flip = arch["flip"] or {}
    for i, name in enumerate(names):
        p = variables[name]["params"]
        if fault == "drop_block" and i == 1:
            gaps.append(jnp.full(ids.shape, jnp.inf))
            continue  # a served model one (sparse) block short
        full = pattern[i % len(pattern)] == "G"
        x, g = _layer(
            p, x,
            None if full or fault == "full_window" else arch["window"],
            not full, arch,
            fault == "drop_expert",  # of every sparse layer, one held
            flip.get(len(gaps)),
        )
        if g is not None:
            gaps.append(g)
    return x, jnp.stack(gaps)


def vouched(gaps):
    """(sparse layers, b, s) gaps -> (b, s) bool: no sparse layer had
    a held expert within that layer's margin of its bar."""
    held_to = jnp.asarray(margins(gaps.shape[0]), F32)[:, None, None]
    return (gaps >= held_to).all(0)


def next_token_logprobs(variables, ids, fault="", arch=None):
    """``(logprobs, vouched)``: :func:`logprobs_and_gaps` with each
    sparse layer's gap held to its margin."""
    logp, gaps = logprobs_and_gaps(variables, ids, fault, arch)
    return logp, vouched(gaps)


def logprobs_and_gaps(variables, ids, fault="", arch=None):
    """``(logprobs, gaps)``: (b, s - 1) the log-probability the model
    gives ``ids[:, t + 1]`` after reading ``ids[:, : t + 1]`` over the
    vocabulary slice held here, and (sparse layers, b, s - 1) position
    t's gap in each sparse layer (``_experts``). ``variables`` is the
    program's parameter tree.

    ``fault`` is the self-test of the comparison built on this, each a
    served model gone wrong as it looks from here: ``drop_block`` (the
    second block, a sparse one, left out), ``drop_expert`` (of every sparse
    block, the held expert that got the most tokens left out), ``full_window`` (the window layers
    attend everything, and still rotate: a model that forgot its
    window, or whose window layers kept too few pages). ``arch``
    overrides entries of ``ARCH`` (tests at small sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    x, gaps = hidden_states(variables, ids, fault, arch)
    p = variables["head"]["params"]
    with jax.default_matmul_precision("highest"):
        x = _rms(x[:, :-1], p["ln"]["scale"], {**ARCH, **(arch or {})}["eps"])
        logits = x @ p["logits"]["kernel"].astype(F32)
    logp = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return logp, gaps[..., :-1]


def logprobs_and_gap(variables, ids, fault="", arch=None):
    """:func:`logprobs_and_gaps` with the smallest gap over the sparse
    layers, (b, s - 1): what ``scripts/kexaone_limits.py`` reads."""
    logp, gaps = logprobs_and_gaps(variables, ids, fault, arch)
    return logp, gaps.min(0)
