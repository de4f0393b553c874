"""Plain reference of DeepSeek-V3.2-Exp's decoder (``deepseek_v32``) as
``configs/deepseek-v3.2-exp.json`` cuts it: every layer's equations
(``DEEPSEEK_V32.md``) in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision, the whole sequence at once: the lightning
indexer's scores of every query against every earlier key, a plain
top-k over them, the mask ADDED to the attention's scores, full softmax
over EXPANDED keys and values (no absorption, no cache, no pages, no
threshold by bisection), the rotation written out from positions, every
held expert meeting every token under a mask. Its own copy of
everything: it imports nothing of the program and no other reference,
and reads the program's parameter tree alone: which layer is which is
what its subtree holds (``experts`` or ``mlp_gate``), every width is a
shape; what a tree cannot say is ``ARCH`` below, the published values.

Per layer, ``N`` an RMS norm with a learned scale, no projection bias:

    u = N1(x)
    c_q = N(W_qa u);  q_h = W_qb c_q = [q_nope | q_rope];  [c | k_r] = W_kva u
    c <- N(c);  k_h = [W_UK,h c | rope(k_r)],  v_h = W_UV,h c
    qI_j = (W_iq c_q)_j;  kI = LayerNorm(W_ik u)   (scale and bias)
    rope on the first 64 of the 128 values of qI_j and kI
    w_j = (W_iw u)_j 64^-1/2 128^-1/2
    I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)),  s <= t
    S(t) = the min(2048, t + 1) positions of largest I(t, .)
    m = W_o softmax_{s in S(t)}(q k^T * 0.1352) v
    h = x + m;  y = h + F(N2(h))
    F: down(silu(gate) * up), the dense MLP of a leading layer, or the
    routed experts held here (sigmoid scores; of 8 groups of 32 the 4
    whose two largest score + bias sum highest; in them the 8 largest
    score + bias; weights the scores normalised over the 8, times 2.5)
    plus the shared expert.

The pass returns ``(logprobs, vouched)``: a position is NOT vouched
where, in some sparse layer, a held expert, or a group at the cut
between the groups kept and dropped, came within that layer's margin
(``MARGINS``) of changing sides. A flip at the 2,048th index score is
NOT kept out (``correct.why``): its effect belongs in the tolerance.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: What the parameter tree cannot say: the published constants.
ARCH = dict(
    eps=1e-6,  # rms_norm_eps (the index key's LayerNorm too)
    top_k=8,  # num_experts_per_tok
    scale=2.5,  # routed_scaling_factor
    n_group=8,
    topk_group=4,
    held_first=0,  # the first expert of this chip's share
    index_topk=2048,
    rope_base=10000.0,  # rope_theta
    yarn=dict(factor=40.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
              mscale=1.0, mscale_all_dim=1.0),
    #: A dtype name: every sub-layer's output, every layer's output and
    #: what a position CACHES (its latent row and its index key) are
    #: rounded to it. None in the reference proper; the precision
    #: reading (what the served model keeps in bfloat16, kept in less).
    round_to=None,
)
#: The published index key is 128 wide. A tree whose key is narrower is
#: the configuration's ``rehearse`` block (toy widths on a CPU), which
#: keeps this many positions a query so that its contexts of 239 to 429
#: select (at fewer, one flipped position of 48 moved a toy logit by 0.6): laid over ``ARCH`` there and nowhere else.
REHEARSAL = dict(index_topk=192)
PUBLISHED_INDEX_DIM = 128

#: One margin a SPARSE layer, by its ordinal, in the units of
#: :func:`_experts`' gap (``correct.why`` has the readings).
MARGINS = (0.072, 0.105, 0.105, 0.105)

#: Every fault this reference knows: a served model gone wrong, as it
#: looks from here.
FAULTS = ("drop_block", "drop_expert", "drop_selection")
#: The reference in the precision BELOW the one the configuration
#: states, as a fault of its own.
PRECISION = {"outputs_float8": dict(round_to="float8_e4m3fn")}
CONTROLS = (*FAULTS, *PRECISION)

#: Positions a block of queries (attention and indexer) and of the MLPs
#: covers, and columns a block of the dense MLP's hidden width: beside a
#: served model's weights and pool the float32 temporaries of a whole
#: long row are what a chip has no room for.
BLOCK = 256
COLUMNS = 4608
HEADS = 32  # heads whose expanded K and V stand at a time


def margins(layers: int):
    return tuple(MARGINS[min(n, len(MARGINS) - 1)] for n in range(layers))


def _w(p, name):
    return p[name]["kernel"].astype(F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (
        scale.astype(F32)
    )


def _layer_norm(x, p, eps):
    x = x - x.mean(-1, keepdims=True)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return x * p["scale"].astype(F32) + p["bias"].astype(F32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(qk_dim: int, yarn: dict) -> float:
    """``qk_dim ** -0.5 x yarn_mscale(factor, mscale_all_dim) ** 2``:
    0.1352 at 192, factor 40."""
    return qk_dim ** -0.5 * yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]
    ) ** 2


def yarn_inv_freq(dim: int, base: float, yarn: dict):
    """DeepSeek-V3's YaRN frequencies (``find_correction_range`` and a
    linear ramp between the interpolated and the plain ones)."""

    def correction_dim(rotations):
        return (dim * math.log(
            yarn["original_max"] / (rotations * 2 * math.pi)
        )) / (2 * math.log(base))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0, 1
    )
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def _rotate(x, inv_freq, mscale):
    """Rotate-half over (b, s, ..., d) at positions 0..s-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq  # (s, half)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=("arch", "select"))
def _attention(p, u, arch, select=True):
    """Latent attention with K and V EXPANDED a head, a block of
    queries against every key; ``select``: each query reads the
    ``index_topk`` positions its index scores rank highest (else every
    position before it: the ``drop_selection`` control)."""
    arch = dict(arch)
    yarn, eps = dict(arch["yarn"]), arch["eps"]

    def cached(t):  # a cache plane in the precision reading's type
        to = arch["round_to"]
        return t.astype(to).astype(F32) if to else t

    c_q = _rms(u @ _w(p, "q_a"), p["q_norm"]["scale"], eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, _w(p, "q_b"))
    kv = u @ _w(p, "kv_a")
    w = p["kv_b"].astype(F32)  # (kv_rank, heads, nope + v)
    rank, heads = w.shape[:2]
    # The out projection reads heads x v values: what is left of
    # kv_b's width is the part of q and k that does not rotate.
    nope = w.shape[2] - p["out"]["kernel"].shape[0] // heads
    rope = q.shape[-1] - nope
    c_kv = cached(_rms(kv[..., :rank], p["kv_norm"]["scale"], eps))
    inv = yarn_inv_freq(rope, arch["rope_base"], yarn)
    m = yarn_mscale(yarn["factor"], yarn["mscale"]) / yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]
    )
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], inv, m)
    k_r = cached(_rotate(kv[..., rank:], inv, m))  # (b, s, rope): one for all

    def first_rotated(t):  # the first `rope` values of the last axis
        return jnp.concatenate(
            [_rotate(t[..., :rope], inv, m), t[..., rope:]], -1
        )

    q_i = first_rotated(jnp.einsum("bsr,rjd->bsjd", c_q, _w(p, "index_q")))
    k_i = cached(first_rotated(
        _layer_norm(u @ _w(p, "index_k"), p["index_k_norm"], eps)
    ))
    w_i = (u @ _w(p, "index_w")) * (q_i.shape[2] ** -0.5 * q_i.shape[3] ** -0.5)
    s = u.shape[1]
    keep = min(arch["index_topk"], s)
    blocks = [
        jnp.arange(lo, min(lo + BLOCK, s)) for lo in range(0, s, BLOCK)
    ]
    masks = []  # a block of queries: 0 where it reads a position, else -inf
    for at in blocks:
        mask = jnp.broadcast_to(jnp.where(
            jnp.arange(s)[None, :] <= at[:, None], 0.0, -jnp.inf
        ), (u.shape[0], at.shape[0], s))
        if select:
            index = jnp.einsum(
                "bqj,bqjs->bqs", w_i[:, at], jax.nn.relu(
                    jnp.einsum("bqjd,bsd->bqjs", q_i[:, at], k_i)
                ),
            ) + mask
            kth = jax.lax.top_k(index, keep)[0][..., -1:]
            mask = jnp.where(index >= kth, mask, -jnp.inf)
        masks.append(mask)
    out = 0.0
    for h0 in range(0, heads, HEADS):  # K and V of a group of heads
        hs = slice(h0, h0 + HEADS)
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w[:, hs, :nope])
        v = jnp.einsum("bsr,rhv->bshv", c_kv, w[:, hs, nope:])
        o = []
        for at, mask in zip(blocks, masks):
            scores = jnp.einsum(
                "bqhn,bjhn->bhqj", q_nope[:, at, hs], k_nope
            ) + jnp.einsum("bqhr,bjr->bhqj", q_rope[:, at, hs], k_r)
            scores = scores * softmax_scale(q.shape[-1], yarn) + mask[:, None]
            o.append(
                jnp.einsum("bhqj,bjhv->bqhv", jax.nn.softmax(scores, -1), v)
            )
        o = jnp.concatenate(o, axis=1)
        w_o = _w(p, "out").reshape(heads, -1, u.shape[-1])[hs]
        out = out + jnp.einsum("bshv,hvd->bsd", o, w_o)
    return out


def _gated(h, gate, up, down):
    """One gated SiLU MLP, ``COLUMNS`` of its hidden width at a time."""
    out = 0.0
    for lo in range(0, gate.shape[1], COLUMNS):
        at = slice(lo, lo + COLUMNS)
        out = out + (
            jax.nn.silu(h @ gate[:, at].astype(F32))
            * (h @ up[:, at].astype(F32))
        ) @ down[at].astype(F32)
    return out


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "held_first", "n_group", "topk_group", "drop_expert",
))
def _experts(p, h, top_k, scale, held_first, n_group, topk_group,
             drop_expert=False):
    """-> (F(h), gap): the routed experts held here plus the shared
    one, and how near the choice came to falling otherwise for a held
    expert. ``drop_expert``: the busiest held expert's term (of the
    block of positions) is left out.

    The gap. Rounding moves every router LOGIT by about the same
    amount, so it moves a difference of two experts' ``score + bias``
    by about ``hypot`` of the two sigmoids' slopes times as much: the
    distance of a pair is that difference over that ``hypot``. It is
    taken over (a) every held, eligible expert against every eligible
    expert on the other side of the choice, and (b) the weakest group
    kept against the strongest group dropped (a group's score the sum
    of its two largest ``score + bias``, its slope the ``hypot`` of
    those two experts' slopes), counted where the choice under the two
    changing places would put a held expert on the other side: a group
    that changes sides changes which experts compete, and with them the
    bar a held expert clears. (Counted at EVERY kept / dropped pair it
    kept out nine positions of ten on the chip: eight groups cut four
    to four lie within a margin of each other at nearly half the
    positions of a layer, mostly with nothing held at stake.)"""
    s = jax.nn.sigmoid(h @ p["router"].astype(F32))  # (b, s, E)
    chosen_by = s + p["router_bias"].astype(F32)
    slope = s * (1 - s)
    n_exp = s.shape[-1]
    grouped = chosen_by.reshape(*s.shape[:2], n_group, -1)
    two, two_at = jax.lax.top_k(grouped, 2)
    g_score = two.sum(-1)  # (b, s, groups)
    g_slope = jnp.sqrt((jnp.take_along_axis(
        slope.reshape(grouped.shape), two_at, -1
    ) ** 2).sum(-1))
    g_kth = jax.lax.top_k(g_score, topk_group)[0][..., -1:]
    g_kept = g_score >= g_kth
    per = n_exp // n_group

    def chosen_under(kept):
        eligible = jnp.repeat(kept, per, axis=-1)
        limited = jnp.where(eligible, chosen_by, -jnp.inf)
        return eligible, limited, (
            limited >= jax.lax.top_k(limited, top_k)[0][..., -1:]
        )

    eligible, limited, inside = chosen_under(g_kept)
    held = p["w_gate"].shape[0]
    experts = jnp.arange(n_exp)
    held_here = (experts >= held_first) & (experts < held_first + held)
    g_gap = jnp.full(s.shape[:2], jnp.inf)
    if topk_group < n_group:
        # The nearest other grouping: the weakest group kept and the
        # strongest dropped change places. It counts where a held
        # expert would then be on the other side of the choice.
        order = jnp.argsort(-g_score, axis=-1)
        last, first = order[..., topk_group - 1], order[..., topk_group]

        def at(t, g):
            return jnp.take_along_axis(t, g[..., None], -1)[..., 0]

        near = jnp.abs(at(g_score, last) - at(g_score, first)) / jnp.hypot(
            at(g_slope, last), at(g_slope, first)
        )
        groups = jnp.arange(n_group)
        swapped = (g_kept & (groups != last[..., None])) | (
            groups == first[..., None]
        )
        moved = (held_here & (inside != chosen_under(swapped)[2])).any(-1)
        g_gap = jnp.where(moved, near, jnp.inf)
    apart = jnp.abs(chosen_by[..., :, None] - chosen_by[..., None, :]) / (
        jnp.hypot(slope[..., :, None], slope[..., None, :])
    )  # (b, s, e, c)
    across = (inside[..., :, None] != inside[..., None, :]) & (
        eligible[..., :, None] & eligible[..., None, :]
    )
    gap = jnp.minimum(g_gap, jnp.where(
        across & held_here[:, None], apart, jnp.inf
    ).min((-1, -2)))
    picked = jax.lax.top_k(limited, top_k)[1]  # (b, s, k)
    w = jnp.take_along_axis(s, picked, -1)
    w = scale * w / w.sum(-1, keepdims=True)
    out = _gated(
        h, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )
    mine = picked[..., None] == held_first + jnp.arange(held)
    dropped = mine.sum((0, 1, 2)).argmax() if drop_expert else -1
    for e in range(held):  # one upcast at a time
        w_e = jnp.where(mine[..., e], w, 0.0).sum(-1, keepdims=True)
        w_e = jnp.where(e == dropped, 0.0, w_e)
        out = out + w_e * _gated(h, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return out, gap


def _frozen(arch: dict):
    """``arch`` as a static argument: hashable, dicts and all."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in arch.items()
    ))


def _blocks(f, v):
    """``f`` a block of positions at a time: rows of an MLP share
    nothing."""
    parts = [f(v[:, lo: lo + BLOCK]) for lo in range(0, v.shape[1], BLOCK)]
    if isinstance(parts[0], tuple):
        return tuple(jnp.concatenate(t, axis=1) for t in zip(*parts))
    return jnp.concatenate(parts, axis=1)


def _layer(p, x, arch, fault):
    """-> (y, gap): ``gap`` (b, s) of ``_experts``; None from a dense
    layer."""
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]

        def lower(t):
            to = arch["round_to"]
            return t.astype(to).astype(F32) if to else t

        m = _attention(
            p["attn"], _rms(x, p["ln1"]["scale"], eps), _frozen(arch),
            fault != "drop_selection",
        )
        h = x + lower(m)
        v = _rms(h, p["ln2"]["scale"], eps)
        if "experts" in p:
            f, gap = _blocks(lambda t: _experts(
                p["experts"], t, arch["top_k"], arch["scale"],
                arch["held_first"], arch["n_group"], arch["topk_group"],
                fault == "drop_expert",
            ), v)
        else:
            f, gap = _blocks(lambda t: _gated(
                t, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                p["mlp_out"]["kernel"],
            ), v), None
        return lower(h + lower(f)), gap


def hidden_states(variables, ids, fault="", arch=None):
    """The final hidden states (b, s, d) before the head's norm, and
    (sparse layers, b, s) the gap of ``_experts`` in each sparse layer
    (infinite in one a fault left out)."""
    if fault not in ("", *FAULTS, *PRECISION):
        raise ValueError(f"unknown fault {fault!r}")
    ids = jnp.asarray(ids, jnp.int32)
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    narrow = variables[names[0]]["params"]["attn"]["index_k"][
        "kernel"
    ].shape[1] < PUBLISHED_INDEX_DIM
    arch = {
        **ARCH, **(REHEARSAL if narrow else {}), **(arch or {}),
        **PRECISION.get(fault, {}),
    }
    x = variables["embed"]["params"]["tok"]["embedding"].astype(F32)[ids]
    gaps = []
    for i, name in enumerate(names):
        p = variables[name]["params"]
        if fault == "drop_block" and i == 1:
            # a served model one block short (a sparse layer here)
            if "experts" in p:
                gaps.append(jnp.full(ids.shape, jnp.inf))
            continue
        x, g = _layer(p, x, arch, fault)
        if g is not None:
            gaps.append(g)
    return x, jnp.stack(gaps)


def vouched(gaps):
    """(sparse layers, b, s) gaps -> (b, s) bool: no layer's choice came
    within that layer's margin of falling otherwise."""
    held_to = jnp.asarray(margins(gaps.shape[0]), F32)[:, None, None]
    return (gaps >= held_to).all(0)


def logprobs_and_gaps(variables, ids, fault="", arch=None):
    """``(logprobs, gaps)``: (b, s - 1) the log-probability the model
    gives ``ids[:, t + 1]`` after reading ``ids[:, : t + 1]`` over the
    vocabulary slice held here, and (sparse layers, b, s - 1) position
    t's gap in each sparse layer. ``fault`` is the self-test of the
    comparison built on this, each a served model gone wrong as it
    looks from here: ``drop_block`` (the second block left out),
    ``drop_expert`` (the busiest held expert's term left out of every
    sparse layer, a block of positions at a time), ``drop_selection`` (every query attends every position
    before it: a served model whose indexer selects nothing), or a
    precision reading (``PRECISION``). ``arch`` overrides entries of
    ``ARCH`` (tests at small sizes). Computed a ROW at a time: rows
    share nothing, and the float32 temporaries of three long rows at
    once are what a chip has no room for beside a served model."""
    ids = jnp.asarray(ids, jnp.int32)
    parts = [
        _logprobs_and_gaps(variables, ids[r: r + 1], fault, arch)
        for r in range(ids.shape[0])
    ]
    return (
        jnp.concatenate([p[0] for p in parts]),
        jnp.concatenate([p[1] for p in parts], axis=1),
    )


def _logprobs_and_gaps(variables, ids, fault, arch):
    x, gaps = hidden_states(variables, ids, fault, arch)
    p = variables["head"]["params"]
    with jax.default_matmul_precision("highest"):
        x = _rms(
            x[:, :-1], p["ln"]["scale"], {**ARCH, **(arch or {})}["eps"]
        )
        head, nxt = _w(p, "logits"), ids[:, 1:]
        logp = jnp.concatenate([  # a block of positions' logits at a time
            jnp.take_along_axis(
                jax.nn.log_softmax(x[:, lo: lo + BLOCK] @ head, -1),
                nxt[:, lo: lo + BLOCK, None], -1,
            )[..., 0]
            for lo in range(0, x.shape[1], BLOCK)
        ], axis=1)
    return logp, gaps[..., :-1]


def next_token_logprobs(variables, ids, fault="", arch=None):
    """``(logprobs, vouched)``: :func:`logprobs_and_gaps` with each
    sparse layer's gap held to its margin."""
    logp, gaps = logprobs_and_gaps(variables, ids, fault, arch)
    return logp, vouched(gaps)
