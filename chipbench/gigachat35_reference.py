"""Plain reference of GigaChat3.5-432B-A28B's decoder (``gigachat3_5``)
as ``configs/gigachat3.5-432b-a28b.json`` cuts it: every layer's
equations (``GIGACHAT35.md``) in straightforward ``jax.numpy`` at
float32 and ``highest`` matmul precision, the whole sequence at once:
the delta rule as a SCAN OVER POSITIONS (no chunks, no triangular
solve, no carried convolution tail, no kernel, no state kept between
calls), full softmax attention over EXPANDED keys and values under a
plain causal mask (no absorption, no cache), every held expert meeting
every token under a mask. It reads the program's parameter tree and
nothing else of the program: which layer is which is what its subtree
holds (``attn`` or ``mixer``, ``experts`` or ``mlp_gate``), every width
is a shape; what a tree cannot say (the norms' eps, the gate's factor
2, the clamp, the router's and the rotation's constants) is ``ARCH``
below, the published values.

Per layer, ``N`` an RMS norm with a learned scale, no projection bias:

    GDN layer:  q, k, v = silu(conv1d_causal(W_qkv u))    (width 4, depthwise;
                q, k 32 heads, v 64: value head h reads key head h // 2)
                q = q / |q| * d_k^-1/2;  k = k / |k|      (a head)
                g = -exp(A_log) softplus(W_a u + dt_bias)   ONE a value head
                beta = sigmoid(W_b u)
                S_t = exp(g) (I - beta k k^T) S_{t-1} + beta k v^T
                o_t = S_t^T q
                m = W_o [RMSNorm_head(o) * 2 sigmoid(W_z u)]
    MLA layer:  q = W_qb N(W_qa u);  [c | k_r] = W_kva u;  c <- N(c)
                k_h = [W_UK,h c | rope(k_r)],  v_h = W_UV,h c
                m = W_o [softmax(q k^T * 0.10530) v * sigmoid(W_g u)]
    h = x + N2(m),  u = N1(x);   y = h + N4(F(N3(h)))
    F: down(silu(min(gate, 10)) * clip(up, -10, 10)), the dense MLP of a
    leading layer, or the routed experts held here (sigmoid scores,
    top-8 of score + bias, weights normalised over the 8 times 2.5) plus
    the shared expert, each clamped alike.

The pass returns ``(logprobs, vouched)``: a position is NOT vouched
where, in some sparse layer, an expert held here came within that
layer's margin (``MARGINS``) of changing sides: the gap is
``k_exaone_reference._experts``', in its units.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.k_exaone_reference import _rms
from chipbench.xing4_reference import (
    _rotate,
    softmax_scale,
    yarn_inv_freq,
    yarn_mscale,
)

F32 = jnp.float32

#: What the parameter tree cannot say: the published constants.
ARCH = dict(
    eps=1e-6,  # rms_norm_eps and linear_attn_o_norm_eps
    gate_scale=2.0,  # linear_sigmoid_gate_scale
    limit=10.0,  # swiglu_limit
    top_k=8,  # num_experts_per_tok
    scale=2.5,  # routed_scaling_factor
    held_first=0,  # the first expert of this chip's share
    rope_base=100000.0,  # rope_theta
    yarn=dict(factor=8.0, original_max=32768, beta_fast=32.0, beta_slow=1.0,
              mscale=1.0, mscale_all_dim=1.0),
    #: A dtype name: every sub-layer's normed output and every layer's
    #: output is rounded to it. None in the reference proper; the
    #: precision reading (what the served model keeps in bfloat16, kept
    #: in less).
    round_to=None,
)

#: One margin a SPARSE layer, by its ordinal, in the units of
#: ``k_exaone_reference._experts``' gap (the configuration's
#: ``correct.why`` has the readings they are set from).
MARGINS = (0.045, 0.065, 0.065, 0.065)

#: Every fault this reference knows: a served model gone wrong, as it
#: looks from here. ``no_gate`` (the latent layer's output gate left
#: out) and ``no_clamp`` (no ``swiglu_limit`` anywhere) are readings,
#: not controls (``correct.why``).
FAULTS = ("drop_block", "no_delta", "reset_state", "no_gate", "no_clamp")
#: The reference in the precision BELOW the one the configuration
#: states, as a fault of its own.
PRECISION = {"outputs_float8": dict(round_to="float8_e4m3fn")}
#: What the configuration lists under ``correct.controls``: each must
#: read wrong at every seed.
CONTROLS = ("drop_block", "no_delta", "reset_state", "outputs_float8")

#: Where ``reset_state`` empties a row's states when nobody says: the
#: prompt lengths of the correctness sample (``lm_engine``'s 40, chunk
#: - 17 and chunk + 45 tokens at a prefill chunk of 256).
SAMPLE_RESET = (40, 239, 301)

#: Positions a block of the attention's queries and of the expert
#: layer: rows of a block share nothing, and beside a served model's
#: weights, pool and states the float32 temporaries of a whole long row
#: are what a chip has no room for.
BLOCK = 512


def margins(layers: int):
    return tuple(MARGINS[min(n, len(MARGINS) - 1)] for n in range(layers))


def _w(p, name):
    return p[name]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=("arch", "no_gate"))
def _mla(p, u, arch, no_gate=False):
    """Latent attention with K and V EXPANDED a head, a block of
    queries against every key."""
    arch = dict(arch)
    yarn, eps = dict(arch["yarn"]), arch["eps"]
    c_q = _rms(u @ _w(p, "q_a"), p["q_norm"]["scale"], eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, _w(p, "q_b"))
    kv = u @ _w(p, "kv_a")
    w = p["kv_b"].astype(F32)  # (kv_rank, heads, nope + v)
    rank, heads = w.shape[:2]
    # The out projection reads heads x v values: what is left of
    # kv_b's width is the part of q and k that does not rotate.
    nope = w.shape[2] - p["out"]["kernel"].shape[0] // heads
    c_kv = _rms(kv[..., :rank], p["kv_norm"]["scale"], eps)
    inv = yarn_inv_freq(q.shape[-1] - nope, arch["rope_base"], yarn)
    m = yarn_mscale(yarn["factor"], yarn["mscale"]) / yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]
    )
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], inv, m)
    k_r = _rotate(kv[..., rank:], inv, m)  # (b, s, rope): one for all heads
    k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w[..., :nope])
    v = jnp.einsum("bsr,rhv->bshv", c_kv, w[..., nope:])
    s, o = u.shape[1], []
    for lo in range(0, s, BLOCK):
        at = jnp.arange(lo, min(lo + BLOCK, s))
        scores = jnp.einsum("bqhn,bjhn->bhqj", q_nope[:, at], k_nope) + (
            jnp.einsum("bqhr,bjr->bhqj", q_rope[:, at], k_r)
        )
        scores = scores * softmax_scale(q.shape[-1], yarn)
        seen = jnp.arange(s)[None, :] <= at[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        o.append(jnp.einsum("bhqj,bjhv->bqhv", jax.nn.softmax(scores, -1), v))
    o = jnp.concatenate(o, axis=1)
    o = o.reshape(*o.shape[:2], -1)
    if not no_gate:
        o = o * jax.nn.sigmoid(u @ _w(p, "gate"))
    return o @ _w(p, "out")


@functools.partial(jax.jit, static_argnames=(
    "gate_scale", "eps", "no_delta", "reset_at",
))
def _gdn(p, u, gate_scale, eps, no_delta=False, reset_at=None):
    """The Gated DeltaNet mixer, position by position. ``no_delta``:
    the ``beta k k^T`` correction left out (plain gated linear
    attention). ``reset_at`` (the ``reset_state`` control; a position a
    row): the state and the convolution's memory of a row are emptied
    before that position."""
    b, s, _ = u.shape
    heads = p["A_log"].shape[0]  # value heads
    d = p["norm_scale"].shape[0]
    conv_w = p["conv_kernel"].astype(F32)  # (width, channels)
    width, channels = conv_w.shape
    key_heads = (channels // d - heads) // 2
    qkv = u @ _w(p, "qkv")
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        u @ _w(p, "a_proj") + p["dt_bias"].astype(F32)
    )  # (b, s, H)
    beta = jax.nn.sigmoid(u @ _w(p, "b_proj"))

    def unit(t):
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    def step(carry, inp):
        state, memory = carry  # (b, H, d_k, d_v); (b, width - 1, channels)
        t, qkv_t, g_t, beta_t = inp
        if reset_at is not None:
            lost = t == jnp.asarray(reset_at)  # (b,)
            state = jnp.where(lost[:, None, None, None], 0.0, state)
            memory = jnp.where(lost[:, None, None], 0.0, memory)
        window = jnp.concatenate([memory, qkv_t[:, None]], axis=1)
        c = jax.nn.silu((window * conv_w).sum(1))
        q, k, v = jnp.split(c, (key_heads * d, 2 * key_heads * d), -1)
        q, k = (
            jnp.repeat(t_.reshape(b, key_heads, d), heads // key_heads, 1)
            for t_ in (q, k)
        )
        q, k, v = unit(q) * d ** -0.5, unit(k), v.reshape(b, heads, d)
        state = state * jnp.exp(g_t)[..., None, None]
        seen = 0.0 if no_delta else jnp.einsum("bhkv,bhk->bhv", state, k)
        w = beta_t[..., None] * (v - seen)
        state = state + k[..., None] * w[:, :, None, :]
        o = jnp.einsum("bhkv,bhk->bhv", state, q)
        return (state, window[:, 1:]), o

    start = (
        jnp.zeros((b, heads, d, d), F32),
        jnp.zeros((b, width - 1, channels), F32),
    )
    _, o = jax.lax.scan(step, start, (
        jnp.arange(s), jnp.swapaxes(qkv, 0, 1), jnp.swapaxes(g, 0, 1),
        jnp.swapaxes(beta, 0, 1),
    ))
    o = jnp.swapaxes(o, 0, 1)  # (b, s, H, d_v)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * (
        p["norm_scale"].astype(F32)
    )
    gate = gate_scale * jax.nn.sigmoid(u @ _w(p, "g_proj"))
    return (o.reshape(b, s, -1) * gate) @ _w(p, "out_proj")


def _gated(h, gate, up, down, limit):
    """One clamped SwiGLU: ``limit`` None leaves the clamp out."""
    g, u = h @ gate.astype(F32), h @ up.astype(F32)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "held_first", "limit",
))
def _experts(p, h, top_k, scale, held_first, limit):
    """-> (F(h), gap): the routed experts held here plus the shared
    one, every one clamped, and how near a held expert came to changing
    sides: ``k_exaone_reference._experts``' distance (the smallest,
    over the experts across the choice, of the difference of ``score +
    bias`` over the two sigmoids' slopes), written out here because
    that function's experts know no clamp."""
    s = jax.nn.sigmoid(h @ p["router"].astype(F32))  # (b, s, E)
    chosen_by = s + p["router_bias"].astype(F32)
    ranked = -jnp.sort(-chosen_by, axis=-1)
    held = p["w_gate"].shape[0]
    experts = jnp.arange(s.shape[-1])
    held_here = (experts >= held_first) & (experts < held_first + held)
    inside = chosen_by >= ranked[..., top_k - 1: top_k]  # the chosen
    slope = s * (1 - s)
    apart = jnp.abs(chosen_by[..., :, None] - chosen_by[..., None, :]) / (
        jnp.hypot(slope[..., :, None], slope[..., None, :])
    )  # (b, s, e, c)
    across = inside[..., :, None] != inside[..., None, :]
    gap = jnp.where(
        across & held_here[:, None], apart, jnp.inf
    ).min((-1, -2))
    picked = jnp.argsort(-chosen_by, axis=-1)[..., :top_k]  # (b, s, k)
    w = jnp.take_along_axis(s, picked, -1)
    w = scale * w / w.sum(-1, keepdims=True)
    out = _gated(
        h, _w(p, "shared_gate"), _w(p, "shared_up"), _w(p, "shared_down"),
        limit,
    )
    mine = picked[..., None] == held_first + jnp.arange(held)
    for e in range(held):  # one upcast at a time
        w_e = jnp.where(mine[..., e], w, 0.0).sum(-1, keepdims=True)
        out = out + w_e * _gated(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e], limit
        )
    return out, gap


def _frozen(arch: dict):
    """``arch`` as a static argument: hashable, dicts and all."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in arch.items() if k != "round_to"
    ))


def _layer(p, x, arch, fault, reset_at):
    """-> (y, gap): ``gap`` (b, s) of ``_experts``; None from a dense
    layer."""
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]
        limit = None if fault == "no_clamp" else arch["limit"]

        def lower(t):
            to = arch["round_to"]
            return t.astype(to).astype(F32) if to else t

        u = _rms(x, p["ln1"]["scale"], eps)
        if "mixer" in p:
            m = _gdn(
                p["mixer"], u, arch["gate_scale"], eps, fault == "no_delta",
                reset_at,
            )
        else:
            m = _mla(p["attn"], u, _frozen(arch), fault == "no_gate")
        h = x + lower(_rms(m, p["ln1_post"]["scale"], eps))
        v = _rms(h, p["ln2"]["scale"], eps)
        if "experts" in p:
            parts = [  # a position's experts know no other position
                _experts(
                    p["experts"], v[:, lo: lo + BLOCK], arch["top_k"],
                    arch["scale"], arch["held_first"], limit,
                )
                for lo in range(0, v.shape[1], BLOCK)
            ]
            f, gap = (jnp.concatenate(t, axis=1) for t in zip(*parts))
        else:
            f, gap = _gated(
                v, _w(p, "mlp_gate"), _w(p, "mlp_in"), _w(p, "mlp_out"), limit
            ), None
        return lower(h + lower(_rms(f, p["ln2_post"]["scale"], eps))), gap


def hidden_states(variables, ids, fault="", arch=None, reset_at=None):
    """The final hidden states (b, s, d) before the head's norm, and
    (sparse layers, b, s) the gap of ``_experts`` in each sparse layer
    (infinite in one a fault left out). ``reset_at``: a position a row,
    the ``reset_state`` control's."""
    if fault not in ("", *FAULTS, *PRECISION):
        raise ValueError(f"unknown fault {fault!r}")
    arch = {**ARCH, **(arch or {}), **PRECISION.get(fault, {})}
    ids = jnp.asarray(ids, jnp.int32)
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    x = variables["embed"]["params"]["tok"]["embedding"].astype(F32)[ids]
    gaps = []
    for i, name in enumerate(names):
        p = variables[name]["params"]
        if fault == "drop_block" and i == 1:
            # a served model one block short: the latent-attention one
            # (a sparse layer) in the configuration's cut
            if "experts" in p:
                gaps.append(jnp.full(ids.shape, jnp.inf))
            continue
        x, g = _layer(p, x, arch, fault, reset_at)
        if g is not None:
            gaps.append(g)
    return x, jnp.stack(gaps)


def vouched(gaps):
    """(sparse layers, b, s) gaps -> (b, s) bool: no layer had a held
    expert within that layer's margin of changing sides."""
    held_to = jnp.asarray(margins(gaps.shape[0]), F32)[:, None, None]
    return (gaps >= held_to).all(0)


def logprobs_and_gaps(variables, ids, fault="", arch=None, reset_at=None):
    """``(logprobs, gaps)``: (b, s - 1) the log-probability the model
    gives ``ids[:, t + 1]`` after reading ``ids[:, : t + 1]`` over the
    vocabulary slice held here, and (sparse layers, b, s - 1) position
    t's gap in each sparse layer. ``fault`` is the self-test of the
    comparison built on this, each a served model gone wrong as it
    looks from here: ``drop_block`` (the second block, the latent one,
    left out), ``no_delta`` (every GDN layer without the ``beta k k^T``
    correction: a state that only ever adds), ``reset_state`` (every
    GDN layer's state and convolution memory of row r emptied before
    position ``reset_at[r]``, the row's prompt length: a served model
    that loses its state between prefill and decode), ``no_gate`` /
    ``no_clamp`` (readings), or a precision reading (``PRECISION``).
    ``arch`` overrides entries of ``ARCH`` (tests at small sizes).
    Computed a ROW at a time: rows share nothing, and the float32
    temporaries of three rows at once are what a chip has no room for
    beside a served model."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = ids.shape[0]
    if fault == "reset_state":
        reset_at = tuple(reset_at or SAMPLE_RESET)
        reset_at = (reset_at + reset_at[-1:] * rows)[:rows]
    else:
        reset_at = None
    parts = [
        _logprobs_and_gaps(
            variables, ids[r: r + 1], fault, arch,
            reset_at and reset_at[r: r + 1],
        )
        for r in range(rows)
    ]
    return (
        jnp.concatenate([p[0] for p in parts]),
        jnp.concatenate([p[1] for p in parts], axis=1),
    )


def _logprobs_and_gaps(variables, ids, fault, arch, reset_at):
    x, gaps = hidden_states(variables, ids, fault, arch, reset_at)
    p = variables["head"]["params"]
    with jax.default_matmul_precision("highest"):
        x = _rms(
            x[:, :-1], p["ln"]["scale"], {**ARCH, **(arch or {})}["eps"]
        )
        logits = x @ _w(p, "logits")
    logp = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return logp, gaps[..., :-1]


def next_token_logprobs(variables, ids, fault="", arch=None, reset_at=None):
    """``(logprobs, vouched)``: :func:`logprobs_and_gaps` with each
    sparse layer's gap held to its margin."""
    logp, gaps = logprobs_and_gaps(variables, ids, fault, arch, reset_at)
    return logp, vouched(gaps)
