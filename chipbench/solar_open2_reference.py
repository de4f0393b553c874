"""Plain reference of Solar-Open2-250B's decoder (``solar_open2``) as
``configs/solar-open2-250b.json`` cuts it: every layer's equations
(``SOLAR_OPEN2.md``) in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision, the whole sequence at once: the delta
rule as a SCAN OVER POSITIONS (no chunks, no triangular solve, no
carried convolution tail, no kernel, no state kept between calls),
full softmax attention under a plain causal mask, every held expert
meeting every token under a mask. It reads the program's parameter
tree and nothing else of the program: which layer is which is what its
subtree holds (``attn`` or ``mixer``), every width is a shape; what a
tree cannot say (the norms' eps, the factor 2 on ``beta``, the
router's constants) is ``ARCH`` below, the published values. The
expert layer is K-EXAONE's, function for function
(``k_exaone_reference._experts``).

Per layer, ``u = RMSNorm(x)``, no projection bias:

    KDA layer:  q, k, v = silu(conv1d_causal(W_qkv u))    (width 4, depthwise)
                q = q / |q| * d_k^-1/2;  k = k / |k|      (a head)
                g = -exp(A_log) softplus(W_f2 (W_f1 u) + dt_bias)
                beta = 2 sigmoid(W_b u)
                S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T
                o_t = S_t^T q
                m = W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u))]
    GQA layer:  q, k, v = W_q u, W_k u, W_v u   (no rotation, no QK norm)
                m = W_o [softmax(q k^T / sqrt(hd)) v * sigmoid(W_gate u)]
    h = x + m;  y = h + F(RMSNorm(h)),  F the routed experts held here
    (sigmoid scores, top-8 of score + bias, weights normalised over the
    8) plus the shared expert.

The pass returns ``(logprobs, vouched)``: a position is NOT vouched
where, in some layer, an expert held here came within that layer's
margin (``MARGINS``) of changing sides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.k_exaone_reference import _experts, _rms

F32 = jnp.float32

#: What the parameter tree cannot say: the published constants.
ARCH = dict(
    eps=1e-5,  # rms_norm_eps
    beta_max=2.0,  # kda_allow_neg_eigval: beta = 2 sigmoid(.)
    top_k=8,  # num_experts_per_tok
    scale=1.0,  # routed_scaling_factor
    held_first=0,  # the first expert of this chip's share
    #: A dtype name: every mixer's and expert layer's output and every
    #: layer's output is rounded to it. None in the reference proper;
    #: the other precision reading of ``scripts/solar_open2_limits.py``
    #: (what the served model keeps in bfloat16, kept in less).
    round_to=None,
    #: A dtype name: a KDA layer's state is rounded to it after every
    #: position. None in the reference proper; the precision reading of
    #: ``scripts/solar_open2_limits.py`` (what a served model that kept
    #: its state in a precision below float32 looks like from here).
    state_as=None,
    #: A list: every KDA layer appends the state it ends the row in
    #: (``scripts/solar_open2_limits.py --state`` holds a served
    #: slot's state to it).
    states=None,
    flip=None,  # ``k_exaone_reference._experts`` reads these keys
    scores=None,
)

#: One margin a layer (every layer is sparse), in the units of
#: ``_experts``' gap (the configuration's ``correct.why`` has the
#: readings they are set from).
MARGINS = (0.01, 0.025, 0.03, 0.03)

#: Every fault this reference knows: a served model gone wrong, as it
#: looks from here. ``drop_expert`` is a reading, not a control: a chip
#: holds 40 of 320 experts at top-8, one of a token's eight on average,
#: so one held expert less moves a logprob by 0.07-0.27, which the
#: sound readings reach (``correct.why``).
FAULTS = ("drop_block", "no_delta", "reset_state", "drop_expert")
#: The precision readings, as faults of their own: the reference in
#: the precision BELOW the one the configuration states (``ARCH``'s
#: ``state_as`` / ``round_to``), as a served model that kept its KDA
#: state in bfloat16, or everything it keeps in bfloat16 in float8,
#: looks from here. ``state_bfloat16`` is a reading too: logprobs do
#: not see it at any length tried (``correct.why``).
PRECISION = {
    "state_bfloat16": dict(state_as="bfloat16"),
    "outputs_float8": dict(round_to="float8_e4m3fn"),
}
#: What the configuration lists under ``correct.controls``: each must
#: read wrong at every seed.
CONTROLS = ("drop_block", "no_delta", "reset_state", "outputs_float8")

#: Where ``reset_state`` empties a row's states when nobody says: the
#: prompt lengths of the correctness sample (``lm_engine``'s 40, chunk
#: - 17 and chunk + 45 tokens at a prefill chunk of 256).
SAMPLE_RESET = (40, 239, 301)


def margins(layers: int):
    return tuple(MARGINS[min(n, len(MARGINS) - 1)] for n in range(layers))


#: Positions a block of the reference's attention (queries) and expert
#: layer: rows of a block share nothing, and beside a served model's
#: weights, pool and states the float32 temporaries of a whole long
#: row (scores of s x s a head, expert distances of E x E a position)
#: are what a chip has no room for.
BLOCK = 512


@jax.jit
def _attention(p, u):
    q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"].astype(F32))
    kv = jnp.einsum("bsd,dthk->tbshk", u, p["kv"]["kernel"].astype(F32))
    group = q.shape[2] // kv.shape[3]
    k, v = (jnp.repeat(t, group, axis=2) for t in (kv[0], kv[1]))
    s = u.shape[1]
    o = []
    for lo in range(0, s, BLOCK):  # a block of queries against every key
        at = jnp.arange(lo, min(lo + BLOCK, s))
        scores = jnp.einsum("bqhk,bjhk->bhqj", q[:, at], k) / jnp.sqrt(
            F32(q.shape[-1])
        )
        seen = jnp.arange(s)[None, :] <= at[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        o.append(jnp.einsum("bhqj,bjhk->bqhk", jax.nn.softmax(scores, -1), v))
    o = jnp.concatenate(o, axis=1)
    o = o.reshape(*o.shape[:2], -1)
    o = o * jax.nn.sigmoid(u @ p["gate"]["kernel"].astype(F32))
    return o @ p["out"]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "beta_max", "eps", "no_delta", "reset_at", "state_as",
))
def _kda(p, u, beta_max, eps, no_delta=False, reset_at=None, state_as=None):
    """The linear-attention mixer, position by position. ``no_delta``:
    the ``beta k k^T`` correction left out (plain gated linear
    attention). ``reset_at`` (the ``reset_state`` control; a position a
    row): the state and the convolution's memory of a row are emptied
    before that position."""
    b, s, _ = u.shape
    heads = p["A_log"].shape[0]
    conv_w = p["conv_kernel"].astype(F32)  # (width, channels)
    width, channels = conv_w.shape
    d = channels // 3 // heads
    qkv = u @ p["qkv"]["kernel"].astype(F32)
    f = (u @ p["f_down"]["kernel"].astype(F32)) @ (
        p["f_up"]["kernel"].astype(F32)
    ) + p["dt_bias"].astype(F32)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        f.reshape(b, s, heads, d)
    )
    beta = beta_max * jax.nn.sigmoid(u @ p["b_proj"]["kernel"].astype(F32))

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
        q, k, v = (t_.reshape(b, heads, d) for t_ in jnp.split(c, 3, -1))
        q, k = unit(q) * d ** -0.5, unit(k)
        state = state * jnp.exp(g_t)[..., None]
        seen = 0.0 if no_delta else jnp.einsum("bhkv,bhk->bhv", state, k)
        w = beta_t[..., None] * (v - seen)
        state = state + k[..., None] * w[:, :, None, :]
        if state_as:  # a cast there and back is one XLA may take out
            to = jnp.finfo(state_as)
            state = jax.lax.reduce_precision(state, to.nexp, to.nmant)
        o = jnp.einsum("bhkv,bhk->bhv", state, q)
        return (state, window[:, 1:]), o

    start = (
        jnp.zeros((b, heads, d, d), F32),
        jnp.zeros((b, width - 1, channels), F32),
    )
    (last, _), o = jax.lax.scan(step, start, (
        jnp.arange(s), jnp.swapaxes(qkv, 0, 1), jnp.swapaxes(g, 0, 1),
        jnp.swapaxes(beta, 0, 1),
    ))
    o = jnp.swapaxes(o, 0, 1)  # (b, s, H, d_v)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * (
        p["norm_scale"].astype(F32)
    )
    gate = jax.nn.sigmoid(
        (u @ p["g_down"]["kernel"].astype(F32))
        @ p["g_up"]["kernel"].astype(F32)
    )
    out = (o.reshape(b, s, -1) * gate) @ p["out_proj"]["kernel"].astype(F32)
    return out, last


@functools.lru_cache(maxsize=None)
def _experts_compiled(top_k, scale, held_first):
    """``_experts`` as ONE compiled program a shape where nothing in it
    needs a concrete value (no expert to drop, no scores kept): eagerly
    it is some forty small programs a call."""
    arch = dict(top_k=top_k, scale=scale, held_first=held_first, scores=None)
    return jax.jit(lambda p, h: _experts(p, h, arch, False))


def _layer(p, x, arch, fault, reset_at):
    """-> (y, gap): ``gap`` (b, s) of ``_experts``."""
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]

        def lower(t):
            to = arch["round_to"]
            return t.astype(to).astype(F32) if to else t

        u = _rms(x, p["ln1"]["scale"], eps)
        if "mixer" in p:
            m, last = _kda(
                p["mixer"], u, arch["beta_max"], eps, fault == "no_delta",
                reset_at, arch["state_as"],
            )
            if arch["states"] is not None:
                arch["states"].append(last)
        else:
            m = _attention(p["attn"], u)
        h = x + lower(m)
        v = _rms(h, p["ln2"]["scale"], eps)
        if fault == "drop_expert" or arch["scores"] is not None:
            f, gap = _experts(p["experts"], v, arch, fault == "drop_expert")
        else:
            experts = _experts_compiled(
                arch["top_k"], arch["scale"], arch["held_first"]
            )
            parts = [  # a position's experts know no other position
                experts(p["experts"], v[:, lo: lo + BLOCK])
                for lo in range(0, v.shape[1], BLOCK)
            ]
            f, gap = (jnp.concatenate(t, axis=1) for t in zip(*parts))
        return lower(h + lower(f)), gap


def hidden_states(variables, ids, fault="", arch=None, reset_at=None):
    """The final hidden states (b, s, d) before the head's norm, and
    (layers, b, s) the gap of ``_experts`` in each layer (infinite in
    a layer a fault left out). ``reset_at``: a position a row, the
    ``reset_state`` control's."""
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
        if fault == "drop_block" and i == 1:
            gaps.append(jnp.full(ids.shape, jnp.inf))
            continue  # a served model one (linear-attention) block short
        x, g = _layer(variables[name]["params"], x, arch, fault, reset_at)
        gaps.append(g)
    return x, jnp.stack(gaps)


def vouched(gaps):
    """(layers, b, s) gaps -> (b, s) bool: no layer had a held expert
    within that layer's margin of changing sides."""
    held_to = jnp.asarray(margins(gaps.shape[0]), F32)[:, None, None]
    return (gaps >= held_to).all(0)


def logprobs_and_gaps(variables, ids, fault="", arch=None, reset_at=None):
    """``(logprobs, gaps)``: (b, s - 1) the log-probability the model
    gives ``ids[:, t + 1]`` after reading ``ids[:, : t + 1]`` over the
    vocabulary slice held here, and (layers, b, s - 1) position t's gap
    in each layer. ``fault`` is the self-test of the comparison built
    on this, each a served model gone wrong as it looks from here:
    ``drop_block`` (the second block, a linear-attention one, left
    out), ``drop_expert`` (of every block, the busiest held expert left
    out: a reading, see ``FAULTS``), ``no_delta`` (every KDA layer
    without the ``beta k k^T`` correction: a state that only ever
    adds), ``reset_state`` (every KDA layer's state and convolution
    memory of row r emptied before position ``reset_at[r]``, the row's
    prompt length: a served model that loses its state between prefill
    and decode), or a precision reading (``PRECISION``). ``arch``
    overrides entries of ``ARCH`` (tests at small sizes).

    Computed a ROW at a time (rows share nothing, and beside a served
    model's weights, pool and states the float32 temporaries of three
    rows at once are what a chip has no room for); ``drop_expert``
    alone takes the rows together: its busiest expert is the batch's."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = ids.shape[0]
    if fault == "reset_state":
        reset_at = tuple(reset_at or SAMPLE_RESET)
        reset_at = (reset_at + reset_at[-1:] * rows)[:rows]
    else:
        reset_at = None
    if fault == "drop_expert" or rows == 1:
        return _logprobs_and_gaps(variables, ids, fault, arch, reset_at)
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
        logits = x @ p["logits"]["kernel"].astype(F32)
    logp = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return logp, gaps[..., :-1]


def next_token_logprobs(variables, ids, fault="", arch=None, reset_at=None):
    """``(logprobs, vouched)``: :func:`logprobs_and_gaps` with each
    layer's gap held to its margin."""
    logp, gaps = logprobs_and_gaps(variables, ids, fault, arch, reset_at)
    return logp, vouched(gaps)
