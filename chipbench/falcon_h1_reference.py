"""Plain reference of Falcon-H1's decoder (``falcon_h1``) as
``configs/falcon-h1-34b-instruct.json`` cuts it: every layer's
equations in straightforward ``jax.numpy`` at float32 and ``highest``
matmul precision, block by block, the whole sequence at once under a
plain causal mask and the state-space recurrence as a SCAN OVER
POSITIONS (no chunks, no carried convolution tail, no kernel, no
cache). It reads the program's parameter tree and nothing else of the
program; what a tree cannot say (every multiplier, the rotation's
base, the mixer's group count) is ``ARCH`` below, the published
values; every width is read off the tree's shapes.

Per block, ``u = RMSNorm(x)``, no projection bias:

    attention: q, k, v = W_q u, (W_k u) * key_multiplier, W_v u
               (input times attention_in_multiplier); rotate-half at
               rope_theta; causal softmax(q k^T / sqrt(hd)) v, query
               head i reading KV head i // group;
               a = (W_o .) * attention_out_multiplier
    mixer:     p = (W_in (u * ssm_in_multiplier)) * mup, split
               z | xBC | dt, mup the five ssm_multipliers over the
               segments z, x, B, C, dt
               xBC = silu(conv1d_causal(xBC; width 4, depthwise) + bias),
               split x (heads x P) | B | C (groups x N)
               dt = softplus(dt + dt_bias); A = -exp(A_log)
               S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (a head)
               y_t = S_t C_t + D x_t
               y = RMSNorm_grouped(y * silu(z)) * scale     (over groups)
               s = (W_out y) * ssm_out_multiplier
    h = x + a + s;  v = RMSNorm(h)
    out = h + W_down(W_up v * silu(W_gate v * mlp_multipliers[0]))
              * mlp_multipliers[1]

``h0 = embed(ids) * embedding_multiplier``; after the last block
RMSNorm and the untied head times ``lm_head_multiplier``. Every
position is vouched for (no discrete choice): the plain ``(b, s - 1)``
array comes back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: What the parameter tree cannot say: the published constants.
ARCH = dict(
    eps=1e-5,  # rms_norm_eps
    rope_base=1e11,  # rope_theta
    embedding_multiplier=5.656854249492381,
    attention_in_multiplier=1.0,
    key_multiplier=0.011048543456039804,
    attention_out_multiplier=0.0375,
    ssm_in_multiplier=0.25,
    ssm_multipliers=(
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738,
    ),
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    lm_head_multiplier=0.0078125,
    mamba_n_groups=2,
    #: A dtype name: every block's output is rounded to it. None in
    #: the reference proper; the precision reading of
    #: ``scripts/falcon_h1_limits.py`` (what a served model in a
    #: precision below the stated one looks like from here).
    round_to=None,
)

CONTROLS = ("drop_block", "drop_ssm", "reset_state")


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


@functools.partial(jax.jit, static_argnames=("base", "key_mult", "in_mult"))
def _attention(p, u, base, key_mult, in_mult):
    u = u * in_mult
    q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"].astype(F32))
    kv = jnp.einsum("bsd,dthk->tbshk", u, p["kv"]["kernel"].astype(F32))
    k, v = kv[0] * key_mult, kv[1]
    q, k = _rotate(q, base), _rotate(k, base)
    s, group = u.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhk,bjhk->bhqj", q, k) / jnp.sqrt(F32(q.shape[-1]))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    scores = jnp.where(j <= i, scores, -jnp.inf)
    o = jnp.einsum("bhqj,bjhk->bqhk", jax.nn.softmax(scores, -1), v)
    return o.reshape(*o.shape[:2], -1) @ p["out"]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "groups", "in_mult", "mup", "eps", "reset_at",
))
def _mixer(p, u, groups, in_mult, mup, eps, reset_at=None):
    """The state-space mixer, position by position. ``reset_at``
    (the ``reset_state`` control; a position a row): the state and the
    convolution's memory of a row are emptied before that position."""
    b, s, _ = u.shape
    heads = p["A_log"].shape[0]
    conv_w = p["conv_kernel"].astype(F32)  # (width, channels)
    width, channels = conv_w.shape
    # z | x B C | dt: what is left of the projection is z
    d_inner = p["in_proj"]["kernel"].shape[1] - channels - heads
    head_dim = d_inner // heads
    n = (channels - d_inner) // (2 * groups)
    seg = (d_inner, d_inner, groups * n, groups * n, heads)
    scale = jnp.concatenate([jnp.full((m,), v, F32) for m, v in zip(seg, mup)])
    proj = ((u * in_mult) @ p["in_proj"]["kernel"].astype(F32)) * scale
    z, xbc, dt = jnp.split(proj, (d_inner, d_inner + channels), axis=-1)
    a = -jnp.exp(p["A_log"].astype(F32))  # (heads,)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))  # (b, s, heads)

    def step(carry, inp):
        state, memory = carry  # (b, H, P, N); (b, width - 1, channels)
        t, xbc_t, dt_t = inp
        if reset_at is not None:
            lost = t == jnp.asarray(reset_at)  # (b,)
            state = jnp.where(lost[:, None, None, None], 0.0, state)
            memory = jnp.where(lost[:, None, None], 0.0, memory)
        window = jnp.concatenate([memory, xbc_t[:, None]], axis=1)
        c = jax.nn.silu(
            (window * conv_w).sum(1) + p["conv_bias"].astype(F32)
        )
        x, bm, cm = jnp.split(c, (d_inner, d_inner + groups * n), axis=-1)
        x = x.reshape(b, heads, head_dim)
        per = heads // groups
        bm = jnp.repeat(bm.reshape(b, groups, n), per, axis=1)
        cm = jnp.repeat(cm.reshape(b, groups, n), per, axis=1)
        state = (
            state * jnp.exp(dt_t * a)[..., None, None]
            + (dt_t[..., None] * x)[..., None] * bm[:, :, None, :]
        )
        y = (state * cm[:, :, None, :]).sum(-1)
        y = y + p["D"].astype(F32)[:, None] * x
        return (state, window[:, 1:]), y.reshape(b, d_inner)

    start = (
        jnp.zeros((b, heads, head_dim, n), F32),
        jnp.zeros((b, width - 1, channels), F32),
    )
    _, y = jax.lax.scan(
        step, start,
        (jnp.arange(s), jnp.swapaxes(xbc, 0, 1), jnp.swapaxes(dt, 0, 1)),
    )
    y = jnp.swapaxes(y, 0, 1) * jax.nn.silu(z)  # (b, s, d_inner)
    g = y.reshape(b, s, groups, d_inner // groups)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    y = g.reshape(b, s, d_inner) * p["norm_scale"].astype(F32)
    return y @ p["out_proj"]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=("gate_mult", "out_mult"))
def _mlp(v, gate, up, down, gate_mult, out_mult):
    g = (v @ gate.astype(F32)) * gate_mult
    return ((jax.nn.silu(g) * (v @ up.astype(F32))) @ down.astype(F32)) * out_mult


def _block(p, x, arch, drop_ssm, reset_at):
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]
        u = _rms(x, p["ln1"]["scale"], eps)
        a = _attention(
            p["attn"], u, arch["rope_base"], arch["key_multiplier"],
            arch["attention_in_multiplier"],
        ) * arch["attention_out_multiplier"]
        h = x + a
        if not drop_ssm:
            h = h + _mixer(
                p["ssm"], u, arch["mamba_n_groups"],
                arch["ssm_in_multiplier"], tuple(arch["ssm_multipliers"]),
                eps, reset_at,
            ) * arch["ssm_out_multiplier"]
        v = _rms(h, p["ln2"]["scale"], eps)
        out = h + _mlp(
            v, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
            p["mlp_out"]["kernel"], *arch["mlp_multipliers"],
        )
        if arch["round_to"]:
            out = out.astype(arch["round_to"]).astype(F32)
        return out


def hidden_states(variables, ids, fault="", arch=None, reset_at=None):
    """The final hidden states (b, s, d) before the head's norm."""
    if fault not in ("",) + CONTROLS:
        raise ValueError(f"unknown fault {fault!r}")
    arch = {**ARCH, **(arch or {})}
    ids = jnp.asarray(ids, jnp.int32)
    # (rows first, the upcast after: the table is 1.3e9 numbers)
    x = variables["embed"]["params"]["tok"]["embedding"][ids].astype(F32)
    x = x * arch["embedding_multiplier"]
    names = sorted(
        (n for n in variables if n.startswith("decoder_block_")),
        key=lambda n: int(n.rsplit("_", 1)[1]),
    )
    for i, name in enumerate(names):
        if fault == "drop_block" and i == 1:
            continue  # a served model one block short
        x = _block(
            variables[name]["params"], x, arch,
            fault == "drop_ssm" and i == 1,  # one block's mixer left out
            reset_at if fault == "reset_state" else None,
        )
    return x


def next_token_logprobs(variables, ids, fault="", arch=None, reset_at=None):
    """(b, s - 1): the log-probability the model gives ``ids[:, t + 1]``
    after reading ``ids[:, : t + 1]``. ``variables`` is the program's
    parameter tree.

    ``fault`` is the self-test of the comparison built on this, each a
    served model gone wrong as it looks from here: ``drop_block`` (the
    second block left out), ``drop_ssm`` (the second block's mixer
    branch left out: a hybrid served as a transformer there),
    ``reset_state`` (every layer's state and convolution memory of row
    r emptied before position ``reset_at[r]``, the row's prompt
    length: a served model that loses its state between prefill and
    decode; absent, the correctness sample's prompt lengths,
    ``SAMPLE_RESET``). ``arch`` overrides entries of ``ARCH`` (tests
    at small sizes)."""
    ids = jnp.asarray(ids, jnp.int32)
    if fault == "reset_state":
        rows = ids.shape[0]
        reset_at = tuple(reset_at or SAMPLE_RESET)
        reset_at = (reset_at + reset_at[-1:] * rows)[:rows]
    x = hidden_states(variables, ids, fault, arch, reset_at)
    p = variables["head"]["params"]
    a = {**ARCH, **(arch or {})}
    x = _rms(x[:, :-1], p["ln"]["scale"], a["eps"])
    kernel, nxt = p["logits"]["kernel"], ids[:, 1:]
    # The vocabulary in slices, one upcast at a time (the whole head in
    # float32 is 5.35 GB beside a served model's weights and caches):
    # a running log-sum-exp, and the chosen token's logit from the
    # slice that holds it.
    width = -(-kernel.shape[1] // HEAD_SLICES)
    lse = jnp.full(nxt.shape, -jnp.inf)
    chosen = jnp.zeros(nxt.shape, F32)
    for lo in range(0, kernel.shape[1], width):
        logits = _logits(x, kernel[:, lo: lo + width], a["lm_head_multiplier"])
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, -1))
        at = nxt - lo
        here = (at >= 0) & (at < logits.shape[-1])
        picked = jnp.take_along_axis(
            logits, jnp.clip(at, 0, logits.shape[-1] - 1)[..., None], -1
        )[..., 0]
        chosen = chosen + jnp.where(here, picked, 0.0)
    return chosen - lse


HEAD_SLICES = 8


@functools.partial(jax.jit, static_argnames=("mult",))
def _logits(x, kernel, mult):
    with jax.default_matmul_precision("highest"):
        return (x @ kernel.astype(F32)) * mult


#: Where ``reset_state`` empties a row's state when nobody says: the
#: prompt lengths of the correctness sample (``lm_engine``'s 40,
#: chunk - 17 and chunk + 45 tokens at the configuration's prefill
#: chunk of 256), row by row: the point between prefill and decode.
SAMPLE_RESET = (40, 239, 301)
