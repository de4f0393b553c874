"""A gated delta-rule linear-attention mixer (Kimi Delta Attention: the
delta rule with a decay per key channel; Gated DeltaNet: the same rule
with ONE decay a head) as the hybrid decoders use it IN PLACE OF
attention in a block (``transformer_lm.BlockSpec.linear``): such a
block holds a recurrent state a request and NO pages. One parameter
structure, three schedules over it, as ``models/ssm``:

- **whole prompt / chunk pass** (:meth:`KdaMixer.scan`): the recurrence
  in its chunked form at ``_CHUNK`` positions a chunk (a unit
  lower-triangular system a chunk, solved for every chunk of a group
  at once, then a scan that carries the state from chunk to chunk:
  :func:`_chunked`); a chunked-prefill pass starts from the state and
  convolution tail the pass before left. Positions at or past
  ``length`` (a prompt shorter than its bucket) get ``g = 0`` and
  ``beta = 0`` and step nothing, so what comes back is the state and
  tail of the LAST REAL position.
- **one decode step** (:meth:`KdaMixer.step`): a token a row against
  its slot's state (``ops/kda_step``: read once, written once in
  place); a dead row (negative index) keeps state and tail untouched.

What a request owns of a mixer is ``(state, tail)``: the state
``(heads, d_k, d_v)`` in float32 and the last ``d_conv - 1`` inputs of
the causal convolution over ``q | k | v``.

Per position, ``u`` the block's normed input (no projection bias):

    q, k, v  = silu(conv1d_causal(W_qkv u))   q, k: key_heads x d; v: heads x d
    q        = q / |q|_2 * d_k^-1/2;   k = k / |k|_2
               (value head h reads key head h // (heads / key_heads))
    g        = -exp(A_log) * softplus(W_f2 (W_f1 u) + dt_bias)
    alpha    = exp(g)                   a head AND key channel, in (0, 1)
      or, ``head_decay``:  g = -exp(A_log) * softplus(W_a u + dt_bias),
               ONE scalar a head
    beta     = sigmoid(W_b u) * (2 if neg_eigval else 1)       a head
    S_t      = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T
    o_t      = S_t^T q
    out      = W_o [RMSNorm_head(o) * gate_scale sigmoid(W_g2 (W_g1 u))]
               (``rank`` None: one full-width W_g)
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from adapt_tpu.models.ssm import init_a_log, scaled, zero_state
from adapt_tpu.ops.dispatch import record_kernel_choice
from adapt_tpu.ops.kda_step import kda_step

F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
#: Positions a chunk of the chunked prefill.
_CHUNK = 64
#: Chunks whose triangular systems and decayed operands the chunked
#: prefill forms at once, ahead of the scan that carries the state: 512
#: positions, which keeps what is hoisted of a 2,048-position pass at a
#: quarter (~140 MB at 64 heads of 128 x 128, not ~550).
_GROUP = 8
#: Rows of a diagonal block that :func:`_unit_lower_inverse` inverts by
#: forward substitution: its steps in sequence, whatever the chunks.
_SOLVE_BLOCK = 16


@dataclasses.dataclass(frozen=True)
class KdaSpec:
    """A block's linear-attention mixer, read from a model's
    configuration."""

    heads: int  # value heads: a state a head
    head_dim: int  # d_k = d_v
    #: Width of the low-rank pairs that make the decay a key channel
    #: and the gate; None: the gate is ONE full-width projection.
    rank: int | None
    d_conv: int = 4
    #: ``beta`` in (0, 2): ``I - beta k k^T`` may flip a direction.
    neg_eigval: bool = True
    norm_eps: float = 1e-5
    #: Heads of q and k where fewer than ``heads``: value head ``h``
    #: reads key head ``h // (heads // key_heads)``. None: ``heads``.
    key_heads: int | None = None
    #: ONE decay a head from one projection (``A_log`` and ``dt_bias``
    #: a head: Gated DeltaNet), not one a key channel from a low-rank
    #: pair (Kimi Delta Attention).
    head_decay: bool = False
    #: What multiplies the output gate's sigmoid (2: the gate is one at
    #: a pre-activation of zero).
    gate_scale: float = 1.0

    def __post_init__(self):
        if self.heads % self.qk_heads:
            raise ValueError(
                f"heads {self.heads} not divisible by key_heads "
                f"{self.key_heads}"
            )
        if self.rank is None and not self.head_decay:
            raise ValueError(
                "a decay a key channel is made by a low-rank pair: rank "
                "says how wide"
            )

    @property
    def qk_heads(self) -> int:
        return self.key_heads or self.heads

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``q | k | v``."""
        return (2 * self.qk_heads + self.heads) * self.head_dim

    def state_shapes(self, rows: int, dtype):
        """``(state, tail)`` of ``rows`` requests, as shape structs."""
        return (
            jax.ShapeDtypeStruct(
                (rows, self.heads, self.head_dim, self.head_dim), F32
            ),
            jax.ShapeDtypeStruct(
                (rows, self.d_conv - 1, self.conv_dim), jnp.dtype(dtype)
            ),
        )


def init_dt_bias(key, shape, dtype=F32):
    """``softplus(dt_bias)`` log-uniform in [1e-4, 1e-2]: with
    ``models/ssm.init_a_log`` (``exp(A_log)`` uniform in [1, 16], used
    here as it is) a channel's ``alpha`` lies in about (0.85, 0.9999),
    so that a state neither vanishes in a step nor never decays."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-4), jnp.log(1e-2)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


class KdaMixer(nn.Module):
    spec: KdaSpec
    dim: int
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        spec = self.spec

        def dense(n, name):
            return nn.Dense(n, dtype=self.dtype, use_bias=False, name=name)

        self.qkv = dense(spec.conv_dim, "qkv")
        self.conv_kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (spec.d_conv, spec.conv_dim),
        )
        if spec.head_decay:
            self.a_proj = dense(spec.heads, "a_proj")
        else:
            self.f_down = dense(spec.rank, "f_down")
            self.f_up = dense(spec.d_inner, "f_up")
        self.a_log = self.param("A_log", init_a_log, (spec.heads,))
        self.dt_bias = self.param(
            "dt_bias", init_dt_bias,
            (spec.heads if spec.head_decay else spec.d_inner,),
        )
        self.b_proj = dense(spec.heads, "b_proj")
        if spec.rank is None:
            self.g_proj = dense(spec.d_inner, "g_proj")
        else:
            self.g_down = dense(spec.rank, "g_down")
            self.g_up = dense(spec.d_inner, "g_up")
        self.norm_scale = self.param(
            "norm_scale", nn.initializers.ones, (spec.head_dim,)
        )
        self.out_proj = dense(self.dim, "out_proj")

    # -- the pieces every schedule shares ------------------------------

    def _heads(self, t):
        return t.reshape(*t.shape[:-1], -1, self.spec.head_dim)

    def _conv(self, full, s: int):
        """``full`` (b, d_conv - 1 + s, conv_dim): the inputs of ``s``
        positions after the ``d_conv - 1`` before the first of them ->
        normalised ``q``, ``k`` and ``v`` (b, s, heads, d) in the
        served type."""
        w = self.conv_kernel.astype(F32)
        out = nn.silu(sum(
            full[:, j: j + s].astype(F32) * w[j]
            for j in range(self.spec.d_conv)
        ))
        spec = self.spec
        if spec.key_heads is None:
            q, k, v = (self._heads(t) for t in jnp.split(out, 3, axis=-1))
        else:
            qk = spec.qk_heads * spec.head_dim
            q, k, v = (
                self._heads(t)
                for t in jnp.split(out, (qk, 2 * qk), axis=-1)
            )

        def unit(t):
            return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        q, k = unit(q) * spec.head_dim ** -0.5, unit(k)
        if spec.key_heads is not None:
            # A key head under each of its value heads: from here on
            # every schedule counts value heads.
            q, k = (
                jnp.repeat(t, spec.heads // spec.qk_heads, axis=-2)
                for t in (q, k)
            )
        return tuple(t.astype(self.dtype) for t in (q, k, v))

    def _gates(self, u, live):
        """``g`` (.., heads, d_k), or (.., heads) where the decay is a
        head's, and ``beta`` (.., heads) in float32; where ``live``
        (broadcast over the leading axes) is false both are zero: the
        position steps nothing."""
        if self.spec.head_decay:
            f = self.a_proj(u).astype(F32) + self.dt_bias.astype(F32)
            g = -jnp.exp(self.a_log.astype(F32)) * jax.nn.softplus(f)
        else:
            f = self._heads(
                self.f_up(self.f_down(u)).astype(F32)
                + self.dt_bias.astype(F32)
            )
            g = -jnp.exp(self.a_log.astype(F32))[:, None] * jax.nn.softplus(f)
        beta = jax.nn.sigmoid(self.b_proj(u).astype(F32))
        if self.spec.neg_eigval:
            beta = 2.0 * beta
        over = (None,) * (g.ndim - beta.ndim + 1)  # a head's channels too
        return (
            jnp.where(live[(..., *over)], g, 0.0),
            jnp.where(live[..., None], beta, 0.0),
        )

    def _finish(self, o, u):
        """``o`` (.., heads, d_v) float32 from the recurrence -> the
        mixer's output (.., dim): the norm a head (sums in float32),
        the gate, the out-projection."""
        o = o * lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + self.spec.norm_eps
        ) * self.norm_scale.astype(F32)
        z = (
            self.g_proj(u) if self.spec.rank is None
            else self.g_up(self.g_down(u))
        )
        gate = scaled(jax.nn.sigmoid(z.astype(F32)), self.spec.gate_scale)
        o = o.reshape(*o.shape[:-2], self.spec.d_inner) * gate
        return self.out_proj(o.astype(self.dtype))

    # -- schedules -----------------------------------------------------

    def __call__(self, u):
        """The full-sequence forward from an empty state."""
        return self.scan(u, None, None)[0]

    def scan(self, u, carried, length):
        """``u`` (b, s, dim) from ``carried`` = ``(state, tail)`` of the
        ``b`` rows (None: empty) -> ``(out, (state, tail))`` after
        position ``length - 1`` (None: all ``s`` are real)."""
        spec = self.spec
        b, s, _ = u.shape
        if carried is None:
            carried = zero_state(spec, b, self.dtype)
        state, tail = carried
        qkv = self.qkv(u)
        full = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
        q, k, v = self._conv(full, s)
        if length is None:
            live = jnp.ones((1, s), bool)
            new_tail = full[:, s:]
        else:
            live = jnp.arange(s)[None, :] < length
            new_tail = lax.dynamic_slice_in_dim(
                full, length, spec.d_conv - 1, axis=1
            )
        g, beta = self._gates(u, live)
        chunked = kda_chunked_head if spec.head_decay else kda_chunked
        with jax.named_scope("kda_prefill_scan"):
            o, state = jax.vmap(chunked)(q, k, v, g, beta, state)
        return self._finish(o, u), (state, new_tail.astype(tail.dtype))

    def step(self, u_t, carried, live, prefer=None):
        """One token a row: ``u_t`` (b, 1, dim), ``carried`` the rows'
        ``(state, tail)``, ``live`` (b,) bool. A dead row's state and
        tail come back as they went in."""
        state, tail = carried
        u = u_t[:, 0]
        qkv = self.qkv(u)
        window = jnp.concatenate(
            [tail.astype(qkv.dtype), qkv[:, None]], axis=1
        )
        q, k, v = (t[:, 0] for t in self._conv(window, 1))
        g, beta = self._gates(u, live)
        alpha = jnp.exp(g)
        if self.spec.head_decay:  # the head's scalar over its channels
            alpha = jnp.broadcast_to(alpha[..., None], k.shape)
        o, state = kda_step(state, q, k, v, alpha, beta, prefer=prefer)
        tail = jnp.where(
            live[:, None, None], window[:, 1:].astype(tail.dtype), tail
        )
        return self._finish(o, u)[:, None], (state, tail)


def kda_recurrent(q, k, v, g, beta, state):
    """One sequence's recurrence, position by position: ``q``, ``k``
    (s, H, d_k), ``v`` (s, H, d_v), ``g`` (s, H, d_k) and ``beta``
    (s, H) float32, ``state`` (H, d_k, d_v) float32 -> ``o`` (s, H,
    d_v) float32 and the state after position s - 1. What
    :func:`kda_chunked` and ``ops/kda_step`` are held to."""
    q, k, v = (t.astype(F32) for t in (q, k, v))

    def one(state, xs):
        q, k, v, g, beta = xs
        decayed = state * jnp.exp(g)[..., None]
        w = beta[:, None] * (v - jnp.einsum(
            "hkv,hk->hv", decayed, k, precision=_HIGHEST
        ))
        state = decayed + k[..., None] * w[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q, precision=_HIGHEST)

    state, o = lax.scan(one, state, (q, k, v, g, beta))
    return o, state


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def _diagonal_blocks(a, width):
    """``a`` (.., C, C) -> its ``C // width`` diagonal blocks
    (.., C // width, width, width)."""
    return jnp.stack(
        [
            a[..., j: j + width, j: j + width]
            for j in range(0, a.shape[-1], width)
        ],
        axis=-3,
    )


def _unit_lower_inverse(a, block):
    """``(I + tril(a, -1))^-1`` of every matrix of ``a`` (.., C, C) at
    once. The diagonal blocks of ``block`` rows are inverted by forward
    substitution: ``block`` steps in sequence however many matrices
    there are, each a product a row (float32, no pivoting to go wrong:
    the diagonal is one). Neighbouring blocks are then merged by
    products until one block is the matrix:

        [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]
    """
    c = a.shape[-1]
    if c % block or (c // block) & (c // block - 1):
        raise ValueError(
            f"{c} rows are no power of two of blocks of {block}"
        )
    a = jnp.tril(a, -1)
    diag = _diagonal_blocks(a, block)
    eye = jnp.eye(block, dtype=a.dtype)
    rows = diag.ndim - 2

    def row(inv, r):
        a_r = lax.dynamic_index_in_dim(diag, r, rows, keepdims=False)
        new = lax.dynamic_index_in_dim(eye, r, keepdims=False) - _mm(
            "...i,...id->...d", a_r, inv
        )
        return lax.dynamic_update_index_in_dim(inv, new, r, rows), None

    inv = lax.scan(row, jnp.zeros_like(diag), jnp.arange(block))[0]
    width = block
    while width < c:
        pairs = inv.reshape(*inv.shape[:-3], -1, 2, width, width)
        top, bottom = pairs[..., 0, :, :], pairs[..., 1, :, :]
        below = _diagonal_blocks(a, 2 * width)[..., width:, :width]
        corner = -_mm(
            "...ri,...id->...rd", bottom,
            _mm("...ri,...id->...rd", below, top),
        )
        inv = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
            jnp.concatenate([corner, bottom], axis=-1),
        ], axis=-2)
        width *= 2
    return inv[..., 0, :, :]


def _channel_decays(q, k, g):
    """The pre-pass where the decay is a key channel's: ``q``, ``k``
    (n, H, C, d_k) and the log decays through a position ``g`` (n, H,
    C, d_k) of ``n`` chunks -> ``kk``, ``qk`` (n, H, C, C), ``exp(G) *
    K``, ``exp(G) * Q``, ``exp(G_C - G) * K`` (n, H, C, d_k) and
    ``exp(G_C)`` (n, H, d_k). The pairwise decays ``(H, C, C, d_k)``
    live inside ONE chunk's multiply-and-reduce: a map over the chunks,
    never a batch of them."""
    c = q.shape[2]
    at_or_before = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]

    def pairwise(xs):
        g, q, k = xs  # (H, C, d_k)
        # exp(G_r - G_i) for i <= r, zero elsewhere: (H, r, i, d_k)
        between = jnp.exp(jnp.where(
            at_or_before, g[:, :, None, :] - g[:, None, :, :], -jnp.inf
        ))
        return (
            jnp.sum(between * k[:, :, None, :] * k[:, None, :, :], -1),
            jnp.sum(between * q[:, :, None, :] * k[:, None, :, :], -1),
        )

    kk, qk = lax.map(pairwise, (g, q, k))
    into = jnp.exp(g)  # the decay from the chunk's start through r
    to_end = jnp.exp(g[:, :, -1:] - g)
    return kk, qk, into * k, into * q, to_end * k, into[:, :, -1]


def _head_decays(q, k, g):
    """The pre-pass where the decay is ONE scalar a head (``g`` (n, H,
    C)): the decay leaves the channel sums, so that ``K K^T`` and
    ``Q K^T`` are matrix products and the pairwise decays a (C, C)
    matrix a head; ``exp(G_C)`` comes back (n, H, 1)."""
    c = q.shape[2]
    between = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((c, c), bool)),
        g[..., :, None] - g[..., None, :], -jnp.inf,
    ))  # (n, H, r, i)
    kk = between * _mm("nhrk,nhik->nhri", k, k)
    qk = between * _mm("nhrk,nhik->nhri", q, k)
    into = jnp.exp(g)[..., None]
    to_end = jnp.exp(g[:, :, -1:] - g)[..., None]
    return kk, qk, into * k, into * q, to_end * k, into[:, :, -1]


# Jitted so that an eager caller compiles ONE program, not one an
# operation; inside a traced program the call is inlined.
@functools.partial(jax.jit, static_argnums=(0, 7))
def _chunked(decays, q, k, v, g, beta, state, chunk):
    """What both chunked forms share. A chunk's triangular system

        (I + Diag(beta) tril(A, -1)) W = Diag(beta) (V - (exp(G) * K) S_0)

    has a left side that does not read the carried state, so with
    ``T = (I + Diag(beta) tril(A, -1))^-1``

        W = W_v - W_k S_0,  W_v = T (beta * V),  W_k = T (beta * exp(G) * K)

    and ``T``, ``W_v`` and ``W_k`` are formed for every chunk of a
    GROUP at once (``decays``, then ONE blocked inverse), before the
    scan that carries the state over the group's chunks, which keeps
    four products a chunk and no loop. A group is at most ``_GROUP``
    chunks, which bounds what is hoisted; a longer pass is a scan over
    groups."""
    s = q.shape[0]
    chunks = -(-s // chunk)
    groups = -(-chunks // _GROUP)
    per = -(-chunks // groups)  # chunks a group
    block = min(_SOLVE_BLOCK, chunk)
    record_kernel_choice(
        "kda_prefill", chunk=chunk, group=per, solve_block=block,
        solve_steps=groups * block,
    )
    pad = groups * per * chunk - s
    if pad:  # g = 0 and beta = 0: steps nothing
        q, k, v, g, beta = (
            jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            for t in (q, k, v, g, beta)
        )

    def group(state, xs):
        # (n, C, H, ..) as the pass holds them -> (n, H, C, ..) float32
        q, k, v, g, beta = (jnp.swapaxes(t.astype(F32), 1, 2) for t in xs)
        beta = beta[..., None]
        kk, qk, k_in, q_in, k_out, decay = decays(
            q, k, jnp.cumsum(g, axis=2)
        )
        t = _unit_lower_inverse(beta * kk, block)
        w_v = _mm("nhri,nhid->nhrd", t, beta * v)
        w_k = _mm("nhri,nhid->nhrd", t, beta * k_in)

        def one(state, xs):
            w_v, w_k, q_in, qk, k_out, decay = xs
            w = w_v - _mm("hrk,hkv->hrv", w_k, state)
            o = _mm("hrk,hkv->hrv", q_in, state) + _mm(
                "hri,hiv->hrv", qk, w
            )
            state = state * decay[..., None] + _mm(
                "hik,hiv->hkv", k_out, w
            )
            return state, o

        return lax.scan(one, state, (w_v, w_k, q_in, qk, k_out, decay))

    operands = tuple(
        t.reshape(groups, per, chunk, *t.shape[1:])
        for t in (q, k, v, g, beta)
    )
    if groups == 1:  # every pass of 512 positions or fewer: no outer loop
        state, o = group(state, tuple(t[0] for t in operands))
    else:
        state, o = lax.scan(group, state, operands)
    # (.., H, C, d_v) a chunk -> the s real positions' (s, H, d_v)
    o = jnp.swapaxes(o, -3, -2)
    return o.reshape(-1, *o.shape[-2:])[:s], state


def kda_chunked(q, k, v, g, beta, state, chunk=_CHUNK):
    """:func:`kda_recurrent` in its chunked form: inside a chunk of
    ``chunk`` positions, with ``G`` the running sum of ``g``,

        A_ri = sum_c exp(G_r - G_i)_c k_rc k_ic             (i < r)
        (I + Diag(beta) tril(A, -1)) W = Diag(beta) (V - (exp(G) * K) S_0)
        o_r  = S_0^T (exp(G_r) * q_r)
               + sum_{i <= r} [sum_c exp(G_r - G_i)_c q_rc k_ic] w_i
        S_C  = Diag(exp(G_C)) S_0 + sum_i (exp(G_C - G_i) * k_i) w_i^T

    everything in float32 (:func:`_chunked`: the system solved for
    every chunk before the scan that carries the state). ``exp(G_r -
    G_i)`` is formed pairwise (never ``1 / exp(G_i)`` alone, which
    overflows under a strong decay), and only where ``i <= r``, where
    it is at most one."""
    return _chunked(_channel_decays, q, k, v, g, beta, state, chunk)


def kda_chunked_head(q, k, v, g, beta, state, chunk=_CHUNK):
    """:func:`kda_chunked` where the decay is ONE scalar a head (``g``
    (s, H)):

        A_ri = exp(G_r - G_i) (k_r . k_i)                      (i < r)
        (I + Diag(beta) tril(A, -1)) W = Diag(beta) (V - exp(G) * (K S_0))
        o_r  = exp(G_r) S_0^T q_r
               + sum_{i <= r} exp(G_r - G_i) (q_r . k_i) w_i
        S_C  = exp(G_C) S_0 + sum_i exp(G_C - G_i) k_i w_i^T

    ``exp(G_r - G_i)`` is still formed pairwise and only where
    ``i <= r``."""
    return _chunked(_head_decays, q, k, v, g, beta, state, chunk)
