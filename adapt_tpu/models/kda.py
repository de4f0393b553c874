"""A gated delta-rule linear-attention mixer (Kimi Delta Attention: the
delta rule with a decay per key channel) as the hybrid decoders use it
IN PLACE OF attention in a block (``transformer_lm.BlockSpec.linear``):
such a block holds a recurrent state a request and NO pages. One
parameter structure, three schedules over it, as ``models/ssm``:

- **whole prompt / chunk pass** (:meth:`KdaMixer.scan`): the recurrence
  in its chunked form at ``_CHUNK`` positions a chunk (a unit
  lower-triangular solve inside a chunk, a carried state between
  chunks); a chunked-prefill pass starts from the state and convolution
  tail the pass before left. Positions at or past ``length`` (a prompt
  shorter than its bucket) get ``g = 0`` and ``beta = 0`` and step
  nothing, so what comes back is the state and tail of the LAST REAL
  position.
- **one decode step** (:meth:`KdaMixer.step`): a token a row against
  its slot's state (``ops/kda_step``: read once, written once in
  place); a dead row (negative index) keeps state and tail untouched.

What a request owns of a mixer is ``(state, tail)``: the state
``(heads, d_k, d_v)`` in float32 and the last ``d_conv - 1`` inputs of
the causal convolution over ``q | k | v``.

Per position, ``u`` the block's normed input (no projection bias):

    q, k, v  = silu(conv1d_causal(W_qkv u))           heads x d each
    q        = q / |q|_2 * d_k^-1/2;   k = k / |k|_2
    g        = -exp(A_log) * softplus(W_f2 (W_f1 u) + dt_bias)
    alpha    = exp(g)                   a head AND key channel, in (0, 1)
    beta     = sigmoid(W_b u) * (2 if neg_eigval else 1)       a head
    S_t      = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T
    o_t      = S_t^T q
    out      = W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u))]
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from adapt_tpu.models.ssm import init_a_log, zero_state
from adapt_tpu.ops.kda_step import kda_step

F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
#: Positions a chunk of the chunked prefill.
_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class KdaSpec:
    """A block's linear-attention mixer, read from a model's
    configuration."""

    heads: int
    head_dim: int  # d_k = d_v
    #: Width of the low-rank pairs that make the decay and the gate.
    rank: int
    d_conv: int = 4
    #: ``beta`` in (0, 2): ``I - beta k k^T`` may flip a direction.
    neg_eigval: bool = True
    norm_eps: float = 1e-5

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``q | k | v``."""
        return 3 * self.d_inner

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
        self.f_down = dense(spec.rank, "f_down")
        self.f_up = dense(spec.d_inner, "f_up")
        self.a_log = self.param("A_log", init_a_log, (spec.heads,))
        self.dt_bias = self.param("dt_bias", init_dt_bias, (spec.d_inner,))
        self.b_proj = dense(spec.heads, "b_proj")
        self.g_down = dense(spec.rank, "g_down")
        self.g_up = dense(spec.d_inner, "g_up")
        self.norm_scale = self.param(
            "norm_scale", nn.initializers.ones, (spec.head_dim,)
        )
        self.out_proj = dense(self.dim, "out_proj")

    # -- the pieces every schedule shares ------------------------------

    def _heads(self, t):
        return t.reshape(*t.shape[:-1], self.spec.heads, self.spec.head_dim)

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
        q, k, v = (self._heads(t) for t in jnp.split(out, 3, axis=-1))

        def unit(t):
            return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        q = unit(q) * self.spec.head_dim ** -0.5
        return tuple(t.astype(self.dtype) for t in (q, unit(k), v))

    def _gates(self, u, live):
        """``g`` (.., heads, d_k) and ``beta`` (.., heads) in float32;
        where ``live`` (broadcast over the leading axes) is false both
        are zero: the position steps nothing."""
        f = self._heads(
            self.f_up(self.f_down(u)).astype(F32) + self.dt_bias.astype(F32)
        )
        g = -jnp.exp(self.a_log.astype(F32))[:, None] * jax.nn.softplus(f)
        beta = jax.nn.sigmoid(self.b_proj(u).astype(F32))
        if self.spec.neg_eigval:
            beta = 2.0 * beta
        return (
            jnp.where(live[..., None, None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0),
        )

    def _finish(self, o, u):
        """``o`` (.., heads, d_v) float32 from the recurrence -> the
        mixer's output (.., dim): the norm a head (sums in float32),
        the gate, the out-projection."""
        o = o * lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + self.spec.norm_eps
        ) * self.norm_scale.astype(F32)
        gate = jax.nn.sigmoid(self.g_up(self.g_down(u)).astype(F32))
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
        with jax.named_scope("kda_prefill_scan"):
            o, state = jax.vmap(kda_chunked)(q, k, v, g, beta, state)
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
        o, state = kda_step(state, q, k, v, jnp.exp(g), beta, prefer=prefer)
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


def _solve_unit_lower(a, rhs):
    """``(I + tril(a, -1)) w = rhs`` by forward substitution: ``a``
    (H, C, C), ``rhs`` (H, C, d) -> ``w`` (H, C, d). Row ``r`` reads
    the rows before it, so C steps in sequence, each a product a row
    (float32, no pivoting to go wrong: the diagonal is one)."""
    c = a.shape[1]
    a = jnp.tril(a, -1)

    def row(w, r):
        a_r = lax.dynamic_index_in_dim(a, r, 1, keepdims=False)  # (H, C)
        new = lax.dynamic_index_in_dim(rhs, r, 1, keepdims=False) - jnp.einsum(
            "hi,hid->hd", a_r, w, precision=_HIGHEST
        )
        return lax.dynamic_update_index_in_dim(w, new, r, 1), None

    return lax.scan(row, jnp.zeros_like(rhs), jnp.arange(c))[0]


def kda_chunked(q, k, v, g, beta, state, chunk=_CHUNK):
    """:func:`kda_recurrent` in its chunked form: a scan over chunks of
    ``chunk`` positions carries the state; inside a chunk, with ``G``
    the running sum of ``g``,

        A_ri = sum_c exp(G_r - G_i)_c k_rc k_ic             (i < r)
        (I + Diag(beta) tril(A, -1)) W = Diag(beta) (V - (exp(G) * K) S_0)
        o_r  = S_0^T (exp(G_r) * q_r)
               + sum_{i <= r} [sum_c exp(G_r - G_i)_c q_rc k_ic] w_i
        S_C  = Diag(exp(G_C)) S_0 + sum_i (exp(G_C - G_i) * k_i) w_i^T

    everything in float32. ``exp(G_r - G_i)`` is formed pairwise (never
    ``1 / exp(G_i)`` alone, which overflows under a strong decay), and
    only where ``i <= r``, where it is at most one."""
    s, heads, d_k = q.shape
    pad = -s % chunk
    if pad:  # g = 0 and beta = 0: steps nothing
        q, k, v, g, beta = (
            jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            for t in (q, k, v, g, beta)
        )
    q, k, v = (t.astype(F32) for t in (q, k, v))
    at_or_before = jnp.tril(jnp.ones((chunk, chunk), bool))

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    def one(state, xs):
        q, k, v, g, beta = xs  # (C, H, d), (C, H)
        gc = jnp.cumsum(g, axis=0)  # (C, H, d_k): log decay through r
        # exp(G_r - G_i) for i <= r, zero elsewhere: (H, r, i, d_k)
        gh = jnp.swapaxes(gc, 0, 1)
        between = jnp.exp(jnp.where(
            at_or_before[None, :, :, None],
            gh[:, :, None, :] - gh[:, None, :, :], -jnp.inf,
        ))
        kh, qh = jnp.swapaxes(k, 0, 1), jnp.swapaxes(q, 0, 1)
        kk = jnp.sum(between * kh[:, :, None, :] * kh[:, None, :, :], -1)
        qk = jnp.sum(between * qh[:, :, None, :] * kh[:, None, :, :], -1)
        bh = beta.T  # (H, C)
        into = jnp.exp(gh)  # the decay from the chunk's start through r
        rhs = bh[..., None] * (
            jnp.swapaxes(v, 0, 1) - mm("hrk,hkv->hrv", into * kh, state)
        )
        w = _solve_unit_lower(bh[..., None] * kk, rhs)  # (H, C, d_v)
        o = mm("hrk,hkv->hrv", into * qh, state) + mm("hri,hiv->hrv", qk, w)
        to_end = jnp.exp(gh[:, -1:, :] - gh)  # (H, C, d_k)
        state = state * into[:, -1, :, None] + mm(
            "hik,hiv->hkv", to_end * kh, w
        )
        return state, jnp.swapaxes(o, 0, 1)

    def chunks(t):
        return t.reshape(-1, chunk, *t.shape[1:])

    state, o = lax.scan(
        one, state, tuple(chunks(t) for t in (q, k, v, g, beta))
    )
    return o.reshape(-1, heads, v.shape[-1])[:s], state
