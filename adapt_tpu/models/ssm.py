"""A Mamba-2 state-space mixer (the selective scan with a scalar decay
a head, "SSD") as the hybrid decoders use it BESIDE attention in a
block (``transformer_lm.BlockSpec.ssm``). One parameter structure,
three schedules over it — the cached decode is a different schedule
over the same weights, never a different model:

- **whole prompt / chunk pass** (:meth:`Mamba2Mixer.scan`): the
  recurrence in its chunked (block-decomposed) form at
  ``SsmSpec.chunk`` positions a chunk: inside a chunk the products of
  a masked-decay matrix (the MXU), between chunks a carried state; a
  chunked-prefill pass starts from the state and convolution tail the
  pass before left. Positions at or past ``length`` (a prompt shorter
  than its bucket) get ``dt = 0`` and step nothing, so what comes back
  is the state and tail of the LAST REAL position.
- **one decode step** (:meth:`Mamba2Mixer.step`): a token a row against
  its slot's state (``ops/ssm_step``: read once, written once in
  place); a dead row (negative index) keeps state and tail untouched.

What a request owns of a mixer is ``(state, tail)``: the recurrent
state ``(heads, d_state, head_dim)`` in float32 (a recurrence of
thousands of steps accumulates its rounding in the state) and the last
``d_conv - 1`` inputs of the causal convolution. The state is kept
``d_state``-major, transposed against the papers' ``(head_dim,
d_state)``: see ``ops/ssm_step``.

Per position, ``u`` the block's normed input (no projection bias):

    p            = (W_in (u * in_mult)) * mup      z | xBC | dt
    xBC          = silu(conv1d_causal(xBC) + bias)  x | B | C
    dt           = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t          = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t
    y_t          = C_t S_t + D x_t
    y            = RMSNorm_grouped(y * silu(z)) * scale
    out          = W_out y          (the block applies ``out_mult``)
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from adapt_tpu.ops.ssm_step import ssm_step

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """A block's state-space mixer, read from a model's configuration."""

    heads: int
    head_dim: int
    d_state: int
    groups: int = 1
    d_conv: int = 4
    #: Positions a chunk of the block-decomposed prefill.
    chunk: int = 128
    #: On the mixer's input, and on its output (applied by the block).
    in_mult: float = 1.0
    out_mult: float = 1.0
    #: On the in-projection's five segments ``z, x, B, C, dt``.
    mup: tuple[float, float, float, float, float] = (1.0,) * 5
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(
                f"{self.heads} mixer heads do not split into "
                f"{self.groups} groups"
            )
        if len(self.mup) != 5:
            raise ValueError("mup: one multiplier each for z, x, B, C, dt")

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``x | B | C``."""
        return self.d_inner + 2 * self.groups * self.d_state

    @property
    def proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.heads

    def state_shapes(self, rows: int, dtype):
        """``(state, tail)`` of ``rows`` requests, as shape structs."""
        return (
            jax.ShapeDtypeStruct(
                (rows, self.heads, self.d_state, self.head_dim), F32
            ),
            jax.ShapeDtypeStruct(
                (rows, self.d_conv - 1, self.conv_dim), jnp.dtype(dtype)
            ),
        )


def scaled(x, mult: float):
    """``x * mult`` with the product in float32 (a multiplier such as
    0.0375 rounded to bfloat16 first would be off by 0.3% on every
    element alike); nothing at 1."""
    if mult == 1.0:
        return x
    return (x.astype(F32) * mult).astype(x.dtype)


def zero_state(spec: SsmSpec, rows: int, dtype):
    return tuple(
        jnp.zeros(s.shape, s.dtype) for s in spec.state_shapes(rows, dtype)
    )


class Mamba2Mixer(nn.Module):
    spec: SsmSpec
    dim: int
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        spec = self.spec
        self.in_proj = nn.Dense(
            spec.proj_dim, dtype=self.dtype, use_bias=False, name="in_proj"
        )
        self.conv_kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (spec.d_conv, spec.conv_dim),
        )
        self.conv_bias = self.param(
            "conv_bias", nn.initializers.zeros, (spec.conv_dim,)
        )
        self.a_log = self.param("A_log", init_a_log, (spec.heads,))
        self.dt_bias = self.param("dt_bias", init_dt_bias, (spec.heads,))
        self.d_skip = self.param("D", nn.initializers.ones, (spec.heads,))
        self.norm_scale = self.param(
            "norm_scale", nn.initializers.ones, (spec.d_inner,)
        )
        self.out_proj = nn.Dense(
            self.dim, dtype=self.dtype, use_bias=False, name="out_proj"
        )

    # -- the pieces every schedule shares ------------------------------

    def _project(self, u):
        """-> z (.., d_inner), xBC before the convolution (.., conv_dim),
        dt before the softplus (.., heads) in float32."""
        spec = self.spec
        seg = (
            spec.d_inner, spec.d_inner, spec.groups * spec.d_state,
            spec.groups * spec.d_state, spec.heads,
        )
        mup = jnp.concatenate([
            jnp.full((n,), m, F32) for n, m in zip(seg, spec.mup)
        ])
        p = self.in_proj(scaled(u, spec.in_mult)).astype(F32) * mup
        z, xbc, dt = jnp.split(
            p, (spec.d_inner, spec.d_inner + spec.conv_dim), axis=-1
        )
        return z.astype(self.dtype), xbc.astype(self.dtype), dt

    def _conv(self, full, s: int):
        """``full`` (b, d_conv - 1 + s, conv_dim): the inputs of ``s``
        positions after the ``d_conv - 1`` before the first of them ->
        silu(conv + bias) at the ``s`` positions, in the served type."""
        w = self.conv_kernel.astype(F32)
        out = sum(
            full[:, j: j + s].astype(F32) * w[j]
            for j in range(self.spec.d_conv)
        )
        return nn.silu(out + self.conv_bias.astype(F32)).astype(self.dtype)

    def _split(self, xbc):
        """x (.., heads, head_dim); B, C (.., groups, d_state)."""
        spec = self.spec
        gn = spec.groups * spec.d_state
        x, b, c = jnp.split(xbc, (spec.d_inner, spec.d_inner + gn), axis=-1)
        lead = xbc.shape[:-1]
        return (
            x.reshape(*lead, spec.heads, spec.head_dim),
            b.reshape(*lead, spec.groups, spec.d_state),
            c.reshape(*lead, spec.groups, spec.d_state),
        )

    def _dt(self, dt):
        return jax.nn.softplus(dt + self.dt_bias.astype(F32))

    def _a(self):
        return -jnp.exp(self.a_log.astype(F32))

    def _finish(self, y, x, z):
        """``y`` (.., heads, head_dim) float32 from the recurrence ->
        the mixer's output (.., dim): the skip, the gate, the grouped
        norm (sums in float32), the out-projection."""
        spec = self.spec
        y = y + self.d_skip.astype(F32)[:, None] * x.astype(F32)
        lead = y.shape[:-2]
        y = y.reshape(*lead, spec.d_inner) * nn.silu(z.astype(F32))
        g = y.reshape(*lead, spec.groups, spec.d_inner // spec.groups)
        g = g * lax.rsqrt(
            jnp.mean(g * g, axis=-1, keepdims=True) + spec.norm_eps
        )
        y = g.reshape(*lead, spec.d_inner) * self.norm_scale.astype(F32)
        return self.out_proj(y.astype(self.dtype))

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
        z, xbc, dt = self._project(u)
        k = spec.d_conv
        full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        x, bm, cm = self._split(self._conv(full, s))
        dt = self._dt(dt)
        if length is None:
            new_tail = full[:, s:]
        else:
            # Padding steps nothing: no decay and no input there.
            dt = jnp.where(jnp.arange(s)[None, :, None] < length, dt, 0.0)
            new_tail = lax.dynamic_slice_in_dim(full, length, k - 1, axis=1)
        with jax.named_scope("ssm_prefill_scan"):
            y, state = jax.vmap(
                lambda *a: _ssd_chunked(*a, self._a(), spec.chunk)
            )(x, dt, bm, cm, state)
        return self._finish(y, x, z), (state, new_tail.astype(tail.dtype))

    def step(self, u_t, carried, live, prefer=None):
        """One token a row: ``u_t`` (b, 1, dim), ``carried`` the rows'
        ``(state, tail)``, ``live`` (b,) bool. A dead row's state and
        tail come back as they went in."""
        state, tail = carried
        z, xbc, dt = self._project(u_t[:, 0])
        window = jnp.concatenate(
            [tail.astype(xbc.dtype), xbc[:, None]], axis=1
        )
        x, bm, cm = self._split(self._conv(window, 1)[:, 0])
        dt = jnp.where(live[:, None], self._dt(dt), 0.0)
        y, state = ssm_step(state, x, dt, self._a(), bm, cm, prefer=prefer)
        tail = jnp.where(
            live[:, None, None], window[:, 1:].astype(tail.dtype), tail
        )
        return self._finish(y, x, z)[:, None], (state, tail)


def init_a_log(key, shape, dtype=F32):
    """Mamba-2's own: ``A = exp(A_log)`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def init_dt_bias(key, shape, dtype=F32):
    """Mamba-2's own: ``softplus(dt_bias)`` log-uniform in [1e-3,
    1e-1], so that a state neither vanishes in a step nor never
    decays."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


def _ssd_chunked(x, dt, b, c, state, a, chunk):
    """One sequence's recurrence, block-decomposed (Mamba-2's SSD):
    ``x`` (s, H, P), ``dt`` (s, H) float32, ``b``, ``c`` (s, G, N),
    ``state`` (H, N, P) float32, ``a`` (H,) -> ``y`` (s, H, P) float32
    and the state after position s - 1. A scan over chunks carries the
    state; inside a chunk position i reads position j <= i through
    ``C_i . B_j * exp(sum of dt A over (j, i]) * dt_j x_j``: three
    products on the MXU (operands in the served type, float32 sums),
    the decays in float32."""
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    per = heads // groups
    pad = -s % chunk
    if pad:  # dt = 0: steps nothing
        x, dt, b, c = (
            jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            for t in (x, dt, b, c)
        )
    q = chunk
    mm = x.dtype  # the products' operand type
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one(state, xs):
        x, dt, b, c = xs  # (q, H, P), (q, H), (q, G, N), (q, G, N)
        cs = jnp.cumsum(dt * a, axis=0)  # (q, H): log decay through i
        xdt = x.astype(F32) * dt[..., None]
        # inside the chunk
        cb = jnp.einsum("ign,jgn->gij", c, b, preferred_element_type=F32)
        seg = cs.T[:, :, None] - cs.T[:, None, :]  # (H, i, j)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        m = (
            jnp.repeat(cb, per, axis=0) * decay
        ).astype(mm)  # (H, i, j)
        y = jnp.einsum(
            "hij,jhp->ihp", m, xdt.astype(mm), preferred_element_type=F32
        )
        # what the state carried in adds
        sg = state.astype(mm).reshape(groups, per, n, p)
        y_in = jnp.einsum(
            "ign,gknp->igkp", c, sg, preferred_element_type=F32
        ).reshape(q, heads, p)
        y = y + y_in * jnp.exp(cs)[..., None]
        # the state the chunk leaves
        to_end = jnp.exp(cs[-1][None, :] - cs)  # (q, H)
        xg = (xdt * to_end[..., None]).astype(mm).reshape(q, groups, per, p)
        add = jnp.einsum(
            "jgn,jgkp->gknp", b, xg, preferred_element_type=F32
        ).reshape(heads, n, p)
        state = state * jnp.exp(cs[-1])[:, None, None] + add
        return state, y

    def chunks(t):
        return t.reshape(-1, q, *t.shape[1:])

    state, y = lax.scan(one, state, tuple(chunks(t) for t in (x, dt, b, c)))
    return y.reshape(-1, heads, p)[:s], state
