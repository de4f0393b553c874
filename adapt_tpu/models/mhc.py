"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606): the residual is ``n`` streams of
the model's width, and every sub-layer ``F`` (attention, MLP) reads a
learned mix of them and writes back through a doubly stochastic
``n x n``:

    r      = RMSNorm(vec(x))                              over n C
    Hpre~  = a_pre (r phi_pre) + b_pre                    (n)
    Hpost~ = a_post (r phi_post) + b_post                 (n)
    Hres~  = clamp(a_res mat(r phi_res) + b_res, lo, hi)  (n x n)
    H_pre  = sigmoid(Hpre~);  H_post = 2 sigmoid(Hpost~)
    H_res  = Sinkhorn(exp(Hres~)): columns then rows normalised,
             ``sinkhorn_iters`` times
    u      = H_pre x                                      (C)
    x'     = H_res x + H_post^T F(norm(u))

Coefficients, Sinkhorn and both mixes run in float32, whatever the
block's dtype, and the streams themselves are float32 between blocks
(``merge`` returns ``x``'s type). ``x`` is (b, s, n, C): the embedding
copies a token's row into the n streams and the head sums them
(``models/transformer_lm``: ``TokenEmbed.streams``, ``LMHead.streams``).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class HyperSpec:
    """The residual streams as a configuration states them."""

    streams: int  # hc_mult
    sinkhorn_iters: int = 20  # hc_sinkhorn_iters
    eps: float = 1e-6  # hc_eps: the RMSNorm's and Sinkhorn's denominators
    clamp: tuple[float, float] = (-30.0, 30.0)  # mhc_h_res_clamp_min/max

    def __post_init__(self):
        if self.streams < 2:
            raise ValueError(f"streams must be >= 2, got {self.streams}")


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``m`` (..., n, n) positive -> doubly stochastic: ``iters`` times
    the columns normalised, then the rows."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """The coefficients of ONE sub-layer: ``__call__(x)`` -> ``(u,
    mix)``, the sub-layer's input (before the block's own norm) and
    what :func:`merge` needs to write its output back."""

    spec: HyperSpec
    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        spec, n = self.spec, self.spec.streams
        f32 = jnp.float32
        xf = x.astype(f32)  # (b, s, n, C)
        r = nn.RMSNorm(epsilon=spec.eps, dtype=f32, name="norm")(
            xf.reshape(*xf.shape[:-2], n * self.dim)
        )
        phi = self.param(
            "phi", nn.initializers.lecun_normal(), (n * self.dim, n * (n + 2))
        )
        a = self.param("a", nn.initializers.ones, (3,))
        b = self.param("b", nn.initializers.zeros, (n * (n + 2),))
        h = jnp.dot(r, phi.astype(f32), precision="highest")
        a, b = a.astype(f32), b.astype(f32)
        pre = a[0] * h[..., :n] + b[:n]
        post = a[1] * h[..., n: 2 * n] + b[n: 2 * n]
        res = a[2] * h[..., 2 * n:] + b[2 * n:]
        res = jnp.clip(res, *spec.clamp).reshape(*res.shape[:-1], n, n)
        h_res = sinkhorn(jnp.exp(res), spec.sinkhorn_iters, spec.eps)
        # Elementwise, not a product the MXU would round to bfloat16.
        u = (jax.nn.sigmoid(pre)[..., None] * xf).sum(-2)
        return u.astype(self.dtype), (2.0 * jax.nn.sigmoid(post), h_res)


def merge(x, f, mix):
    """``H_res x + H_post^T f``: the streams (b, s, n, C) after a
    sub-layer whose output is ``f`` (b, s, C)."""
    h_post, h_res = mix
    xf = x.astype(jnp.float32)
    out = (h_res[..., None] * xf[..., None, :, :]).sum(-2) + (
        h_post[..., None] * f.astype(jnp.float32)[..., None, :]
    )
    return out.astype(x.dtype)
