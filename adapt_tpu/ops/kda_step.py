"""The decode step of a gated delta-rule layer (Kimi Delta Attention:
the delta rule with a decay per key channel): one token a row against
the row's recurrent state, which is read ONCE, advanced, and written
ONCE in place.

    S~ = Diag(alpha) S        w = beta (v - S~^T k)
    S' = S~ + k w^T           o = S'^T q

per row and head, ``S`` a ``(d_k, d_v)`` matrix in float32: ``d_k`` on
the sublanes and ``d_v`` on the lanes, so that ``v``, ``w`` and ``o``
are rows as the projections make and take them and both ``S~^T k`` and
``S'^T q`` are sums over sublanes. This is no diagonal recurrence
(``ops/ssm_step``): the rank-one correction needs ``S~^T k`` before
anything is written, so a head's 64 KB stands in VMEM for two passes;
and ``alpha``, ``k`` and ``q`` run down the sublanes, three rows a head
that the kernel turns into columns.

A row whose ``alpha`` is one and whose ``beta`` is zero is left EXACTLY
as it was (``1 * S + k * 0``): that is how a dead row of the lockstep
batch (an idle slot, or one whose prompt is still being prefilled pass
by pass into this very state) keeps its state through a step.

``prefer`` as everywhere in ``ops`` (``dispatch.resolve_prefer``): the
Pallas kernel on a TPU, the plain ``jax.numpy`` arm elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapt_tpu.ops.dispatch import on_tpu, pallas_interpret, resolve_prefer

F32 = jnp.float32

#: Bytes of state one grid step may cover: a block is double-buffered
#: on its way in and on its way out, so four of these stand in VMEM.
_STEP_STATE_BYTES = 1 << 20


def kda_step_reference(state, q, k, v, alpha, beta):
    """The plain arm. ``state`` (rows, heads, d_k, d_v) float32; ``q``,
    ``k`` (rows, heads, d_k) as the layer normalised them, ``v`` (rows,
    heads, d_v); ``alpha`` (rows, heads, d_k) float32 in (0, 1], the
    decay a key channel; ``beta`` (rows, heads) float32 (zero, with
    ``alpha`` one: the row keeps its state). Returns ``(o, state')``,
    ``o`` (rows, heads, d_v) float32."""
    q, k, v = (t.astype(F32) for t in (q, k, v))
    decayed = state * alpha[..., None]
    w = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=2))
    new = decayed + k[..., None] * w[..., None, :]
    return jnp.sum(new * q[..., None], axis=2), new


def heads_per_step(heads: int, d_k: int, d_v: int) -> int:
    """Heads a grid step covers: the largest divisor of ``heads`` whose
    float32 block stays within ``_STEP_STATE_BYTES`` (16 heads of 128 x
    128). Derived from the operands, never set."""
    for n in range(heads, 0, -1):
        if heads % n == 0 and n * d_k * d_v * 4 <= _STEP_STATE_BYTES:
            return n
    return 1


def _kernel(s_ref, q_ref, k_ref, v_ref, a_ref, b_ref, o_ref, n_ref):
    heads, d_k, d_v = s_ref.shape[1:]
    q, k, v = (r[0].astype(F32) for r in (q_ref, k_ref, v_ref))

    def column(rows, h):
        # A row (d_k on the lanes) laid over d_v sublanes and
        # transposed: the same value down a sublane, alike on every
        # lane.
        return jnp.broadcast_to(rows[h: h + 1, :], (d_v, d_k)).T

    for h in range(heads):
        kc = column(k, h)
        decayed = s_ref[0, h] * column(a_ref[0], h)
        seen = jnp.sum(decayed * kc, axis=0, keepdims=True)  # S~^T k
        w = b_ref[0, h: h + 1, :] * (v[h: h + 1, :] - seen)
        new = decayed + kc * w
        n_ref[0, h] = new
        o_ref[0, h: h + 1, :] = jnp.sum(
            new * column(q, h), axis=0, keepdims=True
        )


@functools.partial(jax.jit, static_argnames=("heads",))
def _kda_step_impl(state, q, k, v, alpha, beta, heads):
    """The kernel under ONE name in a device trace (``_kda_step_impl``).
    ``beta`` is (rows, H, d_v) float32, a head's scalar laid over its
    lanes (32 KB a row beside 4 MB of state)."""
    rows, total, d_k, d_v = state.shape

    def row(width):
        return pl.BlockSpec((1, heads, width), lambda r, j: (r, j, 0))

    mat = pl.BlockSpec((1, heads, d_k, d_v), lambda r, j: (r, j, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(rows, total // heads),
        in_specs=[mat, row(d_k), row(d_k), row(d_v), row(d_k), row(d_v)],
        out_specs=[row(d_v), mat],
        out_shape=[
            jax.ShapeDtypeStruct((rows, total, d_v), F32),
            jax.ShapeDtypeStruct(state.shape, F32),
        ],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=pallas_interpret(),
    )(state, q, k, v, alpha, beta)


def kda_step(state, q, k, v, alpha, beta, prefer=None):
    """``(o, state')`` as :func:`kda_step_reference`, the state
    advanced in place where the caller donated it."""
    rows, heads, d_k, d_v = state.shape
    step = heads_per_step(heads, d_k, d_v)
    unsupported = None
    if state.dtype != F32:
        unsupported = f"the state is {state.dtype}, not float32"
    elif not on_tpu():
        pass  # the interpreter takes any shape
    elif d_k % 128 or d_v % 128:
        unsupported = (
            f"a head's state ({d_k}, {d_v}) is not whole (128, 128) tiles"
        )
    elif step % 16 and step != heads:
        unsupported = (
            f"{step} of {heads} heads a grid step are not whole sublane "
            "tiles of the q, k and v rows"
        )
    if not resolve_prefer("kda_step", prefer, unsupported, on_tpu()):
        return kda_step_reference(state, q, k, v, alpha, beta)
    return tuple(_kda_step_impl(
        state, q, k, v, alpha,
        jnp.broadcast_to(beta[..., None], (rows, heads, d_v)),
        heads=step,
    ))
