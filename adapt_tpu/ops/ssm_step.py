"""The decode step of a state-space mixer's recurrence (Mamba-2's
selective state update): one token a row against the row's recurrent
state, which is read ONCE, advanced, and written ONCE in place.

    S' = exp(dt A) S + (dt x) (x) B        y = S' C

per row and head, ``S`` a ``(state, head_dim)`` matrix in float32:
``state`` on the sublanes and ``head_dim`` on the lanes, so that ``x``
and ``y`` are rows as the projections make and take them, ``y`` is a
sum over sublanes, and only ``B`` and ``C`` (shared by every head of a
group) have to be turned into columns. At 32 heads of 128 x 256 a
row's state is 4.19 MB; a step that read it for the update and again
for ``y`` (what two plain ``jax.numpy`` expressions may compile to)
would move it three times.

A row whose ``dt`` is zero is left EXACTLY as it was (``exp(0) = 1``,
``0 * x = 0``): that is how a dead row of the lockstep batch (an idle
slot, or one whose prompt is still being prefilled pass by pass into
this very state) keeps its state through a step.

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


def ssm_step_reference(state, x, dt, a, b, c):
    """The plain arm. ``state`` (rows, heads, n, p) float32; ``x``
    (rows, heads, p); ``dt`` (rows, heads) float32, after the softplus
    (zero: the row keeps its state); ``a`` (heads,) float32, negative;
    ``b``, ``c`` (rows, groups, n), head ``h`` reading group ``h //
    (heads // groups)``. Returns ``(y, state')``, ``y`` (rows, heads,
    p) float32."""
    rows, heads, n, p = state.shape
    per = heads // b.shape[1]
    bh = jnp.repeat(b.astype(F32), per, axis=1)  # (rows, heads, n)
    ch = jnp.repeat(c.astype(F32), per, axis=1)
    decay = jnp.exp(dt * a)  # (rows, heads)
    dtx = dt[..., None] * x.astype(F32)  # (rows, heads, p)
    new = (
        state * decay[..., None, None]
        + bh[..., :, None] * dtx[..., None, :]
    )
    y = jnp.sum(new * ch[..., :, None], axis=2)
    return y, new


def heads_per_step(per_group: int, n: int, p: int) -> int:
    """Heads of ONE group a grid step covers: the largest divisor of
    the group whose float32 block stays within ``_STEP_STATE_BYTES``
    (8 heads of 256 x 128). Derived from the operands, never set."""
    for heads in range(per_group, 0, -1):
        if per_group % heads == 0 and heads * n * p * 4 <= _STEP_STATE_BYTES:
            return heads
    return 1


def _kernel(s_ref, dec_ref, dtx_ref, b_ref, c_ref, y_ref, o_ref):
    heads, n, p = s_ref.shape[1:]
    # B and C arrive as rows (n on the lanes); the update wants them
    # down the sublanes and alike on every lane: a row laid over p
    # sublanes and transposed is exactly that.
    bmat = jnp.broadcast_to(b_ref[0, 0].astype(F32), (p, n)).T  # (n, p)
    cmat = jnp.broadcast_to(c_ref[0, 0].astype(F32), (p, n)).T
    for h in range(heads):
        new = (
            s_ref[0, h] * dec_ref[0, h: h + 1, :]
            + bmat * dtx_ref[0, h: h + 1, :]
        )
        o_ref[0, h] = new
        y_ref[0, h: h + 1, :] = jnp.sum(new * cmat, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads",))
def _ssm_step_impl(state, decay, dtx, b, c, heads):
    """The kernel under ONE name in a device trace (``_ssm_step_impl``).
    ``decay`` and ``dtx`` are (rows, H, p) float32 rows (a head's decay
    laid over its lanes: 16 KB a row beside 4 MB of state); ``b``, ``c``
    (rows, groups, 1, n)."""
    rows, total, n, p = state.shape
    groups = b.shape[1]
    steps = total // groups // heads  # grid steps a group

    def of_head(r, g, j):
        return (r, g * steps + j, 0)

    def of_group(r, g, j):
        return (r, g, 0, 0)

    row = pl.BlockSpec((1, heads, p), of_head)
    vec = pl.BlockSpec((1, 1, 1, n), of_group)
    mat = pl.BlockSpec(
        (1, heads, n, p), lambda r, g, j: (r, g * steps + j, 0, 0)
    )
    return pl.pallas_call(
        _kernel,
        grid=(rows, groups, steps),
        in_specs=[mat, row, row, vec, vec],
        out_specs=[row, mat],
        out_shape=[
            jax.ShapeDtypeStruct((rows, total, p), F32),
            jax.ShapeDtypeStruct(state.shape, F32),
        ],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=pallas_interpret(),
    )(state, decay, dtx, b, c)


def ssm_step(state, x, dt, a, b, c, prefer=None):
    """``(y, state')`` as :func:`ssm_step_reference`, the state
    advanced in place where the caller donated it."""
    rows, heads, n, p = state.shape
    groups = b.shape[1]
    step = heads_per_step(max(heads // groups, 1), n, p)
    unsupported = None
    if state.dtype != F32:
        unsupported = f"the state is {state.dtype}, not float32"
    elif heads % groups:
        unsupported = f"{heads} heads do not split into {groups} groups"
    elif not on_tpu():
        pass  # the interpreter takes any shape
    elif n % 128 or p % 128:
        unsupported = (
            f"a head's state ({n}, {p}) is not whole (128, 128) tiles"
        )
    elif step % 8 and step != heads:
        unsupported = (
            f"{step} heads a grid step ({heads} in {groups} groups) are "
            "not whole sublane tiles of the x and y rows"
        )
    if not resolve_prefer("ssm_step", prefer, unsupported, on_tpu()):
        return ssm_step_reference(state, x, dt, a, b, c)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (rows, heads, p))
    dtx = dt[..., None] * x.astype(F32)
    return tuple(_ssm_step_impl(
        state, decay, dtx, b[:, :, None, :], c[:, :, None, :],
        heads=step,
    ))
