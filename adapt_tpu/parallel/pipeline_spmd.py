"""SPMD pipeline parallelism: one XLA program, activations on ICI.

The reference's pipeline is MPMD over TCP — one process per stage, framed
sockets between them (SURVEY.md §2.3). On TPU the idiomatic equivalent for
*homogeneous* stages (transformer blocks) is a single SPMD program: stack
the L identical blocks' params with leading dim L, shard that dim over the
``pp`` mesh axis (each device holds L/P consecutive blocks), and run the
GPipe-style schedule as a ``lax.scan`` whose per-step activation hand-off
is a ``lax.ppermute`` — compiled by XLA onto ICI with no host round-trips,
no framing, no codec (the design SURVEY §2.3 calls for).

Heterogeneous-stage models (ResNet/EfficientNet) use the MPMD path
(``runtime.LocalPipeline`` / the adaptive dispatcher); this module is the
throughput path for block-structured transformers, and it composes with
``dp`` (batch axis) in the same mesh — and it is differentiable, so the
same schedule backs pipelined training steps.

Two schedules, one body:

- ``schedule="serial"`` (GPipe): at tick t, rank p computes microbatch
  ``t - p``; the ppermute hop for a microbatch's activation is CONSUMED
  by the next rank's compute in the very next tick, so the hop sits on
  the critical path — each tick costs compute + hop. T = M + P - 1
  ticks.
- ``schedule="overlap"`` (double-buffered): each rank holds a circular
  buffer of its last ``hop_buffers - 1`` outputs and, inside one scan
  step, ISSUES the ppermute for the activation computed ``d =
  hop_buffers - 1`` ticks ago while computing the current microbatch —
  the two have no data dependency, so XLA schedules the
  collective-permute concurrently with compute (async CP start/done on
  TPU) and hop latency hides under compute: each tick costs
  max(compute, hop). The price is schedule depth — a hop takes d + 1
  ticks to land, T = M + (P - 1)(d + 1) — so for M >> P the wall-clock
  ratio approaches (compute + hop) / max(compute, hop): up to 2x when
  hops rival compute ("On Optimizing the Communication of Model
  Parallelism", PAPERS.md). Outputs are BIT-IDENTICAL to the serial
  schedule: every microbatch runs the same blocks in the same order —
  only the tick a hop occupies moves (tested for 2-4 stages).

Knob plumbing: ``config.PipelineConfig`` carries (schedule,
microbatches, hop_buffers) for drivers; ``benchmarks/micro/
hop_overlap.py`` measures the schedules against each other on CPU.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_block_variables: list[Any]) -> Any:
    """Stack identical-structure per-block param pytrees along a new leading
    axis (the pipeline-shardable layout)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_block_variables)


def spmd_pipeline(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    xs: jax.Array,
    mesh: Mesh,
    axis: str = "pp",
    batch_axis: str | None = None,
    schedule: str = "serial",
    hop_buffers: int = 2,
) -> jax.Array:
    """Run ``xs`` (shape [M, mb, ...]) through L stacked blocks pipelined
    over the ``axis`` dimension of ``mesh``.

    ``block_fn(params_i, x) -> y`` applies ONE block (y.shape == x.shape).
    ``stacked_params`` leaves have leading dim L with L % P == 0.
    If ``batch_axis`` is given, the microbatch batch dim (dim 1 of xs) is
    additionally sharded over it (dp x pp in one program).

    ``schedule="overlap"`` runs the double-buffered schedule (module
    docstring): ``hop_buffers`` >= 2 sets the circular activation-buffer
    depth (send delay = hop_buffers - 1 ticks; 2 = classic double
    buffering, more hides longer hop latency at more ticks). Both
    schedules produce bit-identical outputs.
    """
    if schedule not in ("serial", "overlap"):
        raise ValueError(
            f"schedule={schedule!r}: expected 'serial' or 'overlap'"
        )
    if schedule == "overlap" and hop_buffers < 2:
        raise ValueError(
            f"hop_buffers must be >= 2 for the overlap schedule, got "
            f"{hop_buffers}"
        )
    num_ranks = mesh.shape[axis]
    num_micro = xs.shape[0]
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead % num_ranks:
        raise ValueError(
            f"stacked block count {lead} not divisible by pipeline ranks "
            f"{num_ranks}"
        )

    def local_stack(params_local, h):
        def body(carry, p):
            return block_fn(p, carry), None

        h, _ = lax.scan(body, h, params_local)
        return h

    param_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    x_spec = (
        P(None, batch_axis) if batch_axis is not None else P()
    )
    vary_axes = (axis,) + ((batch_axis,) if batch_axis else ())
    shift = [(i, i + 1) for i in range(num_ranks - 1)]

    def pipelined_serial(params_local, xs_local):
        rank = lax.axis_index(axis)
        ticks = num_micro + num_ranks - 1
        mb_shape = xs_local.shape[1:]

        def step(carry, t):
            prev_y, outputs = carry
            # Hand the previous tick's output to the next rank (ICI hop).
            # The next compute CONSUMES recv immediately, so the hop is
            # on the critical path — the serial schedule's defining cost.
            recv = lax.ppermute(prev_y, axis, shift)
            inject = lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, num_micro - 1), 0, keepdims=False
            )
            h = jnp.where(rank == 0, inject, recv)
            y = local_stack(params_local, h)
            m = t - rank
            is_last = rank == num_ranks - 1
            valid = jnp.logical_and(m >= 0, m < num_micro)
            write = jnp.logical_and(is_last, valid)
            updated = lax.dynamic_update_index_in_dim(
                outputs,
                y.astype(outputs.dtype),
                jnp.clip(m, 0, num_micro - 1),
                0,
            )
            outputs = jnp.where(write, updated, outputs)
            return (y, outputs), None

        init = lax.pcast(
            (
                jnp.zeros(mb_shape, xs_local.dtype),
                jnp.zeros((num_micro, *mb_shape), xs_local.dtype),
            ),
            vary_axes,
            to="varying",
        )
        (_, outputs), _ = lax.scan(step, init, jnp.arange(ticks))
        # Only the last rank holds real outputs; replicate over the pipeline
        # axis (zeros elsewhere make psum a broadcast of rank P-1's buffer).
        return lax.psum(outputs, axis)

    def pipelined_overlap(params_local, xs_local):
        rank = lax.axis_index(axis)
        d = hop_buffers - 1  # send delay (ticks a hop has to hide in)
        ticks = num_micro + (num_ranks - 1) * (d + 1)
        mb_shape = xs_local.shape[1:]

        def step(carry, t):
            cur, sendbuf, outputs = carry
            # Issue the hop for the activation computed d ticks ago
            # (circular buffer slot t % d). It has NO data dependency on
            # this tick's compute below — XLA is free to run the
            # collective-permute concurrently with it, which is the
            # whole point of the schedule.
            send = lax.dynamic_index_in_dim(
                sendbuf, jnp.mod(t, d), 0, keepdims=False
            )
            recv = lax.ppermute(send, axis, shift)
            y = local_stack(params_local, cur)
            m = t - (num_ranks - 1) * (d + 1)
            write = jnp.logical_and(
                rank == num_ranks - 1,
                jnp.logical_and(m >= 0, m < num_micro),
            )
            updated = lax.dynamic_update_index_in_dim(
                outputs,
                y.astype(outputs.dtype),
                jnp.clip(m, 0, num_micro - 1),
                0,
            )
            outputs = jnp.where(write, updated, outputs)
            sendbuf = lax.dynamic_update_index_in_dim(
                sendbuf, y, jnp.mod(t, d), 0
            )
            # Rank 0 injects next tick's microbatch; everyone else
            # consumes what just arrived (computed d+1 ticks ago
            # upstream — bubble ticks carry garbage the output mask
            # drops).
            inject = lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t + 1, 0, num_micro - 1), 0,
                keepdims=False,
            )
            cur = jnp.where(rank == 0, inject, recv)
            return (cur, sendbuf, outputs), None

        first = lax.dynamic_index_in_dim(xs_local, 0, 0, keepdims=False)
        init = lax.pcast(
            (
                jnp.where(
                    rank == 0, first, jnp.zeros(mb_shape, xs_local.dtype)
                ),
                jnp.zeros((d, *mb_shape), xs_local.dtype),
                jnp.zeros((num_micro, *mb_shape), xs_local.dtype),
            ),
            vary_axes,
            to="varying",
        )
        (_, _, outputs), _ = lax.scan(step, init, jnp.arange(ticks))
        return lax.psum(outputs, axis)

    body = (
        pipelined_serial if schedule == "serial" else pipelined_overlap
    )
    pipelined = jax.shard_map(
        body, mesh=mesh, in_specs=(param_specs, x_spec), out_specs=x_spec,
        check_vma=False,
    )
    return pipelined(stacked_params, xs)


def spmd_pipeline_from_config(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    config,
    axis: str = "pp",
    batch_axis: str | None = None,
) -> jax.Array:
    """``spmd_pipeline`` driven by a :class:`adapt_tpu.config.
    PipelineConfig`: splits the [B, ...] batch into
    ``config.microbatches`` and runs its schedule/hop_buffers knobs —
    the one-stop entry for drivers and benchmarks."""
    xs = pipeline_microbatch(x, config.microbatches)
    y = spmd_pipeline(
        block_fn,
        stacked_params,
        xs,
        mesh,
        axis=axis,
        batch_axis=batch_axis,
        schedule=config.schedule,
        hop_buffers=config.hop_buffers,
    )
    return pipeline_unmicrobatch(y)


def pipeline_microbatch(
    x: jax.Array, num_micro: int
) -> jax.Array:
    """[B, ...] -> [M, B/M, ...] microbatch split."""
    if x.shape[0] % num_micro:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by {num_micro} microbatches"
        )
    return x.reshape(num_micro, x.shape[0] // num_micro, *x.shape[1:])


def pipeline_unmicrobatch(y: jax.Array) -> jax.Array:
    """[M, mb, ...] -> [B, ...]."""
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])
