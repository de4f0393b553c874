"""SPMD pipelined KV-cache generation: decode an LM bigger than one chip.

``models.transformer_lm.generate`` is a single-program loop — weights AND
the KV cache for every block must fit one chip. This module partitions the
decoder by block over a ``pp`` mesh axis (the same cut contract as the
scoring path, ``graph_partition(lm.graph, [...])``) and keeps each rank's
block params *and KV caches* device-resident, so an LM whose weights+cache
exceed one chip's HBM generates across P chips. The placement that makes
that true is :func:`shard_for_pipeline`: block weights are staged through
host RAM and each rank receives only its own L/P blocks — the full set is
never materialized on any single device. No reference analog (the
reference is CNN-only, SURVEY.md §2.2); this is SURVEY §2.3 pipeline
parallelism applied to the repo's flagship serving workload the TPU way:
one XLA program, activations on ICI, no host round-trips.

Schedule — a token ring, not GPipe:

- The batch is split into M = P microbatches. At any tick each rank holds
  exactly one microbatch's single-token activation (b/P, 1, d); a
  ``lax.ppermute`` ROTATION (P-1 wraps to 0) hands them all one hop each
  tick.
- Rank p at tick T works on microbatch ``(T-p) mod P`` at decode pass
  ``(T-p) div P``: runs its L/P blocks' cached ``decode_step``.
- The LAST rank additionally runs the LM head, samples the next token
  (per-row keys — ``sample_next_tokens`` — so a microbatch slice draws
  exactly what the full batch would), and puts the *embedding of the
  sampled token* into the rotation; one hop later rank 0 consumes it as
  the next pass's input. Steady state: every rank busy every tick, and
  each microbatch decodes one token per P ticks — aggregate one token per
  tick, the single-chip rate, at P x the memory.
- Prefill runs first with the same schedule over (b/P, s0, d) prompt
  activations (a plain shift, no wrap), building every rank's caches and
  sampling each microbatch's first token.

Fill/drain bubble ticks compute on garbage; instead of guarding every
cache write with a full-slice select, caches carry ONE trash position
(``max_len + 1`` slots) and invalid ticks write there — O(1) writes on the
hot path, and the decode attention's ``positions <= index`` mask never
admits the trash slot for a valid pass.

Parity contract (tested): output is token-for-token identical to
single-program ``generate`` for greedy AND sampled paths, with ragged
prompts and int8 KV caches — same math, same per-row sampling keys, just
a different schedule over the same weights.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapt_tpu.models.transformer_lm import (
    TransformerLM,
    _left_align,
    sample_next_tokens,
    validate_generate_args,
)


@dataclasses.dataclass(frozen=True)
class PipelinedVariables:
    """Weights placed for pipelined decode: block params stacked with the
    leading (depth) dim sharded over the pipeline axis, embed/head
    replicated. Build once with :func:`shard_for_pipeline`, reuse across
    calls."""

    stacked: Any
    embed: Any
    head: Any


def shard_for_pipeline(
    lm: TransformerLM, variables, mesh: Mesh, axis: str = "pp"
) -> PipelinedVariables:
    """Place ``variables`` for pipelined decode — the capacity-critical
    step. Block leaves are staged through HOST memory and ``device_put``
    with a ``P(axis)`` leading-dim sharding, so each rank's devices ever
    receive only their own L/P blocks: total weights may exceed one
    chip's HBM as long as each rank's slice (plus embed + head, which
    are replicated) fits. Never stacks the full block set on one device.
    """
    block_sharding = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())

    def place(*leaves):
        host = np.stack([np.asarray(x) for x in leaves], axis=0)
        return jax.device_put(host, block_sharding)

    stacked = jax.tree.map(
        place, *[variables[name] for name in lm.block_names]
    )
    put_rep = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.device_put(x, replicated), t
    )
    return PipelinedVariables(
        stacked=stacked,
        embed=put_rep(variables["embed"]),
        head=put_rep(variables["head"]),
    )


def pipelined_generate(
    lm: TransformerLM,
    variables,
    prompt: jax.Array,
    steps: int,
    mesh: Mesh,
    axis: str = "pp",
    dp_axis: str | None = None,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
    rng: jax.Array | None = None,
    prompt_lengths: jax.Array | None = None,
    kv_cache_dtype: str = "native",
) -> jax.Array:
    """``generate`` semantics, pipelined over ``mesh.shape[axis]`` ranks.

    prompt: (b, s0) int32 ids with b divisible by the pipeline size (the
    microbatch split) and ``lm.depth`` divisible by it (the block split);
    returns (b, steps) ids identical to single-program ``generate`` with
    the same arguments. All sampling knobs, ragged prompts
    (``prompt_lengths``) and ``kv_cache_dtype="int8"`` carry over.

    ``variables`` may be the raw per-node dict (convenience: each call
    re-stages weights through host memory) or a
    :class:`PipelinedVariables` from :func:`shard_for_pipeline` —
    serving, and any model too big for one chip, should pre-place once
    and reuse.

    ``dp_axis`` composes data parallelism with the pipeline on a 2-D
    mesh: every microbatch's rows shard over ``dp_axis`` (batch must
    divide by pipeline_size * dp_size) while blocks + caches shard over
    ``axis`` — sampling stays per-GLOBAL-row, so output is still
    token-identical to single-program ``generate``.
    """
    num_ranks = mesh.shape[axis]
    dp = mesh.shape[dp_axis] if dp_axis is not None else 1
    b, _ = prompt.shape
    lengths, rng, do_sample = validate_generate_args(
        lm, prompt, steps, temperature, top_k, rng, prompt_lengths,
        kv_cache_dtype, top_p=top_p,
    )
    if lm.depth % num_ranks:
        raise ValueError(
            f"depth {lm.depth} not divisible by pipeline size {num_ranks}"
        )
    if b % num_ranks:
        raise ValueError(
            f"batch {b} not divisible by pipeline size {num_ranks} "
            "(the microbatch split); pad the batch"
        )
    if (b // num_ranks) % dp:
        raise ValueError(
            f"per-microbatch rows {b // num_ranks} not divisible by "
            f"dp size {dp}"
        )
    if not isinstance(variables, PipelinedVariables):
        variables = shard_for_pipeline(lm, variables, mesh, axis)
    return _pipelined_impl(
        lm,
        variables.stacked,
        variables.embed,
        variables.head,
        prompt,
        lengths,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        jnp.asarray(-1 if eos_id is None else eos_id, prompt.dtype),
        rng,
        steps=steps,
        do_sample=do_sample,
        top_k=top_k,
        use_top_p=top_p is not None,
        use_eos=eos_id is not None,
        ragged=prompt_lengths is not None,
        kv_quant=kv_cache_dtype == "int8",
        mesh=mesh,
        axis=axis,
        dp_axis=dp_axis,
    )


@partial(
    jax.jit,
    static_argnames=(
        "lm",
        "steps",
        "do_sample",
        "top_k",
        "use_top_p",
        "use_eos",
        "ragged",
        "kv_quant",
        "mesh",
        "axis",
        "dp_axis",
    ),
)
def _pipelined_impl(
    lm: TransformerLM,
    stacked,
    embed_vars,
    head_vars,
    prompt: jax.Array,
    lengths: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    eos_id: jax.Array,
    rng: jax.Array,
    *,
    steps: int,
    do_sample: bool,
    top_k: int | None,
    use_top_p: bool,
    use_eos: bool,
    ragged: bool,
    kv_quant: bool,
    mesh: Mesh,
    axis: str,
    dp_axis: str | None,
) -> jax.Array:
    g = lm.graph
    num_ranks = mesh.shape[axis]
    b, s0 = prompt.shape
    num_micro = num_ranks  # M == P: tight rotation, no idle ticks
    mb = b // num_micro  # global rows per microbatch
    dp = mesh.shape[dp_axis] if dp_axis is not None else 1
    mb_loc = mb // dp  # rows this dp shard holds per microbatch
    local_blocks = lm.depth // num_ranks
    embed = g.node("embed").module
    head = g.node("head").module
    block = g.node(lm.block_names[0]).module  # identical block structure

    # Cache buffers hold KV heads — fewer than query heads under GQA.
    heads, head_dim = block.cache_heads, block.head_dim
    # One extra slot: bubble ticks write their garbage K/V here instead of
    # forcing a full-slice select per tick. `positions <= index` masking
    # keeps it out of every valid pass's attention window.
    cache_len = lm.max_len + 1
    trash_index = lm.max_len

    if ragged:
        prompt_aligned, pos_ids, valid_from = _left_align(prompt, lengths)
        pos_all = pos_ids.reshape(num_micro, mb, s0)
        vf_all = valid_from.reshape(num_micro, mb)
    else:
        prompt_aligned = prompt
        pos_all = jnp.zeros((num_micro, mb, s0), jnp.int32)  # unused
        vf_all = jnp.zeros((num_micro, mb), jnp.int32)  # unused
    prompts_m = prompt_aligned.reshape(num_micro, mb, s0)

    # Exactly generate()'s key schedule: step_keys[0] samples the prefill
    # token, step_keys[s] samples produced token s.
    rng_next, key0 = jax.random.split(rng)
    if steps > 1:
        step_keys = jnp.concatenate(
            [key0[None], jax.random.split(rng_next, steps - 1)]
        )
    else:
        step_keys = key0[None]

    def cache_buf(last_dim, dtype):
        return jnp.zeros(
            (local_blocks, num_micro, mb_loc, heads, cache_len, last_dim),
            dtype,
        )

    if kv_quant:
        init_k = (cache_buf(head_dim, jnp.int8), cache_buf(1, jnp.float32))
        init_v = (cache_buf(head_dim, jnp.int8), cache_buf(1, jnp.float32))
    else:
        init_k = cache_buf(head_dim, block.dtype)
        init_v = cache_buf(head_dim, block.dtype)

    param_specs = jax.tree.map(lambda _: P(axis), stacked)
    rep = P()
    rep_tree = lambda t: jax.tree.map(lambda _: P(), t)  # noqa: E731
    # Row-carrying operands shard their mb dim over dp (replicated when
    # no dp axis).
    rows3 = P(None, dp_axis, None) if dp_axis else rep
    rows2 = P(None, dp_axis) if dp_axis else rep

    @partial(
        jax.shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(
            param_specs,
            rep_tree(embed_vars),
            rep_tree(head_vars),
            rows3,  # prompts_m
            rows3,  # pos_all
            rows2,  # vf_all
            rep,  # step_keys
            rep,  # temperature
            rep,  # top_p
            rep,  # eos_id
        ),
        out_specs=rows3,
    )
    def run(
        params_loc,
        embed_vars,
        head_vars,
        prompts_m,
        pos_all,
        vf_all,
        step_keys,
        temperature,
        top_p,
        eos_id,
    ):
        rank = lax.axis_index(axis)
        dp_off = (
            lax.axis_index(dp_axis) * mb_loc if dp_axis is not None else 0
        )
        is_last = rank == num_ranks - 1
        shift = [(i, i + 1) for i in range(num_ranks - 1)]
        ring = [(i, (i + 1) % num_ranks) for i in range(num_ranks)]

        def masked_row_update(buf, row, m, on):
            """buf[m] = row where `on` (scalar), else unchanged."""
            old = lax.dynamic_index_in_dim(buf, m, 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                buf, jnp.where(on, row, old), m, 0
            )

        def sample(logits, key, m, done_m):
            toks = sample_next_tokens(
                logits,
                key,
                temperature,
                do_sample=do_sample,
                top_k=top_k,
                top_p=top_p if use_top_p else None,
                # GLOBAL row index: microbatch base + this dp shard's
                # offset — a slice samples what the full batch would.
                row_offset=m * mb + dp_off,
            ).astype(prompts_m.dtype)
            if use_eos:
                toks = jnp.where(done_m, eos_id, toks)
                done_m = done_m | (toks == eos_id)
            return toks, done_m

        # ---- prefill: prompt activations shift down the chain ----------
        def prefill_tick(carry, t):
            h, ck, cv, first, toks, done = carry
            recv = lax.ppermute(h, axis, shift)
            m_in = jnp.clip(t, 0, num_micro - 1)
            ids_in = lax.dynamic_index_in_dim(
                prompts_m, m_in, 0, keepdims=False
            )
            if ragged:
                pos_in = lax.dynamic_index_in_dim(
                    pos_all, m_in, 0, keepdims=False
                )
                emb = embed.apply(
                    embed_vars, ids_in, pos_in, method="embed_positions"
                )
            else:
                emb = embed.apply(embed_vars, ids_in)
            h_in = jnp.where(rank == 0, emb, recv)

            j = t - rank
            m = jnp.clip(j, 0, num_micro - 1)
            valid = (j >= 0) & (j < num_micro)
            vf = (
                lax.dynamic_index_in_dim(vf_all, m, 0, keepdims=False)
                if ragged
                else None
            )

            def blk(x, p_i):
                x2, k_new, v_new = block.apply(
                    p_i, x, cache_len, vf, kv_quant, method="prefill"
                )
                return x2, (k_new, v_new)

            h_out, (k_news, v_news) = lax.scan(blk, h_in, params_loc)

            def write_cache(c, new):
                old = lax.dynamic_index_in_dim(c, m, 1, keepdims=False)
                return lax.dynamic_update_index_in_dim(
                    c, jnp.where(valid, new, old), m, 1
                )

            ck = jax.tree.map(write_cache, ck, k_news)
            cv = jax.tree.map(write_cache, cv, v_news)

            logits = head.apply(head_vars, h_out[:, -1:, :])[:, 0]
            done_m = lax.dynamic_index_in_dim(done, m, 0, keepdims=False)
            t0, done_m = sample(logits, step_keys[0], m, done_m)
            on = valid & is_last
            first = masked_row_update(first, t0, m, on)
            toks_m = lax.dynamic_index_in_dim(toks, m, 0, keepdims=False)
            toks = masked_row_update(toks, toks_m.at[:, 0].set(t0), m, on)
            done = masked_row_update(done, done_m, m, on)
            return (h_out, ck, cv, first, toks, done), None

        init = (
            jnp.zeros((mb_loc, s0, block.dim), block.dtype),
            init_k,
            init_v,
            jnp.zeros((num_micro, mb_loc), prompts_m.dtype),  # first toks
            jnp.zeros((num_micro, mb_loc, steps), prompts_m.dtype),
            jnp.zeros((num_micro, mb_loc), bool),
        )
        (_, ck, cv, first, toks, done), _ = lax.scan(
            prefill_tick, init, jnp.arange(num_micro + num_ranks - 1)
        )
        # Only the last rank sampled; broadcast so rank 0 can inject the
        # first decode pass's tokens.
        first = lax.psum(first, axis)

        if steps == 1:
            return lax.psum(toks, axis)

        # ---- decode: single-token ring rotation ------------------------
        def decode_tick(carry, t):
            h, ck, cv, toks, done = carry
            recv = lax.ppermute(h, axis, ring)
            j = t - rank
            m = jnp.mod(j, num_micro)
            sp = jnp.floor_divide(j, num_micro)  # pass: consumes token sp
            sp_c = jnp.clip(sp, 0, steps - 2)
            valid = (j >= 0) & (j < (steps - 1) * num_micro)
            index = jnp.where(valid, s0 + sp_c, trash_index)
            vf = (
                lax.dynamic_index_in_dim(vf_all, m, 0, keepdims=False)
                if ragged
                else None
            )

            # Rank 0, pass 0 consumes the prefill-sampled token; later
            # passes consume the embedding the last rank put on the ring.
            t_first = lax.dynamic_index_in_dim(first, m, 0, keepdims=False)
            if ragged:
                inj = embed.apply(
                    embed_vars,
                    t_first[:, None],
                    (index - vf)[:, None],
                    method="embed_positions",
                )
            else:
                inj = embed.apply(
                    embed_vars, t_first[:, None], index, method="embed_at"
                )
            h_in = jnp.where((rank == 0) & (sp == 0), inj, recv)

            ck_m = jax.tree.map(
                lambda c: lax.dynamic_index_in_dim(c, m, 1, keepdims=False),
                ck,
            )
            cv_m = jax.tree.map(
                lambda c: lax.dynamic_index_in_dim(c, m, 1, keepdims=False),
                cv,
            )

            def blk(x, xs_i):
                p_i, ck_i, cv_i = xs_i
                x2, ck_i, cv_i = block.apply(
                    p_i, x, ck_i, cv_i, index, vf, kv_quant,
                    method="decode_step",
                )
                return x2, (ck_i, cv_i)

            x_out, (ck_m, cv_m) = lax.scan(
                blk, h_in, (params_loc, ck_m, cv_m)
            )
            # Invalid ticks only touched the trash slot — write back
            # unguarded.
            ck = jax.tree.map(
                lambda c, n: lax.dynamic_update_index_in_dim(c, n, m, 1),
                ck,
                ck_m,
            )
            cv = jax.tree.map(
                lambda c, n: lax.dynamic_update_index_in_dim(c, n, m, 1),
                cv,
                cv_m,
            )

            logits = head.apply(head_vars, x_out)[:, 0]
            done_m = lax.dynamic_index_in_dim(done, m, 0, keepdims=False)
            nxt, done_m = sample(logits, step_keys[sp_c + 1], m, done_m)
            on = valid & is_last
            toks_m = lax.dynamic_index_in_dim(toks, m, 0, keepdims=False)
            toks = masked_row_update(
                toks, toks_m.at[:, sp_c + 1].set(nxt), m, on
            )
            done = masked_row_update(done, done_m, m, on)

            # The sampled token's embedding rides the ring back to rank 0
            # (position index+1 = the pass that consumes it).
            if ragged:
                emb_n = embed.apply(
                    embed_vars,
                    nxt[:, None],
                    (index + 1 - vf)[:, None],
                    method="embed_positions",
                )
            else:
                emb_n = embed.apply(
                    embed_vars, nxt[:, None], index + 1, method="embed_at"
                )
            h_next = jnp.where(is_last, emb_n, x_out)
            return (h_next, ck, cv, toks, done), None

        init_h = jnp.zeros((mb_loc, 1, block.dim), block.dtype)
        (_, _, _, toks, _), _ = lax.scan(
            decode_tick,
            (init_h, ck, cv, toks, done),
            jnp.arange(steps * num_ranks - 1),
        )
        return lax.psum(toks, axis)

    toks = run(
        stacked,
        embed_vars,
        head_vars,
        prompts_m,
        pos_all,
        vf_all,
        step_keys,
        temperature,
        top_p,
        eos_id,
    )
    return toks.reshape(b, steps)
