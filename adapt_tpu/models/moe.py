"""Mixture-of-Experts MLP with Switch/GShard-style static routing.

Beyond reference parity (SURVEY.md §2.2: no MoE constructs anywhere) but
first-class here as the expert-parallel workload. The design is
TPU-idiomatic end to end: routing is expressed as dense one-hot einsums
with a STATIC per-expert capacity, so the whole layer is fixed-shape — no
gather/scatter, no data-dependent shapes, everything tiles onto the MXU
and shards cleanly.

Routing (top-k, k in {1, 2}): softmax gate over experts; each token's
chosen expert slot is its prefix-count position in that expert's queue
(cumsum over the one-hot); tokens past ``capacity = ceil(cf * N * k / E)``
are dropped (their combine weight is zero, output falls back to the
residual path of the surrounding block). Aux load-balance loss is the
standard mean(fraction_tokens * fraction_probs) * E.

Expert parallelism: expert-stacked params carry a leading ``E`` dim;
:func:`adapt_tpu.parallel.expert.expert_shardings` shards that dim over
the ``ep`` mesh axis and GSPMD turns the dispatch/combine einsums into
all-to-alls over ICI (the scaling-book recipe — annotate, don't hand-roll
collectives).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from adapt_tpu.ops.dispatch import on_tpu, pallas_interpret, resolve_prefer


def _expert_params(mod: nn.Module, d: int, e: int, hidden: int):
    """The expert-stacked parameter block shared by the train-side
    (:class:`MoEMlp`) and serve-side (:class:`MoEDecoderMlp`) layers —
    one declaration, so their weights stay structurally interchangeable
    (same names, shapes, initializers; ``parallel.expert`` shards both
    identically)."""
    wg = mod.param("gate", nn.initializers.lecun_normal(), (d, e),
                   jnp.float32)
    w1 = mod.param("w1", nn.initializers.lecun_normal(), (e, d, hidden),
                   jnp.float32)
    b1 = mod.param("b1", nn.initializers.zeros, (e, hidden))
    w2 = mod.param("w2", nn.initializers.lecun_normal(), (e, hidden, d),
                   jnp.float32)
    b2 = mod.param("b2", nn.initializers.zeros, (e, d))
    return wg, w1, b1, w2, b2


def _topk_combine(gates: jax.Array, top_k: int):
    """Per-token top-k gate weights [N, E] (chosen entries carry their
    gate probability, the rest zero) plus the FIRST-choice one-hot —
    the argmax-and-mask loop shared by both routing flavors."""
    combine = jnp.zeros_like(gates)
    first_onehot = None
    remaining = gates
    for choice in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        onehot = jax.nn.one_hot(idx, gates.shape[-1], dtype=gates.dtype)
        if choice == 0:
            first_onehot = onehot
        combine = combine + onehot * gates
        remaining = remaining * (1.0 - onehot)
    return combine, first_onehot


def _switch_aux_loss(gates: jax.Array, first_onehot: jax.Array):
    """THE load-balance aux convention (Switch-style, first choice
    only, minimum 1.0 at perfect balance) — one definition so the two
    MoE layers' sown ``aux_loss`` stay on one scale."""
    e = gates.shape[-1]
    importance = jnp.sum(first_onehot, axis=0)
    frac_tokens = importance / jnp.maximum(jnp.sum(importance), 1.0)
    return jnp.sum(frac_tokens * jnp.mean(gates, axis=0)) * e


def _one_hot_routing(gates: jax.Array, capacity: int, top_k: int):
    """Build (dispatch [N,E,C], combine [N,E,C], aux_loss) from gate
    probabilities [N, E]."""
    n, e = gates.shape
    dispatch_slots = []
    combine_weights = []
    remaining = gates
    # Track how full each expert queue already is from earlier choices.
    base_count = jnp.zeros((e,), jnp.int32)
    first_onehot = None
    for choice in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)  # [N]
        onehot = jax.nn.one_hot(idx, e, dtype=gates.dtype)  # [N, E]
        if choice == 0:
            first_onehot = onehot
        prob = jnp.sum(gates * onehot, axis=-1)  # [N]
        pos = (
            jnp.cumsum(onehot, axis=0) - 1.0 + base_count[None, :]
        ) * onehot  # [N, E]
        slot = jnp.sum(pos, axis=-1).astype(jnp.int32)  # [N]
        keep = slot < capacity
        dispatch = (
            onehot[:, :, None]
            * jax.nn.one_hot(slot, capacity, dtype=gates.dtype)[:, None, :]
            * keep[:, None, None]
        )  # [N, E, C]
        dispatch_slots.append(dispatch)
        combine_weights.append(dispatch * prob[:, None, None])
        base_count = base_count + jnp.sum(
            onehot * keep[:, None], axis=0
        ).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)  # mask chosen expert
    dispatch = sum(dispatch_slots)
    combine = sum(combine_weights)
    return dispatch, combine, _switch_aux_loss(gates, first_onehot)


class MoEDecoderMlp(nn.Module):
    """Dropless per-token MoE for the DECODE/serving paths: each token's
    output is ``sum_{e in its top-k} gate_e * MLP_e(token)`` — no
    capacity, no slots, no cross-token coupling. That independence is
    the point: a token's output is a pure function of its own hidden
    state, so KV-cached decode, verify_chunk, chunked prefill and the
    full-sequence forward all agree EXACTLY (the repo's decode-parity
    contract), where :class:`MoEMlp`'s capacity routing would drop
    different tokens under different batch shapes.

    Computed in the masked-dense form (every expert evaluates every
    token via expert-stacked einsums; combine weights zero the rest) —
    fully static shapes, no gather/scatter. With the expert dim sharded
    over ``ep`` (:func:`adapt_tpu.parallel.expert.expert_shardings`
    applies unchanged — same leading-``E`` params), GSPMD gives each
    device its ``E/ep`` experts over replicated tokens and psums the
    combine: per-device cost ~ ``(E/ep) x`` a dense MLP, the classic
    dense-EP inference schedule. The capacity-routed :class:`MoEMlp`
    remains the train-side layer (its dispatch einsums all-to-all
    instead of replicating token compute)."""

    num_experts: int = 8
    hidden_dim: int = 128
    top_k: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if not 1 <= self.top_k <= self.num_experts:
            # A pick over a fully-masked gate row would re-select
            # expert 0 and silently double its weight.
            raise ValueError(
                f"top_k {self.top_k} outside [1, num_experts="
                f"{self.num_experts}]"
            )
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        wg, w1, b1, w2, b2 = _expert_params(
            self, d, self.num_experts, self.hidden_dim
        )
        gates = jax.nn.softmax(
            tokens.astype(jnp.float32) @ wg, axis=-1
        )  # [N, E]
        combine, first_onehot = _topk_combine(gates, self.top_k)
        self.sow(
            "intermediates", "aux_loss",
            _switch_aux_loss(gates, first_onehot),
        )

        xt = tokens.astype(self.dtype)
        h = jax.nn.gelu(
            jnp.einsum("nd,edh->neh", xt, w1.astype(self.dtype))
            + b1[None, :, :].astype(self.dtype)
        )
        out_e = (
            jnp.einsum("neh,ehd->ned", h, w2.astype(self.dtype))
            + b2[None, :, :].astype(self.dtype)
        )
        out = jnp.einsum(
            "ned,ne->nd", out_e, combine.astype(self.dtype)
        )
        return out.reshape(b, s, d).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """One expert layer as a model's configuration states it: how a
    token is routed over ``num_experts``, and WHICH of them this chip
    holds."""

    num_experts: int  # the router's width: every expert of the layer
    hidden_dim: int  # one expert's gated-SiLU width
    top_k: int
    #: ``"softmax"`` over the router's logits, or ``"sigmoid"`` of each.
    score: str = "softmax"
    #: Divide the chosen experts' scores by their sum.
    normalize: bool = False
    #: ``routed_scaling_factor``: multiplies the (normalised) weights.
    scale: float = 1.0
    #: A per-expert bias added to the scores for the CHOICE only (the
    #: weights do not carry it): DeepSeek-V3's ``e_score_correction_bias``.
    select_bias: bool = False
    #: Width of the one shared expert every token also passes through;
    #: None: no shared expert.
    shared_dim: int | None = None
    #: ``(first, count)``: the experts held here, one chip's share of an
    #: expert-parallel layer. The layer routes over all ``num_experts``
    #: and adds only its own experts' terms of the sum (plus the shared
    #: expert); what the absent experts would add is left out — no code
    #: stands in for the other chips or their exchange. None: all.
    held: tuple[int, int] | None = None
    #: A ``swiglu_limit`` on every expert, the shared one too
    #: (:func:`limited`); None: none.
    swiglu_limit: float | None = None
    #: ``(n_group, topk_group)``: a group-limited choice (DeepSeek-V3):
    #: the experts lie in ``n_group`` groups of consecutive experts, a
    #: group scores the sum of its two largest (score + bias), only the
    #: ``topk_group`` best groups' experts can be chosen. None: no limit.
    groups: tuple[int, int] | None = None

    def __post_init__(self):
        if self.groups is not None:
            n, keep = self.groups
            if not 1 <= keep <= n or self.num_experts % n or (
                keep * (self.num_experts // n) < self.top_k
            ) or self.num_experts // n < 2:
                raise ValueError(
                    f"groups={self.groups}: {self.num_experts} experts do "
                    f"not lie in {n} groups of at least 2 of which {keep} "
                    f"hold top_k {self.top_k}"
                )
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k {self.top_k} outside [1, num_experts="
                f"{self.num_experts}]"
            )
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score={self.score!r}")
        first, count = self.held_range
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"held={self.held}: outside the layer's "
                f"{self.num_experts} experts"
            )

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held or (0, self.num_experts)


def _group_limited(chosen, n_group: int, keep: int):
    """(n, E) choice scores with every expert outside the ``keep`` best
    of ``n_group`` groups at -inf; a group's score is the sum of its two
    largest."""
    n = chosen.shape[0]
    grouped = chosen.reshape(n, n_group, -1)
    best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # (n, groups)
    _, kept = jax.lax.top_k(best, keep)
    mask = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], kept
    ].set(True)
    return jnp.where(mask[..., None], grouped, -jnp.inf).reshape(n, -1)


def route(spec: ExpertSpec, logits: jax.Array, bias: jax.Array | None):
    """Router logits (n, E) float32 -> the chosen experts (n, k) int32
    and their weights (n, k) float32. The choice is by score plus
    ``bias`` (where the spec has one), the weights by score alone."""
    if spec.score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    chosen = scores if bias is None else scores + bias
    if spec.groups is not None:
        chosen = _group_limited(chosen, *spec.groups)
    _, idx = jax.lax.top_k(chosen, spec.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if spec.normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * spec.scale


#: Rows a grid step of the grouped kernel covers, and the most
#: columns of a weight tile in each direction (a 1024 x 2048 bf16 tile
#: is 4 MiB, double-buffered inside the 16 MiB a kernel may use):
#: picked on the chip, PERF.md section 6 (PR 31).
_GMM_ROWS = 128
_GMM_TILE = (1024, 2048)


def _tile(dim: int, cap: int) -> int | None:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; None where there is none."""
    for t in range(min(cap, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return None


def grouped_matmul(x, w, sizes, prefer=None, tiling=None):
    """Rows of ``x`` (m, k), sorted by group, each against ITS group's
    matrix of ``w`` (groups, k, n): ``sizes[g]`` rows belong to group
    ``g``; rows past their sum are nobody's and come back UNDEFINED
    (zero from one path, what the buffer held from the other: the
    kernel never visits a row tile that holds no group's rows, and
    within its last one writes only the groups' own). On a TPU the Pallas
    grouped matmul JAX ships (``megablox.gmm``: a grid step a (row
    tile, group) pair that holds rows, so a group's matrix is read once
    a row tile it touches and not once a row); elsewhere
    ``jax.lax.ragged_dot``. ``prefer`` as everywhere in ``ops``
    (``dispatch.resolve_prefer``)."""
    m, k = x.shape
    n = w.shape[2]
    tiles = tiling or (_GMM_ROWS, _tile(k, _GMM_TILE[0]), _tile(n, _GMM_TILE[1]))
    unsupported = None if all(tiles[1:]) else (
        f"widths ({k}, {n}) have no tile that is a multiple of 128"
    )
    if not resolve_prefer("expert_product", prefer, unsupported, on_tpu()):
        return jax.lax.ragged_dot(x, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    pad = -m % tiles[0]
    return gmm(
        jnp.pad(x, ((0, pad), (0, 0))), w, sizes, x.dtype, tuple(tiles),
        interpret=pallas_interpret(),
    )[:m]


def limited(t, limit: float | None, both: bool = False):
    """A ``swiglu_limit`` on a gated SiLU MLP, ``down(silu(limited(gate))
    * limited(up, both=True))``: the gate held from above, ``up`` on
    ``both`` sides. No limit: ``t`` as it is, no clamp in the program
    (and, called where the operand was, no operation moved in it)."""
    if limit is None:
        return t
    return jnp.clip(t, -limit if both else None, limit)


@functools.partial(jax.jit, static_argnames=("prefer", "limit"))
def expert_product(x, w_gate, w_up, w_down, sizes, prefer=None, limit=None):
    """The grouped product of the experts held: rows of ``x`` (m, d),
    sorted by expert, against each expert's gated SiLU MLP —
    ``sizes[e]`` rows belong to expert ``e``, rows past their sum are
    nobody's and come back zero. Three :func:`grouped_matmul`: every
    row meets ONE expert's matrices. A jitted function of its own so
    that a device trace shows its operations under one name. ``limit``:
    the experts' ``swiglu_limit`` (:func:`limited`)."""
    gate = grouped_matmul(x, w_gate, sizes, prefer)
    up = grouped_matmul(x, w_up, sizes, prefer)
    out = grouped_matmul(
        jax.nn.silu(limited(gate, limit)) * limited(up, limit, both=True),
        w_down, sizes, prefer,
    )
    mine = jnp.arange(x.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(mine, out, 0)


class RoutedExperts(nn.Module):
    """The expert layer of the serving paths: route every token over
    all of the layer's experts (:func:`route`), sort the (token,
    expert) assignments that fell on the experts HELD by expert, one
    grouped product over them (:func:`expert_product`), weighted
    scatter-add back, plus the shared expert. Dropless and
    token-independent — a token's output is a function of its own
    hidden state alone — so prefill, chunked prefill, decode and
    verify agree, as :class:`MoEDecoderMlp`'s contract says.

    Sows ``intermediates/held_tokens``: (n, held) int32, how many of
    token n's assignments each held expert got (0 or 1). The batcher
    sums it over its live rows (``moe.tokens.*`` counters)."""

    spec: ExpertSpec
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        spec = self.spec
        b, s, d = x.shape
        n, k, hid = b * s, spec.top_k, spec.hidden_dim
        first, held = spec.held_range
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d, spec.num_experts),
                            jnp.float32)
        bias = (
            self.param("router_bias", nn.initializers.zeros,
                       (spec.num_experts,), jnp.float32)
            if spec.select_bias else None
        )
        # (E, in, out): fan-in is dim -2, as for a Dense kernel.
        stacked = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
        w_gate = self.param("w_gate", stacked, (held, d, hid), jnp.float32)
        w_up = self.param("w_up", stacked, (held, d, hid), jnp.float32)
        w_down = self.param("w_down", stacked, (held, hid, d), jnp.float32)

        tokens = x.reshape(n, d)
        # Scores in float32 (a near-tie decides which experts run).
        idx, w = route(
            spec, tokens.astype(jnp.float32) @ router.astype(jnp.float32),
            None if bias is None else bias.astype(jnp.float32),
        )
        # Assignments on experts held elsewhere take the sentinel id
        # ``held``: they sort past every group and meet no matrix.
        local = idx - first
        eid = jnp.where((local >= 0) & (local < held), local, held)
        self.sow(
            "intermediates", "held_tokens",
            jnp.sum(eid[:, :, None] == jnp.arange(held), axis=1,
                    dtype=jnp.int32),
        )
        flat = eid.reshape(n * k)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.sum(
            flat[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32
        )
        tok = order // k  # the token of each sorted assignment
        xt = tokens.astype(self.dtype)
        y = expert_product(
            xt[tok], w_gate.astype(self.dtype), w_up.astype(self.dtype),
            w_down.astype(self.dtype), sizes, limit=spec.swiglu_limit,
        )
        y = y.astype(jnp.float32) * w.reshape(n * k)[order][:, None]
        out = jnp.zeros((n, d), jnp.float32).at[tok].add(y)
        if spec.shared_dim is not None:
            def dense(m, name):
                return nn.Dense(
                    m, dtype=self.dtype, use_bias=False, name=name
                )

            limit = spec.swiglu_limit
            out = out + dense(d, "shared_down")(
                nn.silu(limited(
                    dense(spec.shared_dim, "shared_gate")(xt), limit
                )) * limited(
                    dense(spec.shared_dim, "shared_up")(xt), limit, both=True
                )
            ).astype(jnp.float32)
        return out.reshape(b, s, d).astype(x.dtype)


class MoEMlp(nn.Module):
    """Token-routed expert MLP: [B, S, D] -> [B, S, D]."""

    num_experts: int = 8
    hidden_dim: int = 128
    top_k: int = 1
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        assert self.top_k in (1, 2), "top_k must be 1 or 2"
        assert self.top_k <= self.num_experts, (
            f"top_k={self.top_k} needs >= that many experts "
            f"(got {self.num_experts}); a second choice would re-route to "
            "the same expert and double the output"
        )
        b, s, d = x.shape
        n = b * s
        e = self.num_experts
        capacity = max(
            1, math.ceil(self.capacity_factor * n * self.top_k / e)
        )
        tokens = x.reshape(n, d)
        wg, w1, b1, w2, b2 = _expert_params(
            self, d, e, self.hidden_dim
        )

        gates = jax.nn.softmax(
            (tokens.astype(jnp.float32)) @ wg, axis=-1
        ).astype(self.dtype)
        dispatch, combine, aux = _one_hot_routing(
            gates, capacity, self.top_k
        )
        self.sow("intermediates", "aux_loss", aux)

        xt = tokens.astype(self.dtype)
        # Dispatch: [N,E,C] x [N,D] -> [E,C,D]; with w1/w2 sharded on E,
        # GSPMD lowers this to an all-to-all over the ep axis.
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, xt)
        h = jax.nn.gelu(
            jnp.einsum("ecd,edh->ech", expert_in, w1.astype(self.dtype))
            + b1[:, None, :].astype(self.dtype)
        )
        expert_out = (
            jnp.einsum("ech,ehd->ecd", h, w2.astype(self.dtype))
            + b2[:, None, :].astype(self.dtype)
        )
        out = jnp.einsum("nec,ecd->nd", combine, expert_out)
        return out.reshape(b, s, d).astype(x.dtype)
