"""Sequence-parallel long-context prefill: one prompt, many chips.

A 32k-token prompt monopolizes the prefill path however it is chunked —
chunking bounds the per-tick stall (Sarathi-style), disaggregation moves
the wall off the decode tier (PR 9), but the WALL itself is O(S^2)
attention on one chip. This module splits it: the prompt's token axis
shards over an ``sp`` mesh axis, every chip computes its own chunk's
projections / rope / quantization / MLP sequence-locally (token-local
math needs no communication — the Mesh-TensorFlow named-axis split,
PAPERS.md 1811.02084), and the K/V window circulates the ring via
``lax.ppermute`` neighbor hops (the ring-attention communication
pattern of ``parallel/ring_attention``) while each chip computes only
its own chunk's attention-score rows — so the prefill wall drops
~linearly with the ring size.

**The page contract.** Serving demands more than numerical
closeness: the sp-prefilled pages must be what the single-device
chunked prefill would have written, so a request landed through the
prefix cache decodes bit-identically to the collocated path. The
online-softmax accumulation of classic ring attention
(``ring_attention.ring_attention``) re-orders the softmax reduction
per ring step and is far from that. This module keeps the ring
TRANSPORT but not the online-softmax arithmetic: each rank ACCUMULATES
the rotating pool-representation K/V blocks into its full window
(:func:`ring_collect` — P-1 neighbor hops, no global gather primitive)
and then computes its rows' attention with exactly the chunk oracle's
op order (``models.transformer_lm.CausalSelfAttention.prefill_sp``
mirrors ``paged_chunk_attention_reference``). What the tests pin
(``tests/test_sp_prefill.py``, native/int8/int4 pools, sp in {2, 4},
sp x tp at MATCHED decode-tier tp — tp math was never bitwise-equal
across tp widths, only stream-identical, the PR-5 pin): the first
block's pages — projection, rope, quantisation and the ring transport,
everything token-local — are byte-equal; pages of later blocks sit
downstream of attention, where the sp pass reduces a row's softmax sum
and its p @ V product over the whole span and a chunk pass over its
own power-of-two window. The masked columns add exact zeros, but XLA
orders a reduction by its width, so the two differ by a rounding
(under 3 float32 ulps of a plane's largest value; int8 values equal,
their scales an ulp apart; a page whose chunk window IS the span is
byte-equal): the sp pass joins chunked prefill's documented
equivalence class, in which pages differ at ulp across SCHEDULES
(exactly as chunk-size choice already does, module docstring of
``runtime/continuous``), and the serving-level pin is greedy-stream
bit-identity — an argmax flip needs an exact fp tie. Per-chip window
memory is O(S) — the explicit trade against the online-softmax
ring's O(S/P), bought for the exact-oracle arithmetic; the O(S^2/P)
score-block COMPUTE split (the actual prefill wall) is pinned via
compiled-module cost analysis (per-device flops halve per sp
doubling).

**The sp -> tp layout transition.** The program's outputs are
seq-sharded pool-representation K/V; :meth:`SPPrefiller.prefill`
assembles them page-major on the host (per-shard D2H — each device
ships only its own chunk) and the caller lands them on the decode
pool's head-sharded layout through the SAME
``parallel.sharding.KVHandoffPlan`` / ``Pager.adopt_cached`` /
``_adopt_pages`` path as a disaggregated handoff — resharding on the
sender side of the boundary (PAPERS.md 2211.05322), never a gather
inside the decode mesh. Decode stays tp-sharded and untouched; the
request simply admits as a prefix-cache hit.

Composes with tensor parallelism as an ``(sp, tp)`` mesh: weights
place by ``lm_tp_rules`` over the tp axis (replicated over sp), the
kv-head axis of every window block rides the same tp split through
the ring, and the per-block psum pair stays tp-only — bitwise the
single-mesh tp math (the PR-5 pin).
"""

from __future__ import annotations

import threading
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapt_tpu.models.transformer_lm import TransformerLM, validate_tp
from adapt_tpu.ops.paged_attention import fuse_kv
from adapt_tpu.parallel.sharding import lm_tp_rules, replicate, tree_shardings
from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.profiling import aggregate_size_fn, global_compile_sentinel

log = get_logger("sp_prefill")

#: Live prefillers (weak): the ONE "sp.prefill" sentinel watch sums the
#: per-instance program families over this set, so a second prefiller
#: (or a post-recovery rebuild) aggregates instead of silently
#: replacing the first one's watch.
_LIVE_PREFILLERS: "weakref.WeakSet[SPPrefiller]" = weakref.WeakSet()


def _prefiller_family_size(pf: "SPPrefiller") -> int:
    return sum(f._cache_size() for f in list(pf._fn_cache.values()))


def ring_collect(x, mesh: Mesh, axis: str, seq_dim: int = 2,
                 in_spec: P | None = None, out_spec: P | None = None):
    """Collect a seq-sharded array's full window on every rank of the
    ``axis`` ring: P-1 ``lax.ppermute`` neighbor hops rotate the local
    blocks around the ring (exactly :mod:`ring_attention`'s transport —
    after ``i`` hops rank ``r`` holds the block that originated at
    ``r - i`` mod P) while each rank writes the arriving block at its
    global offset. No all-gather primitive, no host staging; the
    result is the full window, bit-identically the concatenation of
    the shards in sequence order.

    ``in_spec``/``out_spec`` default to the KV-leaf convention
    ``(1, kv_h, S, w)`` with ``seq_dim`` sharded over ``axis`` (name
    any other mesh axes — e.g. the tp split of the kv-head dim — in
    both specs; they pass through untouched)."""
    n = int(mesh.shape[axis])
    if n == 1:
        return x
    if in_spec is None:
        in_spec = P(*(axis if i == seq_dim else None
                      for i in range(x.ndim)))
    if out_spec is None:
        out_spec = P(*(None for _ in range(x.ndim)))
    full = x.shape[seq_dim]
    if full % n:
        raise ValueError(
            f"sequence axis {full} not divisible by ring size {n}"
        )
    ring = [(i, (i + 1) % n) for i in range(n)]

    @partial(jax.shard_map, mesh=mesh, in_specs=(in_spec,),
             out_specs=out_spec, check_vma=False)
    def run(xl):
        rank = lax.axis_index(axis)
        s_local = xl.shape[seq_dim]
        shape = list(xl.shape)
        shape[seq_dim] = full
        buf = jnp.zeros(tuple(shape), xl.dtype)
        cur = xl
        for i in range(n):
            src = jnp.mod(rank - i, n)
            buf = lax.dynamic_update_slice_in_dim(
                buf, cur, src * s_local, seq_dim
            )
            if i < n - 1:
                cur = lax.ppermute(cur, axis, ring)
        return buf

    return run(x)


class SPPrefiller:
    """The sequence-parallel prefill program family: one jitted,
    sp-sharded whole-span pass per power-of-two page bucket, producing
    page-major host K/V blocks in the decode pool's representation —
    the payload of a :class:`runtime.disagg.KVHandoff`, equal to what
    the single-device chunked prefill would have written up to the
    rounding of one reordered sum (module docstring).

    Owns its OWN mesh (axes ``(sp,)`` or ``(sp, tp)``) and weight
    placement (tp rules over ``tp_axis``, replicated over the ring) —
    the decode tier's mesh stays tp-only and its pool layout is
    reached only through the handoff landing path (the sp -> tp
    transition happens sender-side, module docstring). Both serving
    entry points drive one of these: ``ContinuousBatcher`` collocated
    admission and ``runtime.disagg.PrefillWorker.step``."""

    def __init__(
        self,
        lm: TransformerLM,
        variables,
        mesh: Mesh,
        page_size: int,
        kv_cache_dtype: str = "native",
        sp_axis: str = "sp",
        tp_axis: str | None = None,
        name: str = "sp0",
    ):
        if sp_axis not in mesh.shape:
            raise ValueError(
                f"mesh has no {sp_axis!r} axis (axes: "
                f"{tuple(mesh.axis_names)})"
            )
        self.sp = int(mesh.shape[sp_axis])
        if self.sp < 2:
            raise ValueError(
                f"sp axis {sp_axis!r} has size {self.sp}; a ring needs "
                "at least 2 ranks (sp=1 is the ordinary prefill path)"
            )
        if tp_axis is not None:
            if tp_axis not in mesh.shape:
                raise ValueError(
                    f"mesh has no {tp_axis!r} axis (axes: "
                    f"{tuple(mesh.axis_names)})"
                )
            self.tp = int(mesh.shape[tp_axis])
            validate_tp(lm, self.tp)
        else:
            self.tp = 1
        if kv_cache_dtype not in ("native", "int8", "int4"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: expected 'native', "
                "'int8' or 'int4'"
            )
        self.lm = lm
        self.name = name
        self.page_size = page_size
        self.kv_cache_dtype = kv_cache_dtype
        self.quantized = kv_cache_dtype != "native"
        self._mesh = mesh
        self._sp_axis = sp_axis
        self._tp_axis = tp_axis
        g = lm.graph
        self._embed = g.node("embed").module
        self._blocks = [g.node(n).module for n in lm.block_names]
        block0 = self._blocks[0]
        self._heads = block0.cache_heads
        # The pool's format has one owner; this module only has to
        # refuse what that format refuses (int4 at an odd head_dim).
        # Imported here: runtime/ imports this module.
        from adapt_tpu.runtime.paged import kv_value_width

        kv_value_width(block0.head_dim, kv_cache_dtype)
        #: The ORIGINAL variables as given — a post-recovery rebuild
        #: re-places from here, not from a possibly-dead placement.
        self._src_variables = variables
        if self.tp > 1:
            self._variables = jax.device_put(
                variables,
                tree_shardings(
                    variables, mesh,
                    rules=partial(lm_tp_rules, axis=tp_axis),
                ),
            )
        else:
            self._variables = replicate(variables, mesh)
        self._repl = NamedSharding(mesh, P())
        self._fn_cache: dict[int, object] = {}
        self._lock = threading.Lock()
        self.prefill_tokens = 0
        self.prefills = 0
        _LIVE_PREFILLERS.add(self)
        global_compile_sentinel().register(
            "sp.prefill",
            size_fn=aggregate_size_fn(
                _LIVE_PREFILLERS, _prefiller_family_size
            ),
        )

    # -- compiled pieces ---------------------------------------------------

    @property
    def variants(self) -> set[int]:
        """Page buckets whose program variant exists — the recovery
        allowance accounting (``recover()``'s nvar rule)."""
        return set(self._fn_cache)

    def _kv_spec(self) -> P:
        """Pool-representation K/V leaves ``(1, kv_h, S, w)``: kv-head
        axis over tp (when composed), sequence axis over the ring.
        One spec serves value planes and scale planes alike (the last
        axis stays whole)."""
        return P(None, self._tp_axis, self._sp_axis, None)

    def _sp_fn(self, nb: int):
        """The jitted sp-sharded whole-span prefill for one pow2 page
        bucket: embed -> per block (seq-local QKV/rope/quantize, ring
        window collect, chunk-oracle attention, seq-local MLP) ->
        pool-representation K/V per block, seq-sharded. Specializes
        per page bucket (log2 variants, the chunked-prefill
        discipline)."""
        if nb in self._fn_cache:
            return self._fn_cache[nb]
        S = nb * self.page_size
        if S % self.sp:
            raise ValueError(
                f"window of {S} tokens not divisible by sp={self.sp}"
            )
        mesh = self._mesh
        h_sh = NamedSharding(mesh, P(None, self._sp_axis, None))
        kv_sh = NamedSharding(mesh, self._kv_spec())
        #: Attention-intermediate row sharding (folded q, score block,
        #: attention output): without this pin GSPMD's propagation may
        #: replicate the O(S^2) score block over the ring — every rank
        #: computing every row — which forfeits the compute split
        #: (verified via compiled-module cost_analysis in the micro
        #: driver).
        rows_sh = NamedSharding(mesh, self._kv_spec())
        in_spec = self._kv_spec()
        out_spec = P(None, self._tp_axis, None, None)

        def gather(tree):
            # The ring transport: every pool-representation leaf (int8
            # values AND f32 scales of a quantized pair) rotates the
            # same ring; the tp split of the kv-head axis passes
            # through untouched.
            return jax.tree.map(
                lambda t: ring_collect(
                    t, mesh, self._sp_axis, seq_dim=2,
                    in_spec=in_spec, out_spec=out_spec,
                ),
                tree,
            )

        qflag = self.kv_cache_dtype if self.quantized else False

        def constrain(t):
            return lax.with_sharding_constraint(t, rows_sh)

        @jax.jit
        def prog(variables, ids):
            pos_ids = jnp.arange(S)[None]
            h = self._embed.apply(
                variables["embed"], ids, pos_ids,
                method="embed_positions",
            )
            h = lax.with_sharding_constraint(h, h_sh)
            outs = []
            for name, block in zip(self.lm.block_names, self._blocks):
                h, ck, cv = block.apply(
                    variables[name], h, gather, qflag, constrain,
                    method="prefill_sp",
                )
                h = lax.with_sharding_constraint(h, h_sh)
                # The pool's rows (K|V fused on the last axis, which no
                # mesh axis shards) — what the landing path scatters.
                outs.append(
                    jax.tree.map(
                        lambda t: lax.with_sharding_constraint(t, kv_sh),
                        fuse_kv(ck, cv),
                    )
                )
            return outs

        self._fn_cache[nb] = prog
        return prog

    # -- request surface ---------------------------------------------------

    def covers(self, prompt_len: int) -> int:
        """Full pages an sp prefill of this prompt would produce (0 =
        nothing to do; the partial last page always re-prefills as the
        decode-side suffix pass, exactly like a disagg handoff)."""
        return max(0, (prompt_len - 1) // self.page_size)

    def prefill(self, prompt) -> tuple[int, list]:
        """Run the sp-sharded prefill of ``prompt``'s full pages.
        Returns ``(n_pages, blocks)`` — per decoder block, the pool's
        own planes page-major: an ``(n_pages, kv_h, page, 2 * w)`` host
        array of fused K|V rows (or a ``(values, k_scales, v_scales)``
        tuple for quantized pools): exactly the payload
        ``ContinuousBatcher.adopt_prefill_pages`` /
        :class:`runtime.disagg.KVHandoff` expect (the page contract:
        module docstring)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        s0 = prompt.shape[0]
        Pg = self.page_size
        m = (s0 - 1) // Pg
        if m < 1:
            raise ValueError(
                f"prompt of {s0} tokens has no full {Pg}-token page to "
                "sp-prefill"
            )
        nb = 1
        while nb < m:
            nb *= 2
        S = nb * Pg
        ids = np.zeros((1, S), np.int32)
        ids[0, : m * Pg] = prompt[: m * Pg]
        with self._lock:
            fn = self._sp_fn(nb)
        outs = fn(
            self._variables, jax.device_put(ids, self._repl)
        )
        kvh = self._heads

        def page_major(t):
            # (1, kv_h, S, w) seq-order -> (m, kv_h, page, w)
            # page-major; the host assembly is the per-shard D2H (each
            # ring rank ships only its own chunk's rows).
            a = np.asarray(t)[0]
            a = a.reshape(kvh, nb, Pg, a.shape[-1])
            return np.ascontiguousarray(np.swapaxes(a, 0, 1)[:m])

        blocks = jax.tree.map(page_major, outs)
        self.prefill_tokens += m * Pg
        self.prefills += 1
        return m, blocks

    def close(self) -> None:
        """Retire this prefiller: its programs leave the aggregate
        sentinel watch (the WeakSet holds it weakly; dropping the
        caches makes a lingering strong ref harmless)."""
        _LIVE_PREFILLERS.discard(self)
        self._fn_cache.clear()


def build_sp_mesh(
    sp_width: int,
    tp: int = 1,
    sp_axis: str = "sp",
    tp_axis: str = "tp",
    devices=None,
) -> Mesh:
    """An ``(sp,)`` or ``(sp, tp)`` mesh over the first
    ``sp_width * tp`` available devices — the default mesh the serving
    entry points build when handed a ``PrefillConfig`` without an
    explicit mesh. Raises when the platform has too few devices (the
    caller degrades to the ordinary prefill path and says so)."""
    need = sp_width * tp
    pool = list(devices) if devices is not None else jax.devices()
    if len(pool) < need:
        raise ValueError(
            f"sp_width={sp_width} x tp={tp} needs {need} devices; "
            f"have {len(pool)}"
        )
    arr = np.asarray(pool[:need])
    if tp > 1:
        return Mesh(arr.reshape(sp_width, tp), (sp_axis, tp_axis))
    return Mesh(arr.reshape(sp_width), (sp_axis,))
