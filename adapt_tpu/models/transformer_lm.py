"""Decoder-only transformer LM with KV-cache incremental decoding.

Beyond reference parity (the reference is CNN-only inference,
SURVEY.md §2.2) but a natural capability for a TPU serving framework:
the causal-attention product path. The full-sequence forward is a
``LayerGraph`` cut by decoder block — the same pipeline-partition
contract as ViT (``models/vit.py``) — while generation runs a
jit-friendly KV-cache loop:

- **Prefill** consumes the prompt in one full causal forward (the flash
  attention dispatch in ``ops/attention`` picks XLA or the streaming
  Pallas kernel by measured score-memory budget) and returns per-block
  K/V caches padded to ``max_len``.
- **Decode** is a ``lax.scan`` over steps: one token's q attends over
  the cache (a single (b, h, 1, max_len) score row — no S x S anything),
  caches update in place via ``dynamic_update_slice``. Static shapes
  throughout, so the whole generate loop is one compiled program.

All modules use ``setup`` (not ``nn.compact``) so ``__call__`` (the
graph/pipeline path), ``prefill`` and ``decode_step`` share one
parameter structure — the cached decode is a different *schedule* over
the same weights, never a different model.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Sequence
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from adapt_tpu.graph.ir import INPUT, LayerGraph
from adapt_tpu.ops.attention import flash_attention
from adapt_tpu.ops.decode_attention import (
    append_kv,
    decode_attention,
    verify_attention,
)
from adapt_tpu.ops.paged_attention import (
    append_kv_paged,
    fuse_kv,
    paged_attention,
    paged_chunk_attention,
    paged_verify_attention,
    pool_values,
)
from adapt_tpu.models.kda import KdaMixer, KdaSpec
from adapt_tpu.models.mhc import HyperConnection, HyperSpec, merge
from adapt_tpu.models.mla import LatentSelfAttention, LatentSpec
from adapt_tpu.models.moe import (
    ExpertSpec,
    MoEDecoderMlp,
    RoutedExperts,
    limited,
)
from adapt_tpu.models.rope import apply_rope
from adapt_tpu.models.ssm import Mamba2Mixer, SsmSpec, scaled
from adapt_tpu.ops.quantize import LANES, quantize_kv_vectors, unpack_int4

_NEG_INF = -1e30


def chosen_logprob(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """THE emitted-token score convention, shared by ``generate`` and
    the continuous batcher (one definition — the parity tests assert
    they agree): log-softmax of the RAW pre-temperature logits at the
    chosen token. logits (n, V), tokens (n,) -> (n,) f32.

    Gathers FIRST: the chosen logit less one log-sum-exp of its row,
    the expression ``jax.nn.log_softmax`` evaluates at that element,
    so no (n, V) result is written for the n values read (at a
    vocabulary of 261,120 that array is 134 MB a decode step)."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    # A row with no finite maximum shifts by 0 (jax.nn.logsumexp's
    # guard): the result is not finite either way.
    m = lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0))
    picked = jnp.take_along_axis(
        logits, tokens[:, None].astype(jnp.int32), axis=-1
    )
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True))
    return ((picked - m) - lse)[:, 0]


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What ONE decoder block is, read from a model's configuration —
    the single description the block, its attention, the KV cache
    (``runtime/paged.cache_groups``) and the TP rules key off. The
    defaults are the GPT-2 block (pre-LayerNorm, fused biased
    projections, tanh-GELU two-matrix MLP, ``head_dim = dim // heads``,
    learned positions outside the block); every other field is a
    departure some published decoder makes from it."""

    dim: int
    heads: int
    mlp_dim: int
    kv_heads: int | None = None
    #: Its own number: a model may project to ``heads * head_dim`` wider
    #: (or narrower) than ``dim``. None: ``dim // heads``.
    head_dim: int | None = None
    #: ``"layernorm"`` or ``"rmsnorm"`` (learned scale, no bias).
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    #: False: the norm on each sub-layer's INPUT (``x + f(norm(x))``).
    #: True: on its OUTPUT (``x + norm(f(x))``).
    post_norm: bool = False
    #: A norm on each sub-layer's input AND on its output, four a block
    #: (``x + norm(f(norm(x)))``: ``ln1`` / ``ln2`` before, ``ln1_post``
    #: / ``ln2_post`` after).
    sandwich_norm: bool = False
    #: RMSNorm over head_dim (learned scale) on q and on k, before any
    #: rotation.
    qk_norm: bool = False
    #: Biases on the attention and MLP projections.
    bias: bool = True
    #: ``"gelu"`` (two matrices), ``"gated_silu"`` (gate, up, down),
    #: ``"experts"`` (``experts`` says which: routed + shared, sorted
    #: grouped product) or ``"moe_dense"`` (the masked-dense GELU
    #: mixture, ``moe_experts`` / ``moe_top_k``).
    mlp: str = "gelu"
    #: A ``swiglu_limit`` on the ``"gated_silu"`` MLP
    #: (``moe.limited``; the experts' is ``experts.swiglu_limit``).
    swiglu_limit: float | None = None
    experts: ExpertSpec | None = None
    moe_experts: int | None = None
    moe_top_k: int = 1
    #: Rotate-half base of this block's q/k rotation; None: no
    #: rotation (learned positions, or none at all).
    rope_base: float | None = None
    #: Attend the previous ``window`` positions only; None: all.
    window: int | None = None
    #: A state-space mixer IN PARALLEL with the attention, on the same
    #: normed input, both added to the residual (``models/ssm``): the
    #: block then owns a recurrent state beside its KV pages, and only
    #: the schedules that carry one serve it (the full forward,
    #: ``prefill``, ``prefill_chunk_paged``, ``decode_step_paged``).
    ssm: SsmSpec | None = None
    #: Multi-head latent attention (``models/mla``) instead of MHA/GQA:
    #: low-rank q and kv, a rotated key part every head shares
    #: (``rope_base`` gives the rotation), and a cache of ONE
    #: ``latent.row``-wide row a position with no head axis
    #: (``runtime/paged.alloc_kv_pools``). ``kv_heads`` / ``head_dim``
    #: / ``qk_norm`` / ``window`` do not apply.
    latent: LatentSpec | None = None
    #: A linear-attention mixer (``models/kda``: the gated delta rule)
    #: IN PLACE OF the attention: the block holds a recurrent state a
    #: request and NO pages (``runtime/paged.cache_groups`` puts it in
    #: no group), and only the schedules that carry a state serve it.
    #: ``heads`` repeats ``linear.heads``; no attention field applies.
    linear: KdaSpec | None = None
    #: An output gate on the attention (latent attention too), ``attn *
    #: sigmoid(W_gate u)`` element by element, before the
    #: out-projection.
    attn_gate: bool = False
    #: The residual as ``streams.streams`` streams of ``dim`` mixed
    #: around every sub-layer (``models/mhc``); the block's input and
    #: output are then (b, s, streams, dim).
    streams: HyperSpec | None = None
    #: Scalar multipliers a family puts on its branches (muP-style):
    #: on the attention's input, on K after its projection, on the
    #: attention's output; on the gate before its activation and on the
    #: MLP's output. The mixer's own are in ``ssm``.
    attn_in_mult: float = 1.0
    key_mult: float = 1.0
    attn_out_mult: float = 1.0
    mlp_gate_mult: float = 1.0
    mlp_out_mult: float = 1.0

    def __post_init__(self):
        if (
            self.head_dim is None and self.linear is None
            and self.dim % self.heads
        ):
            raise ValueError(
                f"model dim {self.dim} not divisible by {self.heads} heads"
            )
        if self.rope_base is not None and self.attn_head_dim % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {self.attn_head_dim}"
            )
        kvh = self.kv_heads
        if kvh is not None:
            if not 1 <= kvh <= self.heads:
                raise ValueError(
                    f"kv_heads {kvh} outside [1, heads={self.heads}]"
                )
            if self.heads % kvh:
                raise ValueError(
                    f"heads {self.heads} not divisible by kv_heads {kvh}"
                )
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm={self.norm!r}")
        if self.mlp not in ("gelu", "gated_silu", "experts", "moe_dense"):
            raise ValueError(f"mlp={self.mlp!r}")
        if (self.mlp == "experts") != (self.experts is not None):
            raise ValueError("mlp='experts' goes with an ExpertSpec")
        if self.swiglu_limit is not None and self.mlp != "gated_silu":
            raise ValueError(
                f"swiglu_limit clamps a gated_silu MLP, not mlp={self.mlp!r} "
                "(experts: ExpertSpec.swiglu_limit)"
            )
        if self.ssm is not None and self.post_norm:
            raise ValueError(
                "a mixer beside the attention reads the block's normed "
                "INPUT: post_norm has none"
            )
        if self.sandwich_norm and (
            self.post_norm or self.ssm or self.streams
        ):
            raise ValueError(
                "sandwich_norm is norms on BOTH sides of a sub-layer: "
                "post_norm says the output alone, and no norm is defined "
                "on the sum of two mixers or on a write-back into streams"
            )
        if self.latent is not None:
            if self.rope_base is None:
                raise ValueError(
                    "latent attention rotates its shared key part: "
                    "rope_base says by what"
                )
            for field in ("kv_heads", "head_dim", "window", "ssm"):
                if getattr(self, field) is not None:
                    raise ValueError(
                        f"{field} does not apply to a latent-attention "
                        "block (its widths are in `latent`)"
                    )
            if self.qk_norm:
                raise ValueError(
                    "qk_norm does not apply to a latent-attention block "
                    "(its latents are normed)"
                )
        if self.streams is not None and (
            self.post_norm or self.ssm or self.linear
        ):
            raise ValueError(
                "residual streams mix around a sub-layer that reads its "
                "normed INPUT, and no recurrent mixer is defined over them"
            )
        if self.linear is not None:
            for field in (
                "kv_heads", "head_dim", "window", "ssm", "latent",
                "rope_base",
            ):
                if getattr(self, field) is not None:
                    raise ValueError(
                        f"{field} does not apply to a linear-attention "
                        "block (it has no attention; its widths are in "
                        "`linear`)"
                    )
            if self.qk_norm or self.attn_gate or self.post_norm:
                raise ValueError(
                    "qk_norm, attn_gate and post_norm do not apply to a "
                    "linear-attention block (the mixer normalises q and "
                    "k, gates its own output and reads the normed INPUT; "
                    "sandwich_norm adds a norm on its output)"
                )
            if self.heads != self.linear.heads:
                raise ValueError(
                    f"heads {self.heads} != linear.heads "
                    f"{self.linear.heads}"
                )

    @property
    def attn_head_dim(self) -> int:
        if self.latent is not None:
            return self.latent.qk_dim
        return self.head_dim or self.dim // self.heads

    @property
    def cache_heads(self) -> int:
        """Head count of the K/V cache: kv_heads under GQA."""
        return self.kv_heads or self.heads

    @property
    def state_spec(self):
        """The spec of the recurrent ``(state, tail)`` a request owns
        of this block (``state_shapes(rows, dtype)``), None where it
        owns none: a mixer beside the attention, or one in its place."""
        return self.ssm or self.linear

    @property
    def cache_row(self) -> int | None:
        """Width of the ONE row a position stores in a latent cache
        (no head axis); None: K and V a KV head."""
        return None if self.latent is None else self.latent.row

    @property
    def cache_index_row(self) -> int | None:
        """Width of the index key a position stores in a SELECTING
        latent block's second plane; None: the block keeps none."""
        if self.latent is None or self.latent.index is None:
            return None
        return self.latent.index.dim


def _norm(kind: str, eps: float, dtype):
    """A norm module by its spec's ``norm`` and ``norm_eps`` (one
    definition for blocks and head)."""
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=dtype)
    return nn.LayerNorm(epsilon=eps, dtype=dtype)


class CausalSelfAttention(nn.Module):
    """Causal MHA/GQA sharing weights between the full-sequence path
    (flash dispatch) and the single-token cached path.

    ``kv_heads`` (grouped-query attention): project K/V to fewer heads
    than Q and let each group of ``heads // kv_heads`` query heads share
    one K/V head. The KV cache — the thing decode streams from HBM every
    step and the thing that caps context per chip — shrinks by that same
    factor, composing multiplicatively with the int8 cache option.
    ``kv_heads=1`` is multi-query attention. ``kv_heads=None`` (or ==
    ``heads``) keeps the fused-QKV MHA parameter structure byte-for-byte
    so existing checkpoints and tests are untouched.

    Head-group convention everywhere (full path, decode, verify): query
    head ``i`` uses KV head ``i // group`` — adjacent query heads share.
    The decode/verify paths never materialize repeated K/V: query heads
    fold into extra query ROWS over the (b, kv_heads, L, hd) cache, so
    the HBM traffic is the small cache, not a broadcast copy."""

    #: What the block is (``BlockSpec``): widths, GQA, ``head_dim``, QK
    #: norm, the rotation's base (None: no rotation; the cache stores
    #: POST-rotation K, so every cached decode path works unchanged) and
    #: the sliding window (decode-side just a dynamic ``valid_from``:
    #: the kernels need no change, and paged serving can RECYCLE pages
    #: behind the window; full-sequence forwards band the causal mask).
    spec: BlockSpec
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        spec, head_dim = self.spec, self.head_dim
        if self._group == 1:
            # MHA: one fused projection (unchanged param structure).
            self.qkv = nn.DenseGeneral(
                (3, self.heads, head_dim), dtype=self.dtype, name="qkv",
                use_bias=spec.bias,
            )
        else:
            self.q_proj = nn.DenseGeneral(
                (self.heads, head_dim), dtype=self.dtype, name="q",
                use_bias=spec.bias,
            )
            self.kv_proj = nn.DenseGeneral(
                (2, self.cache_heads, head_dim), dtype=self.dtype,
                name="kv", use_bias=spec.bias,
            )
        if spec.qk_norm:
            self.q_norm = nn.RMSNorm(epsilon=spec.norm_eps, dtype=self.dtype)
            self.k_norm = nn.RMSNorm(epsilon=spec.norm_eps, dtype=self.dtype)
        if spec.attn_gate:
            self.gate = nn.Dense(
                self.heads * head_dim, dtype=self.dtype, name="gate",
                use_bias=False,
            )
        self.out = nn.Dense(
            self.dim, dtype=self.dtype, name="out", use_bias=spec.bias
        )

    @property
    def dim(self) -> int:
        return self.spec.dim

    @property
    def heads(self) -> int:
        return self.spec.heads

    @property
    def window(self) -> int | None:
        return self.spec.window

    @property
    def rope(self) -> bool:
        return self.spec.rope_base is not None

    @property
    def _group(self) -> int:
        """Query heads per KV head (1 = plain MHA)."""
        return self.heads // self.cache_heads

    @property
    def cache_heads(self) -> int:
        """Head count of K/V cache buffers — kv_heads under GQA, heads
        otherwise. External cache allocators MUST use this (not
        ``heads``) or GQA models get heads-sized buffers and shape
        errors at runtime."""
        return self.spec.cache_heads

    @property
    def head_dim(self) -> int:
        return self.spec.attn_head_dim

    def _project(self, x):
        """-> q (b, h, s, hd); k, v (b, kv_h, s, hd) (kv_h == h for
        MHA)."""
        if self._group == 1:
            qkv = self.qkv(x)  # (b, s, 3, h, hd)
            q, k, v = jnp.moveaxis(qkv, 2, 0)
        else:
            q = self.q_proj(x)  # (b, s, h, hd)
            k, v = jnp.moveaxis(self.kv_proj(x), 2, 0)  # (b, s, kv_h, hd)
        if self.spec.qk_norm:  # over head_dim, before any rotation
            q, k = self.q_norm(q), self.k_norm(k)
        k = scaled(k, self.spec.key_mult)
        # -> (b, heads-axis, s, hd)
        return tuple(jnp.swapaxes(t, 1, 2) for t in (q, k, v))

    def _merge_heads(self, o, s):
        """(b, h, s, hd) attention output -> (b, s, h * hd), the out
        projection's input (h * hd is ``dim`` only where head_dim is
        ``dim // heads``)."""
        return jnp.swapaxes(o, 1, 2).reshape(
            o.shape[0], s, self.heads * self.head_dim
        )

    def _finish(self, o, x):
        """The tail every schedule shares: (b, h, s, hd) attention
        output of the block input ``x`` (b, s, d) -> merged heads,
        gated where the spec says (the sigmoid in float32), projected
        out."""
        o = self._merge_heads(o, x.shape[1])
        if self.spec.attn_gate:
            gate = jax.nn.sigmoid(self.gate(x).astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(o.dtype)
        return self.out(o)

    def _repeat_kv(self, t):
        """Expand (b, kv_h, s, hd) -> (b, h, s, hd) for the full-sequence
        flash path: repeat is adjacent-block so query head i lines up
        with KV head i // group."""
        g = self._group
        return t if g == 1 else jnp.repeat(t, g, axis=1)

    def _group_q(self, q):
        """Fold query-head groups into query rows: (b, h, s, hd) ->
        (b, kv_h, g*s, hd), row index = group_member * s + position —
        the cached-path attention then runs against the UN-repeated
        (b, kv_h, L, hd) cache with identical einsums."""
        b, h, s, hd = q.shape
        g = self._group
        return q.reshape(b, h // g, g * s, hd)

    def _ungroup_o(self, o, s):
        """Inverse of ``_group_q`` on the attention output."""
        b, kvh, gs, hd = o.shape
        return o.reshape(b, kvh * (gs // s), s, hd)

    def _rope_qk(self, q, k, positions):
        """Rotate q and k by ``positions`` when rope is on (no-op
        otherwise). Runs BEFORE GQA group folding / caching, so the
        cache holds post-rotation K."""
        base = self.spec.rope_base
        if base is None:
            return q, k
        return apply_rope(q, positions, base), apply_rope(k, positions, base)

    def __call__(self, x):
        b, s, d = x.shape
        q, k, v = self._project(x)
        q, k = self._rope_qk(q, k, jnp.arange(s))
        o = flash_attention(
            q, self._repeat_kv(k), self._repeat_kv(v), causal=True,
            window=self.window,
        )
        return self._finish(o, x)

    def _window_from(self, index, b, valid_from):
        """Effective ``valid_from`` for cached decode under a sliding
        window: the window's left edge per row, max-composed with any
        ragged left padding. None when windowless and dense."""
        if self.window is None:
            return valid_from
        idx = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32).reshape(-1), (b,)
        )
        w_from = jnp.maximum(idx - self.window + 1, 0)
        if valid_from is not None:
            w_from = jnp.maximum(w_from, valid_from)
        return w_from

    # One scale per cached key/value vector — the shared scheme in
    # ops.quantize (the kernel tests and on-chip smoke quantize with the
    # same function, so the definition cannot fork).
    _quantize_kv = staticmethod(quantize_kv_vectors)

    def _cache_repr(self, width, k, v):
        """``(k, v)`` in the representation of the cache they are about
        to be written to. ``width`` = the lanes ONE vector takes in a
        quantized cache's value plane, None for a native cache: native
        K/V pass as given; a quantized cache gets ``(values, scales)``
        pairs by the shared absmax scheme, and its width is
        authoritative for the dtype — ``head_dim // 2`` lanes a vector
        is int4-PACKED (two nibbles per int8 lane —
        ``ops.quantize.quantize_kv_vectors(..., "int4")``), so every
        write packs to match without any extra plumbing. THE one
        quantize-before-write definition, so the decode/prefill/verify
        paths cannot diverge."""
        if width is None:
            return k, v
        dt = "int4" if width * 2 == k.shape[-1] else "int8"
        return self._quantize_kv(k, dt), self._quantize_kv(v, dt)

    def _write_kv_pair(self, cache_k, cache_v, k, v, write):
        """Fan one K/V write out over a DENSE cache's representation
        (two strips, ``(values, scales)`` pairs when quantized):
        ``write(member, new)`` — the call site's own primitive
        (``append_kv``) — runs on every member."""
        quantized = isinstance(cache_k, tuple)
        k, v = self._cache_repr(
            cache_k[0].shape[-1] if quantized else None, k, v
        )
        return jax.tree.map(write, (cache_k, cache_v), (k, v))

    def _write_kv_pool(self, pool, k, v, write):
        """The paged twin: a block's POOL holds K and V of a position
        as ONE fused row (``ops.paged_attention.fuse_kv``; beside it,
        when quantized, the two scale planes), so ``write(plane, new)``
        — page scatter, chunk scatter, ``append_kv_paged`` — runs once
        on the fused rows and once per scale plane."""
        quantized = isinstance(pool, tuple)
        k, v = self._cache_repr(
            pool[0].shape[-1] // 2 if quantized else None, k, v
        )
        return jax.tree.map(write, pool, fuse_kv(k, v))

    def prefill(self, x, max_len: int, valid_from=None, quantize_cache=False):
        """Full causal attention over the prompt, returning output plus
        K/V caches padded to ``max_len`` (zeros beyond the prompt are
        masked by position in ``decode_step``).

        ``valid_from`` (b,) enables ragged batches: row i's keys at
        positions < valid_from[i] are left-padding and masked out. The
        masked variant rides the same measured dispatch as the dense one
        — the Pallas kernel carries the per-row mask as an SMEM scalar,
        so a ragged long-context prefill streams instead of falling back
        to the O(S^2) oracle.

        ``quantize_cache`` stores the cache quantized: ``True`` /
        ``"int8"`` = int8 (one absmax scale per key/value vector),
        ``"int4"`` = the 15-level nibble lattice PACKED two per int8
        lane (values ``head_dim // 2`` wide, same scale plane). This is
        a CONTEXT-CAPACITY feature, not a
        speed feature: cache bytes drop ~1.9x vs bf16 (measured
        603,979,776 -> 320,864,256 at bs8/2k, so ~1.9x more context per
        chip), but the hardware A/B (r04 `lm_decode_long_{native,int8}`)
        measured decode ~12% SLOWER (1,964 vs 2,226 tok/s at 2k context,
        GPT-2-small) — XLA does not fuse the per-step dequant for free,
        so the bandwidth saving does not show up as throughput at this
        size. Caches become ``(int8 values, f32 scales)`` pairs."""
        b, s, d = x.shape
        q, k, v = self._project(x)
        pos = jnp.arange(s)
        if valid_from is not None:
            # LOGICAL positions (0 at each row's first real token) keep
            # a ragged row's rotations bitwise-equal to its solo run.
            pos = pos[None, :] - valid_from[:, None]
        q, k = self._rope_qk(q, k, pos)
        o = flash_attention(
            q, self._repeat_kv(k), self._repeat_kv(v),
            causal=True, valid_from=valid_from, window=self.window,
        )
        pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
        out = self._finish(o, x)
        if quantize_cache:
            dt = "int4" if quantize_cache == "int4" else "int8"
            kv_, ks = self._quantize_kv(k, dt)
            vv_, vs = self._quantize_kv(v, dt)
            return (
                out,
                (jnp.pad(kv_, pad), jnp.pad(ks, pad)),
                (jnp.pad(vv_, pad), jnp.pad(vs, pad)),
            )
        return out, jnp.pad(k, pad), jnp.pad(v, pad)

    # Write K tokens' K or V at ``index`` (scalar: whole batch at one
    # position, the generate() lockstep; (b,): each ROW at its own
    # position — continuous batching and batched speculation, where
    # every slot is at a different sequence length). One definition in
    # ``ops/decode_attention.append_kv`` shared with the verify paths.
    _cache_write = staticmethod(append_kv)

    def decode_step(
        self, x_t, cache_k, cache_v, index, valid_from=None, quantized=False,
        attn_impl=None,
    ):
        """One token: write its K/V at ``index``, attend its q over the
        cache. ``index`` is traced — the same compiled step serves every
        position — and may be scalar (whole batch in lockstep) or (b,)
        (each row at its own position; see ``_cache_write``).
        ``valid_from`` (b,) masks a ragged batch's left padding out of
        the cache window. ``quantized`` caches are ``(int8 values, f32
        scales)`` pairs (see ``prefill``). The attention itself is
        :func:`adapt_tpu.ops.decode_attention.decode_attention` —
        ``attn_impl`` (None = measured auto, ``"xla"``, ``"pallas"``)
        picks between the einsum schedule and the streaming Pallas
        kernel that dequantizes int8 caches in VMEM."""
        b = x_t.shape[0]
        q, k, v = self._project(x_t)  # q (b, h, 1, hd); k/v (b, kv_h, 1, hd)
        if self.rope:
            idx = jnp.broadcast_to(
                jnp.asarray(index, jnp.int32).reshape(-1), (b,)
            )
            logical = idx - (0 if valid_from is None else valid_from)
            q, k = self._rope_qk(q, k, logical[:, None])
        # GQA: fold query-head groups into query rows so the attention
        # runs unchanged against the small (b, kv_h, L, hd) cache.
        q = self._group_q(q)  # (b, kv_h, g, hd)
        # The cache representation is authoritative (tuple iff
        # quantized — prefill builds it that way); the ``quantized``
        # parameter is the callers' static-arg plumbing, kept for
        # signature stability.
        del quantized
        cache_k, cache_v = self._write_kv_pair(
            cache_k, cache_v, k, v,
            lambda c, t: self._cache_write(c, t, index),
        )
        o = decode_attention(
            q, cache_k, cache_v, index,
            self._window_from(index, b, valid_from), prefer=attn_impl,
        ).astype(x_t.dtype)
        o = self._ungroup_o(o, 1)  # (b, h, 1, hd)
        return self._finish(o, x_t), cache_k, cache_v


    def decode_step_paged(
        self, x_t, pool, page_table, index, valid_from=None,
        attn_impl=None, head_shard=None,
    ):
        """One token against a PAGED cache (``ops/paged_attention``):
        write this step's K|V row into the slot's physical page at
        ``index``'s (page, offset), then attend over the table-mapped
        window. ``index`` scalar or (b,) as in ``decode_step``;
        ``pool`` is the block's pool (``runtime/paged.alloc_kv_pools``):
        the fused (num_pages, kv_h, P, 2 * hd) plane, or a quantized
        ``(int8 values, k_scales, v_scales)`` triple (scales
        (num_pages, kv_h, P, 1); this step's K/V quantize via the
        shared absmax scheme before the scatter, and dequant fuses into
        the attention — see ``ops/paged_attention``). Returns ``(out,
        pool)``. ``page_table`` (b, pages_per_slot)
        int32 (idle rows may map everything to the trash page — their
        writes land there, unread). ``head_shard`` = ``(mesh, axis)``
        when the caller's program is tp-partitioned: the Pallas kernel
        then runs per head shard (``ops.paged_attention._head_sharded``)
        — here and in the chunk/verify twins below."""
        b = x_t.shape[0]
        page = pool_values(pool).shape[2]
        q, k, v = self._project(x_t)  # q (b, h, 1, hd); k/v (b, kv_h, 1, hd)
        idx = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32).reshape(-1), (b,)
        )
        if self.rope:
            logical = idx - (0 if valid_from is None else valid_from)
            q, k = self._rope_qk(q, k, logical[:, None])
        q = self._group_q(q)  # (b, kv_h, g, hd)
        # Negative index = dead row (idle or mid-chunked-prefill slot in
        # a lockstep batch). Its garbage write MUST go to the trash page
        # — the row may own real pages (a prefilling slot does), and
        # table[row, 0] would be prompt page 0. Attention masks every
        # position (cols <= negative is empty), so nothing reads back.
        live_row = idx >= 0
        safe = jnp.maximum(idx, 0)
        phys = jnp.take_along_axis(
            page_table, (safe // page)[:, None], axis=1
        )[:, 0]  # (b,) physical page of each row's write
        phys = jnp.where(live_row, phys, 0)
        off = safe % page

        def write(plane, t):  # rows (phys[i], :, off[i], :) <- token i
            return append_kv_paged(plane, t, phys[:, None], off[:, None])

        pool = self._write_kv_pool(pool, k, v, write)
        o = paged_attention(
            q, pool, page_table, index,
            self._window_from(index, b, valid_from), prefer=attn_impl,
            head_shard=head_shard,
        ).astype(x_t.dtype)
        o = self._ungroup_o(o, 1)
        return self._finish(o, x_t), pool

    def prefill_chunk_paged(
        self, x, pool, pages, pos0, attn_impl=None, head_shard=None,
    ):
        """Incremental prefill of a CHUNK of positions [pos0, pos0 + C)
        directly against a paged window: write the chunk's K/V into its
        own pages (one O(C) scatter), then attend the whole window in
        place via :func:`paged_chunk_attention` — no gathered strip, no
        scatter-back (the chunked-prefill counterpart of
        ``decode_step_paged``). ``pages`` (n,) covers [0, pos0 + C)
        (pow2 trash padding allowed); ``pos0`` is page-aligned and C is
        a whole number of pages. Batch 1 (prefill is per request).
        Quantized pools quantize the chunk's
        K/V before the page scatter — note the chunk then ATTENDS the
        already-quantized earlier window, so a chunked/suffix prefill
        over int8 pools carries the cache's quantization error into the
        chunk's hidden states (same fine print as chunk fp contraction
        widths, one quantization step coarser)."""
        b, c, d = x.shape
        page = pool_values(pool).shape[2]
        q, k, v = self._project(x)  # q (1, h, C, hd); k/v (1, kv_h, C, hd)
        q, k = self._rope_qk(q, k, pos0 + jnp.arange(c))
        q = self._group_q(q)  # (1, kv_h, g*C, hd)
        n_chunk = c // page
        chunk_pages = lax.dynamic_slice(
            jnp.asarray(pages, jnp.int32), (pos0 // page,), (n_chunk,)
        )
        kvh = k.shape[1]

        def to_pages(t):  # (1, kv_h, C, w) -> (n_chunk, kv_h, page, w)
            return jnp.swapaxes(
                t[0].reshape(kvh, n_chunk, page, t.shape[3]), 0, 1
            )

        def write(plane, t):
            return plane.at[chunk_pages].set(
                to_pages(t).astype(plane.dtype)
            )

        pool = self._write_kv_pool(pool, k, v, write)
        o = paged_chunk_attention(
            q, pool, pages, pos0, c, prefer=attn_impl,
            window=self.window, head_shard=head_shard,
        ).astype(x.dtype)
        o = self._ungroup_o(o, c)  # (1, h, C, hd)
        return self._finish(o, x), pool

    def prefill_sp(self, x, gather, quantize_cache=False, constrain=None):
        """SEQUENCE-PARALLEL prefill body: the whole span's attention
        in one layer-synchronous pass, written so every per-row
        operation mirrors the computation :meth:`prefill_chunk_paged`
        runs for that row, op for op — the sp-sharded prefill program
        (``parallel/sp_prefill``) equals the single-device chunked
        prefill up to the rounding of the row reductions, whose width
        differs between the two (chunked prefill's documented ulp fine
        print; see the sp_prefill module docstring).

        ``x`` is (1, S, d) with the S axis sp-sharded under GSPMD
        (projections, rope, quantization and the MLP are all
        token-local, so they compute shard-locally for free).
        ``gather`` is the caller's window collective — the ring
        collect in ``parallel/sp_prefill.ring_collect`` (K/V blocks
        rotate via ``lax.ppermute`` neighbor hops; each rank
        accumulates the full window) — applied to the POOL
        REPRESENTATION of K/V, exactly what the paged pools would
        hold: ``quantize_cache`` False keeps native dtype,
        ``"int8"``/``"int4"`` quantize with the shared absmax scheme
        (int4 packed two nibbles per lane) BEFORE the window is read,
        so the chunk-attends-the-already-quantized-window fine print
        of chunked prefill is reproduced exactly. The attention math
        mirrors ``paged_chunk_attention_reference`` op for op (f32
        scores, scale columns, -1e30 mask, softmax, scale-weighted
        probabilities) with the mask ``col <= row`` — per-row
        identical to any chunk schedule's mask, with trailing bucket
        padding contributing exact zeros.

        Returns ``(out, cache_k, cache_v)`` where the caches are the
        pool-representation ``(1, kv_h, S, w)`` arrays (or
        ``(values, scales)`` tuples) in sequence order — the caller
        slices them into page-major handoff blocks.

        ``constrain`` (optional) pins the attention intermediates'
        row axis to the caller's sp sharding
        (``with_sharding_constraint`` on ``(1, kv_h, rows, X)``
        arrays): without it GSPMD's propagation may replicate the
        score block — every rank computing every row — which is
        numerically identical but forfeits exactly the O(S^2/P)
        compute split this path exists for. Resharding never changes
        values, so the page contract is constraint-blind."""
        b, s, d = x.shape
        q, k, v = self._project(x)
        q, k = self._rope_qk(q, k, jnp.arange(s))
        if quantize_cache:
            dt = "int4" if quantize_cache == "int4" else "int8"
            ck = self._quantize_kv(k, dt)
            cv = self._quantize_kv(v, dt)
        else:
            ck, cv = k, v
        kg = gather(ck)
        vg = gather(cv)
        pin = constrain if constrain is not None else (lambda t: t)
        q = pin(self._group_q(q))  # (1, kv_h, g*S, hd), rows sp-sharded
        sm = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        if quantize_cache:
            kv_, ksc = kg
            vv_, vsc = vg
            if kv_.shape[-1] * 2 == q.shape[-1]:  # packed int4 nibbles
                kv_, vv_ = unpack_int4(kv_), unpack_int4(vv_)
            s_ = jnp.einsum(
                "bhqd,bhkd->bhqk",
                q.astype(jnp.float32),
                kv_.astype(jnp.float32),
            ) * jnp.swapaxes(ksc, 2, 3) * sm
        else:
            kv_, vv_ = kg, vg
            s_ = jnp.einsum(
                "bhqd,bhkd->bhqk",
                q.astype(jnp.float32),
                kv_.astype(jnp.float32),
            ) * sm
        rows = jnp.arange(q.shape[2]) % s  # folded row -> position
        cols = jnp.arange(s)
        live = cols[None, :] <= rows[:, None]
        if self.window is not None:
            live = live & (cols[None, :] > rows[:, None] - self.window)
        s_ = pin(jnp.where(live[None, None], s_, -1e30))
        p = jax.nn.softmax(s_, axis=-1)
        if quantize_cache:
            p = p * jnp.swapaxes(vsc, 2, 3)
        o = pin(jnp.einsum(
            "bhqk,bhkd->bhqd", p, vv_.astype(jnp.float32)
        ).astype(q.dtype))
        o = self._ungroup_o(o, s)  # (1, h, S, hd)
        return self._finish(o, x), ck, cv

    def verify_chunk(self, x, cache_k, cache_v, index, tree_tail=0):
        """Append a CHUNK of ``K`` tokens at positions
        ``index..index+K-1`` in ONE cached pass — the speculative-decode
        verify primitive: each chunk row's query attends the cache up to
        its own position (``p <= index + row``), so the K logits equal
        exactly what K sequential ``decode_step`` calls would produce,
        for one forward instead of K. ``index`` is scalar (the
        single-request speculative loop) or (b,) (BATCHED speculation:
        every slot verifies its own chunk at its own position — rows
        desynchronize, the compiled program does not; a negative row
        index marks a dead slot whose writes and reads are trash-masked).
        The chunk K/V write is one ``append_kv`` scatter; rejected
        suffixes need no rollback — the position mask simply never
        admits them (the same trash-slot discipline the continuous
        batcher uses). Quantized ``(int8 values, f32 scales)`` cache
        pairs quantize the chunk's K/V with the shared absmax scheme
        before the append — the same values K sequential quantized
        ``decode_step`` calls would write, so quantized verify logits
        equal the sequential quantized decode's.

        ``tree_tail`` = w > 0 marks the chunk's last w rows as TREE
        LEAVES — grouped draft candidates for ONE logical position,
        ``index + chain + 1`` (chain = K - 1 - w): they embed/rotate at
        that shared logical position, write at their own DISTINCT
        physical cache slots (``index + row``, inside the speculative
        slack), and attend the chain plus only themselves
        (``ops.decode_attention.verify_attention``'s tree mask) — one
        verify pass scores every leaf of a draft token tree."""
        b, kc, d = x.shape
        q, k, v = self._project(x)  # q (b, h, K, hd); k/v (b, kv_h, K, hd)
        offs = jnp.arange(kc)
        if tree_tail:
            # Leaves share the logical position after the chain.
            offs = jnp.minimum(offs, kc - tree_tail)
        if jnp.ndim(index):
            pos = index[:, None] + offs[None, :]  # (b, K)
        else:
            pos = index + offs
        q, k = self._rope_qk(q, k, pos)
        q = self._group_q(q)  # (b, kv_h, g*K, hd), row = member*K + pos
        cache_k, cache_v = self._write_kv_pair(
            cache_k, cache_v, k, v, lambda c, t: append_kv(c, t, index)
        )
        o = verify_attention(
            q, cache_k, cache_v, index, kc, window=self.window,
            tree_tail=tree_tail,
        ).astype(x.dtype)
        o = self._ungroup_o(o, kc)  # (b, h, K, hd)
        return self._finish(o, x), cache_k, cache_v

    def verify_chunk_paged(
        self, x, pool, page_table, index, attn_impl=None,
        tree_tail=0, head_shard=None,
    ):
        """Batched verify over a PAGED cache: scatter each slot's K
        chunk tokens into its own pages at ``index[b]..index[b]+K-1``
        (table-mapped, ``append_kv_paged``), then attend each
        row's paged window up to its own diagonal
        (:func:`paged_verify_attention`) — ``verify_chunk``'s exact
        semantics over ``decode_step_paged``'s layout. ``index`` (b,);
        a negative row is dead (idle or mid-chunked-prefill slot): its
        writes route to the trash page and its positions all mask.
        A quantized ``(values, k_scales, v_scales)`` pool takes the
        chunk's quantized rows into all three planes (the scale planes
        ride the same page table). ``tree_tail`` as in
        ``verify_chunk``."""
        b, kc, _ = x.shape
        page = pool_values(pool).shape[2]
        q, k, v = self._project(x)  # q (b, h, K, hd); k/v (b, kv_h, K, hd)
        idx = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32).reshape(-1), (b,)
        )
        offs = jnp.arange(kc)
        if tree_tail:
            offs = jnp.minimum(offs, kc - tree_tail)
        if self.rope:
            q, k = self._rope_qk(q, k, idx[:, None] + offs[None, :])
        q = self._group_q(q)  # (b, kv_h, g*K, hd)
        live_row = idx >= 0
        pos = jnp.maximum(idx, 0)[:, None] + jnp.arange(kc)[None, :]
        phys = jnp.take_along_axis(page_table, pos // page, axis=1)
        phys = jnp.where(live_row[:, None], phys, 0)  # dead -> trash page
        off = pos % page
        # (phys[b,t], :, off[b,t], :) <- token t of slot b. Dead rows'
        # K writes pile onto the trash page — never read (their masks
        # are empty).
        def write(plane, t):
            return append_kv_paged(plane, t, phys, off)

        pool = self._write_kv_pool(pool, k, v, write)
        o = paged_verify_attention(
            q, pool, page_table, idx, kc, prefer=attn_impl,
            window=self.window, tree_tail=tree_tail,
            head_shard=head_shard,
        ).astype(x.dtype)
        o = self._ungroup_o(o, kc)
        return self._finish(o, x), pool


class DecoderBlock(nn.Module):
    """One decoder block, built from its :class:`BlockSpec`; residuals
    stay inside the node so block boundaries are clean pipeline cuts
    (same contract as ViT's ``EncoderBlock``).

    ``_attn_res`` and ``_mlp_res`` are the TWO touch points every
    schedule shares (full forward, prefill, decode_step, verify_chunk,
    paged chunk prefill): where the norms sit, and which MLP runs — the
    dense ones, the masked-dense mixture
    (:class:`adapt_tpu.models.moe.MoEDecoderMlp`) or the routed experts
    (:class:`adapt_tpu.models.moe.RoutedExperts`). Every MLP is
    token-independent, so a mixture serves through every decode path
    with the exact cache-parity contract of the dense block."""

    spec: BlockSpec
    dtype: jnp.dtype = jnp.float32

    @property
    def dim(self) -> int:
        return self.spec.dim

    @property
    def heads(self) -> int:
        return self.spec.heads

    @property
    def mlp_dim(self) -> int:
        return self.spec.mlp_dim

    @property
    def window(self) -> int | None:
        return self.spec.window

    @property
    def cache_heads(self) -> int:
        """Cache-buffer head count (see ``CausalSelfAttention.cache_heads``)."""
        return self.spec.cache_heads

    @property
    def head_dim(self) -> int:
        return self.spec.attn_head_dim

    def setup(self):
        spec = self.spec
        self.ln1 = _norm(spec.norm, spec.norm_eps, self.dtype)
        if spec.linear is not None:
            # In PLACE of attention: every schedule goes through
            # ``_mixers``, which never reaches ``self.attn`` here.
            self.mixer = KdaMixer(spec.linear, spec.dim, dtype=self.dtype)
        else:
            attention = (
                CausalSelfAttention if spec.latent is None
                else LatentSelfAttention
            )
            self.attn = attention(spec, dtype=self.dtype)
        self.ln2 = _norm(spec.norm, spec.norm_eps, self.dtype)
        if spec.sandwich_norm:
            self.ln1_post = _norm(spec.norm, spec.norm_eps, self.dtype)
            self.ln2_post = _norm(spec.norm, spec.norm_eps, self.dtype)
        if spec.streams is not None:
            self.hc_attn = HyperConnection(
                spec.streams, spec.dim, dtype=self.dtype
            )
            self.hc_mlp = HyperConnection(
                spec.streams, spec.dim, dtype=self.dtype
            )
        if spec.ssm is not None:
            self.ssm = Mamba2Mixer(spec.ssm, spec.dim, dtype=self.dtype)
        if spec.mlp == "experts":
            self.experts = RoutedExperts(spec.experts, dtype=self.dtype)
        elif spec.mlp == "moe_dense":
            self.moe = MoEDecoderMlp(
                num_experts=spec.moe_experts,
                hidden_dim=spec.mlp_dim,
                top_k=spec.moe_top_k,
                dtype=self.dtype,
            )
        else:
            def dense(n):
                return nn.Dense(n, dtype=self.dtype, use_bias=spec.bias)

            if spec.mlp == "gated_silu":
                self.mlp_gate = dense(spec.mlp_dim)
            self.mlp_in = dense(spec.mlp_dim)
            self.mlp_out = dense(spec.dim)

    def _mlp(self, x):
        kind = self.spec.mlp
        if kind == "experts":
            return self.experts(x)
        if kind == "moe_dense":
            return self.moe(x)
        if kind == "gated_silu":
            gate = scaled(self.mlp_gate(x), self.spec.mlp_gate_mult)
            return scaled(
                self.mlp_out(
                    nn.silu(limited(gate, self.spec.swiglu_limit))
                    * limited(self.mlp_in(x), self.spec.swiglu_limit, True)
                ),
                self.spec.mlp_out_mult,
            )
        return self.mlp_out(nn.gelu(self.mlp_in(x)))

    def _attn_in(self, x):
        """The normed input the block's mixers read (the attention's
        own multiplier goes on in ``_mixers``)."""
        return x if self.spec.post_norm else self.ln1(x)

    def _attn_in_streams(self, x):
        """``_attn_in`` where the residual is streams: ``(u, back)``,
        the normed mix of the streams the mixers read and what
        ``_attn_res`` writes their output back through."""
        u, back = self.hc_attn(x)
        return self.ln1(u), back

    def _attn_res(self, x, a, s=None, back=None):
        """The residual after the token mixers: the attention's output
        ``a`` and, beside it, the state-space mixer's ``s``; ``back``
        (``_attn_in_streams``): the streams' write-back."""
        a = scaled(a, self.spec.attn_out_mult)
        if back is not None:
            return merge(x, a, back)
        if s is not None:
            a = a + scaled(s, self.spec.ssm.out_mult)
        if self.spec.sandwich_norm:
            return x + self.ln1_post(a)
        return x + (self.ln1(a) if self.spec.post_norm else a)

    def _mixers(self, x, attend, mix=None, caches=1):
        """The one shape every schedule of a block has: ``attend(u)``
        -> ``(a, *cache)`` is the schedule's attention call on the
        normed input ``u``; a block with a state-space mixer also runs
        ``mix(ssm, u)`` -> ``(s, carried')`` on the SAME ``u`` and
        returns ``carried'`` last. A linear-attention block runs
        ``mix`` INSTEAD of ``attend`` and returns None in each of the
        ``caches`` places the schedule's attention would have filled
        (it has no pages), then ``carried'``."""
        if self.spec.linear is not None:
            s, carried = mix(self.mixer, self._attn_in(x))
            if self.spec.sandwich_norm:
                s = self.ln1_post(s)
            return (self._mlp_res(x + s), *(None,) * caches, carried)
        if self.spec.streams is None:
            u, back = self._attn_in(x), None
        else:
            u, back = self._attn_in_streams(x)
        a, *cache = attend(scaled(u, self.spec.attn_in_mult))
        if self.spec.ssm is None:
            return (self._mlp_res(self._attn_res(x, a, back=back)), *cache)
        s, carried = mix(self.ssm, u)
        return (self._mlp_res(self._attn_res(x, a, s)), *cache, carried)

    def _no_state(self, schedule: str):
        if self.spec.state_spec is not None:
            raise NotImplementedError(
                f"{schedule} carries no recurrent state: a block with a "
                "state-space or linear-attention mixer serves through "
                "prefill, prefill_chunk_paged and decode_step_paged"
            )

    def _mlp_res(self, x):
        if self.spec.streams is not None:
            u, mix = self.hc_mlp(x)
            return merge(x, self._mlp(self.ln2(u)), mix)
        if self.spec.post_norm:
            return x + self.ln2(self._mlp(x))
        if self.spec.sandwich_norm:
            return x + self.ln2_post(self._mlp(self.ln2(x)))
        return x + self._mlp(self.ln2(x))

    def __call__(self, x):
        return self._mixers(
            x, lambda u: (self.attn(u),), lambda ssm, u: (ssm(u), None)
        )[0]

    def prefill(self, x, max_len: int, valid_from=None, quantize_cache=False,
                length=None):
        """``length`` (a block with a state-space mixer; batch 1): the
        prompt's real length inside ``x``'s bucket; the recurrent
        ``(state, tail)`` after its last real position comes back
        last."""
        if valid_from is not None:
            self._no_state("a left-padded (ragged) prefill")
        return self._mixers(
            x,
            lambda u: self.attn.prefill(u, max_len, valid_from, quantize_cache),
            lambda ssm, u: ssm.scan(u, None, length),
            caches=2,
        )

    def decode_step(
        self, x_t, cache_k, cache_v, index, valid_from=None, quantized=False,
        attn_impl=None,
    ):
        self._no_state("decode_step over dense cache strips")
        return self._mixers(x_t, lambda u: self.attn.decode_step(
            u, cache_k, cache_v, index, valid_from, quantized,
            attn_impl=attn_impl,
        ))

    def decode_step_paged(
        self, x_t, pool, page_table, index, valid_from=None,
        attn_impl=None, head_shard=None, carried=None,
    ):
        """``carried``: the rows' recurrent ``(state, tail)`` where the
        block has a state-space mixer; advanced for the rows whose
        ``index`` is not negative and returned last."""
        return self._mixers(
            x_t,
            lambda u: self.attn.decode_step_paged(
                u, pool, page_table, index, valid_from,
                attn_impl=attn_impl, head_shard=head_shard,
            ),
            lambda ssm, u: ssm.step(
                u, carried,
                jnp.broadcast_to(jnp.asarray(index).reshape(-1) >= 0,
                                 (x_t.shape[0],)),
            ),
        )

    def prefill_chunk_paged(
        self, x, pool, pages, pos0, attn_impl=None, head_shard=None,
        carried=None, length=None,
    ):
        """``carried`` / ``length`` as in ``decode_step_paged`` /
        ``prefill``: the pass starts from the state the pass before
        left and returns the one after its last real position."""
        return self._mixers(
            x,
            lambda u: self.attn.prefill_chunk_paged(
                u, pool, pages, pos0, attn_impl, head_shard
            ),
            lambda ssm, u: ssm.scan(u, carried, length),
        )

    def prefill_sp(self, x, gather, quantize_cache=False, constrain=None):
        self._no_state("sequence-parallel prefill")
        return self._mixers(x, lambda u: self.attn.prefill_sp(
            u, gather, quantize_cache, constrain
        ))

    def verify_chunk(self, x, cache_k, cache_v, index, tree_tail=0):
        self._no_state("verify_chunk (a rejected token cannot be un-stepped)")
        return self._mixers(x, lambda u: self.attn.verify_chunk(
            u, cache_k, cache_v, index, tree_tail
        ))

    def verify_chunk_paged(
        self, x, pool, page_table, index, attn_impl=None,
        tree_tail=0, head_shard=None,
    ):
        self._no_state(
            "verify_chunk_paged (a rejected token cannot be un-stepped)"
        )
        return self._mixers(x, lambda u: self.attn.verify_chunk_paged(
            u, pool, page_table, index, attn_impl=attn_impl,
            tree_tail=tree_tail, head_shard=head_shard,
        ))


class TokenEmbed(nn.Module):
    """Token + (optionally) learned positional embeddings.

    ``use_pos=False`` drops the position table entirely — the rope
    decoder's position signal lives in the attention rotations, not in
    the residual stream; the three embed entry points keep their
    signatures so every schedule calls them identically."""

    vocab: int
    dim: int
    max_len: int
    dtype: jnp.dtype = jnp.float32
    use_pos: bool = True
    #: On the looked-up rows (a family's embedding multiplier).
    scale: float = 1.0
    #: Residual streams (``models/mhc``): above 1 a token's row is
    #: copied into that many, (b, s, streams, dim), in FLOAT32: the
    #: streams are mixed in float32 around every sub-layer, and kept in
    #: the served type between blocks they would be rounded twice a
    #: layer (the drift that flips a router near a tie, PERF.md
    #: section 6, PR 43); a sub-layer still computes in ``dtype``.
    streams: int = 1
    #: Lanes a row of the two tables is HELD with (unset: ``dim``): a
    #: holder that keeps its copy of the tables padded to whole lane
    #: tiles (:func:`lane_tiled`) says so here; every lookup gathers the
    #: held row and keeps its first ``dim`` lanes.
    table_dim: int | None = None

    def setup(self):
        held = self.table_dim or self.dim
        self.tok = nn.Embed(self.vocab, held, dtype=self.dtype)
        if self.use_pos:
            self.pos = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (self.max_len, held),
                jnp.float32,
            )

    def _lookup(self, ids, pos_rows):
        """The one lookup: the tokens' rows, cut to ``dim`` BEFORE scale,
        positions (``pos_rows(table)``: the rows of the position table
        the caller's schedule wants) and streams."""
        out = scaled(self.tok(ids)[..., : self.dim], self.scale)
        if self.use_pos:
            p = pos_rows(self.pos)[..., : self.dim]
            out = out + p.astype(self.dtype)
        if self.streams == 1:
            return out
        return jnp.broadcast_to(
            out.astype(jnp.float32)[..., None, :],
            (*out.shape[:-1], self.streams, self.dim),
        )

    def __call__(self, ids):
        return self._lookup(ids, lambda pos: pos[: ids.shape[1]])

    def embed_at(self, ids_t, index):
        """Embed a single token column at traced position ``index``."""
        return self._lookup(
            ids_t,
            lambda pos: lax.dynamic_slice(pos, (index, 0), (1, self.dim)),
        )

    def embed_positions(self, ids, pos_ids):
        """Embed with explicit per-row position ids (ragged batches:
        a left-padded row's logical positions start at 0 at its first
        real token, not at buffer column 0)."""
        return self._lookup(ids, lambda pos: pos[jnp.clip(pos_ids, 0)])


def lane_tiled(embed: TokenEmbed) -> TokenEmbed:
    """``embed`` for a holder that gathers ROWS from its tables program
    after program: where ``dim`` is not a whole number of lane tiles the
    device's default layout puts a table's LONG axis on the lanes (50257
    pads by 0.09%, rows of 1600 would pad by 4%), and every program that
    looks a row up first rewrites the whole table row-major (GPT-2-XL:
    322 MB a program, PERF.md section 6, PR 47). Rows held padded to the
    next tile lie row-major and are gathered as they lie. Whole-tile
    rows: ``embed`` itself."""
    pad = -embed.dim % LANES
    return embed.clone(table_dim=embed.dim + pad) if pad else embed


@partial(jax.jit, static_argnums=1)
def _rows_at(tables, held: int):
    """Every table of the tree with rows of ``held`` lanes: cut, or
    padded with zeros. ONE program for both tables (a start-up pays a
    compile each)."""

    def fit(table):
        have = table.shape[-1]
        if have > held:
            return table[:, :held]
        return jnp.pad(table, ((0, 0), (0, held - have)))

    return jax.tree.map(fit, tables)


def embed_tables_for(embed: TokenEmbed, variables):
    """The graph's ``variables`` with the embedding tables' rows at the
    width ``embed`` holds them with: padded (the model's tree to a
    :func:`lane_tiled` module's) or cut (the way back). Only the
    ``embed`` entry is rebuilt; at that width already, ``variables``
    itself."""
    held = embed.table_dim or embed.dim
    if jax.tree.leaves(variables["embed"])[0].shape[-1] == held:
        return variables
    return {**variables, "embed": _rows_at(variables["embed"], held)}


class LMHead(nn.Module):
    """Final norm + untied vocab projection (logits in f32 for stable
    sampling). ``norm`` / ``norm_eps`` / ``bias`` follow the blocks'
    spec."""

    vocab: int
    dtype: jnp.dtype = jnp.float32
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    bias: bool = True
    #: On the logits (a family's head multiplier).
    scale: float = 1.0
    #: Residual streams (``models/mhc``): above 1 the input is (b, s,
    #: streams, dim) and the streams are summed before the norm.
    streams: int = 1

    def setup(self):
        self.ln = _norm(self.norm, self.norm_eps, self.dtype)
        self.logits = nn.Dense(
            self.vocab, dtype=jnp.float32, use_bias=self.bias
        )

    def __call__(self, x):
        if self.streams > 1:
            x = x.astype(jnp.float32).sum(-2)
        out = self.logits(self.ln(x).astype(jnp.float32))
        return out if self.scale == 1.0 else out * self.scale


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    """A built LM: the pipeline-partitionable graph plus the decode
    metadata ``generate`` needs."""

    graph: LayerGraph
    depth: int
    max_len: int

    @property
    def vocab(self) -> int:
        """Read from the head module — one source of truth, no field that
        could drift from the actual logits dimension."""
        return self.graph.node("head").module.vocab

    @property
    def block_names(self) -> list[str]:
        return [f"decoder_block_{i}" for i in range(self.depth)]


def transformer_lm(
    vocab: int,
    dim: int | None = None,
    depth: int | None = None,
    heads: int | None = None,
    mlp_dim: int | None = None,
    max_len: int = 1024,
    dtype: jnp.dtype = jnp.float32,
    name: str = "transformer_lm",
    kv_heads: int | None = None,
    moe_experts: int | None = None,
    moe_top_k: int = 1,
    window: int | None = None,
    pos: str = "learned",
    blocks: Sequence[BlockSpec] | None = None,
    embed_scale: float = 1.0,
    head_scale: float = 1.0,
) -> TransformerLM:
    """A decoder is a LIST OF BLOCK SPECS (:class:`BlockSpec`) between
    an embedding and a head. ``blocks=`` gives the list as a model's
    configuration states it, one spec a layer (a per-layer pattern of
    window / full attention, dense / expert MLPs, rotations on some
    layers and none on others); ``pos`` is then ``"learned"`` or
    ``"none"`` (no position table: positions live in the blocks'
    rotations or nowhere), and the head's norm and bias follow the
    last block's spec. Without ``blocks`` the keywords below build
    ``depth`` copies of one spec — the GPT-2 block and its variants:

    ``kv_heads < heads`` builds a grouped-query (GQA) decoder: KV
    caches shrink by ``heads // kv_heads`` (``kv_heads=1`` = MQA), the
    serving-era cache-capacity knob — see ``CausalSelfAttention``.

    ``moe_experts`` builds a Mixtral-shaped MoE decoder: every block's
    MLP becomes a dropless per-token mixture of that many experts
    (``moe_top_k`` active per token, ``mlp_dim`` = per-expert hidden).
    Served by every decode path with exact cache parity, and
    EP-shardable via ``parallel.expert.place_experts`` — see
    :class:`DecoderBlock` / :class:`adapt_tpu.models.moe.MoEDecoderMlp`.

    ``pos="rope"`` swaps learned positional embeddings for rotary ones
    (q/k rotate by logical position in every schedule; the cache holds
    post-rotation K, so all decode paths serve it unchanged).

    ``window`` builds a sliding-window (Mistral-style) decoder: each
    position attends only the previous ``window`` positions. Cached
    decode masks the window as a dynamic ``valid_from`` (no kernel
    changes; blocks behind the window skip compute), and the paged
    batcher RECYCLES pages that fall wholly behind it mid-request —
    pool usage bounds by the window, not the sequence.

    ``embed_scale`` / ``head_scale``: a family's multipliers on the
    embedded rows and on the logits.
    """
    if blocks is None:
        if pos not in ("learned", "rope"):
            raise ValueError(f"pos={pos!r}: expected 'learned' or 'rope'")
        blocks = [BlockSpec(
            dim, heads, mlp_dim, kv_heads=kv_heads,
            mlp="gelu" if moe_experts is None else "moe_dense",
            moe_experts=moe_experts, moe_top_k=moe_top_k, window=window,
            rope_base=10000.0 if pos == "rope" else None,
        )] * depth
    else:
        if pos not in ("learned", "none"):
            raise ValueError(
                f"pos={pos!r}: with blocks= expected 'learned' or 'none' "
                "(a rotation is a block's rope_base)"
            )
        blocks = list(blocks)
        dim = blocks[0].dim
    last = blocks[-1]
    streams = {b.streams.streams if b.streams else 1 for b in blocks}
    if len(streams) > 1:
        raise ValueError(
            f"blocks disagree on the residual streams: {sorted(streams)}"
        )
    streams = streams.pop()
    g = LayerGraph(name)
    prev = g.add(
        "embed",
        TokenEmbed(vocab, dim, max_len, dtype=dtype,
                   use_pos=pos == "learned", scale=embed_scale,
                   streams=streams),
        INPUT,
    )
    for i, spec in enumerate(blocks):
        prev = g.add(
            f"decoder_block_{i}", DecoderBlock(spec, dtype=dtype), prev
        )
    g.add(
        "head",
        LMHead(vocab, dtype=dtype, norm=last.norm, norm_eps=last.norm_eps,
               bias=last.bias, scale=head_scale, streams=streams),
        prev,
    )
    return TransformerLM(graph=g, depth=len(blocks), max_len=max_len)


def lm_tiny(vocab: int = 256, max_len: int = 64) -> TransformerLM:
    """Small LM for tests."""
    return transformer_lm(vocab, 64, 4, 4, 128, max_len, name="lm_tiny")


def validate_tp(lm: TransformerLM, tp: int) -> None:
    """Eager divisibility checks for megatron-style tensor parallelism
    (``parallel.sharding.lm_tp_rules`` placement + head-sharded KV
    caches): every decoder block's query heads, KV/cache heads, model
    dim and MLP hidden must divide by ``tp``, or the column/row splits
    (and the cache's head-axis sharding) cannot land evenly. Raises a
    named ValueError instead of an opaque device_put/GSPMD error. The
    ``cache_heads`` check is the GQA-aware one: KV heads shard over tp,
    so kv_heads % tp == 0 keeps each shard's query-head groups aligned
    with its own resident KV heads (collective-free attention)."""
    if tp <= 1:
        return
    for name in lm.block_names:
        block = lm.graph.node(name).module
        if block.spec.latent is not None:
            raise ValueError(
                f"{name}: a latent-attention block does not split over tp "
                "(its cache has no head axis to shard: every chip keeps "
                "the whole row and serves its own requests)"
            )
        if block.spec.ssm is not None:
            raise ValueError(
                f"{name}: a state-space mixer does not split over tp (its "
                "recurrent state would shard by head beside the KV heads; "
                "no rule places it)"
            )
        if block.spec.linear is not None:
            raise ValueError(
                f"{name}: a linear-attention mixer does not split over tp "
                "(its recurrent state would shard by head; no rule places "
                "it)"
            )
        if block.spec.mlp == "experts":
            raise ValueError(
                f"{name}: routed experts do not split over tp (a chip "
                "holds whole experts: ExpertSpec.held); the exchange "
                "across chips is not built"
            )
        for what, n in (
            ("heads", block.heads),
            ("cache (KV) heads", block.cache_heads),
            ("model dim", block.dim),
            ("mlp hidden dim", block.mlp_dim),
        ):
            if n % tp:
                raise ValueError(
                    f"{name}: {what} {n} not divisible by tp={tp} — "
                    "megatron TP splits heads/KV-heads column-wise and "
                    "dim/mlp row-wise, all must divide evenly"
                )


def nucleus_filter(lg: jax.Array, top_p: jax.Array) -> jax.Array:
    """Top-p (nucleus) truncation with a TRACED p: keep the smallest
    descending-probability prefix whose mass reaches ``top_p`` (the
    crossing token inclusive; the top-1 token always survives, so the
    filter can never empty a row). ``top_p`` is scalar or per-row (n,).
    Costs one (n, V) sort — callers on hot paths gate it behind a
    static use-flag like the top-k sort."""
    sorted_desc = jnp.sort(lg, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    p = jnp.asarray(top_p)
    if p.ndim:
        p = p[:, None]
    keep = (cum - probs) < p  # mass BEFORE this token still under p
    # p == 1.0 must be an EXACT identity (no filtering): with peaked
    # logits the f32 cumsum saturates at 1.0 before the tail, so
    # (cum - probs) < 1.0 alone would drop tokens whose probability
    # rounds below the cumsum's ulp — and a mixed batch sharing one
    # compiled filter (continuous batching) would then diverge from the
    # filter-free solo path.
    keep = keep | (p >= 1.0)
    kth = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(lg >= kth, lg, -jnp.inf)


def sample_next_tokens(
    logits: jax.Array,
    key: jax.Array,
    temperature: jax.Array,
    *,
    do_sample: bool,
    top_k: int | None,
    top_p: jax.Array | float | None = None,
    row_offset: jax.Array | int = 0,
) -> jax.Array:
    """logits (n, V) -> (n,) token ids: greedy argmax, or sample from
    ``softmax(logits / temperature)`` optionally truncated to ``top_k``
    and/or the ``top_p`` nucleus (k first, then p — the usual serving
    composition).

    Sampling keys are PER ROW — the step key folded with the row's
    *global* batch index (``row_offset + i``) — so any contiguous slice
    of a batch draws exactly what the full batch draws for those rows.
    That slice-invariance is what lets pipelined decode
    (:mod:`adapt_tpu.parallel.pipeline_decode`), which samples one
    microbatch at a time on the last pipeline rank, match single-program
    :func:`generate` token-for-token even at temperature > 0."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    lg = logits / temperature
    if top_k is not None:
        # lax.top_k, not a full vocab sort: this runs once per decoded
        # token on the serving hot path.
        kth = lax.top_k(lg, top_k)[0][:, -1:]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)
    if top_p is not None:
        lg = nucleus_filter(lg, top_p)
    rows = row_offset + jnp.arange(lg.shape[0])
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, rows)
    return jax.vmap(jax.random.categorical)(keys, lg)


def _left_align(prompt: jax.Array, lengths: jax.Array):
    """Right-padded ragged rows -> (left-aligned buffer, per-row logical
    position ids, per-row left-pad counts). Row i shifts right by
    ``s0 - lengths[i]`` so every row's last real token sits at buffer
    column s0-1 and decode shares one scalar cache index across the
    batch; logical positions are 0 at each row's first real token
    (negatives mark padding)."""
    _, s0 = prompt.shape
    pad = (s0 - lengths)[:, None]  # (b, 1)
    cols = jnp.arange(s0)[None, :]
    src = jnp.clip(cols - pad, 0)
    aligned = jnp.take_along_axis(prompt, src, axis=1)
    pos_ids = cols - pad
    return aligned, pos_ids, pad[:, 0]


def validate_generate_args(
    lm: TransformerLM,
    prompt: jax.Array,
    steps: int,
    temperature: float,
    top_k: int | None,
    rng: jax.Array | None,
    prompt_lengths: jax.Array | None,
    kv_cache_dtype: str,
    top_p: float | None = None,
) -> tuple[jax.Array, jax.Array, bool]:
    """Shared request validation for :func:`generate` and the pipelined
    decoder: returns ``(lengths, rng, do_sample)`` with every constraint
    checked eagerly (clear ValueErrors instead of opaque trace errors)."""
    b, s0 = prompt.shape
    specs = [lm.graph.node(n).module.spec for n in lm.block_names]
    if any(sp.state_spec for sp in specs):
        raise ValueError(
            "generate() decodes over dense cache strips, which carry no "
            "recurrent state: a model with state-space or linear-attention "
            "mixers serves through ContinuousBatcher"
        )
    if any(sp.latent for sp in specs):
        raise ValueError(
            "generate() decodes over dense per-head cache strips: a "
            "latent-attention model keeps one row a position (a selecting "
            "one an index key beside it) and serves through "
            "ContinuousBatcher"
        )
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if s0 + steps > lm.max_len:
        raise ValueError(
            f"prompt {s0} + steps {steps} exceeds max_len {lm.max_len}"
        )
    do_sample = bool(temperature > 0.0)
    if do_sample and rng is None:
        raise ValueError("temperature > 0 requires an rng key")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_k is not None and top_k > lm.vocab:
        # lax.top_k with k > axis size fails at trace time with an opaque
        # XLA error; name the real constraint instead.
        raise ValueError(f"top_k {top_k} exceeds vocab size {lm.vocab}")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if kv_cache_dtype not in ("native", "int8", "int4"):
        raise ValueError(
            f"kv_cache_dtype={kv_cache_dtype!r}: expected 'native', "
            "'int8' or 'int4'"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)  # unused by the greedy path
    if prompt_lengths is None:
        lengths = jnp.full((b,), s0, jnp.int32)
    else:
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        if lengths.shape != (b,):
            raise ValueError(
                f"prompt_lengths shape {lengths.shape} != ({b},)"
            )
        # Out-of-range lengths would silently gather a corrupted prompt
        # (clip hides it). Validate eagerly when values are concrete;
        # traced callers (generate under an outer jit) must pre-validate.
        try:
            import numpy as _np

            lv = _np.asarray(lengths)
        except jax.errors.TracerArrayConversionError:
            pass
        else:
            if (lv < 1).any() or (lv > s0).any():
                raise ValueError(
                    f"prompt_lengths must be in [1, {s0}], got {lv}"
                )
    return lengths, rng, do_sample


def generate(
    lm: TransformerLM,
    variables,
    prompt: jax.Array,
    steps: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
    rng: jax.Array | None = None,
    prompt_lengths: jax.Array | None = None,
    kv_cache_dtype: str = "native",
    decode_attn: str | None = None,
    return_logprobs: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Generation as one compiled program: prefill over the prompt + a
    ``lax.scan`` of single-token cached decode steps.

    prompt: (b, s0) int32 token ids, s0 >= 1; returns (b, steps) ids.

    Ragged batches: pass right-padded prompts plus ``prompt_lengths``
    (b,) — rows are left-aligned internally (so every row's next token
    lands at one shared cache index), position embeddings are row
    logical (0 at each row's first real token), and the left padding is
    masked out of every attention window. Each row's output then starts
    at ITS OWN continuation, exactly as if it had been generated alone.

    ``kv_cache_dtype="int8"`` stores the KV cache quantized (absmax
    int8 per key/value vector): ~1.9x fewer cache bytes than bf16, so
    ~1.9x more context fits per chip, at a small logits perturbation
    (tested against the native-cache path). Use it for CAPACITY, not
    speed — the hardware A/B measured decode ~12% slower than the
    native cache at 2k context (see ``prefill``'s docstring and
    ``benchmarks/results/r04/lm_decode_long_*.json``).
    ``"int4"`` halves the value bytes again (two nibbles packed per
    int8 lane, same per-vector f32 scale plane) at a larger
    perturbation — the serving tier gates its top-1 agreement against
    int8 rather than claiming losslessness.

    Sampling: ``temperature=0`` (default) is greedy argmax and needs no
    ``rng``; ``temperature > 0`` samples from ``softmax(logits / T)``,
    optionally truncated to the ``top_k`` highest-probability tokens
    and/or the ``top_p`` nucleus (smallest probability mass >= p; k
    then p when both are set — the standard serving knobs). ``eos_id``
    makes a finished row emit
    ``eos_id`` forever after — scan length is static, so "stop" means
    "pad with EOS", the jit-friendly convention.

    Compilation: only the *shape* of the request is static (steps,
    top_k, and the sample/top_p/eos on-off booleans); temperature,
    top_p, and eos_id are traced operands, so a server sweeping them
    per request reuses one compiled program.

    ``decode_attn`` picks the per-step attention implementation (None =
    measured auto, ``"xla"``, ``"pallas"`` — see
    :mod:`adapt_tpu.ops.decode_attention`).

    ``return_logprobs=True`` returns ``(tokens, logprobs)`` where
    ``logprobs[b, t]`` is the MODEL's log-probability (log-softmax of
    the raw, pre-temperature logits) of the emitted token — the serving
    convention: sampling knobs shape which token gets picked, the
    reported score is always the model's own.
    """
    lengths, rng, do_sample = validate_generate_args(
        lm, prompt, steps, temperature, top_k, rng, prompt_lengths,
        kv_cache_dtype, top_p=top_p,
    )
    if decode_attn not in (None, "xla", "pallas"):
        raise ValueError(
            f"decode_attn={decode_attn!r}: expected None, 'xla' or 'pallas'"
        )
    return _generate_impl(
        lm,
        variables,
        prompt,
        lengths,
        jnp.asarray(temperature, jnp.float32),
        # top_p rides as a traced operand (servers sweep it per request
        # without recompiling); use_top_p is the static on/off.
        jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        jnp.asarray(-1 if eos_id is None else eos_id, prompt.dtype),
        rng,
        steps=steps,
        do_sample=do_sample,
        top_k=top_k,
        use_top_p=top_p is not None,
        use_eos=eos_id is not None,
        ragged=prompt_lengths is not None,
        # Static: False, "int8" or "int4" — prefill's quantize_cache
        # builds the matching (values, scales) representation and the
        # decode path follows the cache's own width from there.
        kv_quant=(
            kv_cache_dtype if kv_cache_dtype != "native" else False
        ),
        decode_attn=decode_attn,
        return_logprobs=return_logprobs,
    )


@partial(
    jax.jit,
    static_argnames=(
        "lm", "steps", "do_sample", "top_k", "use_top_p", "use_eos",
        "ragged", "kv_quant", "decode_attn", "return_logprobs",
    ),
)
def _generate_impl(
    lm: TransformerLM,
    variables,
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
    decode_attn: str | None = None,
    return_logprobs: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    g = lm.graph
    b, s0 = prompt.shape
    embed = g.node("embed").module
    head = g.node("head").module
    blocks = [g.node(n).module for n in lm.block_names]

    if ragged:
        prompt, pos_ids, valid_from = _left_align(prompt, lengths)
    else:
        pos_ids = None
        valid_from = None

    def pick(lg, key):
        """logits (b, V) -> token ids (b,); per-row keys (see
        sample_next_tokens)."""
        return sample_next_tokens(
            lg, key, temperature, do_sample=do_sample, top_k=top_k,
            top_p=top_p if use_top_p else None,
        )

    # ---- prefill ---------------------------------------------------------
    if ragged:
        h = embed.apply(
            variables["embed"], prompt, pos_ids, method="embed_positions"
        )
    else:
        h = embed.apply(variables["embed"], prompt)
    caches = []
    for name, block in zip(lm.block_names, blocks):
        h, ck, cv = block.apply(
            variables[name],
            h,
            lm.max_len,
            valid_from,
            kv_quant,
            method="prefill",
        )
        caches.append((ck, cv))
    logits = head.apply(variables["head"], h[:, -1:, :])  # (b, 1, V)
    rng, key0 = jax.random.split(rng)
    first = pick(logits[:, 0], key0).astype(prompt.dtype)  # (b,)
    done0 = (first == eos_id) if use_eos else jnp.zeros((b,), bool)

    first_lp = (
        chosen_logprob(logits[:, 0], first) if return_logprobs else None
    )

    # ---- decode ----------------------------------------------------------
    # Each iteration consumes the carried token and emits its successor,
    # so steps-1 iterations (plus the prefill's `first`) produce exactly
    # `steps` tokens with no dead final forward.
    def step(carry, key):
        tok, index, done, caches = carry
        if ragged:
            # Logical position differs per row (index - left padding).
            x_t = embed.apply(
                variables["embed"],
                tok[:, None],
                (index - valid_from)[:, None],
                method="embed_positions",
            )
        else:
            x_t = embed.apply(
                variables["embed"], tok[:, None], index, method="embed_at"
            )  # (b, 1, d)
        new_caches = []
        for name, block, (ck, cv) in zip(lm.block_names, blocks, caches):
            x_t, ck, cv = block.apply(
                variables[name],
                x_t,
                ck,
                cv,
                index,
                valid_from,
                kv_quant,
                decode_attn,
                method="decode_step",
            )
            new_caches.append((ck, cv))
        lg = head.apply(variables["head"], x_t)[:, 0]  # (b, V)
        nxt = pick(lg, key).astype(tok.dtype)
        if use_eos:
            nxt = jnp.where(done, eos_id.astype(tok.dtype), nxt)
            done = done | (nxt == eos_id)
        out = (
            (nxt, chosen_logprob(lg, nxt)) if return_logprobs else nxt
        )
        return (nxt, index + 1, done, tuple(new_caches)), out

    (_, _, _, _), rest = lax.scan(
        step,
        (first, jnp.asarray(s0, jnp.int32), done0, tuple(caches)),
        jax.random.split(rng, steps - 1) if steps > 1 else jnp.zeros(
            (0, 2), jnp.uint32
        ),
    )
    if return_logprobs:
        rest_tok, rest_lp = rest
        tokens = jnp.concatenate(
            [first[:, None], jnp.swapaxes(rest_tok, 0, 1)], axis=1
        )
        lps = jnp.concatenate(
            [first_lp[:, None], jnp.swapaxes(rest_lp, 0, 1)], axis=1
        )
        return tokens, lps  # (b, steps) each
    return jnp.concatenate(
        [first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1
    )  # (b, steps)


def logits_full(lm: TransformerLM, variables, ids: jax.Array) -> jax.Array:
    """Full-sequence causal logits — the oracle the cached decode must
    match position-for-position (and the pipeline-partition path)."""
    return lm.graph.apply(variables, ids)
