"""Multi-head latent attention (MLA, DeepSeek-V2/V3) behind the
schedules every attention kind of ``models/transformer_lm`` has: the
full forward, ``prefill``, ``prefill_chunk_paged`` and
``decode_step_paged``.

Per token ``u`` (the block's normed input), every projection without
bias:

    c_q = RMSNorm(u W_qa)                       (q_rank)
    q_h = c_q W_qb -> [q_nope (nope) | q_rope (rope)]    a head
    [c_kv | k_r] = u W_kva;  c_kv <- RMSNorm(c_kv)       (kv_rank | rope)
    q_rope, k_r rotated (rotate-half; YaRN's frequencies where the
    spec names them); ONE k_r for all heads
    k_h = [c_kv W_UK,h | k_r],  v_h = c_kv W_UV,h
    a   = concat_h(softmax(q_h k_h^T * scale) v_h) W_o
    (``spec.attn_gate``: the heads' values times sigmoid(u W_g), element
    by element, before W_o; in the absorbed form after ``W_UV``, so the
    kernels are the ungated layer's)

THE CACHE holds ``[c_kv | k_r]``: ``kv_rank + rope`` values a position,
once, not per head (``ops/latent_attention``). The full forward and the
whole-prompt prefill take the EXPANDED form above; decode and chunked
prefill read the cache in the ABSORBED form: ``q~_h = [q_nope,h
W_UK,h^T | q_rope,h]`` meets the row whole, the probabilities weight
``c_kv`` and ``W_UV,h`` goes on after. Same weights, two schedules.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from adapt_tpu.models.rope import (
    YarnSpec,
    apply_rope,
    yarn_frequencies,
    yarn_mscale,
)
from adapt_tpu.ops.attention import flash_attention
from adapt_tpu.ops.latent_attention import (
    append_latent_paged,
    latent_chunk_attention,
    latent_paged_attention,
    rows_to_pages,
)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The latent attention's widths as a configuration states them."""

    q_rank: int  # q_lora_rank
    kv_rank: int  # kv_lora_rank: the cached latent
    nope_dim: int  # qk_nope_head_dim
    rope_dim: int  # qk_rope_head_dim: the shared rotated key part
    v_dim: int  # v_head_dim
    yarn: YarnSpec | None = None

    def __post_init__(self):
        if self.rope_dim % 2:
            raise ValueError(f"rope needs an even rope_dim, got {self.rope_dim}")

    @property
    def row(self) -> int:
        """What one position stores: ``[c_kv | k_r]``."""
        return self.kv_rank + self.rope_dim

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_dim ** -0.5``, times YaRN's ``mscale_all_dim`` factor
        squared where the spec has one (DeepSeek-V3's)."""
        scale = self.qk_dim ** -0.5
        if self.yarn is not None and self.yarn.mscale_all_dim:
            scale *= yarn_mscale(
                self.yarn.factor, self.yarn.mscale_all_dim
            ) ** 2
        return scale


def latent_only(what: str) -> None:
    raise NotImplementedError(
        f"{what} moves per-head K and V: a latent-attention block keeps "
        "one [c_kv | k_r] row a position and serves through the full "
        "forward, prefill, prefill_chunk_paged and decode_step_paged"
    )


class LatentSelfAttention(nn.Module):
    """Causal MLA over a block's ``BlockSpec`` (``spec.latent`` says
    the widths, ``spec.rope_base`` the rotation's base)."""

    spec: "BlockSpec"  # noqa: F821 — models/transformer_lm.BlockSpec
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        spec, lat = self.spec, self.spec.latent

        def dense(n, name):
            return nn.Dense(n, dtype=self.dtype, use_bias=False, name=name)

        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=(1, 2)
        )
        self.q_a = dense(lat.q_rank, "q_a")
        self.q_norm = nn.RMSNorm(epsilon=spec.norm_eps, dtype=self.dtype)
        self.q_b = nn.DenseGeneral(
            (spec.heads, lat.qk_dim), dtype=self.dtype, use_bias=False,
            name="q_b",
        )
        self.kv_a = dense(lat.row, "kv_a")
        self.kv_norm = nn.RMSNorm(epsilon=spec.norm_eps, dtype=self.dtype)
        #: ``[W_UK | W_UV]`` a head, read as a matrix by the expanded
        #: form and in its two halves by the absorbed one.
        self.kv_b = self.param(
            "kv_b", fan_in, (lat.kv_rank, spec.heads, lat.nope_dim + lat.v_dim)
        )
        if spec.attn_gate:
            self.gate = dense(spec.heads * lat.v_dim, "gate")
        self.out = dense(spec.dim, "out")

    def _rotate(self, x, positions):
        """(b, heads, s, rope) rotated at ``positions``."""
        lat, base = self.spec.latent, self.spec.rope_base
        if lat.yarn is None:
            return apply_rope(x, positions, base)
        return apply_rope(
            x, positions, base,
            freqs=yarn_frequencies(lat.rope_dim, base, lat.yarn),
            mscale=yarn_mscale(lat.yarn.factor, lat.yarn.mscale)
            / yarn_mscale(lat.yarn.factor, lat.yarn.mscale_all_dim),
        )

    def _project(self, x, positions):
        """-> q_nope (b, s, h, nope), q_rope (b, s, h, rope) rotated,
        and the cache rows (b, s, row) = [RMSNorm(c_kv) | k_r
        rotated]."""
        lat = self.spec.latent
        q = self.q_b(self.q_norm(self.q_a(x)))  # (b, s, h, qk)
        q_nope, q_rope = q[..., : lat.nope_dim], q[..., lat.nope_dim:]
        q_rope = jnp.swapaxes(
            self._rotate(jnp.swapaxes(q_rope, 1, 2), positions), 1, 2
        )
        kv = self.kv_a(x)  # (b, s, row)
        c_kv = self.kv_norm(kv[..., : lat.kv_rank])
        k_r = self._rotate(kv[:, None, :, lat.kv_rank:], positions)[:, 0]
        return q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)

    def _w_uk_uv(self):
        w = self.kv_b.astype(self.dtype)
        nope = self.spec.latent.nope_dim
        return w[..., :nope], w[..., nope:]

    def _finish(self, o, x):
        """The tail both forms share: the heads' values (b, s, h *
        v_dim) of the block input ``x``, gated where the spec says (the
        sigmoid in float32, as ``CausalSelfAttention._finish``),
        projected out."""
        if self.spec.attn_gate:
            gate = jax.nn.sigmoid(self.gate(x).astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(o.dtype)
        return self.out(o)

    def _expanded(self, x, q_nope, q_rope, rows):
        """Full causal attention with K and V expanded a head."""
        lat = self.spec.latent
        b, s = rows.shape[:2]
        w_uk, w_uv = self._w_uk_uv()
        c_kv, k_r = rows[..., : lat.kv_rank], rows[..., lat.kv_rank:]
        k = jnp.concatenate([
            jnp.einsum("bsr,rhn->bhsn", c_kv, w_uk),
            jnp.broadcast_to(
                k_r[:, None], (b, self.spec.heads, s, lat.rope_dim)
            ),
        ], axis=-1)
        v = jnp.einsum("bsr,rhv->bhsv", c_kv, w_uv)
        q = jnp.swapaxes(jnp.concatenate([q_nope, q_rope], axis=-1), 1, 2)
        # flash_attention scales by its operands' width ** -0.5; the
        # rest of the scale goes on q. V rides zero-padded to that
        # width and its lanes are cut back off.
        q = q * jnp.asarray(
            lat.softmax_scale * lat.qk_dim ** 0.5, q.dtype
        )
        v = jnp.pad(v, [(0, 0)] * 3 + [(0, lat.qk_dim - lat.v_dim)])
        o = flash_attention(q, k, v, causal=True)[..., : lat.v_dim]
        return self._finish(jnp.swapaxes(o, 1, 2).reshape(b, s, -1), x)

    def _absorb_q(self, q_nope, q_rope):
        """-> q~ (b, s, h, row): ``W_UK`` folded into the query."""
        w_uk, _ = self._w_uk_uv()
        return jnp.concatenate(
            [jnp.einsum("bshn,rhn->bshr", q_nope, w_uk), q_rope], axis=-1
        )

    def _unabsorb_o(self, o, x):
        """(b, s, h, kv_rank) weighted latents -> the block's output."""
        _, w_uv = self._w_uk_uv()
        o = jnp.einsum("bshr,rhv->bshv", o, w_uv)
        return self._finish(o.reshape(*o.shape[:2], -1), x)

    def __call__(self, x):
        return self._expanded(x, *self._project(x, jnp.arange(x.shape[1])))

    def prefill(self, x, max_len: int, valid_from=None, quantize_cache=False):
        """Full causal attention over the prompt; returns ``(out, rows,
        None)``: the cache rows (b, max_len, row) padded with zeros past
        the prompt, and no second operand (a latent row is whole: the
        batcher's whole-prompt prefill puts it into the pool as it is)."""
        if valid_from is not None:
            latent_only("a left-padded (ragged) prefill")
        if quantize_cache:
            latent_only("a quantized KV cache")
        q_nope, q_rope, rows = self._project(x, jnp.arange(x.shape[1]))
        out = self._expanded(x, q_nope, q_rope, rows)
        return out, jnp.pad(
            rows, ((0, 0), (0, max_len - x.shape[1]), (0, 0))
        ), None

    def decode_step_paged(
        self, x_t, pool, page_table, index, valid_from=None,
        attn_impl=None, head_shard=None,
    ):
        """One token against the latent paged cache: write its row at
        ``index``'s (page, offset), then attend the table-mapped window
        in the absorbed form. ``index`` scalar or (b,); a negative one
        is a dead row, whose write goes to the trash page. Returns
        ``(out, pool)``."""
        if valid_from is not None:
            latent_only("a ragged (left-padded) batch")
        if head_shard is not None:
            latent_only("a tp-partitioned decode step")
        lat = self.spec.latent
        b, page = x_t.shape[0], pool.shape[2]
        idx = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32).reshape(-1), (b,)
        )
        q_nope, q_rope, rows = self._project(x_t, idx[:, None])
        safe = jnp.maximum(idx, 0)
        phys = jnp.take_along_axis(
            page_table, (safe // page)[:, None], axis=1
        )[:, 0]
        phys = jnp.where(idx >= 0, phys, 0)  # dead -> trash
        pool = append_latent_paged(
            pool, rows[:, 0], phys, safe % page, prefer=attn_impl
        )
        o = latent_paged_attention(
            self._absorb_q(q_nope, q_rope)[:, 0], pool, page_table, idx,
            sm_scale=lat.softmax_scale, v_width=lat.kv_rank,
            prefer=attn_impl,
        ).astype(x_t.dtype)
        return self._unabsorb_o(o[:, None], x_t), pool

    def prefill_chunk_paged(
        self, x, pool, pages, pos0, attn_impl=None, head_shard=None,
    ):
        """Incremental prefill of positions ``[pos0, pos0 + C)``
        against the latent paged window: the chunk's rows go into its
        own pages (one scatter), then the window is attended in the
        absorbed form (``latent_chunk_attention``). ``pages`` (n,)
        covers ``[0, pos0 + C)``; ``pos0`` is page-aligned and C a
        whole number of pages. Batch 1."""
        if head_shard is not None:
            latent_only("a tp-partitioned prefill pass")
        lat = self.spec.latent
        c, page = x.shape[1], pool.shape[2]
        q_nope, q_rope, rows = self._project(x, pos0 + jnp.arange(c))
        chunk_pages = lax.dynamic_slice(
            jnp.asarray(pages, jnp.int32), (pos0 // page,), (c // page,)
        )
        pool = pool.at[chunk_pages].set(
            rows_to_pages(rows[0], page).astype(pool.dtype)
        )
        o = latent_chunk_attention(
            jnp.swapaxes(self._absorb_q(q_nope, q_rope)[0], 0, 1),
            pool, pages, pos0, lat.softmax_scale, lat.kv_rank,
        ).astype(x.dtype)  # (h, C, kv_rank)
        return self._unabsorb_o(jnp.swapaxes(o, 0, 1)[None], x), pool

    # -- what moves per-head K and V ---------------------------------------

    def decode_step(self, *_, **__):
        latent_only("decode_step over dense cache strips")

    def prefill_sp(self, *_, **__):
        latent_only("sequence-parallel prefill")

    def verify_chunk(self, *_, **__):
        latent_only("verify_chunk (speculative decoding)")

    def verify_chunk_paged(self, *_, **__):
        latent_only("verify_chunk_paged (speculative decoding)")
