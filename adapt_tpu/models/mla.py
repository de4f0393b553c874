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

A SELECTING block (``LatentSpec.index``, DeepSeek sparse attention)
adds a lightning indexer and reads only what it picks:

    qI_j = (c_q W_iq)_j   j index heads of ``dim``, from the SAME c_q
    kI   = LayerNorm(u W_ik)   ONE key a position (scale and bias)
    both rotated on their FIRST ``rope_dim`` values, the MLA's frequencies
    w_j  = (u W_iw)_j * heads^-1/2 * dim^-1/2
    I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s));  query t attends the
    ``top_k`` positions s <= t of largest I(t, s) and no other

Its cache is the latent rows AND the index keys, a plane each under one
page table (``ops/sparse_latent_attention``): the pool operand of the
paged schedules is then the pair ``(rows, index keys)``.

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
    pages_to_rows,
    rows_to_pages,
)
from adapt_tpu.ops.sparse_latent_attention import (
    selected_latent_attention,
    sparse_latent_paged_attention,
)


@dataclasses.dataclass(frozen=True)
class IndexSpec:
    """The lightning indexer's widths (``index_n_heads``,
    ``index_head_dim``, the rotated part, ``index_topk``)."""

    heads: int
    dim: int  # ONE key this wide a position, cached
    rope_dim: int
    top_k: int

    def __post_init__(self):
        if self.rope_dim % 2 or self.rope_dim > self.dim:
            raise ValueError(
                f"rope_dim {self.rope_dim} is odd or over dim {self.dim}"
            )
        if self.top_k < 1:
            raise ValueError(f"top_k {self.top_k} < 1")


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The latent attention's widths as a configuration states them."""

    q_rank: int  # q_lora_rank
    kv_rank: int  # kv_lora_rank: the cached latent
    nope_dim: int  # qk_nope_head_dim
    rope_dim: int  # qk_rope_head_dim: the shared rotated key part
    v_dim: int  # v_head_dim
    yarn: YarnSpec | None = None
    #: A lightning indexer: the block attends the ``index.top_k``
    #: positions it scores highest and keeps an index key a position
    #: beside the row. None: every position is attended.
    index: IndexSpec | None = None

    def __post_init__(self):
        if self.rope_dim % 2:
            raise ValueError(f"rope needs an even rope_dim, got {self.rope_dim}")
        if self.index is not None and self.index.rope_dim != self.rope_dim:
            raise ValueError(
                f"the indexer rotates with the attention's frequencies: "
                f"index.rope_dim {self.index.rope_dim} != rope_dim "
                f"{self.rope_dim}"
            )

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
        if lat.index is not None:
            ix = lat.index
            self.index_q = nn.DenseGeneral(
                (ix.heads, ix.dim), dtype=self.dtype, use_bias=False,
                name="index_q",
            )
            self.index_k = dense(ix.dim, "index_k")
            self.index_k_norm = nn.LayerNorm(
                epsilon=spec.norm_eps, dtype=self.dtype
            )
            self.index_w = dense(ix.heads, "index_w")

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

    def _project(self, x, positions, index=False):
        """-> q_nope (b, s, h, nope), q_rope (b, s, h, rope) rotated,
        and the cache rows (b, s, row) = [RMSNorm(c_kv) | k_r
        rotated]; with ``index`` a fourth, the indexer's operands
        (:meth:`_index`)."""
        lat = self.spec.latent
        c_q = self.q_norm(self.q_a(x))
        q = self.q_b(c_q)  # (b, s, h, qk)
        q_nope, q_rope = q[..., : lat.nope_dim], q[..., lat.nope_dim:]
        q_rope = jnp.swapaxes(
            self._rotate(jnp.swapaxes(q_rope, 1, 2), positions), 1, 2
        )
        kv = self.kv_a(x)  # (b, s, row)
        c_kv = self.kv_norm(kv[..., : lat.kv_rank])
        k_r = self._rotate(kv[:, None, :, lat.kv_rank:], positions)[:, 0]
        rows = jnp.concatenate([c_kv, k_r], axis=-1)
        if index:
            return q_nope, q_rope, rows, self._index(x, c_q, positions)
        return q_nope, q_rope, rows

    def _index(self, x, c_q, positions):
        """The indexer's operands of block input ``x`` and its normed
        query latent: q_i (b, s, j, d), w (b, s, j) float32 with the
        two scales on it, k_i (b, s, d) the cached key."""
        ix = self.spec.latent.index

        def rotated(t):  # (b, s, j, d): the first rope_dim values
            r = self._rotate(
                jnp.swapaxes(t[..., : ix.rope_dim], 1, 2), positions
            )
            return jnp.concatenate(
                [jnp.swapaxes(r, 1, 2), t[..., ix.rope_dim:]], axis=-1
            )

        q_i = rotated(self.index_q(c_q))
        k_i = rotated(self.index_k_norm(self.index_k(x))[:, :, None])[:, :, 0]
        w = self.index_w(x).astype(jnp.float32) * (
            ix.heads ** -0.5 * ix.dim ** -0.5
        )
        return q_i, w, k_i

    def _selected(self, x, q_nope, q_rope, q_pos, index, rows, keys_i):
        """Attention of the queries at ``q_pos`` (s,) over the window
        ``rows`` / ``keys_i`` (b, L, .) by position, each query reading
        what its index scores select (the masked form, absorbed)."""
        lat = self.spec.latent
        q_i, w, _ = index
        o = jax.vmap(
            lambda q, qi, wi, r, k: selected_latent_attention(
                jnp.swapaxes(q, 0, 1), qi, wi, r, k, q_pos,
                lat.softmax_scale, lat.kv_rank, lat.index.top_k,
            )
        )(self._absorb_q(q_nope, q_rope), q_i, w, rows, keys_i)
        return self._unabsorb_o(
            jnp.swapaxes(o, 1, 2).astype(x.dtype), x
        )

    def _attend_prompt(self, x):
        """A SELECTING block's full causal forward of ``x`` from
        position 0 -> (out, rows, index keys). A prompt no selection
        can shorten (at most ``top_k`` positions) attends as the block
        without an indexer does."""
        positions = jnp.arange(x.shape[1])
        q_nope, q_rope, rows, index = self._project(x, positions, True)
        if x.shape[1] <= self.spec.latent.index.top_k:
            out = self._expanded(x, q_nope, q_rope, rows)
        else:
            out = self._selected(
                x, q_nope, q_rope, positions, index, rows, index[2]
            )
        return out, rows, index[2]

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
        if self.spec.latent.index is not None:
            return self._attend_prompt(x)[0]
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
        if self.spec.latent.index is not None:
            # The pool's pair: rows and index keys, padded alike.
            out, *planes = self._attend_prompt(x)
            pad = ((0, 0), (0, max_len - x.shape[1]), (0, 0))
            return out, tuple(jnp.pad(p, pad) for p in planes), None
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
        if lat.index is not None:
            return self._decode_selected(
                x_t, pool, page_table, index, attn_impl
            )
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

    def _decode_selected(self, x_t, pool, page_table, index, attn_impl):
        """``decode_step_paged`` of a selecting block: the token's row
        and index key go into their planes, the slot's live index keys
        are scored, and the latent attention reads the selected
        positions only (``sparse_latent_paged_attention``)."""
        lat = self.spec.latent
        rows_pool, keys_pool = pool
        b, page = x_t.shape[0], rows_pool.shape[2]
        idx = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32).reshape(-1), (b,)
        )
        q_nope, q_rope, rows, (q_i, w, k_i) = self._project(
            x_t, idx[:, None], True
        )
        safe = jnp.maximum(idx, 0)
        phys = jnp.take_along_axis(
            page_table, (safe // page)[:, None], axis=1
        )[:, 0]
        phys = jnp.where(idx >= 0, phys, 0)  # dead -> trash
        rows_pool = append_latent_paged(
            rows_pool, rows[:, 0], phys, safe % page, prefer=attn_impl
        )
        keys_pool = append_latent_paged(
            keys_pool, k_i[:, 0], phys, safe % page, prefer=attn_impl
        )
        o = sparse_latent_paged_attention(
            self._absorb_q(q_nope, q_rope)[:, 0], q_i[:, 0], w[:, 0],
            rows_pool, keys_pool, page_table, idx,
            sm_scale=lat.softmax_scale, v_width=lat.kv_rank,
            top_k=lat.index.top_k, prefer=attn_impl,
        ).astype(x_t.dtype)
        return self._unabsorb_o(o[:, None], x_t), (rows_pool, keys_pool)

    def _chunk_selected(self, x, pool, pages, pos0):
        """``prefill_chunk_paged`` of a selecting block: the chunk's
        rows and index keys go into their own pages of both planes,
        then the chunk's queries take the masked form over the window."""
        rows_pool, keys_pool = pool
        c, page = x.shape[1], rows_pool.shape[2]
        q_pos = pos0 + jnp.arange(c)
        q_nope, q_rope, rows, index = self._project(x, q_pos, True)
        pages = jnp.asarray(pages, jnp.int32)
        chunk_pages = lax.dynamic_slice(pages, (pos0 // page,), (c // page,))
        rows_pool = rows_pool.at[chunk_pages].set(
            rows_to_pages(rows[0], page).astype(rows_pool.dtype)
        )
        keys_pool = keys_pool.at[chunk_pages].set(
            rows_to_pages(index[2][0], page).astype(keys_pool.dtype)
        )

        def window(plane):
            return pages_to_rows(plane[pages]).reshape(
                1, -1, plane.shape[1]
            )

        out = self._selected(
            x, q_nope, q_rope, q_pos, index, window(rows_pool),
            window(keys_pool),
        )
        return out, (rows_pool, keys_pool)

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
        if lat.index is not None:
            return self._chunk_selected(x, pool, pages, pos0)
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
