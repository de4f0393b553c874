"""Disaggregated prefill/decode serving: a prefill tier that streams
KV pages to the decode batcher, so decode ticks never run a long
prompt's prefill inline.

The pathology (measured by ``continuous.prefill_stall_s`` and the
``benchmarks/load`` long-tail preset): the collocated
``ContinuousBatcher`` runs every admission's prefill INSIDE the tick
loop, so under heavy-tailed prompt lengths a p99 prompt's prefill wall
lands directly on every decoding request's inter-token latency — the
decode batch convoys behind the fattest prefill. Production fleets
split the two phases onto separate pools (compute-bound prefill,
latency/bandwidth-bound decode — the same specialization the source
paper applies to its pipeline workers, PAPER.md §0); this module is
that split, TPU-native and single-process-testable:

- :class:`PrefillWorker` — the prefill tier: admission + CHUNKED
  prefill against its own paged pool (one page-aligned chunk pass per
  ``step()``, the Sarathi-style bound on any single stall), then a
  page-gather and handoff of the prompt's FULL pages' K/V. The worker
  is deliberately layout-blind about the decode side: it ships the
  full head range, host-staged, and never needs to know the decode
  mesh.
- **The wire** — a handoff is one ``comm.framing.Message``
  (``MSG_KV_PAGES``): the K/V page chunks ride as concatenated
  zero-copy codec frames (``codec.pack_frames`` — scatter-write parts
  on send, views of the receive buffer on receive; the PR-1 contract,
  pinned via ``codec.copy_stats()``), described by the new
  ``FLAG_PAGE_ANNEX`` page-range annex (request id, page geometry,
  per-tensor frame lengths). ``loopback()`` is the in-process
  transport: it performs the kernel's gather into one buffer and
  re-parses it through the SAME ``frame_parts``/``parse_frame`` pair
  the socket paths use, so corruption/truncation behave exactly as
  they would off a real socket.
- **Decode-side landing** — ``ContinuousBatcher.adopt_prefill_pages``:
  pages register in the paged PREFIX CACHE under the same content keys
  admission probes (rc=0, resident, evictable), their bytes scatter in
  shard-locally via a ``parallel.sharding.KVHandoffPlan`` (head-
  sharded decode pools receive per-shard slices — aligned union,
  never a gather), and the request then enters through the ordinary
  ``submit()``: admission sees a prefix-cache hit and prefills only
  the suffix. Because the landing path IS the existing prefix-cache
  insertion path, int8 pools (values + scales move under one plan),
  tensor parallelism and speculative mode compose for free, and
  greedy streams stay bit-identical to the collocated path.
- :class:`DisaggServer` — the disaggregated submit path: a placement
  policy (``config.DisaggConfig``: prompt-length threshold, tightened
  when decode occupancy is high) chooses collocated vs disaggregated
  PER REQUEST, with automatic collocated fallback whenever the
  prefill tier cannot help (pool pressure, dead lease, no full page,
  corrupt handoff) — placement is an optimization, never a
  correctness gate. With a ``control.registry.WorkerRegistry`` the
  prefill pool holds a ROLE-TAGGED lease (``role="prefill"``): the
  pipeline dispatcher's acquisition skips it, and the policy stops
  routing to a tier whose lease expired.

Observability: ``disagg.{handoff_bytes,handoff_s,pages_streamed}``
(+ ``disagg.handoff_bytes_raw`` — wire bytes are POST-codec when a
``wire_codec`` is set, so raw/wire is the compression ratio)
plus the ``kv_handoff`` flight event per landing;
``continuous.prefill_stall_s`` on the decode batcher shows what the
handoff removed. ``docs/SERVING.md`` "Disaggregated prefill/decode"
covers sizing and when collocated wins.

Single-process scope (v1): the server drives both tiers from one
thread — the prefill CHUNK is the stall bound, which is what the
load harness measures. The wire format is the cross-host format; a
remote prefill tier sends the same ``MSG_KV_PAGES`` frames through
``comm.framing.send_msg`` unchanged.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import time
import weakref
import zlib
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from adapt_tpu.comm import codec
from adapt_tpu.comm.framing import (
    MSG_KV_PAGES,
    Message,
    frame_parts,
    parse_frame,
)
from adapt_tpu.config import DisaggConfig, PrefillConfig, SLOSpec
from adapt_tpu.models.transformer_lm import TransformerLM
from adapt_tpu.parallel.sp_prefill import SPPrefiller, build_sp_mesh
from adapt_tpu.runtime.capacity import prefill_tier_book
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.ops.paged_attention import pool_planes
from adapt_tpu.runtime.paged import Pager, alloc_kv_pools, pool_geometry
from adapt_tpu.runtime.scheduler import QueueFullError
from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.profiling import (
    aggregate_size_fn,
    global_compile_sentinel,
)
from adapt_tpu.utils.tracing import global_flight_recorder, global_tracer

log = get_logger("disagg")

_LEN_PREFIX = 8  # comm.framing._LEN.size — the frame length prefix


class HandoffError(RuntimeError):
    """A KV handoff frame could not be decoded or landed (corrupt or
    truncated wire bytes, geometry mismatch). The server fails the
    REQUEST cleanly — empty result, ``request_failed`` flight event —
    and keeps serving."""


#: Live prefill workers (weak) — the ONE "disagg.prefill" compile-
#: sentinel watch sums their per-instance chunk-program caches, the
#: same aggregation discipline as the batcher's prefill family.
_LIVE_WORKERS: "weakref.WeakSet[PrefillWorker]" = weakref.WeakSet()


def _worker_family_size(w: "PrefillWorker") -> int:
    return sum(f._cache_size() for f in list(w._fn_cache.values()))


@dataclasses.dataclass
class KVHandoff:
    """One prefilled request's streamable state: the prompt, the page
    geometry, and per-block page-major chunks of the pool's own planes
    (``runtime/paged.alloc_kv_pools``) covering the prompt's first
    ``n_pages`` FULL pages: ``(n_pages, kv_heads, page_size, 2 *
    head_dim)`` fused K|V rows; quantized pools carry ``(values,
    k_scales, v_scales)`` tuples — the scale planes are part of the
    page."""

    req_id: int
    prompt: np.ndarray
    page_size: int
    n_pages: int
    quantized: bool
    #: One entry per decoder block: the fused plane's pages, or the
    #: quantized ``(values, k_scales, v_scales)`` tuple of them.
    blocks: list
    #: KV dtype on the wire: "native", "int8" or "int4" (int4 members
    #: carry PACKED ``head_dim // 2`` value lanes — the width is part
    #: of the wire geometry the decode side validates). Defaults to
    #: the legacy mapping of ``quantized``.
    kv_dtype: str = ""

    def __post_init__(self):
        if not self.kv_dtype:
            self.kv_dtype = "int8" if self.quantized else "native"


def _leaves(handoff: KVHandoff) -> list[np.ndarray]:
    """The handoff's tensors in WIRE ORDER: prompt first, then each
    block's planes (quantized blocks flatten to values, k_scales,
    v_scales)."""
    out: list[np.ndarray] = [np.ascontiguousarray(handoff.prompt, np.int32)]
    for block in handoff.blocks:
        out.extend(pool_planes(block))
    return out


def handoff_raw_nbytes(handoff: KVHandoff) -> int:
    """Uncompressed payload bytes of a handoff (every wire tensor's
    host nbytes) — the numerator dashboards divide
    ``disagg.handoff_bytes`` by to read the wire compression ratio."""
    return sum(int(arr.nbytes) for arr in _leaves(handoff))


def _wire_tensors(
    handoff: KVHandoff, head_ranges: list[tuple[int, int]] | None
) -> list[np.ndarray]:
    """The tensors actually framed, in wire order. Without
    ``head_ranges`` this is :func:`_leaves` verbatim. With them, every
    KV leaf (never the prompt) ships as one contiguous slice per
    ``(lo, hi)`` destination head tile — sender-side resharding: the
    wire already carries the aligned-union slices the destination's
    :class:`~adapt_tpu.parallel.sharding.KVHandoffPlan` would cut, so
    a tp=2 prefill tier feeds a tp=4 decode replica without either
    side materializing a cross-mesh gather. The ranges must tile the
    head axis exactly (``parallel.sharding.head_tiles`` builds legal
    ones) or this raises — a slicing the receiver cannot reassemble
    must fail at pack time, by name."""
    leaves = _leaves(handoff)
    if not head_ranges:
        return leaves
    out = [leaves[0]]
    for arr in leaves[1:]:
        h = int(arr.shape[1])
        cover = 0
        for lo, hi in head_ranges:
            if int(lo) != cover or hi <= lo:
                raise HandoffError(
                    f"head_ranges {head_ranges} do not tile the "
                    f"{h}-head axis contiguously"
                )
            cover = int(hi)
        if cover != h:
            raise HandoffError(
                f"head_ranges cover {cover} of {h} kv heads"
            )
        for lo, hi in head_ranges:
            # One contiguous copy per tile — the same bytes a
            # destination shard's device_put would stage anyway.
            out.append(np.ascontiguousarray(arr[:, lo:hi]))
    return out


def pack_handoff(
    handoff: KVHandoff,
    wire_codec: str = "raw",
    head_ranges: list[tuple[int, int]] | None = None,
) -> Message:
    """Frame a handoff for the comm tier: every tensor becomes one
    zero-copy codec frame (``codec.pack_frames`` with the raw codec —
    scatter-write parts, no payload copy; ``codec.copy_stats()`` pins
    it), concatenated in wire order as the message payload; the
    page-range annex carries the geometry and per-tensor frame
    lengths needed to slice them back out.

    ``wire_codec`` != "raw" compresses each tensor through the
    ``ops.quantize`` page codec stack before framing (lossless "lz",
    or lossy "int8"/"int4"/"zfp" on FLOAT tensors only — the prompt
    and int value planes always pack lossless). The annex then
    carries per-tensor codec meta, and the crc is computed over the
    COMPRESSED payload — corruption is detected before any decode
    touches the bytes, exactly like the raw path.

    ``head_ranges`` (destination head tiles from
    ``parallel.sharding.head_tiles``) reshards SENDER-SIDE: each KV
    tensor frames as one slice per tile, in tile order, and the annex
    records the tiling so :func:`unpack_handoff` can reassemble the
    full head range — the cross-replica tp-mismatch wire (a tp=2
    prefill pool feeding a tp=4 decode replica ships four 2-head
    slices per leaf, never a gathered whole)."""
    parts: list = []
    frame_lens: list[int] = []
    crc = 0
    leaf_meta: list[dict] | None = None
    wire = _wire_tensors(handoff, head_ranges)
    if wire_codec != "raw":
        from adapt_tpu.ops.quantize import encode_page

        leaf_meta = []
        for arr in wire:
            payload, meta = encode_page(np.asarray(arr), wire_codec)
            frame_lens.append(len(payload))
            leaf_meta.append(meta)
            crc = zlib.crc32(payload, crc)
            parts.append(memoryview(payload))
    else:
        raw = codec.get_codec("none")
        for arr in wire:
            frames = codec.pack_frames(raw, arr)
            frame_lens.append(codec.frames_nbytes(frames))
            for p in frames:
                # Payload integrity: flipped bits in a KV page would
                # otherwise scatter SILENTLY into a live pool (raw codec
                # frames parse fine whatever the bytes hold). One crc
                # pass over views — no copy, ~free next to the transfer
                # itself.
                crc = zlib.crc32(p, crc)
            parts.extend(frames)
    meta = {
        "req_id": int(handoff.req_id),
        "page_size": int(handoff.page_size),
        "n_pages": int(handoff.n_pages),
        "quantized": bool(handoff.quantized),
        "kv_dtype": handoff.kv_dtype,
        "blocks": len(handoff.blocks),
        "prompt_len": int(handoff.prompt.shape[0]),
        "frame_lens": frame_lens,
        "crc32": crc,
    }
    if head_ranges:
        meta["head_ranges"] = [
            [int(lo), int(hi)] for lo, hi in head_ranges
        ]
    if leaf_meta is not None:
        meta["wire_codec"] = wire_codec
        meta["leaf_meta"] = leaf_meta
    annex = json.dumps(meta).encode()
    return Message(
        msg_type=MSG_KV_PAGES,
        stage_index=0,
        request_id=int(handoff.req_id),
        attempt=0,
        payload=parts,
        page_annex=annex,
    )


def unpack_handoff(msg: Message) -> KVHandoff:
    """Decode a ``MSG_KV_PAGES`` message back into a :class:`KVHandoff`.
    The returned arrays VIEW the message's receive buffer (the
    zero-copy receive contract — ``codec.unpack_many`` slices, never
    joins). Any malformed annex, frame or geometry raises
    :class:`HandoffError` — a corrupt handoff must fail the request by
    name, never scatter garbage into a live pool."""
    try:
        if msg.msg_type != MSG_KV_PAGES:
            raise ValueError(f"not a KV-pages message: {msg.msg_type}")
        if msg.page_annex is None:
            raise ValueError("missing page annex")
        meta = json.loads(msg.page_annex.decode())
        n_blocks = int(meta["blocks"])
        quantized = bool(meta["quantized"])
        # The crc always runs on the WIRE payload — post-codec bytes
        # when wire compression is on — so corruption is caught before
        # any codec decode touches the buffer.
        got_crc = zlib.crc32(msg.payload)
        if got_crc != int(meta["crc32"]):
            raise ValueError(
                f"payload crc mismatch ({got_crc:#x} != "
                f"{int(meta['crc32']):#x}) — corrupt KV pages"
            )
        wire_codec = meta.get("wire_codec")
        if wire_codec:
            # Compressed annex: slice the payload by the per-tensor
            # frame lengths and decode each through the page codec
            # stack. Decoded tensors are fresh host arrays (the
            # zero-copy receive contract applies to the raw path
            # only — a compressed wire trades the view for the
            # bandwidth).
            from adapt_tpu.ops.quantize import decode_page

            mv = memoryview(msg.payload)
            lens = [int(x) for x in meta["frame_lens"]]
            if sum(lens) != len(mv):
                raise ValueError(
                    f"frame lengths sum to {sum(lens)}, payload is "
                    f"{len(mv)} bytes"
                )
            arrs, off = [], 0
            for ln, lmeta in zip(lens, meta["leaf_meta"]):
                arrs.append(decode_page(mv[off:off + ln], lmeta))
                off += ln
        else:
            arrs = codec.unpack_many(msg.payload, meta["frame_lens"])
        per_block = 3 if quantized else 1
        ranges = meta.get("head_ranges")
        if ranges:
            # Sender-side-resharded wire: each KV tensor arrived as
            # one slice per destination head tile. Reassemble the full
            # head range on the HOST (np.concatenate along the head
            # axis — the fetch_head_shards discipline: host concat,
            # never a device-side gather); adoption re-slices per the
            # local pool's own plan, so a tp-matched receiver pays one
            # view, not a reorder.
            r = len(ranges)
            if len(arrs) != 1 + n_blocks * per_block * r:
                raise ValueError(
                    f"{len(arrs)} tensors for {n_blocks} blocks x "
                    f"{r} head tiles (quantized={quantized})"
                )
            widths = [int(hi) - int(lo) for lo, hi in ranges]
            joined = [arrs[0]]
            for i in range(n_blocks * per_block):
                pieces = arrs[1 + i * r : 1 + (i + 1) * r]
                for p, w in zip(pieces, widths):
                    if p.ndim < 2 or p.shape[1] != w:
                        raise ValueError(
                            f"head tile shape {p.shape} != declared "
                            f"width {w}"
                        )
                joined.append(
                    pieces[0] if r == 1
                    else np.concatenate(pieces, axis=1)
                )
            arrs = joined
        if len(arrs) != 1 + n_blocks * per_block:
            raise ValueError(
                f"{len(arrs)} tensors for {n_blocks} blocks "
                f"(quantized={quantized})"
            )
        prompt = np.asarray(arrs[0], np.int32).reshape(-1)
        if prompt.shape[0] != int(meta["prompt_len"]):
            raise ValueError("prompt length mismatch")
        blocks = []
        it = iter(arrs[1:])
        for _ in range(n_blocks):
            if quantized:
                blocks.append((next(it), next(it), next(it)))
            else:
                blocks.append(next(it))
        return KVHandoff(
            req_id=int(meta["req_id"]),
            prompt=prompt,
            page_size=int(meta["page_size"]),
            n_pages=int(meta["n_pages"]),
            quantized=quantized,
            blocks=blocks,
            kv_dtype=str(
                meta.get("kv_dtype")
                or ("int8" if quantized else "native")
            ),
        )
    except HandoffError:
        raise
    except Exception as e:  # noqa: BLE001 — every decode failure is one error
        raise HandoffError(f"malformed KV handoff: {e!r}") from e


def loopback(msg: Message) -> Message:
    """The in-process transport: gather the frame exactly as the
    kernel would (``frame_parts`` — the same scatter list
    ``send_msg`` hands to ``sendmsg``), then re-parse it through
    ``parse_frame`` (the same body ``recv_msg`` uses). The returned
    message's payload views the gathered buffer, so the receive side
    exercises the true zero-copy parse path; tests corrupt the
    gathered bytes to prove truncation fails cleanly."""
    wire = bytearray(b"".join(frame_parts(msg)))
    body = memoryview(wire)[_LEN_PREFIX:]
    expect = int.from_bytes(wire[:_LEN_PREFIX], "big")
    if len(body) != expect:
        raise HandoffError(
            f"truncated frame: {len(body)} of {expect} bytes"
        )
    try:
        return parse_frame(body)
    except ConnectionError as e:
        raise HandoffError(str(e)) from e


@dataclasses.dataclass
class _PrefillJob:
    req_id: int
    prompt: np.ndarray
    #: Positions to prefill: the prompt's full pages only ([0, m*P)) —
    #: the partial last page re-prefills decode-side as the suffix
    #: pass (the prefix probe never shares the final page anyway).
    target: int
    slot: int = -1
    pf_done: int = 0
    #: Set when an sp dispatch failed and the job fell back to the
    #: chunk path — the sp scan must not pick it up again (retrying a
    #: deterministic failure forever would starve the queue).
    no_sp: bool = False


class PrefillWorker:
    """The prefill tier: admission + chunked prefill against its OWN
    paged pool, producing :class:`KVHandoff`\\ s.

    Drives like a miniature batcher: :meth:`submit` queues a request,
    each :meth:`step` admits waiting jobs into free slots (FIFO,
    all-or-nothing page reservation) and runs ONE page-aligned chunk
    pass per active slot (``prefill_chunk`` bounds any single stall;
    ``None`` = the whole span in one pass — only sensible when the
    worker runs on its own thread/host), then gathers finished jobs'
    pages off the pool and frees them. The chunk math is EXACTLY the
    decode batcher's chunked-prefill body
    (``models.prefill_chunk_paged`` with the same power-of-two window
    padding), so handed pages are bit-identical to what the decode
    side's own chunked prefill would have written — the foundation of
    the disaggregated path's bit-identity contract."""

    def __init__(
        self,
        lm: TransformerLM,
        variables,
        page_size: int = 128,
        slots: int = 2,
        pool_pages: int | None = None,
        prefill_chunk: int | None = None,
        kv_cache_dtype: str = "native",
        name: str = "prefill0",
        prefill: PrefillConfig | None = None,
        sp_mesh=None,
    ):
        if kv_cache_dtype not in ("native", "int8", "int4"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: expected 'native', "
                "'int8' or 'int4'"
            )
        if prefill_chunk is not None and (
            prefill_chunk < page_size or prefill_chunk % page_size
        ):
            raise ValueError(
                f"prefill_chunk must be a positive multiple of "
                f"page_size {page_size}, got {prefill_chunk}"
            )
        self.lm = lm
        self.variables = variables
        self.name = name
        self.page_size = page_size
        self.kv_cache_dtype = kv_cache_dtype
        self.quantized = kv_cache_dtype != "native"
        self._chunk = prefill_chunk
        g = lm.graph
        self._embed = g.node("embed").module
        self._blocks = [g.node(n).module for n in lm.block_names]
        block0 = self._blocks[0]
        self._heads = block0.cache_heads
        self._head_dim = block0.head_dim
        pps, worst = pool_geometry(slots, lm.max_len, page_size)
        if pool_pages is None:
            pool_pages = worst
        self._pager = Pager(pool_pages, slots, pps)
        # The decode batcher's pool format, by the same definition.
        self._pools = [
            alloc_kv_pools(
                pool_pages, self._heads, page_size, self._head_dim,
                block0.dtype, kv_cache_dtype,
            )
            for _ in lm.block_names
        ]
        self._queue: collections.deque[_PrefillJob] = collections.deque()
        self._slots: list[_PrefillJob | None] = [None] * slots
        self._table_dev = None
        self._fn_cache: dict[Any, Any] = {}
        self.prefill_tokens = 0
        self.handoffs = 0
        # -- sequence-parallel long-context prefill ------------------------
        # ``PrefillConfig{sp_threshold, sp_width}``: jobs of at least
        # the threshold bypass the pool/chunk loop entirely — one
        # sp-sharded whole-span program (``parallel/sp_prefill``)
        # produces the handoff in a single :meth:`step` dispatch, and
        # the prompt's O(S^2) attention splits over the ring instead of
        # serializing on one chip. Failures fall back to the chunk path
        # when the pool can cover the job, else fail the request
        # cleanly through :attr:`failed_jobs` (drained by
        # ``DisaggServer.tick``).
        self._sp_cfg = prefill
        self._sp: SPPrefiller | None = None
        self.sp_prefills = 0
        self.failed_jobs: list[tuple[int, str]] = []
        if prefill is not None and prefill.enabled:
            mesh = sp_mesh
            if mesh is None:
                mesh = build_sp_mesh(
                    prefill.sp_width, 1, prefill.sp_axis
                )
            self._sp = SPPrefiller(
                lm, variables, mesh, page_size,
                kv_cache_dtype=kv_cache_dtype,
                sp_axis=prefill.sp_axis,
                tp_axis=(
                    "tp" if "tp" in getattr(mesh, "shape", {}) else None
                ),
                name=f"{name}-sp",
            )
            global_metrics().set_gauge(
                "prefill.sp_width", float(self._sp.sp)
            )
        _LIVE_WORKERS.add(self)
        global_compile_sentinel().register(
            "disagg.prefill",
            size_fn=aggregate_size_fn(_LIVE_WORKERS, _worker_family_size),
        )

    # -- compiled pieces ---------------------------------------------------

    def _chunk_fn(self, cbucket: int, n_pad: int):
        """One chunk pass over [pos0, pos0 + cbucket): the decode
        batcher's ``_prefill_suffix_fn`` body minus the sampling tail
        (the prefill tier never emits — the first token samples
        decode-side on the suffix pass). Specializes per (chunk
        bucket, pow2 window pages)."""
        key = ("chunk", cbucket, n_pad)
        if key in self._fn_cache:
            return self._fn_cache[key]

        @partial(jax.jit, donate_argnums=(1,))
        def chunkfn(variables, pools, pages, ids, pos):
            pos0 = pos[0]
            pos_ids = pos0 + jnp.arange(cbucket)[None]
            h = self._embed.apply(
                variables["embed"], ids, pos_ids,
                method="embed_positions",
            )
            new_pools = []
            for name, block, pool in zip(
                self.lm.block_names, self._blocks, pools
            ):
                h, pool = block.apply(
                    variables[name], h, pool, pages, pos0,
                    method="prefill_chunk_paged",
                )
                new_pools.append(pool)
            return new_pools

        self._fn_cache[key] = chunkfn
        return chunkfn

    def _gather_fn(self, nb: int):
        """Gather ``nb`` physical pages' K/V off every block's pool in
        one program (ONE device->host fetch for the whole handoff)."""
        key = ("gather", nb)
        if key in self._fn_cache:
            return self._fn_cache[key]

        @jax.jit
        def gather(pools, pages):
            return jax.tree.map(lambda pool: pool[pages], pools)

        self._fn_cache[key] = gather
        return gather

    # -- request lifecycle -------------------------------------------------

    def submit(self, req_id: int, prompt) -> int:
        """Queue one prompt for prefill; returns the number of full
        pages the eventual handoff will cover. Raises ``ValueError``
        for prompts with no full page or that can never fit the
        pool — the placement policy screens both, so reaching here is
        a caller bug."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        s0 = prompt.shape[0]
        m = (s0 - 1) // self.page_size
        if m < 1:
            raise ValueError(
                f"prompt of {s0} tokens has no full {self.page_size}-"
                "token page to hand off"
            )
        # sp-eligible jobs never touch the pool (the sp program holds
        # the whole span sp-sharded), so the pool bound does not apply.
        if m > self._pager.num_allocatable and not self.sp_eligible(s0):
            raise ValueError(
                f"prompt needs {m} pages but the prefill pool holds "
                f"{self._pager.num_allocatable}"
            )
        self._queue.append(
            _PrefillJob(
                req_id=req_id, prompt=prompt, target=m * self.page_size
            )
        )
        return m

    def cancel(self, req_id: int) -> bool:
        """Drop a queued or mid-prefill job (its pages free
        immediately). False if the job is not here (already handed
        off, or never submitted)."""
        for i, job in enumerate(self._queue):
            if job.req_id == req_id:
                del self._queue[i]
                return True
        for i, job in enumerate(self._slots):
            if job is not None and job.req_id == req_id:
                self._pager.free_slot(i)
                self._slots[i] = None
                return True
        return False

    def pending(self) -> int:
        """Jobs queued or mid-prefill."""
        return len(self._queue) + sum(
            1 for j in self._slots if j is not None
        )

    def sp_eligible(self, s0: int) -> bool:
        """Whether a prompt of ``s0`` tokens takes the
        sequence-parallel path (``PrefillConfig.sp_threshold``) —
        also consulted by the placement policy, since sp jobs are
        exempt from the pool-capacity bound."""
        return (
            self._sp is not None
            and s0 >= self._sp_cfg.sp_threshold
            and (s0 - 1) // self.page_size >= 1
        )

    def _sp_pass(self, job: _PrefillJob) -> KVHandoff | None:
        """Run one sp-eligible job through the sp-sharded whole-span
        program: the entire prefill in ONE dispatch, handoff built
        straight from the program's page-major output — the job never
        touches the pool. On failure: chunk-path fallback when the
        pool can cover the job (front re-queue, FIFO restored), else
        the request fails cleanly via :attr:`failed_jobs`."""
        tracer = global_tracer()
        t0 = tracer.now() if tracer.enabled else 0.0
        try:
            m, blocks = self._sp.prefill(job.prompt)
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            if job.target // self.page_size <= (
                self._pager.num_allocatable
            ):
                log.exception(
                    "sp prefill failed for request %d; falling back "
                    "to the chunked path", job.req_id,
                )
                job.no_sp = True  # never re-picked by the sp scan
                self._queue.appendleft(job)
            else:
                self.failed_jobs.append((job.req_id, str(e)[:200]))
            return None
        toks = m * self.page_size
        self.prefill_tokens += toks
        self.sp_prefills += 1
        self.handoffs += 1
        reg = global_metrics()
        reg.inc("disagg.prefill_tokens_total", float(toks))
        reg.inc("disagg.sp_prefills_total")
        if tracer.enabled:
            tracer.add_span(
                "disagg.sp_prefill",
                start=t0,
                end=tracer.now(),
                request=job.req_id,
                pages=m,
                sp=self._sp.sp,
            )
        global_flight_recorder().record(
            "sp_prefill",
            request=job.req_id,
            pages=m,
            sp=self._sp.sp,
            tier="prefill",
        )
        return KVHandoff(
            req_id=job.req_id,
            prompt=job.prompt,
            page_size=self.page_size,
            n_pages=m,
            quantized=self.quantized,
            blocks=blocks,
            kv_dtype=self.kv_cache_dtype,
        )

    def _admit(self) -> None:
        for i, job in enumerate(self._slots):
            if job is not None or not self._queue:
                continue
            nxt = self._queue[0]
            n_pages = nxt.target // self.page_size
            # FIFO head-of-line, all-or-nothing — the batcher's own
            # admission discipline.
            if not self._pager.alloc(i, n_pages):
                self._pager.free_slot(i)
                return
            nxt = self._queue.popleft()
            nxt.slot, nxt.pf_done = i, 0
            self._slots[i] = nxt

    def _pass(self, job: _PrefillJob) -> None:
        P = self.page_size
        pos0 = job.pf_done
        clen = min(self._chunk or job.target, job.target - pos0)
        n_strip = (pos0 + clen) // P
        owned = self._pager.owned(job.slot)
        n_pad = 1
        while n_pad < n_strip:
            n_pad *= 2
        pages = owned[:n_strip] + [0] * (n_pad - n_strip)
        ids = np.zeros((1, clen), np.int32)
        ids[0, :] = job.prompt[pos0:pos0 + clen]
        self._pools = self._chunk_fn(clen, n_pad)(
            self.variables,
            self._pools,
            jnp.asarray(np.asarray(pages, np.int32)),
            jnp.asarray(ids),
            jnp.asarray(np.asarray([pos0], np.int32)),
        )
        job.pf_done = pos0 + clen
        self.prefill_tokens += clen
        global_metrics().inc(
            "disagg.prefill_tokens_total", float(clen)
        )

    def _finish(self, job: _PrefillJob) -> KVHandoff:
        P = self.page_size
        m = job.target // P
        owned = self._pager.owned(job.slot)[:m]
        nb = 1
        while nb < m:
            nb *= 2
        pages = np.asarray(owned + [0] * (nb - m), np.int32)
        gathered = self._gather_fn(nb)(self._pools, jnp.asarray(pages))
        host = jax.device_get(gathered)  # ONE fused fetch
        blocks = jax.tree.map(lambda x: np.asarray(x)[:m], host)
        self._pager.free_slot(job.slot)
        self._slots[job.slot] = None
        self.handoffs += 1
        return KVHandoff(
            req_id=job.req_id,
            prompt=job.prompt,
            page_size=P,
            n_pages=m,
            quantized=self.quantized,
            blocks=blocks,
            kv_dtype=self.kv_cache_dtype,
        )

    def step(self) -> list[KVHandoff]:
        """One prefill-tier scheduling round: dispatch at most ONE
        sp-eligible job through the sequence-parallel program (its
        whole span in one sp-sharded pass — the sp counterpart of the
        chunk-pass stall bound), then admit waiting jobs, run ONE
        chunk pass per active slot, and hand off the finished ones.
        Returns this round's completed handoffs (possibly empty)."""
        done: list[KVHandoff] = []
        if self._sp is not None:
            for i, job in enumerate(self._queue):
                if not job.no_sp and self.sp_eligible(
                    job.prompt.shape[0]
                ):
                    del self._queue[i]
                    h = self._sp_pass(job)
                    if h is not None:
                        done.append(h)
                    break  # one sp dispatch per step — the stall bound
        self._admit()
        tracer = global_tracer()
        for job in list(self._slots):
            if job is None:
                continue
            t0 = tracer.now() if tracer.enabled else 0.0
            self._pass(job)
            if tracer.enabled:
                tracer.add_span(
                    "disagg.prefill_chunk",
                    start=t0,
                    end=tracer.now(),
                    request=job.req_id,
                    pos0=int(job.pf_done),
                )
            if job.pf_done >= job.target:
                done.append(self._finish(job))
        return done

    def stats(self) -> dict:
        ps = self._pager.stats()
        return {
            "queued": len(self._queue),
            "active": sum(1 for j in self._slots if j is not None),
            "prefill_tokens": self.prefill_tokens,
            "handoffs": self.handoffs,
            "sp_prefills": self.sp_prefills,
            "sp_width": self._sp.sp if self._sp is not None else 1,
            "pool_pages": ps.num_pages,
            "pages_in_use": ps.in_use,
        }


@dataclasses.dataclass
class _Routed:
    """Server-side request state: where the request currently lives."""

    tier: str  # "prefill" | "decode" | "done"
    rid: int | None = None  # decode-batcher id once submitted there
    kwargs: dict | None = None  # deferred decode.submit arguments
    t_submit: float = 0.0


class DisaggServer:
    """The disaggregated submit path: one placement policy in front of
    a :class:`PrefillWorker` and a decode-side
    :class:`~adapt_tpu.runtime.continuous.ContinuousBatcher` (the
    handoff lands through its paged prefix cache).

    Mirrors the batcher's synchronous driver surface (``submit`` /
    ``tick`` / ``cancel`` / ``run`` / ``result`` / ``stats``), so the
    load harness drives either interchangeably. Each :meth:`tick`:
    heartbeats the prefill pool's role-tagged lease, runs one prefill
    scheduling round, lands completed handoffs over the loopback wire
    (real frames — the cross-host format), submits the landed
    requests to the decode batcher (prefix-cache hit admission), and
    runs one decode tick. Single-threaded by design (v1): the chunk
    pass is the stall bound the harness measures."""

    def __init__(
        self,
        decode: ContinuousBatcher,
        prefill: PrefillWorker,
        config: DisaggConfig | None = None,
        registry=None,
        lease_ttl_s: float = 2.0,
        telemetry_url: str | None = None,
        wire_codec: str | None = None,
    ):
        if prefill.page_size != decode._page:
            raise ValueError(
                f"prefill page size {prefill.page_size} != decode page "
                f"size {decode._page}"
            )
        if prefill.kv_cache_dtype != decode._kv_dtype:
            raise ValueError(
                "prefill/decode kv_cache_dtype mismatch "
                f"(prefill {prefill.kv_cache_dtype!r}, decode "
                f"{decode._kv_dtype!r})"
            )
        if prefill.lm.vocab != decode.lm.vocab:
            raise ValueError("prefill/decode vocab mismatch")
        self.decode = decode
        self.prefill = prefill
        self.cfg = config or DisaggConfig()
        #: MSG_KV_PAGES wire codec (``pack_handoff``). Explicit arg
        #: wins; otherwise inherited from the decode batcher's
        #: ``CacheTierConfig.wire_codec`` when it runs a cache tier
        #: (ONE config names every tier boundary's codec); "raw" —
        #: today's zero-copy frames — when neither names one.
        if wire_codec is None:
            tier_cfg = getattr(decode, "_tier_cfg", None)
            wire_codec = tier_cfg.wire_codec if tier_cfg else "raw"
        from adapt_tpu.ops.quantize import PAGE_CODECS

        if wire_codec not in PAGE_CODECS:
            raise ValueError(
                f"wire_codec={wire_codec!r}: expected one of "
                f"{PAGE_CODECS}"
            )
        self.wire_codec = wire_codec
        if self.cfg.busy_prompt_threshold <= decode._page:
            log.warning(
                "busy_prompt_threshold %d <= page size %d: busy-tier "
                "prompts just over the threshold may have no full page "
                "and will collocate anyway",
                self.cfg.busy_prompt_threshold, decode._page,
            )
        self._registry = registry
        self._lease_ttl = lease_ttl_s
        self._lease_key = f"prefill:{prefill.name}"
        #: Lease metadata. ``telemetry_url`` (the tier's exporter
        #: ``/telemetry.json``) advertises the HTTP-PULL federation
        #: fallback: a dispatcher that does not own this process's
        #: comm link discovers the endpoint off the lease and polls it
        #: (``utils.telemetry.FederatedStore.poll_registry``) — the
        #: lease is the membership record, so it is also the telemetry
        #: directory.
        self._lease_meta = {"role": "prefill"}
        if telemetry_url is not None:
            self._lease_meta["telemetry"] = telemetry_url
        if registry is not None:
            # ROLE-TAGGED lease: the pipeline dispatcher's _acquire
            # skips role-tagged workers, and this policy stops routing
            # to the tier when the lease expires (alive(role=)).
            self._lease_token = registry.register(
                self._lease_key,
                meta=dict(self._lease_meta),
                ttl_s=lease_ttl_s,
            )
        #: Lease-meta capacity book refresh (rate-limited): the
        #: prefill tier's ``/fleet/capacity`` path. register() on an
        #: EXISTING key replaces meta and renews the lease without
        #: firing join watchers, so the refresh is free of membership
        #: side effects. Gated on the decode batcher's capacity plane
        #: — ``CapacityConfig(enabled=False)`` is ONE switch for the
        #: whole replica.
        cap = decode._capacity
        self._cap_lease_s = (
            cap.cfg.lease_refresh_s if cap is not None else 0.0
        )
        self._cap_last_lease = 0.0
        #: Drain switch (close()): stops lease keepalive/resurrection
        #: so the placement policy falls back to collocated for good.
        self._closed = False
        self._route: dict[int, _Routed] = {}
        self._done: dict[int, np.ndarray] = {}
        #: sid -> decode rid for CLAIMED requests (route entries prune
        #: at claim so a long-lived server does not grow per-request
        #: state; this bounded map keeps logprobs() reachable after
        #: result() — same eviction discipline as the batcher's
        #: unclaimed-logprobs cap).
        self._claimed_rids: collections.OrderedDict[int, int] = (
            collections.OrderedDict()
        )
        self._next_sid = 0
        # Placement books (instance-scoped, mirrored as disagg.*
        # counters).
        self.disaggregated = 0
        self.collocated = 0
        self.failed = 0
        # Closed-loop degradation: a scheduler-configured decode
        # batcher's controller gains its busy-threshold rung the
        # moment this server fronts it (the controller holds the
        # server weakly — see runtime/scheduler).
        ctrl = getattr(decode, "_controller", None)
        if ctrl is not None:
            ctrl.attach_disagg(self)

    # -- placement ---------------------------------------------------------

    def _prefill_alive(self) -> bool:
        if self._registry is None:
            return True
        return self._lease_key in self._registry.alive(role="prefill")

    def _placement(self, s0: int, slo: SLOSpec | None = None) -> bool:
        """True = disaggregate. The knobs live in
        ``config.DisaggConfig``; every fallback is collocated.
        PRIORITY is visible here (``SLOSpec.priority``): a
        high-priority request (> 0) always sees the tight BUSY
        threshold — its TTFT budget is the one the decode tier's
        in-tick prefill stalls would blow, and its long prompt is
        exactly the work the decode tier must not pay inline while
        lower classes wait on inter-token latency."""
        m = (s0 - 1) // self.decode._page
        if m < 1:
            return False  # nothing to hand off
        slots = self.decode.slots
        occupancy = sum(
            1 for s in slots if s.req is not None
        ) / len(slots)
        busy = occupancy >= self.cfg.busy_occupancy or (
            slo is not None and slo.priority > 0
        )
        threshold = (
            self.cfg.busy_prompt_threshold
            if busy
            else self.cfg.prompt_threshold
        )
        if s0 < threshold:
            return False
        if m > self.prefill._pager.num_allocatable and not (
            self.prefill.sp_eligible(s0)
        ):
            # The prefill pool can never cover it — unless the tier's
            # sequence-parallel path will take it (sp jobs hold their
            # span sp-sharded in the program, never in the pool).
            return False
        return self._prefill_alive()

    # -- request lifecycle -------------------------------------------------

    def submit(
        self,
        prompt,
        steps: int,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        eos_id: int | None = None,
        rng=None,
        stop: list | None = None,
        on_token: Callable[[int, int, int], None] | None = None,
        slo: SLOSpec | None = None,
    ) -> int:
        """Queue one request; returns the SERVER-side id (use it with
        :meth:`cancel` / :meth:`result`). Collocated requests submit
        to the decode batcher immediately; disaggregated ones enter
        the prefill tier and reach the decode batcher when their
        pages land (TTFT/queue-wait/SLO all measure from THIS call —
        the decode submit carries the original arrival stamp)."""
        dec = self.decode
        # THE decode-side validation body, shared with the collocated
        # path: a disaggregated request fails HERE, synchronously,
        # exactly like a collocated submit would — never minutes later
        # at handoff landing.
        prompt, _ = dec.validate_request(
            prompt, steps, temperature=temperature, top_k=top_k,
            top_p=top_p, rng=rng, stop=stop, slo=slo,
        )
        s0 = prompt.shape[0]
        if s0 > self.prefill.lm.max_len:
            raise ValueError(
                f"prompt {s0} exceeds the prefill tier's max_len "
                f"{self.prefill.lm.max_len}"
            )
        sid = self._next_sid
        self._next_sid += 1
        if on_token is not None:
            # Callbacks must see the SERVER id — the id this submit
            # returned and the one cancel()/result() accept. The decode
            # batcher invokes them with its OWN rid, which desyncs from
            # sids as soon as placements interleave; a caller feeding
            # the callback's id back into cancel() would then target a
            # different request.
            user_cb = on_token

            def on_token(rid, tok, idx, _sid=sid, _cb=user_cb):
                _cb(_sid, tok, idx)

        kwargs = dict(
            steps=steps,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_id=eos_id,
            rng=rng,
            stop=stop,
            on_token=on_token,
            slo=slo,
        )
        now = time.perf_counter()
        if self._placement(s0, slo):
            # Admission-control pre-check (records the rejection like
            # a collocated submit's would): a request the decode queue
            # would reject RIGHT NOW must fail synchronously, not
            # after its whole prefill ran — the landing-time rejection
            # in _land still backs up the race window.
            dec.admission_check(slo, request=sid)
            self.disaggregated += 1
            global_metrics().inc("disagg.disaggregated_total")
            self._route[sid] = _Routed(
                tier="prefill", kwargs=kwargs, t_submit=now
            )
            self.prefill.submit(sid, prompt)
            self._route[sid].kwargs["prompt"] = prompt
        else:
            self.collocated += 1
            global_metrics().inc("disagg.collocated_total")
            rid = dec.submit(prompt, t_submit=now, **kwargs)
            self._route[sid] = _Routed(
                tier="decode", rid=rid, t_submit=now
            )
        return sid

    def cancel(self, sid: int) -> bool:
        r = self._route.get(sid)
        if r is None or r.tier == "done":
            return False
        if r.tier == "decode":
            return self.decode.cancel(r.rid)
        # Still in the prefill tier: nothing streamed yet — drop with
        # an empty result, and emit the finish lifecycle edge so the
        # admit/finish books a driver reads off the flight recorder
        # stay coherent across tiers.
        if self.prefill.cancel(sid):
            self._done[sid] = np.zeros((0,), np.int32)
            r.tier = "done"
            r.kwargs = None  # drop the retained prompt/rng/callback
            global_flight_recorder().record(
                "cancel", request=sid, state="prefill"
            )
            global_flight_recorder().record(
                "finish", request=sid, reason="cancelled", tokens=0
            )
            return True
        return False

    def _fail(self, sid: int, err: Exception) -> None:
        """A handoff that cannot land fails the REQUEST cleanly: empty
        result (no wedged ``result()``), loud flight events, serving
        continues."""
        self.failed += 1
        self._done[sid] = np.zeros((0,), np.int32)
        r = self._route.get(sid)
        if r is not None:
            r.tier = "done"
            r.kwargs = None  # drop the retained prompt/rng/callback
        global_metrics().inc("disagg.handoff_failed_total")
        global_flight_recorder().record(
            "request_failed", request=sid, reason=str(err)[:200]
        )
        global_flight_recorder().record(
            "finish", request=sid, reason="failed", tokens=0
        )
        log.error("KV handoff failed for request %d: %s", sid, err)

    def _land(self, handoff: KVHandoff) -> None:
        """Stream one handoff over the wire and land it: frame ->
        loopback transport -> parse -> adopt into the decode pool ->
        decode submit (prefix-cache-hit admission)."""
        sid = handoff.req_id
        r = self._route.get(sid)
        if r is None or r.tier != "prefill":
            return  # cancelled between chunk passes and handoff
        t0 = time.perf_counter()
        try:
            msg = pack_handoff(handoff, wire_codec=self.wire_codec)
            wire_bytes = sum(
                p.nbytes if isinstance(p, memoryview) else len(p)
                for p in frame_parts(msg)
            )
            raw_bytes = handoff_raw_nbytes(handoff)
            landed = unpack_handoff(loopback(msg))
            adopted = self.decode.adopt_prefill_pages(
                landed.prompt,
                landed.blocks,
                landed.page_size,
                landed.kv_dtype,
            )
        except (HandoffError, ValueError) as e:
            self._fail(sid, e)
            return
        wall = time.perf_counter() - t0
        reg = global_metrics()
        # handoff_bytes counts WIRE (post-codec) bytes — the frames
        # actually shipped; handoff_bytes_raw the uncompressed payload,
        # so the wire compression ratio is raw/bytes on any dashboard
        # (they coincide when wire_codec == "raw").
        reg.inc("disagg.handoff_bytes", float(wire_bytes))
        reg.inc("disagg.handoff_bytes_raw", float(raw_bytes))
        reg.inc("disagg.pages_streamed", float(handoff.n_pages))
        reg.observe("disagg.handoff_s", wall)
        global_flight_recorder().record(
            "kv_handoff",
            request=sid,
            pages=handoff.n_pages,
            adopted=adopted,
            bytes=wire_bytes,
            blocks=len(handoff.blocks),
            wall_s=round(wall, 6),
        )
        kwargs = dict(r.kwargs)
        prompt = kwargs.pop("prompt")
        try:
            # submit() pre-validated the decode-side constraints, but
            # this stays guarded: a late rejection here must fail ONLY
            # this request (the module contract), never escape tick().
            rid = self.decode.submit(
                prompt, t_submit=r.t_submit, **kwargs
            )
        except (ValueError, TypeError, QueueFullError) as e:
            # QueueFullError: admission control filled up while the
            # prefill ran. The adopted pages stay registered rc=0 in
            # the prefix LRU (land-then-LRU — evictable capacity, or
            # a free prefix hit for a retry), the prefill tier's own
            # pages were already freed at handoff, and ONLY this
            # request fails; the batcher recorded request_rejected.
            self._fail(sid, e)
            return
        r.tier, r.rid, r.kwargs = "decode", rid, None

    def tick(self) -> int:
        """One server scheduling round: prefill step -> land handoffs
        -> decode tick. Returns the decode tick's active-slot count.

        The decode batcher's tick() here dispatches round *t* and
        commits round *t−1* — the handoffs
        landed above still enter admission on THIS call (admission is
        dispatch-side), only result delivery lags one round. The
        driver needs no pacing changes: :meth:`run`'s busy loop keys
        off slot occupancy, which the batcher releases at commit, and
        ``_collect`` drains the in-flight round explicitly before
        claiming results."""
        if (
            self._registry is not None
            and not self._closed
            and not self._registry.heartbeat(
                self._lease_key, self._lease_ttl
            )
        ):
            # The lease expired between ticks (e.g. a long compile gap
            # outlasted the TTL). This tier is self-evidently alive —
            # it is ticking — so re-register (etcd keepalive
            # semantics: expiry means re-register, not retire) instead
            # of silently degrading every future placement to
            # collocated. ``close()`` is the drain switch: a closed
            # server never resurrects its lease.
            self._lease_token = self._registry.register(
                self._lease_key,
                meta=dict(self._lease_meta),
                ttl_s=self._lease_ttl,
            )
        if (
            self._registry is not None
            and not self._closed
            and self._cap_lease_s > 0
        ):
            cap_now = time.monotonic()
            if cap_now - self._cap_last_lease >= self._cap_lease_s:
                self._cap_last_lease = cap_now
                self._lease_meta["capacity"] = prefill_tier_book(
                    self.prefill
                )
                self._lease_token = self._registry.register(
                    self._lease_key,
                    meta=dict(self._lease_meta),
                    ttl_s=self._lease_ttl,
                )
        for handoff in self.prefill.step():
            self._land(handoff)
        if self.prefill.failed_jobs:
            # An sp job that could neither run nor fall back to the
            # chunk path (pool too small for its span): fail the
            # REQUEST cleanly, exactly like a corrupt handoff.
            for sid, err in self.prefill.failed_jobs:
                self._fail(sid, RuntimeError(err))
            self.prefill.failed_jobs.clear()
        return self.decode.tick()

    def drain(self) -> int:
        """Commit the decode tier's in-flight pipelined round, if any
        (no-op at depth 1 / when idle) — the server-level pipeline
        boundary drivers reach for between measurement phases."""
        return self.decode.drain()

    def _busy(self) -> bool:
        if self.prefill.pending():
            return True
        st = self.decode.stats()
        return bool(st["active"] or st["queued"])

    def run(self, max_ticks: int = 100_000) -> dict[int, np.ndarray]:
        """Tick until every submitted request completed; returns
        ``{server_id: tokens}`` (failed/cancelled-in-prefill requests
        map to empty arrays) and clears the finished set."""
        ticks = 0
        while self._busy():
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"run() exceeded {max_ticks} ticks")
        return self._collect()

    def _collect(self) -> dict[int, np.ndarray]:
        # Pipeline boundary: commit any in-flight decode round before
        # claiming results (run() below would also drain, but only
        # after its occupancy check — be explicit at the handoff).
        self.decode.drain()
        dec_done = self.decode.run(max_ticks=1)  # drained: returns dict
        out = dict(self._done)
        self._done = {}
        claimed = list(out)
        for sid, r in self._route.items():
            if r.tier == "decode" and r.rid in dec_done:
                out[sid] = dec_done[r.rid]
                claimed.append(sid)
        # Claimed requests leave the routing table — a long-lived
        # server must not grow one entry per request served.
        for sid in claimed:
            self._remember_rid(sid)
        return out

    def result(self, sid: int, max_ticks: int = 100_000) -> np.ndarray:
        """Drive ticks until ``sid`` finishes; returns (and claims) its
        tokens — empty for a failed or prefill-cancelled request,
        never a wedge."""
        ticks = 0
        while True:
            if sid in self._done:
                self._remember_rid(sid)
                return self._done.pop(sid)
            r = self._route.get(sid)
            if r is None:
                raise KeyError(f"unknown request {sid}")
            if r.tier == "decode":
                # Claim opportunistically; decode.run() only returns
                # when IT is drained, so tick until the rid lands.
                with self.decode._cv:
                    if r.rid in self.decode._done:
                        out = self.decode._done.pop(r.rid)
                        self._remember_rid(sid)
                        return out
            if r.tier == "done":
                raise KeyError(f"request {sid} already claimed")
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(
                    f"result({sid}) exceeded {max_ticks} ticks"
                )

    def _remember_rid(self, sid: int) -> None:
        """Prune ``sid``'s routing entry (claimed), keeping its decode
        rid in the bounded claimed map so :meth:`logprobs` still
        resolves."""
        r = self._route.pop(sid, None)
        if r is not None and r.rid is not None:
            self._claimed_rids[sid] = r.rid
            while len(self._claimed_rids) > 4096:
                self._claimed_rids.popitem(last=False)

    def logprobs(self, sid: int) -> np.ndarray:
        r = self._route.get(sid)
        rid = r.rid if r is not None else self._claimed_rids.get(sid)
        if rid is None:
            raise KeyError(f"no logprobs for request {sid}")
        return self.decode.logprobs(rid)

    # Harness compatibility: warmup() reads the model + buckets off
    # the driven object.
    @property
    def lm(self):
        return self.decode.lm

    @property
    def prompt_buckets(self):
        return self.decode.prompt_buckets

    def capacity_book(self) -> dict | None:
        """One self-describing book for the whole disaggregated pair:
        the decode batcher's capacity book with the prefill tier's
        book nested under ``"prefill"`` (None when the capacity plane
        is disabled). What a DisaggServer process hands
        ``serve_metrics(capacity_provider=...)``."""
        book = self.decode.capacity_book()
        if book is None:
            return None
        book = dict(book)
        book["prefill"] = prefill_tier_book(self.prefill)
        return book

    def stats(self) -> dict:
        out = self.decode.stats()
        pf = self.prefill.stats()
        out.update(
            prefill_queued=pf["queued"],
            prefill_active=pf["active"],
            prefill_tier_tokens=pf["prefill_tokens"],
            handoffs=pf["handoffs"],
            disaggregated=self.disaggregated,
            collocated_submits=self.collocated,
            handoff_failed=self.failed,
            # Sequence-parallel tier books: the worker's sp-path
            # dispatch count and live ring width (1 = sp off). A
            # decode-side sp_prefills (collocated sp) would be
            # clobbered here by design — a DisaggServer's sp work
            # happens in the prefill tier.
            sp_prefills=pf["sp_prefills"],
            sp_width=pf["sp_width"],
        )
        # "queued" should reflect the whole server, or a driver's
        # drain loop would stop while the prefill tier still holds
        # work.
        out["queued"] += pf["queued"] + pf["active"]
        return out

    def close(self) -> None:
        """Drain the prefill tier: release its role-tagged lease and
        stop resurrecting it — every later placement collocates. THE
        operator drain switch (a raw registry deregister alone would
        be re-registered by the next tick's keepalive). The decode
        batcher's own close() is the caller's to run."""
        self._closed = True
        if self._registry is not None:
            self._registry.deregister(
                self._lease_key, self._lease_token
            )
