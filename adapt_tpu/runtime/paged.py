"""Host-side page bookkeeping for the paged KV cache.

The device side is ``ops/paged_attention`` (how a pool plane is appended
to and read, and the scalar-prefetch kernels); this module owns the
ALLOCATOR — a free list of physical pages, per-slot page ownership, and
the (slots, pages_per_slot) page table the compiled step consumes — and
the pool's FORMAT: :func:`alloc_kv_pools` is the one place a block's
pool is shaped (ONE fused K|V plane, or a quantized ``(values,
k_scales, v_scales)`` triple), :func:`pool_geometry` the one place the
table width and the
default pool size are computed. The batcher, the disaggregated prefill
worker and the sp prefiller all call them. The bookkeeping is plain
numpy/python on the serving control path — page churn is a few integers
per request, never worth a device round trip.

Prefix caching rides on the same bookkeeping: a FULL page of prompt
K/V is immutable once written (position p's K/V depend only on tokens
[0..p], so page i is determined by tokens[0..(i+1)*page_size)), which
makes the page the natural sharing unit. Pages carry REFCOUNTS; a
finished request's registered pages drop to rc=0 but stay resident in
an LRU of evictables, and a later request whose prompt hashes to the
same content keys shares them (rc+1) instead of recomputing —
``lookup_share`` / ``register``. Allocation evicts rc=0 cached pages
only under pool pressure, oldest first.

The registry doubles as a RADIX TREE over token blocks: every content
key IS a root-to-node path (the key for page j is the byte string of
tokens [0, (j+1)*P), so a key's parent is itself minus one page of
tokens), which means the flat ``key -> page`` dict already encodes the
trie — what ``_radix`` adds is the per-node metadata (block depth, hit
heat) and the token-level accounting that makes PARTIAL matches
first-class: an admission walks the deepest resident path and prefills
only the suffix past it (a 900-token match on a 1000-token prompt
recomputes 100 tokens), ``radix_probe`` scores a queued prompt's
resident prefix without touching the books (the scheduler's
cache-aware admission ordering reads it), and the
``radix_partial_hits`` / ``radix_hit_tokens`` books say how much
prefill the tree actually absorbed. Copy-on-write fan-out leans on the
same refcounts: ``retain``/``release_claim`` let a fan-out group hold
a raw claim on a shared page so N sibling continuations admit against
it (rc bumps, no copies), and a sibling forks a private copy only for
the one partial page it must write into (``cow_forks`` counts them).

Conventions (shared with ``ops/paged_attention``):
- page 0 is the shared TRASH page: never allocated, the target of every
  unallocated table entry and of idle slots' garbage writes. Reads of it
  are always masked; concurrent garbage writes to it are unordered and
  unread.
- a slot's table row holds its pages in logical order; entries past its
  allocation point at the trash page.

Tensor parallelism is invisible here by design: the POOLS shard on
their head axis over the mesh (``runtime/continuous``), but a page is a
page — the table, the free list, refcounts and prefix keys are logical
bookkeeping, identical on every shard, so the allocator never changes
with the mesh (``table()`` is uploaded replicated).

Quantization is equally invisible: an int8 batcher keeps THREE planes
per block (``(int8 values, k_scales, v_scales)`` —
``ops/paged_attention``'s quantized layout) addressed by ONE page id
space, so every allocator decision (alloc/free/recycle/prefix-share)
applies to a page's values and its scale planes atomically — a
prefix-shared page always carries the scales its int8 payload was
written with. ``insert_prefill_pages`` scatters any plane (``kv``
trailing dim is the fused row for values, 1 for scale planes).

No reference analog (SURVEY.md §2.2) — serving-memory frontier.
"""

from __future__ import annotations

import collections
import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PagerStats:
    num_pages: int  # total pool pages incl. trash
    free: int  # immediately allocatable (free list + evictable cache)
    in_use: int  # rc > 0, excl. trash
    cached: int  # rc == 0 but resident for prefix reuse
    prefix_hits: int
    prefix_misses: int
    prefix_capacity_skips: int  # resident page, but the table row was full
    radix_nodes: int  # resident token-block nodes (== registered keys)
    radix_partial_hits: int  # admissions whose match ended mid-path
    radix_hit_tokens: int  # prompt tokens answered from resident nodes
    cow_forks: int  # fan-out page forks (private copy of a shared page)


@dataclasses.dataclass
class _RadixNode:
    """Metadata for one resident token-block node. The tree STRUCTURE
    lives in the content keys themselves (a node's key is its full
    root path; the parent key is the same bytes minus one page of
    tokens), so nodes need no child pointers — only what a flat key
    can't carry: the block depth and how hot the node runs."""

    depth: int  # 1-based page depth (covers depth * page_tokens tokens)
    hits: int = 0  # lookup_share acquisitions through this node


class Pager:
    """Free-list page allocator with refcounted prefix sharing over a
    pool of ``num_pages`` physical pages (page 0 reserved as trash) for
    ``slots`` lockstep slots whose table rows are ``pages_per_slot``
    wide."""

    def __init__(
        self,
        num_pages: int,
        slots: int,
        pages_per_slot: int,
        page_tokens: int | None = None,
    ):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2, got {num_pages}")
        if pages_per_slot < 1:
            raise ValueError(
                f"pages_per_slot must be >= 1, got {pages_per_slot}"
            )
        self.num_pages = num_pages
        self.pages_per_slot = pages_per_slot
        #: Tokens per page — lets the radix books convert page depths
        #: to token counts and ``radix_probe`` walk a raw prompt. None
        #: (a caller that never probes by tokens) degrades the radix
        #: view to depth-0 nodes with the byte registry untouched.
        self.page_tokens = page_tokens
        # Pop from the end -> low page ids hand out first (determinism
        # helps test reproducibility; no perf meaning).
        self._free = list(range(num_pages - 1, 0, -1))
        self._owned: list[list[int]] = [[] for _ in range(slots)]
        #: Rolling-window offset: how many LEADING logical ordinals of
        #: each slot have been released mid-request (sliding-window
        #: recycling). owned[0] then sits at table ordinal base[slot].
        self._base: list[int] = [0 for _ in range(slots)]
        self._rc: dict[int, int] = {}
        # Content-addressed prefix registry: key -> page, both ways.
        self._by_key: dict[bytes, int] = {}
        self._key_of: dict[int, bytes] = {}
        # rc==0 registered pages, oldest-first (eviction order).
        self._lru: collections.OrderedDict[int, None] = (
            collections.OrderedDict()
        )
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_capacity_skips = 0
        #: Radix metadata, keyed by the SAME content keys as _by_key
        #: (kept in lockstep: inserted by register/adopt_cached, dropped
        #: by the two eviction paths) — the byte registry stays the one
        #: source of residency truth, radix coherence with the host
        #: tier's spill/readmit keys is free.
        self._radix: dict[bytes, _RadixNode] = {}
        self.radix_partial_hits = 0
        self.radix_hit_tokens = 0
        self.radix_evictions = 0
        self.cow_forks = 0
        #: Optional eviction callback ``(page, key) -> None``, invoked
        #: just BEFORE a registered rc=0 page leaves the pool (LRU
        #: eviction under allocation pressure, or an ``evict_cached``
        #: sweep) — the hierarchical-cache seam: a host tier
        #: (``HostKVTier`` via ``runtime/continuous``) captures the
        #: page's bytes here so eviction spills instead of killing the
        #: content. The page's HBM bytes are still readable when the
        #: hook runs (pools are functional arrays; the new owner's
        #: write dispatches later), and the hook must not reenter the
        #: pager.
        self.evict_hook = None

    @property
    def num_allocatable(self) -> int:
        """Pages the allocator can ever hand out: the pool minus the
        reserved trash page — the denominator occupancy gauges and
        capacity planning should use (``num_pages`` counts the trash
        page too)."""
        return self.num_pages - 1

    # -- raw pages ---------------------------------------------------------

    def _take_one(self) -> int | None:
        if self._free:
            return self._free.pop()
        if self._lru:  # evict the coldest cached prefix page
            page, _ = self._lru.popitem(last=False)
            key = self._key_of.pop(page)
            del self._by_key[key]
            self._radix_drop(key)
            if self.evict_hook is not None:
                self.evict_hook(page, key)
            return page
        return None

    # -- radix metadata (keys double as root-to-node paths) ----------------

    def _radix_add(self, key: bytes) -> None:
        if key not in self._radix:
            depth = (
                len(key) // (4 * self.page_tokens)
                if self.page_tokens
                else 0
            )
            self._radix[key] = _RadixNode(depth=depth)

    def _radix_drop(self, key: bytes) -> None:
        if self._radix.pop(key, None) is not None:
            self.radix_evictions += 1

    def can_alloc(self, n: int) -> bool:
        return len(self._free) + len(self._lru) >= n

    def alloc(self, slot: int, n: int) -> bool:
        """Grant ``n`` MORE pages to ``slot``; all-or-nothing. False if
        the pool cannot cover it even after evicting every rc=0 cached
        page (caller leaves the request queued)."""
        owned = self._owned[slot]
        if self._base[slot] + len(owned) + n > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {self._base[slot]}+{len(owned)}+{n} pages "
                f"exceeds table width {self.pages_per_slot}"
            )
        if not self.can_alloc(n):
            return False
        for _ in range(n):
            page = self._take_one()
            self._rc[page] = 1
            owned.append(page)
        return True

    def _release_one(self, page: int) -> None:
        """Drop one claim on ``page``; at rc=0 it returns to the free
        list — unless registered as prefix cache, in which case it
        stays resident and evictable (LRU)."""
        self._rc[page] -= 1
        if self._rc[page] == 0:
            del self._rc[page]
            if page in self._key_of:
                self._lru[page] = None  # newest = last evicted
            else:
                self._free.append(page)

    def free_slot(self, slot: int) -> None:
        """Drop ``slot``'s claim on all its pages."""
        for page in reversed(self._owned[slot]):
            self._release_one(page)
        self._owned[slot] = []
        self._base[slot] = 0

    def release_prefix(self, slot: int, n: int) -> None:
        """Sliding-window recycling: release ``slot``'s first ``n``
        logical pages MID-REQUEST (they fell wholly behind the
        attention window — masked forever, written never again). Their
        table ordinals point at the trash page from here on; shared /
        registered pages follow the usual rc / LRU rules, so a released
        prompt page can still serve future prefix hits."""
        if n <= 0:
            return
        if n > len(self._owned[slot]):
            raise ValueError(
                f"slot {slot}: releasing {n} of "
                f"{len(self._owned[slot])} owned pages"
            )
        for page in self._owned[slot][:n]:
            self._release_one(page)
        self._owned[slot] = self._owned[slot][n:]
        self._base[slot] += n

    def base(self, slot: int) -> int:
        return self._base[slot]

    def hold(self, slot: int, lo: int, hi: int) -> None:
        """Make ``slot`` own exactly the logical pages its next pass
        touches, ordinals ``[lo, hi)``: release what fell behind ``lo``
        and grant up to ``hi`` (capped at the table's width). For a
        pool sized ``slots x the widest hold + 1``
        (:func:`group_pool_pages`) the grant cannot fail. ``hi < lo``
        (a pass dispatched for a row whose request ended, by step
        count, inside a tick still in flight: its window moved past
        its last position) releases the same and grants nothing: what
        the row writes goes to the trash page through a zero entry."""
        lo, hi = min(lo, self.pages_per_slot), min(hi, self.pages_per_slot)
        self.release_prefix(
            slot, min(max(lo - self._base[slot], 0), len(self._owned[slot]))
        )
        if not self._owned[slot]:
            # Nothing held: the ordinals before ``lo`` are never backed
            # (a prompt longer than the window keeps only its tail).
            self._base[slot] = max(self._base[slot], lo)
        need = hi - self._base[slot] - len(self._owned[slot])
        if need > 0 and not self.alloc(slot, need):
            raise RuntimeError(
                f"slot {slot}: a cache group's pool cannot cover pages "
                f"[{lo}, {hi}) — sized below group_pool_pages"
            )

    def owned(self, slot: int) -> list[int]:
        return list(self._owned[slot])

    def table_row(self, slot: int) -> np.ndarray:
        """``slot``'s row of :meth:`table`."""
        row = np.zeros((self.pages_per_slot,), np.int32)
        b = self._base[slot]
        row[b: b + len(self._owned[slot])] = self._owned[slot]
        return row

    def table(self) -> np.ndarray:
        """(slots, pages_per_slot) int32; unallocated (and released)
        entries -> trash page 0."""
        t = np.zeros((len(self._owned), self.pages_per_slot), np.int32)
        for i, pages in enumerate(self._owned):
            b = self._base[i]
            t[i, b: b + len(pages)] = pages
        return t

    # -- prefix sharing ----------------------------------------------------

    @staticmethod
    def prefix_key(tokens: np.ndarray, upto: int) -> bytes:
        """Content key for the page covering positions [upto-P, upto):
        the whole prompt prefix [0, upto) (K/V at position p depend on
        every earlier token, so the key must cover them all)."""
        return np.ascontiguousarray(tokens[:upto], np.int32).tobytes()

    def lookup_share(self, slot: int, key: bytes) -> int | None:
        """If ``key``'s page is resident, acquire it for ``slot``
        (rc+1, out of the eviction LRU) and return it."""
        page = self._by_key.get(key)
        if page is None:
            self.prefix_misses += 1
            return None
        # Row-capacity check mirrors alloc()'s accounting: the recycled
        # window base occupies leading ordinals even though the pages are
        # gone (ADVICE r4 — len(owned) alone silently overflowed the row
        # for any future caller sharing into a partially-recycled slot).
        if self._base[slot] + len(self._owned[slot]) + 1 > self.pages_per_slot:
            # A miss for accounting (hits+misses == probes) with its own
            # counter: the page WAS resident, the row was just full.
            self.prefix_misses += 1
            self.prefix_capacity_skips += 1
            return None
        self._lru.pop(page, None)
        self._rc[page] = self._rc.get(page, 0) + 1
        self._owned[slot].append(page)
        self.prefix_hits += 1
        node = self._radix.get(key)
        if node is not None:
            node.hits += 1
        return page

    def retain(self, page: int) -> None:
        """Take one RAW claim on ``page`` (rc+1, out of the eviction
        LRU) without binding it to a slot — how a fan-out group pins
        its shared last-prompt page so it cannot recycle before every
        queued sibling has forked off it. Balance with
        :meth:`release_claim`."""
        self._lru.pop(page, None)
        self._rc[page] = self._rc.get(page, 0) + 1

    def release_claim(self, page: int) -> None:
        """Drop a :meth:`retain` claim; the usual rc=0 rules apply
        (registered pages park in the LRU, others return free)."""
        self._release_one(page)

    def record_prefix_match(self, matched_pages: int, prompt_len: int) -> None:
        """Token-weighted admission accounting for one radix walk:
        ``matched_pages`` leading pages of a ``prompt_len``-token
        prompt were answered from resident nodes. A match that ends
        strictly inside the prompt's shareable page run is a PARTIAL
        hit — the case whole-run keying would have scored as a total
        miss."""
        if matched_pages <= 0 or not self.page_tokens:
            return
        self.radix_hit_tokens += matched_pages * self.page_tokens
        if matched_pages < (prompt_len - 1) // self.page_tokens:
            self.radix_partial_hits += 1

    def note_cow_fork(self) -> None:
        """One fan-out sibling forked a private copy of a shared page
        (the copy-on-write write point)."""
        self.cow_forks += 1

    def radix_probe(self, tokens) -> tuple[int, int, int]:
        """Read-only radix walk for a prompt: ``(matched_pages,
        matched_tokens, heat)`` of the deepest resident token-block
        path, where ``heat`` sums the path nodes' lifetime hit counts.
        No counters move and nothing is acquired — safe to call per
        queued candidate (the scheduler's cache-aware ordering and
        `prefix_cached` both score with it). The walk caps at
        ``(len(tokens)-1)//page_tokens`` pages, mirroring the admission
        probe: the page holding the last prompt token is never shared
        because its tail positions get written."""
        if not self.page_tokens:
            return (0, 0, 0)
        tokens = np.ascontiguousarray(
            np.asarray(tokens, np.int32).reshape(-1)
        )
        raw = tokens.tobytes()
        step = 4 * self.page_tokens
        pages = heat = 0
        for j in range((tokens.shape[0] - 1) // self.page_tokens):
            node = self._radix.get(raw[: (j + 1) * step])
            if node is None:
                break
            pages += 1
            heat += node.hits
        return (pages, pages * self.page_tokens, heat)

    def radix_sketch(self, k: int) -> list[tuple[bytes, int, int]]:
        """Top-``k`` resident radix nodes by token-weighted heat:
        ``[(content_key, depth, hits)]``, hottest first. Weight is
        ``depth * (1 + hits)`` — depth counts the tokens a match at
        this node saves, the ``1 +`` keeps never-hit (freshly
        registered) deep prefixes rankable at all. Read-only snapshot
        (``list()`` at C speed, same stats()-era discipline: exporter
        threads may call while the ticking thread mutates) — the
        capacity plane's affinity-sketch export
        (``runtime/capacity.sketch_from_pager``)."""
        if not self.page_tokens or k <= 0:
            return []
        items = list(self._radix.items())
        items.sort(
            key=lambda kv: (
                kv[1].depth * (1 + kv[1].hits), kv[1].depth,
            ),
            reverse=True,
        )
        return [(key, n.depth, n.hits) for key, n in items[:k]]

    def adopt_cached(self, keys: list[bytes]) -> list[tuple[int, int]]:
        """Adopt EXTERNALLY prefilled prefix pages into the cache — the
        disaggregated-serving landing path (``runtime/disagg``): for
        every key not already resident, take a pool page, register it
        under its content key and park it rc=0 in the LRU (newest), so
        the next admission whose prompt hashes to these keys shares
        them exactly like locally computed prefix pages (evictable
        under pressure by the usual rules until then). Returns
        ``[(ordinal, page)]`` for the keys actually adopted — the
        caller scatters ONLY those ordinals' K/V (already-resident keys
        dedupe against the cache; first writer won). Returns ``[]``
        with nothing taken when the pool cannot cover the new pages
        all-or-nothing (the caller falls back to a collocated
        prefill — adoption is an optimization, never a correctness
        gate)."""
        fresh = [
            (i, k) for i, k in enumerate(keys) if k not in self._by_key
        ]
        if not fresh or not self.can_alloc(len(fresh)):
            return []
        out = []
        for i, key in fresh:
            page = self._take_one()
            self._by_key[key] = page
            self._key_of[page] = key
            self._radix_add(key)
            self._lru[page] = None  # rc=0, resident, newest
            out.append((i, page))
        return out

    def evict_cached(self, n: int | None = None) -> int:
        """Evict up to ``n`` (default: all) COLD prefix-cache pages —
        rc=0 LRU residents, oldest first — back to the free list,
        dropping their content keys. The degradation ladder's sweep
        rung (``runtime/scheduler``): capacity-NEUTRAL by construction
        (``can_alloc`` already counts the LRU and ``alloc`` evicts on
        demand), it trades the cache's speculative prefix-hit value
        for the allocator's free-list fast path under overload. Live
        (rc>0) pages are untouched; the pool partition (used + free +
        cached) is conserved. Returns the count evicted."""
        evicted = 0
        while self._lru and (n is None or evicted < n):
            page, _ = self._lru.popitem(last=False)
            key = self._key_of.pop(page)
            del self._by_key[key]
            self._radix_drop(key)
            if self.evict_hook is not None:
                self.evict_hook(page, key)
            self._free.append(page)
            evicted += 1
        return evicted

    def resident(self, key: bytes) -> bool:
        """True when ``key``'s page is in the pool (owned or cached) —
        the no-accounting residency probe the host-tier readmit path
        uses BEFORE ``lookup_share`` (which counts a hit or miss)."""
        return key in self._by_key

    def cached_pages(self) -> list[tuple[int, bytes]]:
        """The rc=0 prefix-cache residents with their content keys,
        oldest (next-evicted) first — the proactive spill sweep's
        working set. Spill candidates come ONLY from here: a page
        referenced by a live slot (rc > 0) never appears, which is
        what keeps lossy host-tier codecs away from live decode
        state."""
        return [(p, self._key_of[p]) for p in self._lru]

    def register(self, page: int, key: bytes) -> None:
        """Publish ``page`` (currently owned, rc>=1) as the cache entry
        for ``key``. First writer wins; a page may carry one key."""
        if key in self._by_key or page in self._key_of:
            return
        self._by_key[key] = page
        self._key_of[page] = key
        self._radix_add(key)

    def stats(self) -> PagerStats:
        # list(...) snapshots the dict at C speed: stats() is now also
        # read from exporter scrape threads (the memory collector in
        # utils.profiling) while the ticking thread mutates _rc, and a
        # generator over live .values() could raise "dict changed size
        # during iteration" mid-scrape.
        return PagerStats(
            num_pages=self.num_pages,
            free=len(self._free) + len(self._lru),
            in_use=sum(1 for r in list(self._rc.values()) if r > 0),
            cached=len(self._lru),
            prefix_hits=self.prefix_hits,
            prefix_misses=self.prefix_misses,
            prefix_capacity_skips=self.prefix_capacity_skips,
            radix_nodes=len(self._radix),
            radix_partial_hits=self.radix_partial_hits,
            radix_hit_tokens=self.radix_hit_tokens,
            cow_forks=self.cow_forks,
        )


@dataclasses.dataclass
class HostTierStats:
    pages: int  # host-resident pages (warm + cold; disk excluded)
    warm: int
    cold: int
    disk: int  # pages persisted to the optional disk tier
    host_bytes: int  # encoded bytes resident in host memory
    spilled: int  # lifetime pages accepted by put()
    dropped: int  # lifetime pages that fell off the cold end
    codec_bytes_saved: int  # lifetime raw - encoded bytes


@dataclasses.dataclass
class _HostPage:
    """One spilled page: per block, a tuple of ``(payload, meta)``
    encoded planes in the pool's own order (one fused plane for native
    pools, ``(values, k_scales, v_scales)`` for quantized ones)."""

    blocks: list
    nbytes: int  # encoded bytes (payload sum)
    raw_nbytes: int


class HostKVTier:
    """The host-DRAM (optionally disk-backed) spill tier under the
    :class:`Pager` — ROADMAP item 3's "cache tiers below the Pager".

    Pages evicted from the HBM prefix LRU land here under the SAME
    content keys the admission probe computes, encoded by the
    ``ops.quantize`` page codec stack: the WARM sub-tier keeps a
    lossless codec (readmits are bit-exact), pages demoted past the
    warm capacity re-encode with the COLD codec (lossy allowed —
    every page here is rc=0 by construction, never referenced by a
    live slot), and pages past the total host capacity either persist
    to ``disk_dir`` or drop (counted). ``get`` decodes a page back to
    its pool-shaped host arrays for the readmit landing path
    (``ContinuousBatcher._maybe_readmit`` -> ``Pager.adopt_cached``
    -> ``_adopt_pages``).

    Plain-python bookkeeping like the Pager itself — no jax, no
    metrics registry (the batcher bridges the books to ``cache_tier.*``
    counters and ``memory.host_bytes`` / ``memory.pages_spilled``
    gauges); thread-safety follows the pager's model (mutations on the
    ticking thread, ``stats()`` tolerant of racing reads)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._warm: collections.OrderedDict[bytes, _HostPage] = (
            collections.OrderedDict()
        )
        self._cold: collections.OrderedDict[bytes, _HostPage] = (
            collections.OrderedDict()
        )
        #: key -> (path, blocks-meta) for disk-persisted pages.
        self._disk: dict[bytes, tuple[str, list]] = {}
        self._bytes = 0
        self.spilled = 0
        self.dropped = 0
        self.codec_bytes_saved = 0
        if cfg.disk_dir:
            os.makedirs(cfg.disk_dir, exist_ok=True)

    # -- encoding ----------------------------------------------------------

    @staticmethod
    def _encode(blocks, codec: str) -> _HostPage:
        from adapt_tpu.ops.quantize import encode_page

        enc, nbytes, raw = [], 0, 0
        from adapt_tpu.ops.paged_attention import pool_planes

        for block in blocks:
            out = []
            for plane in pool_planes(block):
                payload, meta = encode_page(np.asarray(plane), codec)
                nbytes += len(payload)
                raw += meta["raw_nbytes"]
                out.append((payload, meta))
            enc.append(tuple(out))
        return _HostPage(blocks=enc, nbytes=nbytes, raw_nbytes=raw)

    @staticmethod
    def _decode(entry: _HostPage) -> list:
        from adapt_tpu.ops.quantize import decode_page

        blocks = []
        for enc in entry.blocks:
            planes = [decode_page(p, m) for p, m in enc]
            blocks.append(planes[0] if len(planes) == 1 else tuple(planes))
        return blocks

    def _book(self, entry: _HostPage, sign: int) -> None:
        self._bytes += sign * entry.nbytes

    # -- the tier API ------------------------------------------------------

    def contains(self, key: bytes) -> bool:
        return (
            key in self._warm or key in self._cold or key in self._disk
        )

    def put(self, key: bytes, blocks) -> tuple[int, int]:
        """Spill one page (per block, the pool's host planes, shapes
        ``(kvh, page, w)``) into the WARM sub-tier under its
        content key. Idempotent for resident keys (MRU touch only).
        Returns ``(raw_bytes, encoded_bytes)`` for the caller's
        accounting."""
        if self.contains(key):
            if key in self._warm:
                self._warm.move_to_end(key)
            return (0, 0)
        entry = self._encode(blocks, self.cfg.warm_codec)
        self._warm[key] = entry
        self._book(entry, +1)
        self.spilled += 1
        self.codec_bytes_saved += entry.raw_nbytes - entry.nbytes
        self._demote()
        return (entry.raw_nbytes, entry.nbytes)

    def _demote(self) -> None:
        """Warm overflow -> COLD (re-encode through the cold codec:
        warm is lossless, so the cold payload is exactly what a
        direct cold-encode of the original would hold); cold overflow
        -> disk when configured, else dropped (counted)."""
        cold_cap = self.cfg.host_capacity_pages - self.cfg.warm_capacity_pages
        while len(self._warm) > self.cfg.warm_capacity_pages:
            key, entry = self._warm.popitem(last=False)
            self._book(entry, -1)
            if cold_cap <= 0:
                self._overflow(key, entry)
                continue
            cold = (
                entry
                if self.cfg.cold_codec == self.cfg.warm_codec
                else self._encode(self._decode(entry), self.cfg.cold_codec)
            )
            if cold is not entry:
                self.codec_bytes_saved += entry.nbytes - cold.nbytes
            self._cold[key] = cold
            self._book(cold, +1)
        while (
            len(self._cold) > max(cold_cap, 0) and self._cold
        ):
            key, entry = self._cold.popitem(last=False)
            self._book(entry, -1)
            self._overflow(key, entry)

    def _overflow(self, key: bytes, entry: _HostPage) -> None:
        if not self.cfg.disk_dir:
            self.dropped += 1
            return
        import hashlib
        import pickle

        path = os.path.join(
            self.cfg.disk_dir,
            hashlib.sha256(key).hexdigest()[:32] + ".kvpage",
        )
        with open(path, "wb") as f:
            pickle.dump(entry, f)
        self._disk[key] = (path, None)

    def get(self, key: bytes):
        """Decoded per-block host planes for ``key`` (the pool's own
        structure, as :meth:`put` took them), or None. MRU-touches the
        entry (it stays host-resident after a
        readmit: the HBM copy is rc=0 evictable and may bounce right
        back)."""
        entry = self._warm.get(key)
        if entry is not None:
            self._warm.move_to_end(key)
            return self._decode(entry)
        entry = self._cold.get(key)
        if entry is not None:
            self._cold.move_to_end(key)
            return self._decode(entry)
        disk = self._disk.get(key)
        if disk is not None:
            import pickle

            try:
                with open(disk[0], "rb") as f:
                    entry = pickle.load(f)
            except OSError:
                del self._disk[key]
                return None
            return self._decode(entry)
        return None

    @property
    def pages(self) -> int:
        return len(self._warm) + len(self._cold)

    @property
    def host_bytes(self) -> int:
        return self._bytes

    def stats(self) -> HostTierStats:
        return HostTierStats(
            pages=self.pages,
            warm=len(self._warm),
            cold=len(self._cold),
            disk=len(self._disk),
            host_bytes=self._bytes,
            spilled=self.spilled,
            dropped=self.dropped,
            codec_bytes_saved=self.codec_bytes_saved,
        )


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The blocks of a model that share ONE pool geometry and ONE page
    table: same ``(window, kv_heads, head_dim, row)``. A window group
    recycles behind its window; a full group keeps the whole request."""

    name: str  # "full", "window", or "<kind><n>" where a kind repeats
    window: int | None
    kv_heads: int
    head_dim: int
    blocks: tuple[int, ...]  # indices into the model's block list
    #: A LATENT group (``BlockSpec.cache_row``): a position stores one
    #: row this wide, with no head axis and no K|V halves; ``kv_heads``
    #: and ``head_dim`` then say nothing of the pool. None: K and V a
    #: KV head.
    row: int | None = None
    #: A SELECTING latent group (``BlockSpec.cache_index_row``): a
    #: position also stores one index key this wide, in a second plane
    #: the same page table addresses. None: one plane.
    index_row: int | None = None

    @property
    def position_values(self) -> int:
        """Values ONE position stores in a block's pool."""
        if self.row:
            return self.row + (self.index_row or 0)
        return 2 * self.kv_heads * self.head_dim


def cache_groups(specs) -> list[CacheGroup]:
    """Group a model's blocks (their ``BlockSpec``) by cache geometry,
    in order of first appearance. One group: every block alike, which
    is every model before per-layer patterns. A block that keeps NO
    pages (``BlockSpec.linear`` set: linear attention, a recurrent
    state in their place) belongs to no group and gets no pool."""
    keys: dict[tuple, list[int]] = {}
    for i, spec in enumerate(specs):
        if spec.linear is not None:
            continue
        keys.setdefault(
            (spec.window, spec.cache_heads, spec.attn_head_dim,
             spec.cache_row, spec.cache_index_row), []
        ).append(i)
    kinds = ["full" if k[0] is None else "window" for k in keys]
    out = []
    for n, (key, blocks) in enumerate(keys.items()):
        kind = kinds[n]
        if kinds.count(kind) > 1:
            kind += str(kinds[:n].count(kind))
        out.append(CacheGroup(
            kind, *key[:3], tuple(blocks), row=key[3], index_row=key[4]
        ))
    return out


#: What a feature may need of a model's cache (``CacheLayout.lacks``),
#: in the order a refusal names them.
CACHE_PROPERTIES = (
    "one_group", "pages_only", "per_head_pages", "one_plane",
)


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """What a model's blocks keep for a request (:func:`cache_layout`):
    the one description the batcher sizes its pools from and refuses
    features by."""

    groups: tuple[CacheGroup, ...]  # the one that reserves whole first
    group_of: tuple[int, ...]  # a block's group (0: it keeps no pages)
    #: Blocks with a recurrent ``(state, tail)`` a SLOT, beside their
    #: pages (``BlockSpec.ssm``) or in place of them (``.linear``).
    state_blocks: tuple[int, ...]
    latent_blocks: tuple[int, ...]  # one row a position, no head axis
    #: Latent blocks that SELECT what they read: an index key a
    #: position in a second plane beside the row.
    selecting_blocks: tuple[int, ...] = ()

    def lacks(self, prop: str) -> tuple[str, str] | None:
        """None where this cache has ``prop`` (of ``CACHE_PROPERTIES``),
        else what it has in its place, and the detail a refusal adds."""
        if prop == "one_group" and len(self.groups) > 1:
            # A shared or moved prompt page would need every window
            # layer's last positions beside it.
            return (
                f"a cache in {len(self.groups)} layer groups",
                ", ".join(g.name for g in self.groups),
            )
        if prop == "pages_only" and self.state_blocks:
            # Pages without the state of the same position: half a cache.
            return "recurrent state", (
                f"{len(self.state_blocks)} blocks keep a mixer's state a "
                "slot, beside their pages or in place of them"
            )
        if prop == "per_head_pages" and self.latent_blocks:
            # No head axis and no K|V halves to shard, move or re-encode.
            row = self.groups[self.group_of[self.latent_blocks[0]]].row
            return "a latent cache", (
                f"{len(self.latent_blocks)} blocks keep one {row}-value "
                "row a position, no head axis"
            )
        if prop == "one_plane" and self.selecting_blocks:
            # A page shared, moved or verified is its rows AND the index
            # keys of the same positions; nothing moves the pair yet.
            group = self.groups[self.group_of[self.selecting_blocks[0]]]
            return "a selecting cache", (
                f"{len(self.selecting_blocks)} blocks keep a "
                f"{group.index_row}-value index key a position in a second "
                "plane and attend the positions it picks"
            )
        return None


def cache_layout(specs) -> CacheLayout:
    """The layout of a model's blocks (their ``BlockSpec``); the group
    that reserves whole, the full-attention one if any, comes first."""
    groups = sorted(cache_groups(specs), key=lambda g: g.window is not None)
    group_of = [0] * len(specs)
    for gi, g in enumerate(groups):
        for bi in g.blocks:
            group_of[bi] = gi
    return CacheLayout(
        tuple(groups), tuple(group_of),
        tuple(i for i, sp in enumerate(specs) if sp.state_spec is not None),
        tuple(i for i, sp in enumerate(specs) if sp.latent is not None),
        tuple(
            i for i, sp in enumerate(specs)
            if sp.cache_index_row is not None
        ),
    )


def window_hold_pages(
    window: int, page_size: int, chunk: int, prefill_chunk: int | None
) -> int:
    """The most pages of ONE request a window layer holds at a time:
    a span of L consecutive positions touches at most
    ``ceil((L - 1) / page) + 1`` pages, and a decode scan of ``chunk``
    steps touches the window behind its first write through its last
    write (L = window + chunk - 1); a chunked-prefill pass starts on a
    page edge, so it touches the window behind it plus its own pages.
    Grant and release are taken from ONE position, the one the pass
    starts at ON THE DEVICE (``ContinuousBatcher._dispatched_pos``: a
    chunk past the committed one while a tick is in flight), so the
    overlapped tick order holds what the synchronous one holds, one
    chunk further on."""
    hold = -(-(window + chunk - 2) // page_size) + 1
    if prefill_chunk is not None:
        hold = max(
            hold, -(-(window - 1) // page_size) + prefill_chunk // page_size
        )
    return hold


def group_pool_pages(
    group: CacheGroup, slots: int, pages_per_slot: int, page_size: int,
    chunk: int, prefill_chunk: int | None,
) -> int:
    """Pool of a group that grants pages pass by pass (``Pager.hold``):
    every slot's widest hold, plus the trash page."""
    hold = pages_per_slot
    if group.window is not None:
        hold = min(hold, window_hold_pages(
            group.window, page_size, chunk, prefill_chunk
        ))
    return slots * hold + 1


def kv_value_width(head_dim: int, kv_cache_dtype: str) -> int:
    """Lanes ONE cached vector takes in a pool's value plane:
    ``head_dim``, halved for int4 (two nibbles packed per int8 lane —
    which needs an even ``head_dim``). A row of the plane is two of
    them, K then V."""
    if kv_cache_dtype != "int4":
        return head_dim
    if head_dim % 2:
        raise ValueError(
            f"kv_cache_dtype='int4' packs two nibbles per int8 lane "
            f"and needs an even head_dim, got {head_dim}"
        )
    return head_dim // 2


def alloc_kv_pools(
    pool_pages: int,
    kv_heads: int,
    page_size: int,
    head_dim: int,
    dtype,
    kv_cache_dtype: str = "native",
    row: int | None = None,
    index_row: int | None = None,
):
    """One decoder block's zeroed page pool — THE definition of what a
    pool is. ``row`` (a latent-attention block, ``CacheGroup.row``):
    ONE plane ``(pool_pages, row, page_size)`` of the block's ``dtype``
    — ``row`` values are what one position stores (``[c_kv | k_r]``),
    whole: there are no K|V halves and there is no head axis, a
    page's positions lie on the minor axis (where a TPU puts them for
    a row that does not fill whole lane tiles), and every consumer
    knows the format by the plane's three dimensions
    (``ops/latent_attention``). With ``index_row`` (a SELECTING latent
    block, ``CacheGroup.index_row``) the pool is the PAIR ``(rows,
    index keys)``: a second plane ``(pool_pages, index_row,
    page_size)`` of the same format, one index key a position, which
    the same page table addresses (one grant and one free a page, as
    the quantized pool's scale planes): the score pass streams it and
    never the rows (``ops/sparse_latent_attention``). Otherwise:

    A position's K and V live side by side on the lanes of ONE
    row: lanes ``[0, w)`` hold K, lanes ``[w, 2w)`` V, ``w`` =
    :func:`kv_value_width`. Native: one ``(pool_pages, kv_heads,
    page_size, 2 * head_dim)`` plane of the block's ``dtype`` — at
    head_dim 64 a row is exactly one 128-lane tile, so the plane lives
    row-major on a TPU, the per-token write is one in-place scatter and
    the kernels read it as it lives (``ops/paged_attention``:
    ``append_kv_paged``, ``_attend_fused``). Quantized (``"int8"`` /
    ``"int4"``): a ``(values, k_scales, v_scales)`` triple — the int8
    value plane fused the same way, plus one float32 scale per cached
    vector, ``(pool_pages, kv_heads, page_size, 1)`` for K and for V,
    page-addressed by the SAME table, so a shared page always carries
    the scales its values were written with. Every consumer reads the
    format off the operand (tuple or not, the last dimension); the
    KV-head axis is dim 1 of every plane, which is what tensor
    parallelism shards."""
    if row is not None:
        if kv_cache_dtype != "native":
            raise NotImplementedError(
                f"kv_cache_dtype={kv_cache_dtype!r}: a latent pool is not "
                "quantized (one scale a K or V vector has no meaning for "
                "a [c_kv | k_r] row)"
            )
        rows = jnp.zeros((pool_pages, row, page_size), dtype)
        if index_row is None:
            return rows
        return rows, jnp.zeros((pool_pages, index_row, page_size), dtype)
    width = kv_value_width(head_dim, kv_cache_dtype)
    plane = (pool_pages, kv_heads, page_size)
    if kv_cache_dtype == "native":
        return jnp.zeros(plane + (2 * head_dim,), dtype)
    return (
        jnp.zeros(plane + (2 * width,), jnp.int8),
        jnp.zeros(plane + (1,), jnp.float32),
        jnp.zeros(plane + (1,), jnp.float32),
    )


def pool_geometry(
    slots: int, max_len: int, page_size: int, slack: int = 0
) -> tuple[int, int]:
    """``(pages_per_slot, default_pool_pages)``: the page-table width
    that covers ``max_len`` positions plus ``slack`` (the speculative
    verify's overshoot: ``draft_k + tree_width``), and the worst-case
    pool — every slot's row full, plus the trash page."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    pages_per_slot = -(-(max_len + slack) // page_size)
    return pages_per_slot, slots * pages_per_slot + 1


@partial(jax.jit, donate_argnums=(0,))
def insert_prefill_pages(pool, pages, kv):
    """Scatter a prefilled request's contiguous (1, kv_h, S, w) rows —
    one plane of the pool's representation (``fuse_kv``): the fused K|V
    rows, or a scale column — into its physical ``pages`` ((n,) int32,
    logical order) of that plane. S pads up
    to n*page positions — pad columns hold zeros that sit beyond the
    prompt (masked until decode overwrites them). One scatter on the
    page axis; jit specializes per (n, S), both bucket-bounded. A
    latent pool ``(pages, row, page)`` takes (1, S, row) rows."""
    n = pages.shape[0]
    if pool.ndim == 3:
        from adapt_tpu.ops.latent_attention import rows_to_pages

        page = pool.shape[2]
        kvp = jnp.pad(kv[0], ((0, n * page - kv.shape[1]), (0, 0)))
        return pool.at[pages].set(rows_to_pages(kvp, page).astype(pool.dtype))
    _, kvh, page, hd = pool.shape
    s = kv.shape[2]
    kvp = jnp.pad(kv[0], ((0, 0), (0, n * page - s), (0, 0)))
    kvp = jnp.swapaxes(kvp.reshape(kvh, n, page, hd), 0, 1)
    return pool.at[pages].set(kvp.astype(pool.dtype))
