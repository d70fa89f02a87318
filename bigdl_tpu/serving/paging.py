"""Paged KV cache: one refcounted block pool under every KV surface.

A request that kept a full ``(cache_len, ...)`` KV row would bill a
32-token chat the same HBM as a document that fills ``cache_len``, and
a prefix hit would have to *copy* a retained row before the first
novel token is prefilled. BigDL's block-manager discipline (Dai et
al., 2018, arxiv 1804.05839) applied at page granularity avoids both:
the unit of KV storage is a fixed ``page_size``-token **page** of one
persistent ``(max_pages, page_size, ...)`` device buffer per layer, and
every KV surface of the serving engine is host-side bookkeeping over
page ids —

* ``PagePool`` — the allocator: a free list plus per-page reference
  counts over the device tree. Pages are claimed (``alloc``), shared
  (``share``: refcount bump, never a tensor copy — the zero-copy ethos
  of "RPC Considered Harmful", arxiv 1805.08430, applied intra-engine),
  and returned (``free``: a page is reusable only when its LAST
  reference drops).
* ``BlockTable`` — one request's view: the ordered page ids whose
  concatenation is its KV row. Token position ``i`` lives at offset
  ``i % page_size`` of page ``pages[i // page_size]``. ``fork`` shares
  every page copy-on-write; ``ensure_writable`` breaks a share with a
  single-page device copy only when a writer actually lands on a page
  someone else still references.
* ``PagedPrefixIndex`` — the prefix cache: a radix trie over token-id
  prefixes with LRU, pin and generation bookkeeping, whose entries hold
  pages. A donation SHARES the donor slot's pages into the entry, a hit
  SHARES the entry's aligned pages into the new request's table, and
  eviction / host-tier demotion are refcount moves plus — for demotion
  only — one bulk device→host spill per page.

Why shared pages are never written (the COW invariant the engine
maintains): the engine requires ``prefill_chunk % page_size == 0``, so
the chunk-aligned reuse boundary ``base`` is page-aligned — a hit
shares exactly the pages covering ``[0, base)`` and the first novel
write lands at ``base``, i.e. at offset 0 of a freshly allocated page.
Decode and speculative-verify writes land at positions ``>= prompt_len
> base`` for the same reason. ``ensure_writable`` therefore never fires
on the engine's own paths; it exists (and is tested) as the safety net
for future writers — n>1 completion forks — that DO write under a
share.

Two kinds of state (a model some of whose layers keep a fixed recurrent
state a sequence, ``models/hybrid.py``): the model's pool is then
``{"pages": ..., "lanes": ...}``, and everything here that treats a tree
as rows of pages reads ``page_leaves(pool)`` — the ``lanes`` sub-tree
(leaves led by the serving lane) is the engine's, one lane a slot. A
cached prefix of such a model is its pages AND the state at some
position: ``SnapshotStore`` is the refcounted allocator over a fixed
device store of lane-state copies, a ``PrefixEntry`` carries
``snaps`` ((position, snapshot id), each one reference), and a match is
only as long as the deepest snapshot at or under it (``match``).

Thread contract: the engine loop thread is the only mutator;
``stats()`` / ``snapshot()`` readers may race in from HTTP/debug
threads, so counters, the trie and the free list sit behind internal
locks. Lock order is strictly index → pool (``PagedPrefixIndex`` calls
``PagePool`` while holding its own lock; the pool never calls back), so
the two locks cannot deadlock.
"""

from __future__ import annotations

import threading
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

__all__ = ["PagePool", "BlockTable", "PagedPrefixIndex", "PrefixEntry",
           "PrefixMatch", "SnapshotStore", "SCRATCH_PAGE", "page_leaves",
           "lane_leaves", "with_pages", "with_lanes"]


def _two_kinds(pool) -> bool:
    return isinstance(pool, dict) and "lanes" in pool


def page_leaves(pool):
    """The sub-tree of ``pool`` whose leaves lead with pages: all of it,
    or the ``"pages"`` half of a pool that also holds lane state."""
    return pool["pages"] if _two_kinds(pool) else pool


def lane_leaves(pool):
    """The sub-tree whose leaves lead with the serving lane; None for a
    pool that is pages alone."""
    return pool["lanes"] if _two_kinds(pool) else None


def with_pages(pool, pages):
    return {**pool, "pages": pages} if _two_kinds(pool) else pages


def with_lanes(pool, lanes):
    return {**pool, "lanes": lanes}

#: page id 0 is never allocated: it is the write sink for idle dispatch
#: lanes (an all-zero block table routes their junk KV writes here) and
#: the padding value of every device block-table array, so a gather
#: through padding reads initialized — if garbage — memory that the
#: causal mask then discards.
SCRATCH_PAGE = 0


class _RefCounts:
    """The refcounted free list under ``PagePool`` and ``SnapshotStore``:
    ids ``first..n-1`` are handed out LIFO with one reference, gain
    references by ``share`` and return to the list when ``free`` drops the
    last one. Sharing or freeing a free id is a bookkeeping bug and fails
    loudly; ``noun`` names the id in those errors. ``allocated``,
    ``shared`` and ``freed`` are cumulative, so ``allocated - freed`` ids
    are out at all times."""

    noun = "id"

    def __init__(self, n: int, first: int):
        # LIFO: recently freed ids are re-issued first, so a churning
        # workload keeps touching the same HBM region
        self._free: List[int] = list(range(n - 1, first - 1, -1))
        self._refs = np.zeros(n, np.int32)
        self._lock = threading.Lock()
        self.allocated = 0
        self.shared = 0
        self.freed = 0

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` free ids (refcount 1 each), all-or-nothing:
        ``None`` when fewer than ``n`` are free, so a caller never
        holds a partial reservation it must unwind."""
        if n < 0:
            raise ValueError(f"alloc(n={n})")
        with self._lock:
            if len(self._free) < n:
                return None
            ids = [self._free.pop() for _ in range(n)]
            for i in ids:
                self._refs[i] = 1
            self.allocated += n
            return ids

    def share(self, ids: Sequence[int]) -> None:
        """Add one reference to each id — the whole of what a prefix
        hit or a table fork costs."""
        with self._lock:
            for i in ids:
                if self._refs[i] <= 0:
                    raise RuntimeError(
                        f"share() of free {self.noun} {i}")
                self._refs[i] += 1
            self.shared += len(ids)

    def free(self, ids: Sequence[int]) -> None:
        """Drop one reference from each id; it returns to the free
        list only when its last reference drops."""
        with self._lock:
            for i in ids:
                if self._refs[i] <= 0:
                    raise RuntimeError(
                        f"free() of free {self.noun} {i}")
                self._refs[i] -= 1
                if self._refs[i] == 0:
                    self._free.append(i)
                    self.freed += 1

    def refcount(self, i: int) -> int:
        with self._lock:
            return int(self._refs[i])


class PagePool(_RefCounts):
    """Refcounted block allocator over one persistent device KV tree.

    ``buffers`` is ``model.init_page_pool(max_pages, page_size, ...)``
    — a per-layer tuple of ``(k, v)`` (or quantized ``(k_q, v_q,
    k_scale, v_scale)``) arrays whose leading dim indexes pages (what
    lies behind it is the attention layer's business); the pool never
    touches device memory itself, it only decides which page ids are
    live. The engine rebinds ``buffers`` after every donating dispatch
    (decode/prefill writes, COW copies).

    Counters are cumulative and monotonic (the engine publishes them as
    the ``bigdl_serving_page_*_total`` instruments): ``allocated`` =
    pages handed out by ``alloc``, ``shared`` = reference bumps from
    ``share``, ``cow_forks`` = shares broken by ``ensure_writable``,
    ``freed`` = pages whose last reference dropped (so
    ``allocated - freed == pages_in_use`` at all times).
    """

    noun = "page"

    def __init__(self, buffers, page_size: int):
        import jax

        # a pool that also holds lane state: its pages half alone
        leaves = jax.tree_util.tree_leaves(page_leaves(buffers))
        if not leaves:
            raise ValueError("PagePool needs a non-empty buffer tree")
        max_pages = int(leaves[0].shape[0])
        if max_pages < 2:
            raise ValueError(
                f"max_pages must be >= 2 (page 0 is the reserved "
                f"scratch page), got {max_pages}")
        if page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {page_size}")
        self.buffers = buffers
        self.max_pages = max_pages
        self.page_size = int(page_size)
        #: device bytes one page owns across every layer's buffers
        #: (scale sidecars included) — the billing unit
        self.page_bytes = sum(int(l.nbytes) for l in leaves) // max_pages
        super().__init__(max_pages, first=1)   # page 0 is scratch
        self.cow_forks = 0

    def note_cow_fork(self) -> None:
        with self._lock:
            self.cow_forks += 1

    # ------------------------------------------------------------ views
    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return self.max_pages - 1 - len(self._free)

    @property
    def capacity_bytes(self) -> int:
        # graftlint: ok[lock-discipline] — max_pages and page_bytes are immutable after __init__
        return self.max_pages * self.page_bytes

    @property
    def bytes_in_use(self) -> int:
        # graftlint: ok[lock-discipline] — page_bytes is immutable after __init__ (pages_in_use takes the lock)
        return self.pages_in_use * self.page_bytes

    def holder_bytes(self, pages: Sequence[int]) -> float:
        """One holder's pro-rata device footprint: each held page's
        bytes divided by its CURRENT refcount, so a page shared by
        ``r`` holders bills ``1/r`` to each and the sum over all
        holders of a page is exactly its bytes — the conservation
        property the usage ledger's paged KV billing rests on."""
        with self._lock:
            total = 0.0
            for p in pages:
                r = int(self._refs[p])
                if r > 0:
                    total += self.page_bytes / r
            return total

    def stats(self) -> dict:
        with self._lock:
            in_use = self.max_pages - 1 - len(self._free)
            return {
                "max_pages": self.max_pages,
                "page_size": self.page_size,
                "page_bytes": self.page_bytes,
                "pages_in_use": in_use,
                "free_pages": len(self._free),
                "bytes_in_use": in_use * self.page_bytes,
                "capacity_bytes": self.max_pages * self.page_bytes,
                "allocated_total": self.allocated,
                "shared_total": self.shared,
                "cow_forks_total": self.cow_forks,
                "freed_total": self.freed,
            }


class BlockTable:
    """One request's ordered view of pool pages: position ``i`` lives
    at offset ``i % page_size`` of ``pages[i // page_size]``. The table
    owns one reference per listed page; ``free()`` (or the engine's
    release path) drops them all."""

    __slots__ = ("pool", "pages")

    def __init__(self, pool: PagePool, pages: List[int]):
        self.pool = pool
        self.pages = pages

    @classmethod
    def build(cls, pool: PagePool, shared: Sequence[int],
              n_fresh: int) -> Optional["BlockTable"]:
        """Assemble a table from a shared prefix head plus ``n_fresh``
        newly allocated pages, atomically: on allocation failure the
        shared references are never taken and ``None`` comes back, so
        the caller (the engine's admission path) can reclaim and
        retry without unwinding anything."""
        fresh = pool.alloc(n_fresh)
        if fresh is None:
            return None
        pool.share(shared)
        return cls(pool, list(shared) + fresh)

    def __len__(self) -> int:
        return len(self.pages)

    def fork(self) -> "BlockTable":
        """Copy-on-write clone: every page shared, nothing copied —
        the n>1-completions primitive."""
        self.pool.share(self.pages)
        return BlockTable(self.pool, list(self.pages))

    def ensure_writable(self, idx: int,
                        copy_page: Callable[[int, int], None]) -> bool:
        """Break the share on ``pages[idx]`` before a write: when the
        page's refcount is > 1, allocate a fresh page, have the caller
        copy the old page's device contents into it (``copy_page(dst,
        src)`` — one jitted single-page copy), and swap the table over
        to the private copy. Returns True when a COW copy happened.
        Raises when the pool is exhausted — the engine reserves a
        request's full span at admission precisely so this cannot
        trigger mid-flight."""
        page = self.pages[idx]
        if self.pool.refcount(page) <= 1:
            return False
        fresh = self.pool.alloc(1)
        if fresh is None:
            raise RuntimeError(
                "ensure_writable: pool exhausted mid-COW")
        copy_page(fresh[0], page)
        self.pool.free([page])
        self.pages[idx] = fresh[0]
        self.pool.note_cow_fork()
        return True

    def covering(self, n_tokens: int) -> Tuple[int, ...]:
        """The page ids holding positions ``[0, n_tokens)``."""
        ps = self.pool.page_size
        return tuple(self.pages[: -(-int(n_tokens) // ps)])

    def as_array(self, table_len: int) -> np.ndarray:
        """Fixed-shape device-dispatch form: the page ids padded to
        ``table_len`` with the scratch page, so compiled shapes depend
        only on the pool geometry, never on this request's length."""
        out = np.full(table_len, SCRATCH_PAGE, np.int32)
        out[: len(self.pages)] = self.pages
        return out

    def free(self) -> None:
        self.pool.free(self.pages)
        self.pages = []


class SnapshotStore(_RefCounts):
    """Refcounted allocator over a fixed device store of lane-state
    copies (the engine owns the arrays; this decides which ids are
    live). Holders are requests in flight (the snapshot they resumed
    from and those their prefill took) and prefix entries; a snapshot
    returns to the free list when its last reference drops.
    ``last_used`` is stamped when a snapshot is taken and when one is
    restored: what nothing resumes from ages out first
    (``PagedPrefixIndex.reclaim_snapshot``)."""

    noun = "snapshot"

    def __init__(self, capacity: int, snapshot_bytes: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.snapshot_bytes = int(snapshot_bytes)
        super().__init__(self.capacity, first=0)
        self._used = np.zeros(capacity, np.int64)
        self._stamp = 0
        # cumulative flow (snapshots taken are ``allocated``)
        self.restored = 0
        self.skipped = 0

    def take(self) -> Optional[int]:
        """A free snapshot id with one reference, stamped as just used;
        None when the store is full."""
        got = self.alloc(1)
        if got is None:
            return None
        with self._lock:
            self._stamp += 1
            self._used[got[0]] = self._stamp
        return got[0]

    def touch(self, sid: int) -> None:
        """A restore read ``sid``."""
        with self._lock:
            self._stamp += 1
            self._used[sid] = self._stamp
            self.restored += 1

    def note_skipped(self) -> None:
        with self._lock:
            self.skipped += 1

    def last_used(self, sid: int) -> int:
        with self._lock:
            return int(self._used[sid])

    @property
    def in_use(self) -> int:
        with self._lock:
            return self.capacity - len(self._free)

    def stats(self) -> dict:
        with self._lock:
            used = self.capacity - len(self._free)
            return {"snapshot_capacity": self.capacity,
                    "snapshots_in_use": used,
                    "snapshot_bytes": self.snapshot_bytes,
                    "bytes_in_use": used * self.snapshot_bytes,
                    "taken_total": self.allocated,
                    "restored_total": self.restored,
                    "skipped_total": self.skipped,
                    "freed_total": self.freed}


class PrefixMatch(NamedTuple):
    """The best cached prefix, how many of its tokens an admission may
    skip (``length``), and how many it shares (``matched``). The two
    differ only for a model with lane state, where ``length`` is the
    deepest snapshot at or under ``matched`` (and under the prompt's
    last position, which is always computed)."""

    entry: Optional["PrefixEntry"]
    length: int
    matched: int


class PrefixEntry:
    """One retained prefix: ``tokens`` (the exact token ids whose KV
    ``pages`` hold, positions ``0..length-1``, in position order) and
    the LRU/ref-count bookkeeping. ``snaps`` (a model with lane state
    only) are the ``(position, snapshot id)`` pairs, ascending, at which
    the recurrent state of this prefix is kept; the entry holds one
    reference on each. ``tier`` says where the KV currently
    lives: ``"device"`` (``pages`` of the pool) or ``"host"``
    (``host_buf``, one engine-opaque pinned host buffer per page;
    ``pages`` is empty while demoted so stale use shares nothing)."""

    __slots__ = ("tokens", "pages", "refs", "last_used", "hits", "tier",
                 "host_buf", "snaps")

    def __init__(self, tokens: np.ndarray, pages: Tuple[int, ...],
                 stamp: int, snaps: Tuple[Tuple[int, int], ...] = ()):
        self.tokens = tokens
        self.pages = pages
        self.snaps = snaps
        self.refs = 0
        self.last_used = stamp
        self.hits = 0
        self.tier = "device"
        self.host_buf = None

    @property
    def length(self) -> int:
        return int(self.tokens.shape[0])

    def resume_at(self, limit: int) -> Tuple[int, Optional[int]]:
        """The deepest ``(position, snapshot id)`` at or under ``limit``;
        ``(0, None)`` where none stands."""
        best = (0, None)
        for pos, sid in self.snaps:
            if best[0] < pos <= limit:
                best = (pos, sid)
        return best

    def __repr__(self):
        return (f"PrefixEntry(len={self.length}, "
                f"pages={len(self.pages)}, tier={self.tier}, "
                f"refs={self.refs}, hits={self.hits})")


class _Node:
    """Radix-trie node: edge-compressed children keyed by first token;
    ``entry`` marks a retained prefix ending exactly here."""

    __slots__ = ("children", "entry")

    def __init__(self):
        # first_token -> (edge_tokens np.ndarray, child _Node)
        self.children: Dict[int, Tuple[np.ndarray, "_Node"]] = {}
        self.entry: Optional[PrefixEntry] = None


def _common_len(a: np.ndarray, b: np.ndarray) -> int:
    n = min(a.shape[0], b.shape[0])
    if n == 0:
        return 0
    neq = np.flatnonzero(a[:n] != b[:n])
    return int(neq[0]) if neq.size else n


class PagedPrefixIndex:
    """Radix-trie index over token-id prefixes → retained pool pages.

    Real serving traffic is prefix-heavy: system prompts, few-shot
    templates and multi-turn conversations share long identical prompt
    heads, and recomputing those heads through chunked prefill makes
    TTFT scale with FULL prompt length. A new request whose prompt
    shares a cached prefix shares the entry's aligned pages and
    chunk-prefills only the novel tail. Correctness of reuse rests on
    KV causality — the KV at position ``i`` depends only on tokens
    ``0..i`` — so any entry sharing the first ``m`` tokens with a
    prompt yields ``m`` valid positions, even when the entry diverges
    afterwards (partial match) or extends past the prompt (truncated
    match).

    Pure HOST bookkeeping; storage motion is refcounts:

    * ``match(prompt)`` → best ``(entry, length, matched)``; the engine
      consumes a hit as ``entry.pages[: base // page_size]`` via
      ``PagePool.share`` and commits it with ``record_hit``.
    * ``donate_pages(tokens, pages)`` — a finished/preempted slot's
      covering pages are SHARED into a new entry.
    * ``reclaim(n_pages, spill)`` — eviction under allocation pressure:
      LRU unpinned entries drop their page references until the pool
      can satisfy the allocation. With a host budget and a ``spill``
      callback the victim DEMOTES instead: its pages are bulk-copied to
      pinned host buffers (one per page, outside the index lock) and
      the entry stays in the trie as a host-tier resident. The total
      retained prefix set thus scales with host RAM, not HBM.
    * ``promote_pages(entry, pages)`` — the engine has allocated fresh
      pages and device_put the host buffers back; the entry flips to
      device tier.
    * ``acquire`` / ``release`` / ``pin_covering`` pin an entry in
      WHATEVER tier it occupies: a pinned entry is never evicted,
      demoted or host-evicted.

    ``max_entries`` caps the entry count (0 disables the cache; the
    page pool bounds the bytes), ``host_pages`` is the host tier's page
    budget (0 disables the tier: eviction drops). Every structural
    change (insert / evict / demote / host-evict / promote) bumps
    ``generation``, so a caller can validate a cached ``lookup`` result
    before acting on it.
    """

    def __init__(self, pool: PagePool, *, max_entries: int,
                 min_tokens: int = 1, token_bytes: float = 0.0,
                 devices: int = 1, host_pages: int = 0,
                 snapshots: Optional[SnapshotStore] = None):
        if snapshots is not None and host_pages > 0:
            raise ValueError(
                "a prefix index whose entries hold state snapshots has "
                "no host tier: a demoted entry's pages would come back "
                "without the state that belongs to them")
        if max_entries < 0:
            raise ValueError(
                f"max_entries must be >= 0, got {max_entries}")
        if host_pages < 0:
            raise ValueError(
                f"host_pages must be >= 0, got {host_pages}")
        if min_tokens < 1:
            raise ValueError(
                f"min_tokens must be >= 1, got {min_tokens}")
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        self.pool = pool
        self.max_entries = int(max_entries)
        #: devices the pool's pages are sharded across (the serving
        #: mesh's model-axis size; 1 unsharded) — ``stats()`` derives
        #: the per-device share one chip's HBM pays
        self.devices = int(devices)
        #: prefixes shorter than this are never matched or donated —
        #: a few shared tokens are not worth an entry
        self.min_tokens = min_tokens
        #: device KV bytes one cached token position occupies; the
        #: exchange rate behind the ``bytes_saved`` savings credit
        self.token_bytes = float(token_bytes)
        #: host-tier budget in PAGES (0 disables the tier; eviction
        #: then drops instead of demoting)
        self.host_pages = int(host_pages) if max_entries > 0 else 0
        #: the lane-state snapshot allocator (a model with lane state);
        #: None for a model that is pages alone
        self.snapshots = snapshots
        #: matched tokens admissions prefilled again because no snapshot
        #: stood at the match (cumulative, ``record_hit``/``record_miss``)
        self.shortfall_tokens = 0
        self.hits_shortened = 0
        self._root = _Node()
        self._entries: List[PrefixEntry] = []
        self._host_entries: List[PrefixEntry] = []
        self._stamp = 0
        self._lock = threading.Lock()
        #: bumped on every structural change — see the class docstring
        self.generation = 0
        # cumulative flow (monotonic, for stats deltas)
        self.hits = 0
        self.misses = 0
        #: subset of ``hits`` served out of the host tier (the entry
        #: needed a promotion before its pages were consumable)
        self.host_hits = 0
        self.reused_tokens = 0
        #: device KV bytes reuse avoided recomputing + rewriting —
        #: the cache's cumulative savings credit (reused positions x
        #: token_bytes), per-request shares ledgered by the engine's
        #: usage accounting
        self.bytes_saved = 0
        self.donations = 0
        self.evictions = 0
        # host-tier flow
        self.demotions = 0
        self.promotions = 0
        self.host_evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # ------------------------------------------------------------ match
    def match(self, prompt: np.ndarray) -> PrefixMatch:
        """Best cached prefix for ``prompt``: walk the trie as deep as
        the prompt's tokens agree, then take the better of (a) the
        deepest entry ENDING on the walked path (a full-entry match —
        every one of its tokens is a prefix of the prompt) and (b) any
        entry in the subtree below the divergence point (a PARTIAL
        match: the entry shares exactly the walked depth, then
        diverges or extends — its KV is still valid for the shared
        head, by causality). Returns ``PrefixMatch(entry, length,
        matched)`` with ``matched >= min_tokens``, else ``(None, 0,
        0)``. With a snapshot store a candidate is worth the deepest
        snapshot it holds at or under what it shares (and under the
        prompt's last position), so ``length <= matched``, and among
        the entries below a divergence the one that resumes deepest
        wins, the most recently used on ties.

        PURE: no counters move and no LRU stamp is touched — the
        engine uses ``lookup`` both to probe admissions and to SCORE
        queued candidates for prefix-aware ordering, and scoring must
        not pollute the hit-rate. The engine's admission decision
        lands via ``record_hit`` / ``record_miss``."""
        prompt = np.asarray(prompt, np.int32)
        with self._lock:
            best: Optional[PrefixEntry] = None
            best_len = best_raw = 0
            stateful = self.snapshots is not None
            cap = int(prompt.shape[0]) - 1

            def consider(cand: Optional[PrefixEntry], ln: int):
                nonlocal best, best_len, best_raw
                if cand is None:
                    return
                raw = ln
                if stateful:
                    ln = cand.resume_at(min(ln, cap))[0]
                if (ln, raw) > (best_len, best_raw):
                    best, best_len, best_raw = cand, ln, raw

            node, depth, off = self._root, 0, prompt
            while True:
                if node.entry is not None:
                    consider(node.entry, node.entry.length)
                if off.shape[0] == 0:
                    # prompt exhausted AT a node: entries extending
                    # below all share the full walked depth
                    consider(self._mru_below(node, min(depth, cap)), depth)
                    break
                nxt = node.children.get(int(off[0]))
                if nxt is None:
                    # no child continues the prompt, but every entry
                    # below this node still shares `depth` tokens
                    consider(self._mru_below(node, min(depth, cap)), depth)
                    break
                edge, child = nxt
                m = _common_len(edge, off)
                depth += m
                if m < edge.shape[0]:
                    # diverged (or prompt exhausted) mid-edge: every
                    # entry below shares exactly `depth` tokens
                    consider(self._mru_below(child, min(depth, cap)), depth)
                    break
                node, off = child, off[m:]
            if best is None or best_raw < self.min_tokens:
                return PrefixMatch(None, 0, 0)
            return PrefixMatch(best, best_len, best_raw)

    def record_shortfall(self, tokens: int) -> None:
        """``tokens`` matched positions are prefilled again: no snapshot
        stood at the match (the engine's admission decision)."""
        with self._lock:
            if tokens > 0:
                self.shortfall_tokens += int(tokens)
                self.hits_shortened += 1

    def record_hit(self, entry: PrefixEntry, reused_tokens: int,
                   host: bool = False) -> None:
        """Commit an admission's hit: LRU touch, per-entry and global
        hit counts, and the chunk-aligned reused-token figure the
        engine actually skipped prefill for. ``host=True`` marks a hit
        the engine served via a host-tier promotion — the tier split
        behind the ``bigdl_serving_prefix_host_hits_total`` counter."""
        with self._lock:
            self._stamp += 1
            entry.last_used = self._stamp
            entry.hits += 1
            self.hits += 1
            if host:
                self.host_hits += 1
            self.reused_tokens += int(reused_tokens)
            self.bytes_saved += int(reused_tokens * self.token_bytes)

    def record_miss(self) -> None:
        with self._lock:
            self.misses += 1

    def _mru_below(self, node: _Node, limit: Optional[int] = None
                   ) -> Optional[PrefixEntry]:
        """Most-recently-used entry in ``node``'s subtree (entry count
        is bounded by ``max_entries`` plus the host tier, so the DFS
        is trivially cheap). With a snapshot store and a ``limit``,
        the entry that resumes deepest at or under ``limit`` first."""
        # graftlint: ok[lock-discipline] — the store reference is immutable after __init__ (callers hold the index lock)
        if self.snapshots is None or limit is None:
            key = lambda e: e.last_used
        else:
            key = lambda e: (e.resume_at(limit)[0], e.last_used)
        best = node.entry
        for edge, child in node.children.values():
            c = self._mru_below(child, limit)
            if c is not None and (best is None or key(c) > key(best)):
                best = c
        return best

    # -------------------------------------------------------- pin/unpin
    def acquire(self, entry: PrefixEntry) -> None:
        """Pin ``entry``: a pinned entry is never evicted, demoted or
        host-evicted."""
        with self._lock:
            entry.refs += 1

    def release(self, entry: PrefixEntry) -> None:
        with self._lock:
            if entry.refs <= 0:
                raise RuntimeError(
                    f"release() without matching acquire(): {entry!r}")
            entry.refs -= 1

    def pin_covering(self, tokens: np.ndarray
                     ) -> Optional[PrefixEntry]:
        """Find an entry of which ``tokens`` is a (non-strict) prefix
        and PIN it (caller must ``release``); None when no such entry
        exists. The preemption path pins the entry it just donated so
        LRU pressure cannot evict — and the demote sweep cannot spill
        — the victim's KV before its automatic resume consumes it."""
        with self._lock:
            entry = self._covering_entry(
                np.asarray(tokens, np.int32))
            if entry is not None:
                entry.refs += 1
            return entry

    # --------------------------------------------------------- donation
    def donate_pages(self, tokens: np.ndarray,
                     pages: Sequence[int],
                     snaps: Sequence[Tuple[int, int]] = (),
                     supersede: Optional[PrefixEntry] = None
                     ) -> Optional[PrefixEntry]:
        """Retain a request's prefix by sharing the ``pages`` that hold
        its KV (position order; the caller keeps its own references —
        the slot's table is freed separately). Returns the new entry;
        declined (None) when too short, already covered by an existing
        entry (LRU-touched instead), or the entry budget is exhausted
        by pinned entries. May evict the LRU ``refs == 0`` entry — the
        budget resolves by recency, never by silently dropping pinned
        entries. ``snaps`` (a model with lane state) are the donor's
        ``(position, snapshot id)`` pairs at or under the donated
        length; the entry SHARES each, the donor keeps its own
        references. ``supersede``: the entry the same request donated
        when its prompt's prefill ended; the longer one takes its place
        (one entry a request, as when only a finished request donated)
        unless it is pinned, demoted or gone."""
        # own the key: np.asarray would ALIAS an int32 caller buffer,
        # and a client reusing one preallocated prompt array across
        # requests would then rewrite the trie key under an entry
        # whose pages still hold the OLD tokens' KV — a silent
        # wrong-prefix hit later
        tokens = np.array(tokens, np.int32, copy=True)
        # graftlint: ok[lock-discipline] — the pool reference is immutable after __init__; page_size is a pool constant
        n_pages = -(-tokens.shape[0] // self.pool.page_size)
        with self._lock:
            if (self.max_entries == 0
                    or tokens.shape[0] < self.min_tokens
                    or n_pages == 0):
                return None
            if n_pages > len(pages):
                raise ValueError(
                    f"donate_pages: {tokens.shape[0]} tokens need "
                    f"{n_pages} pages, got {len(pages)}")
            covered = self._covering_entry(tokens)
            if covered is not None:
                self._stamp += 1
                covered.last_used = self._stamp
                return None
            if supersede is not None and supersede.refs == 0 \
                    and supersede in self._entries:
                self._drop_device_entry(supersede, evicted=False)
            if len(self._entries) >= self.max_entries:
                victim = self._lru_unpinned()
                if victim is None:
                    return None
                self._drop_device_entry(victim)
            held = tuple(pages[:n_pages])
            # index -> pool lock order (see module docstring): the pool
            # never calls back into the index, so this nesting is safe
            self.pool.share(held)
            kept = tuple(sorted((int(p), int(sid)) for p, sid in snaps
                                if p <= tokens.shape[0]))
            if self.snapshots is not None:
                self.snapshots.share([sid for _, sid in kept])
            self._stamp += 1
            self.generation += 1
            entry = PrefixEntry(tokens, held, self._stamp, kept)
            self._insert(entry)
            self._entries.append(entry)
            self.donations += 1
            return entry

    def _drop_device_entry(self, entry: PrefixEntry,
                           evicted: bool = True) -> None:
        """Take a device-tier entry out (lock held): drop its page
        references and remove it from the trie. ``evicted`` False: a
        longer entry of the same request takes its place, which is no
        eviction."""
        self._entries.remove(entry)
        self._trie_remove(entry)
        self.pool.free(entry.pages)
        entry.pages = ()
        self._free_snaps(entry)
        self.evictions += int(evicted)
        self.generation += 1

    def _free_snaps(self, entry: PrefixEntry) -> None:
        """An entry that goes takes its snapshot references with it."""
        if self.snapshots is not None and entry.snaps:
            self.snapshots.free([sid for _, sid in entry.snaps])
        entry.snaps = ()

    def reclaim_snapshot(self) -> bool:
        """Free one snapshot for a prefill that wants to take one: the
        least recently used snapshot that only unpinned entries hold
        leaves every one of them (the entries stay, their matches
        resume shallower). False when every snapshot is held by a
        request in flight or a pinned entry."""
        # graftlint: ok[lock-discipline] — the store reference is immutable after __init__ and has its own lock
        store = self.snapshots
        with self._lock:
            holders: Dict[int, List[PrefixEntry]] = {}
            for e in self._entries:
                for _, sid in e.snaps:
                    holders.setdefault(sid, []).append(e)
            free_able = [sid for sid, es in holders.items()
                         if store.refcount(sid) == len(es)
                         and all(e.refs == 0 for e in es)]
            if not free_able:
                return False
            victim = min(free_able, key=store.last_used)
            for e in holders[victim]:
                e.snaps = tuple(p for p in e.snaps if p[1] != victim)
            store.free([victim] * len(holders[victim]))
            self.generation += 1
            return True

    # --------------------------------------------------------- pressure
    def reclaim(self, n_pages: int,
                spill: Optional[Callable[[Tuple[int, ...]], list]]
                = None) -> bool:
        """Free pool pages for an ``n_pages`` allocation by evicting
        LRU unpinned entries; True when the pool can now satisfy it.
        With ``spill`` and host budget, victims demote: ``spill(pages)``
        returns one pinned host buffer per page (run OUTSIDE the index
        lock — it dispatches device work), or None to abandon the
        demotion and drop the victim. Note an evicted entry only frees
        the pages nobody else references — shared pages survive under
        their other holders, so reclaim can legitimately run out of
        victims before the pool has ``n_pages`` free."""
        # graftlint: ok[lock-discipline] — the pool reference is immutable and the pool has its OWN lock; calling it under the index lock would nest the two
        while self.pool.free_pages < n_pages:
            with self._lock:
                victim = self._lru_unpinned()
                if victim is None:
                    return self.pool.free_pages >= n_pages
                demote = (spill is not None and self.host_pages > 0
                          and self._make_host_page_room(
                              len(victim.pages)))
                self._entries.remove(victim)
                self.evictions += 1
                self.generation += 1
                if demote:
                    victim.tier = "host"
                    victim.host_buf = None
                    self._host_entries.append(victim)
                else:
                    self._trie_remove(victim)
                    self._free_snaps(victim)
            held = victim.pages
            if demote:
                buf = spill(held)
                with self._lock:
                    if buf is None:
                        # spill failed: degrade to a plain drop
                        if victim in self._host_entries:
                            self._host_entries.remove(victim)
                            self._trie_remove(victim)
                            self.generation += 1
                    elif victim in self._host_entries:
                        victim.host_buf = buf
                        self.demotions += 1
            # graftlint: ok[lock-discipline] — the pool reference is immutable and the pool has its OWN lock; freeing outside the index lock avoids nesting the two
            self.pool.free(held)
            victim.pages = ()
        return True

    def _make_host_page_room(self, incoming: int) -> bool:
        """Ensure the host tier can absorb ``incoming`` more pages
        (lock held), evicting host-LRU ``refs == 0`` entries past the
        page budget; False when pinned entries block it (the demotion
        then degrades to a drop — never an over-budget spill)."""
        if incoming > self.host_pages:
            return False
        while (self._host_pages_in_use_locked() + incoming
               > self.host_pages):
            cand = [e for e in self._host_entries if e.refs == 0]
            if not cand:
                return False
            hv = min(cand, key=lambda e: e.last_used)
            self._host_entries.remove(hv)
            self._trie_remove(hv)
            hv.host_buf = None
            self.host_evictions += 1
            self.generation += 1
        return True

    def _host_pages_in_use_locked(self) -> int:
        return sum(len(e.host_buf) for e in self._host_entries
                   if e.host_buf is not None)

    # -------------------------------------------------------- promotion
    def promote_pages(self, entry: PrefixEntry,
                      pages: Sequence[int]) -> None:
        """Flip a host-tier entry back to device residency over freshly
        allocated ``pages`` (the caller has already device_put each
        host buffer into its page): LRU touch, host buffer dropped,
        generation bump — probes that captured the entry as host-tier
        re-validate before acting."""
        with self._lock:
            if entry.tier != "host" or entry not in self._host_entries:
                raise RuntimeError(
                    f"promote_pages() of a non-host entry: {entry!r}")
            self._host_entries.remove(entry)
            entry.tier = "device"
            entry.pages = tuple(pages)
            entry.host_buf = None
            self._entries.append(entry)
            self._stamp += 1
            entry.last_used = self._stamp
            self.promotions += 1
            self.generation += 1

    @property
    def device_pages(self) -> int:
        """Total pages referenced by device-tier entries — the upper
        bound on what a full ``reclaim`` sweep could return to the
        pool (shared pages survive under their other holders, so the
        true yield can be lower). Admission scoring input."""
        with self._lock:
            return sum(len(e.pages) for e in self._entries)

    def drop_all(self) -> None:
        """Release every retained entry's page references (engine
        stop/crash path — the leak check counts on this)."""
        with self._lock:
            for e in list(self._entries):
                self._drop_device_entry(e)
            for e in list(self._host_entries):
                self._host_entries.remove(e)
                self._trie_remove(e)
                e.host_buf = None
                self.generation += 1

    def _covering_entry(self, tokens: np.ndarray
                        ) -> Optional[PrefixEntry]:
        """An existing entry of which ``tokens`` is a (non-strict)
        prefix — any future prompt matches it at least as deeply as it
        would match ``tokens``, so the donation adds nothing."""
        node, off = self._root, tokens
        while True:
            if off.shape[0] == 0:
                return self._mru_below(node)
            nxt = node.children.get(int(off[0]))
            if nxt is None:
                return None
            edge, child = nxt
            m = _common_len(edge, off)
            if m == off.shape[0]:
                return self._mru_below(child)
            if m < edge.shape[0]:
                return None
            node, off = child, off[m:]

    def _lru_unpinned(self) -> Optional[PrefixEntry]:
        cand = [e for e in self._entries if e.refs == 0]
        return min(cand, key=lambda e: e.last_used) if cand else None

    # ---------------------------------------------------- trie plumbing
    def _insert(self, entry: PrefixEntry) -> None:
        node, off = self._root, entry.tokens
        while off.shape[0] > 0:
            nxt = node.children.get(int(off[0]))
            if nxt is None:
                child = _Node()
                node.children[int(off[0])] = (off, child)
                child.entry = entry
                return
            edge, child = nxt
            m = _common_len(edge, off)
            if m < edge.shape[0]:
                # split the edge at the divergence point
                mid = _Node()
                node.children[int(off[0])] = (edge[:m], mid)
                mid.children[int(edge[m])] = (edge[m:], child)
                node, off = mid, off[m:]
            else:
                node, off = child, off[m:]
        node.entry = entry

    def _trie_remove(self, entry: PrefixEntry) -> None:
        # walk to the entry's node, clearing the marker; structural
        # merge of pass-through nodes is skipped — the trie is bounded
        # by entries * key-length and rebuilt nodes are reused by the
        # next insert along the same path
        node, off = self._root, entry.tokens
        path: List[Tuple[_Node, int]] = []
        while off.shape[0] > 0:
            nxt = node.children.get(int(off[0]))
            if nxt is None:
                return
            edge, child = nxt
            m = _common_len(edge, off)
            if m < edge.shape[0]:
                return
            path.append((node, int(off[0])))
            node, off = child, off[m:]
        if node.entry is entry:
            node.entry = None
        # prune now-empty leaf chains so the trie cannot grow without
        # bound across many donate/evict cycles
        while path:
            parent, first = path.pop()
            edge, child = parent.children[first]
            if child.entry is None and not child.children:
                del parent.children[first]
            else:
                break

    # ------------------------------------------------------------ bytes
    @property
    def bytes_in_use(self) -> int:
        """Pro-rata device bytes the retained entries hold (a page
        shared with live requests bills the index only its refcount
        share) — the honest `/debug/memory` attribution."""
        with self._lock:
            return int(sum(self.pool.holder_bytes(e.pages)
                           for e in self._entries))

    @property
    def capacity_bytes(self) -> int:
        # graftlint: ok[lock-discipline] — the pool reference is immutable after __init__
        return self.pool.capacity_bytes

    @property
    def host_capacity_bytes(self) -> int:
        # graftlint: ok[lock-discipline] — host_pages and the pool reference are immutable after __init__
        return self.host_pages * self.pool.page_bytes

    @property
    def host_bytes_in_use(self) -> int:
        with self._lock:
            return (self._host_pages_in_use_locked()
                    * self.pool.page_bytes)

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        """Operational snapshot: occupancy and cumulative
        hit/reuse/eviction flow (the engine's ``stats()['prefix_cache']``
        and ``/debug/requests`` both render this). ``rows`` is the
        entry cap; ``host_rows`` the host tier's page budget."""
        with self._lock:
            looked = self.hits + self.misses
            dev_pages = sum(len(e.pages) for e in self._entries)
            host_pages = self._host_pages_in_use_locked()
            pro_rata = int(sum(self.pool.holder_bytes(e.pages)
                               for e in self._entries))
            return {
                "entries": len(self._entries),
                "rows": self.max_entries,
                "pages": dev_pages,
                "bytes": pro_rata,
                "capacity_bytes": self.pool.capacity_bytes,
                "devices": self.devices,
                "bytes_per_device": pro_rata // self.devices,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hits / looked, 4)
                            if looked else 0.0,
                "reused_tokens": self.reused_tokens,
                "bytes_saved": self.bytes_saved,
                "donations": self.donations,
                "evictions": self.evictions,
                # host tier (page units)
                "host_rows": self.host_pages,
                "host_entries": len(self._host_entries),
                "host_pages": host_pages,
                "host_bytes": host_pages * self.pool.page_bytes,
                "host_capacity_bytes": (self.host_pages
                                        * self.pool.page_bytes),
                "host_hits": self.host_hits,
                "device_hits": self.hits - self.host_hits,
                "demotions": self.demotions,
                "promotions": self.promotions,
                "host_evictions": self.host_evictions,
                "shortfall_tokens": self.shortfall_tokens,
                "hits_shortened": self.hits_shortened,
            }

    def snapshot(self) -> List[dict]:
        """Per-entry debug view, both tiers (LRU order, oldest
        first)."""
        with self._lock:
            return [{"length": e.length, "pages": list(e.pages),
                     "snapshots": [list(p) for p in e.snaps],
                     "tier": e.tier, "refs": e.refs, "hits": e.hits,
                     "last_used": e.last_used}
                    for e in sorted(self._entries + self._host_entries,
                                    key=lambda e: e.last_used)]
