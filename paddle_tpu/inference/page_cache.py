"""The host side of the engine's paged cache: what the scheduler
(``inference/serving.py``) counts, shares and evicts, and never what a
page holds.  Nothing here touches the device (no ``jax``): the pools
themselves are the engine's, and what is in a page is the step's
business (``inference/paged_layout.py``).

- ``PageAllocator``: a free list of physical page ids with explicit
  reference counts; also the allocator of a recurrent state's snapshot
  entries.
- ``PrefixCache`` (``_TrieNode``): the radix cache of committed full
  pages by their tokens, a page of each kind of page a block, an
  optional host tier, and the state snapshots a block may hold beside
  its pages.  A prefill hands it the FIRST kind's full pages chunk by
  chunk, as each is committed, and the rest (a window kind's pages, the
  snapshots) when the prompt is done.  It keeps a snapshot at a block
  beside its pages (taken where a prefill chunk ends on a page
  boundary: the engine's ``_state_chunk`` cuts chunks so that they do)
  and serves a hit as far as the deepest block that has BOTH; what the
  pages matched beyond it is prefilled again (``state_lost_tokens``).  Snapshots have their own
  budget (the engine's ``state_snapshots``) and LRU.
- ``_KindPages``: one kind of page as the host holds it: pool size,
  allocator, table, the pages each slot holds.
"""

from __future__ import annotations

import heapq
import threading
from typing import TYPE_CHECKING, Dict, List, Optional

import numpy as np

if TYPE_CHECKING:
    from .paged_layout import PageKind


class PageAllocator:
    """Host-side physical-page free list with EXPLICIT refcounts (reuse
    is LIFO so hot pages stay cache/TLB friendly).

    Round-11: pages are shared copy-on-write between the prefix-cache
    trie and any number of live requests, so ownership is counted —
    ``alloc`` hands out a page at refcount 1, every additional sharer
    ``acquire``\\ s it, and ``release`` only returns it to the free list
    when the count reaches zero.  The invariant ``available + live ==
    num_pages`` is a CHECKED CONTRACT (``assert_consistent``) callable
    at any point — under the race sanitizer's thread hammer and at
    engine teardown — so a COW bug (double release, leaked ref)
    surfaces as a hard failure instead of silent pool exhaustion.

    Concurrency Doctor round: every mutation runs under ``_lock``
    (whole method bodies — a bare ``if not self.free`` outside the lock
    is exactly the check-then-act shape RACE004 flags).  The serving
    tick itself is single-threaded; the lock is for the multi-host
    control plane (hammer harness today, replica-per-host tomorrow) and
    is uncontended — and therefore cheap — in the common path."""

    def __init__(self, num_pages: int):
        self.free: List[int] = list(range(num_pages - 1, -1, -1))
        self.total = num_pages
        self.refs: List[int] = [0] * num_pages
        self._lock = threading.Lock()

    def alloc(self) -> Optional[int]:
        with self._lock:
            if not self.free:
                return None
            p = self.free.pop()
            self.refs[p] = 1
            return p

    def acquire(self, page: int) -> int:
        """Add a reference to an already-live page (prefix sharing)."""
        with self._lock:
            if self.refs[page] <= 0:
                raise AssertionError(
                    f"acquire of dead page {page} (refcount "
                    f"{self.refs[page]}) — prefix-cache/table corruption")
            self.refs[page] += 1
            return page

    def release(self, pages) -> None:
        """Drop one reference per page; a page returns to the free list
        only when its last reference is gone."""
        with self._lock:
            for p in reversed(list(pages)):
                p = int(p)
                if self.refs[p] <= 0:
                    raise AssertionError(
                        f"release of free page {p} — double release")
                self.refs[p] -= 1
                if self.refs[p] == 0:
                    self.free.append(p)

    @property
    def available(self) -> int:
        # lock-free snapshot: advisory under concurrency, exact when the
        # pool is quiescent (scheduler decisions re-check under alloc)
        return len(self.free)

    @property
    def live(self) -> int:
        return sum(1 for r in self.refs if r > 0)

    def assert_consistent(self) -> None:
        """The checked pool contract, atomically under the lock:
        every page is exactly one of free or live
        (``available + live == total``), no refcount is negative, free
        pages carry no references, and the free list holds unique
        in-range page ids."""
        with self._lock:
            live = sum(1 for r in self.refs if r > 0)
            if len(self.free) + live != self.total:
                raise AssertionError(
                    f"page pool out of balance: available={len(self.free)} "
                    f"+ live={live} != total={self.total}")
            neg = [p for p, r in enumerate(self.refs) if r < 0]
            if neg:
                raise AssertionError(f"negative refcounts on pages {neg}")
            bad = [p for p in self.free if self.refs[p] != 0]
            if bad:
                raise AssertionError(f"free pages with live refs: {bad}")
            if len(set(self.free)) != len(self.free):
                raise AssertionError("duplicate pages on the free list")
            oob = [p for p in self.free if not 0 <= p < self.total]
            if oob:
                raise AssertionError(f"out-of-range pages on free list: {oob}")

    def assert_balanced(self) -> None:
        """Back-compat alias for the pre-round-18 leak check."""
        self.assert_consistent()


class _TrieNode:
    """One committed full page of tokens in the prefix cache.

    Round 16 (the tiered KV plane): a node lives in one of two TIERS —
    ``device`` (``page`` is a live pool page id, the trie holds one
    allocator ref on it) or ``host`` (``page`` is None and ``host_kv``
    carries the page's per-layer K/V stacked [L, kvh, page, d] pair,
    placed in the pinned-host memory space).

    Where the model has further kinds of page (``PagedLayout.kinds``),
    ``more`` holds the block's page of each: a window kind's page, or
    None once it was evicted alone (or the prefill that committed the
    block had already given it back).

    Where sequences hold a recurrent state (``PagedLayout.state``),
    ``snap`` is the entry of the state pools that holds the state AT the
    end of this block (a snapshot; None: none was kept) and
    ``snap_tick`` when it was last restored from (when it was taken,
    while ``snap_used`` is False: nobody has restored from it yet)."""

    __slots__ = ("children", "key", "page", "parent", "tick", "host_kv",
                 "more", "snap", "snap_tick", "snap_used")

    def __init__(self, key=None, page=None, parent=None, kinds: int = 0):
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.key = key
        self.page = page
        self.parent = parent
        self.tick = 0
        self.host_kv = None
        self.more: List[Optional[int]] = [None] * kinds
        self.snap: Optional[int] = None
        self.snap_tick = 0
        self.snap_used = False

    @property
    def tier(self) -> str:
        return "device" if self.host_kv is None else "host"


class PrefixCache:
    """Radix/trie prefix cache over the engine's page pools.

    Keys are page-granular token chunks (``page_size`` tokens per edge),
    values are PHYSICAL page ids in the per-layer pools.  A node exists
    only for pages whose prompt tokens were fully committed by a prefill
    chunk whose launch the host has read (the engine inserts the full
    pages of what a prompt has committed at EVERY chunk's commit, not
    only at its last: a re-ask that waits behind its own document's
    prefill finds them), and the trie holds its own allocator reference
    on each node's page — so cached prefixes survive the requests that
    produced them, and ``lookup`` can hand the same physical pages to a
    new request copy-on-write (the new request only ever WRITES at
    positions at or past its private suffix, and the prefill that
    committed a page writes only past what it has committed, so shared
    pages are read-only by construction; the last partial prompt page
    is always private because only full pages are keyed, and at least
    one suffix token is always left to prefill so the hit request still
    produces first-token logits).  A block committed before its prompt
    was done holds its first-kind page alone: where the layout has a
    window kind or a recurrent state it is not restorable until the
    prompt's last insert gives it the rest, and ``lookup_all`` shrinks a
    hit to what is whole.

    Eviction is LRU over refcount-0 leaves (allocator refcount 1 = the
    trie's own reference, no live request) under pool pressure — interior
    nodes become leaves as their children evict, so a cold chain drains
    bottom-up.

    Round 16 — the TIERED cache (``host_tier_pages > 0``): under pool
    pressure, LRU refcount-0 pages are DEMOTED to the pinned-host
    memory space (``demote_fn`` — parallel/memory.place_on_host through
    the engine's pool gather) instead of evicted; a later lookup that
    reaches a host-tier node PROMOTES it back into a device page
    (``promote_fn``) and the hit proceeds exactly as a device hit — the
    demote→promote round trip is bit-identical (pure residency moves,
    no re-quantization).  Demotion needs no leaf-ness (the trie
    structure is untouched), so interior pages demote too; only when
    the host tier itself overflows its cap are LRU host-tier LEAVES
    truly dropped, bottom-up like classic eviction.

    KINDS of page (``windows``: one ``(allocator, window)`` a further
    kind of the layout): a cached block holds a page of each kind.  The
    rows that continue a hit of ``P`` tokens read the first kind's pages
    of ``[0, P)`` and a window kind's pages of the positions from ``P +
    1 - window`` on, so a hit is served as far as BOTH are whole
    (``lookup_all``) and shrinks to the longest prefix of which that
    holds, never to something wrong.  A window kind's page may be
    evicted ALONE, from any block (``evict_window``): first those no
    possible hit can need (the run of blocks a hit would read is broken
    already), then the least recently used.

    SNAPSHOTS of a recurrent state (``snaps``: the allocator of the
    state pools' snapshot entries): a block may hold, beside its pages,
    the entry with the state at its end.  Pages say what the attention
    layers saw of a prefix, a snapshot what the recurrent layers made
    of it, and a sequence can only go on from a point where it has
    BOTH: ``lookup_all`` serves a hit as far as the deepest block of
    the walk that has a snapshot, and says how many matched tokens lay
    beyond it (they are prefilled again).  Snapshots have a budget and
    an LRU of their own (``evict_snapshots``: those never restored
    from first, then by when one was last restored from; ``snap_nodes`` is the few blocks that hold one, by
    entry, so neither eviction nor the live count walks the trie); a
    block that goes takes its snapshot along."""

    def __init__(self, page_size: int, alloc: PageAllocator, *,
                 host_tier_pages: int = 0, demote_fn=None,
                 promote_fn=None, windows=(), snaps=None):
        self.page_size = int(page_size)
        self.alloc = alloc
        self.windows = tuple(windows)       # (allocator, window) a kind
        self.snaps: Optional[PageAllocator] = snaps
        self.snap_nodes: Dict[int, _TrieNode] = {}  # entry -> its block
        self.snapshots_taken = 0
        self.evicted_snapshots = 0
        self.evicted_window_pages = 0
        self.root = _TrieNode()
        self._tick = 0
        self.hits = 0
        self.lookups = 0
        self.hit_tokens = 0
        # hits the engine's SECOND look-up found (a slot that waited
        # with nothing launched, matched again when its first chunk was
        # packed), and the tokens they added to the admission's match
        self.late_hits = 0
        self.late_hit_tokens = 0
        self.inserted_pages = 0
        self.evicted_pages = 0
        # host tier (round 16)
        self.host_tier_pages = int(host_tier_pages)
        self.demote_fn = demote_fn
        self.promote_fn = promote_fn
        if self.host_tier_pages > 0 and (demote_fn is None
                                         or promote_fn is None):
            raise ValueError(
                "host_tier_pages > 0 needs demote_fn/promote_fn (the "
                "engine's pool residency hooks)")
        self.host_pages = 0
        self.host_hits = 0
        self.demoted_pages = 0
        self.promoted_pages = 0

    def _chunks(self, tokens, npages: int):
        """The keys of a prompt's first ``npages`` blocks: each block's
        token ids as bytes (a session of 64k tokens is 512 keys a lookup
        and as many an insert: a tuple of Python ints a block cost 8 ms
        a pass)."""
        ps = self.page_size
        ids = np.ascontiguousarray(np.asarray(tokens)[:npages * ps],
                                   dtype=np.int32)
        return [ids[i * ps:(i + 1) * ps].tobytes() for i in range(npages)]

    def lookup(self, prompt):
        """Walk the trie with the prompt's full pages; returns
        ``(pages, matched_tokens)`` with one allocator ref acquired per
        returned page (the caller owns them like alloc'd pages).  At
        most ``(len(prompt) - 1) // page_size`` pages match, so the
        suffix containing the last prompt token — whose logits seed
        generation — is always prefilled privately.

        Hit STATS are committed separately (``record_hit``) by the
        engine once the request is actually admitted — a lookup whose
        admission aborts on pool pressure releases its refs and must
        not count as a served hit."""
        if self.windows:
            raise ValueError("a cache over several kinds of page is "
                             "asked through lookup_all")
        pages, matched, _, _ = self.lookup_all(prompt)
        return pages[0], matched

    def _first_read(self, blocks: int, window: int) -> int:
        """The first block whose window-kind page the rows continuing a
        hit of ``blocks`` blocks read: the one holding position ``P + 1
        - window``."""
        return max(0, blocks * self.page_size + 1 - window) // self.page_size

    def lookup_all(self, prompt):
        """``lookup`` for every kind of page and the recurrent state:
        ``(pages, matched_tokens, snapshot entry or None, tokens matched
        beyond it)`` with ``pages[0]`` the first kind's pages of ``[0,
        matched)`` and ``pages[k]`` the k-th kind's pages of the blocks
        from ``_first_read`` to the hit's last, a ref acquired on each.
        The hit is the longest prefix of the walk whose window-kind
        pages are all there.  In a cache with ``snaps`` it ends at the
        deepest block of that prefix that holds a snapshot, a ref
        acquired on the entry too: the caller gives it back once the
        launch that reads it is committed.  Without, the last two are
        ``None, 0``."""
        self.lookups += 1
        self._tick += 1
        limit = max(0, (len(prompt) - 1) // self.page_size)
        node = self.root
        path: List[_TrieNode] = []
        for key in self._chunks(prompt, limit):
            child = node.children.get(key)
            if child is None:
                break
            # freshen recency FIRST: the promote hook may itself demote
            # under pool pressure and trim the host tier — the node
            # being promoted must never be the LRU drop candidate
            child.tick = self._tick
            if child.host_kv is not None:
                # host-tier hit: promote back into a device page before
                # handing it out.  No capacity to promote into (even
                # after the promote hook's own demotion attempt) ends
                # the walk — the suffix simply prefills cold.
                page = self.promote_fn(child.host_kv)
                if page is None:
                    break
                child.page, child.host_kv = int(page), None
                self.host_pages -= 1
                self.promoted_pages += 1
                self.host_hits += 1
            self.alloc.acquire(child.page)
            path.append(child)
            node = child
        # the longest prefix whose window-kind pages are whole: run[k]
        # counts the blocks ending at the current one that hold kind k's
        blocks, run = 0, [0] * len(self.windows)
        for j, n in enumerate(path, 1):
            run = [r + 1 if n.more[k] is not None else 0
                   for k, r in enumerate(run)]
            if all(r >= j - self._first_read(j, w)
                   for r, (_, w) in zip(run, self.windows)):
                blocks = j
        snap, lost = None, 0
        if self.snaps is not None:
            whole = blocks
            while blocks and path[blocks - 1].snap is None:
                blocks -= 1
            lost = (whole - blocks) * self.page_size
            if blocks:
                path[blocks - 1].snap_tick = self._tick
                path[blocks - 1].snap_used = True
                snap = self.snaps.acquire(path[blocks - 1].snap)
        # what lies past a hit that shrank is handed back
        self.alloc.release([n.page for n in path[blocks:]])
        pages = [[n.page for n in path[:blocks]]]
        for k, (alloc, w) in enumerate(self.windows):
            pages.append([alloc.acquire(n.more[k]) for n in
                          path[self._first_read(blocks, w):blocks]])
        return pages, blocks * self.page_size, snap, lost

    def probe(self, prompt) -> int:
        """Matched FULL-PAGE tokens for ``prompt`` across BOTH tiers,
        with no refs acquired and no stats/LRU mutation — the fleet
        router's cross-replica reachability query (a host-tier page on
        any replica makes that replica the preferred prefill target)."""
        limit = max(0, (len(prompt) - 1) // self.page_size)
        node = self.root
        matched = 0
        for key in self._chunks(prompt, limit):
            child = node.children.get(key)
            if child is None:
                break
            matched += self.page_size
            node = child
        return matched

    def record_hit(self, matched_tokens: int, before: Optional[int] = None
                   ) -> None:
        """Count a served hit of ``matched_tokens``.  ``before``: what
        the request's admission had matched, where this is the hit of
        its second look-up (a LATE hit: it counts the tokens it added,
        and as a hit of its own only if the admission found nothing)."""
        added = matched_tokens - (before or 0)
        if before is not None:
            self.late_hits += 1
            self.late_hit_tokens += added
        if added > 0:
            self.hits += 0 if before else 1
            self.hit_tokens += added

    def insert(self, prompt, pages, more=(), snaps=()) -> int:
        """Commit the FULL pages of what a prefill has committed of its
        prompt: called with a growing prefix as the prompt's chunks are
        committed, with ``more`` and ``snaps`` when the last is.  New
        nodes acquire a trie reference on their page; existing nodes are
        left untouched, so a page takes ONE trie reference however often
        its block is inserted (and a concurrent prefill of the same
        prefix keeps its private copy, which simply frees when that
        request finishes).
        ``more[k]`` is ``(first block, pages)``: the k-th further kind's
        pages the slot still holds, from that block on; a block that
        lacks its page of that kind, new or not, takes it.  ``snaps``
        is ``(blocks, entry)`` a state snapshot the prefill took: the
        block that ends there takes the entry over (the caller's
        reference becomes the trie's) unless it has one, in which case,
        or where no such block is committed, the entry is given back.
        Returns the number of newly committed pages."""
        self._tick += 1
        n = min(len(prompt) // self.page_size, len(pages))
        node = self.root
        added = 0
        at = {int(b): int(e) for b, e in snaps}
        for i, key in enumerate(self._chunks(prompt, n)):
            child = node.children.get(key)
            if child is None:
                child = _TrieNode(key, self.alloc.acquire(int(pages[i])),
                                  node, len(self.windows))
                node.children[key] = child
                self.inserted_pages += 1
                added += 1
            for k, (first, held) in enumerate(more):
                if child.more[k] is None and 0 <= i - first < len(held):
                    child.more[k] = self.windows[k][0].acquire(
                        int(held[i - first]))
            child.tick = self._tick
            if child.snap is None and i + 1 in at:
                child.snap, child.snap_tick = at.pop(i + 1), self._tick
                child.snap_used = False
                self.snap_nodes[child.snap] = child
                self.snapshots_taken += 1
            node = child
        if at:
            self.snaps.release(at.values())
        return added

    def _nodes(self):
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

    def evict(self, pages_needed: int) -> int:
        """LRU-evict refcount-0 leaves (trie-only pages) until
        ``pages_needed`` pages were freed or nothing evictable is left.
        Returns pages actually freed.

        One traversal collects the evictable leaves into a tick-ordered
        heap; a parent that becomes an evictable leaf when its last
        child is freed is pushed then — O(nodes + m log m) for m freed
        pages instead of re-walking the trie per page.  Ticks are
        stable within the call (no lookup/insert runs concurrently).

        With the host tier enabled this DEMOTES instead: LRU refcount-0
        DEVICE pages (leaf or interior — demotion keeps the trie
        structure) move to pinned host, freeing their pool pages; the
        host tier's own overflow then drops LRU host LEAVES."""
        if self.host_tier_pages > 0:
            return self._demote_lru(pages_needed)
        freed = 0
        seq = 0                      # tie-break: heap never compares nodes
        heap = []
        for n in self._nodes():
            if not n.children and self.alloc.refs[n.page] == 1:
                heap.append((n.tick, seq, n))
                seq += 1
        heapq.heapify(heap)
        while freed < pages_needed and heap:
            _, _, victim = heapq.heappop(heap)
            parent = victim.parent
            del parent.children[victim.key]
            self.alloc.release([victim.page])
            self._drop_more(victim)
            self.evicted_pages += 1
            freed += 1
            if (parent is not self.root and not parent.children
                    and self.alloc.refs[parent.page] == 1):
                heap_entry = (parent.tick, seq, parent)
                seq += 1
                heapq.heappush(heap, heap_entry)
        return freed

    def _drop_more(self, node: _TrieNode) -> None:
        """Give back a block's pages of the further kinds and its state
        snapshot (the block itself is going)."""
        for k, (alloc, _) in enumerate(self.windows):
            if node.more[k] is not None:
                alloc.release([node.more[k]])
                node.more[k] = None
        if node.snap is not None:
            self._drop_snapshot(node)

    def _drop_snapshot(self, node: _TrieNode) -> None:
        del self.snap_nodes[node.snap]
        self.snaps.release([node.snap])
        node.snap = None
        self.evicted_snapshots += 1

    def evict_snapshots(self, needed: int, used: bool = True) -> int:
        """Give back up to ``needed`` snapshot entries that only the trie
        holds: those nobody ever restored from first (the snapshots a
        prompt leaves inside its own suffix), then the least recently
        restored-from (the blocks stay, with their pages: a later hit is
        served as far as the deepest snapshot above them).  A snapshot
        that sessions come back to so outlives a burst of prompts that
        each leave a few nobody will ask for: losing the one at the end
        of a 64k-token history costs every turn of that session 128
        chunk steps until one of them has taken it again.  ``used``
        False: only those nobody restored from (what a snapshot INSIDE a
        prompt may displace: it is a bet that someone comes back to that
        point, and does not outbid one that sessions have come back to).
        Returns entries freed."""
        found = sorted((n for e, n in self.snap_nodes.items()
                        if self.snaps.refs[e] == 1
                        and (used or not n.snap_used)),
                       key=lambda n: (n.snap_used, n.snap_tick, n.snap))
        for n in found[:max(needed, 0)]:
            self._drop_snapshot(n)
        return min(len(found), max(needed, 0))

    def evict_window(self, k: int, pages_needed: int) -> int:
        """Evict up to ``pages_needed`` pages of the k-th further kind
        that only the trie holds, from ANY block (the block stays, with
        its first-kind page).  First those no possible hit can need: a
        hit that ends at block j reads the pages of the blocks from
        ``_first_read(j)`` to j, so a page is of use only while some
        block at or below it, as far as a window reaches, still has its
        whole run; then the least recently used.  Returns pages freed."""
        alloc, w = self.windows[k]
        # (useful, tick, n, node) of every candidate; a node's run is the
        # count of blocks ending at it that hold the kind's page
        found = []
        stack = [(c, 1, 0) for c in self.root.children.values()]
        order = []
        while stack:
            n, depth, run = stack.pop()
            run = run + 1 if n.more[k] is not None else 0
            whole = run >= depth - self._first_read(depth, w)
            order.append((n, depth, whole))
            stack.extend((c, depth + 1, run) for c in n.children.values())
        # reach[n]: blocks down to the nearest block at or below n whose
        # run is whole (children before parents: the walk's reverse)
        reach: Dict[int, int] = {}
        far = 1 << 30
        for n, depth, whole in reversed(order):
            r = 0 if whole else min(
                (reach[id(c)] + 1 for c in n.children.values()), default=far)
            reach[id(n)] = r
            if n.more[k] is not None and alloc.refs[n.more[k]] == 1:
                # of use to the hit that ends r blocks further down, if
                # that hit's run reaches back as far as this block
                useful = r < far and \
                    depth > self._first_read(depth + r, w)
                found.append((useful, n.tick, len(found), n))
        found.sort(key=lambda f: f[:3])
        freed = 0
        for _, _, _, n in found[:pages_needed]:
            alloc.release([n.more[k]])
            n.more[k] = None
            self.evicted_window_pages += 1
            freed += 1
        return freed

    def _demote_lru(self, pages_needed: int) -> int:
        """Tiered pressure relief: demote up to ``pages_needed`` LRU
        refcount-0 device pages to the host tier (their pool pages
        free), then trim the host tier back under its cap by dropping
        LRU host LEAVES.  Returns device pages freed."""
        freed = 0
        seq = 0
        heap = []
        for n in self._nodes():
            if n.host_kv is None and self.alloc.refs[n.page] == 1:
                heap.append((n.tick, seq, n))
                seq += 1
        heapq.heapify(heap)
        while freed < pages_needed and heap:
            _, _, victim = heapq.heappop(heap)
            victim.host_kv = self.demote_fn(victim.page)
            victim.page = None
            self.host_pages += 1
            self.demoted_pages += 1
            freed += 1
        # host-tier overflow: drop LRU host LEAVES, one traversal + a
        # heap (the evict() shape) — a parent that becomes a droppable
        # host leaf is pushed as its child goes.  tick == _tick marks
        # the lookup path currently being promoted (recency set before
        # the promote hook runs) — never a drop candidate.
        if self.host_pages > self.host_tier_pages:
            trim = []
            for n in self._nodes():
                if (n.host_kv is not None and not n.children
                        and n.tick < self._tick):
                    trim.append((n.tick, seq, n))
                    seq += 1
            heapq.heapify(trim)
            while self.host_pages > self.host_tier_pages and trim:
                _, _, drop = heapq.heappop(trim)
                parent = drop.parent
                del parent.children[drop.key]
                self.host_pages -= 1
                self.evicted_pages += 1
                if (parent is not self.root and not parent.children
                        and parent.host_kv is not None
                        and parent.tick < self._tick):
                    heapq.heappush(trim, (parent.tick, seq, parent))
                    seq += 1
        return freed

    def clear(self) -> None:
        """Drop every trie reference (engine teardown); host-tier
        payloads (no allocator ref) just drop."""
        for n in list(self._nodes()):
            if n.host_kv is None:
                self.alloc.release([n.page])
            self._drop_more(n)
        self.root = _TrieNode()
        self.host_pages = 0
        assert not self.snap_nodes

    def assert_consistent(self) -> None:
        """The checked trie/tier contract (hammer + teardown): every
        node lives in EXACTLY one tier (device page XOR host payload),
        device pages are unique across the trie with a live allocator
        refcount (the trie's own reference), and the ``host_pages``
        counter matches the actual host-tier node count."""
        seen_device: Dict[int, int] = {}
        host_nodes = 0
        for n in self._nodes():
            has_page = n.page is not None
            has_host = n.host_kv is not None
            if has_page == has_host:
                raise AssertionError(
                    f"trie node {n.key!r} in "
                    f"{'both tiers' if has_page else 'no tier'} — "
                    f"page={n.page!r} host_kv set={has_host}")
            if has_host:
                host_nodes += 1
                continue
            if n.page in seen_device:
                raise AssertionError(
                    f"device page {n.page} held by two trie nodes "
                    f"({seen_device[n.page]!r} and {n.key!r})")
            seen_device[n.page] = n.key
            if self.alloc.refs[n.page] <= 0:
                raise AssertionError(
                    f"trie node {n.key!r} holds dead page {n.page} "
                    f"(refcount {self.alloc.refs[n.page]})")
        if host_nodes != self.host_pages:
            raise AssertionError(
                f"host-tier counter drift: counter={self.host_pages} "
                f"actual={host_nodes}")
        for k, (alloc, _) in enumerate(self.windows):
            held = [n.more[k] for n in self._nodes()
                    if n.more[k] is not None]
            if len(set(held)) != len(held):
                raise AssertionError(
                    f"a page of kind {k + 1} held by two trie nodes")
            dead = [p for p in held if alloc.refs[p] <= 0]
            if dead:
                raise AssertionError(
                    f"trie nodes hold dead pages {dead} of kind {k + 1}")
        held = [n.snap for n in self._nodes() if n.snap is not None]
        if len(set(held)) != len(held):
            raise AssertionError("a state snapshot held by two trie nodes")
        if {e: id(n) for e, n in self.snap_nodes.items()} != {
                n.snap: id(n) for n in self._nodes() if n.snap is not None}:
            raise AssertionError("snap_nodes is not the trie's snapshots")
        dead = [e for e in held if self.snaps.refs[e] <= 0]
        if dead:
            raise AssertionError(f"trie nodes hold dead snapshots {dead}")

    @property
    def cached_pages(self) -> int:
        return sum(1 for n in self._nodes() if n.host_kv is None)

    @property
    def snapshots_live(self) -> int:
        return len(self.snap_nodes)

    def stats(self) -> Dict[str, int]:
        state = {} if self.snaps is None else {
            "snapshots_live": self.snapshots_live,
            "snapshots_taken": self.snapshots_taken,
            "snapshots_evicted": self.evicted_snapshots}
        return {"lookups": self.lookups, "hits": self.hits,
                "hit_tokens": self.hit_tokens,
                "late_hits": self.late_hits,
                "late_hit_tokens": self.late_hit_tokens,
                "cached_pages": self.cached_pages,
                "inserted_pages": self.inserted_pages,
                "evicted_pages": self.evicted_pages,
                "evicted_window_pages": self.evicted_window_pages,
                "host_pages": self.host_pages,
                "host_hits": self.host_hits,
                "demoted_pages": self.demoted_pages,
                "promoted_pages": self.promoted_pages, **state}


class _KindPages:
    """One KIND of page (``paged_layout.PageKind``) as the host holds
    it: the pool's size and its trash page (the last), the allocator, the table
    ``[slots, pages_per_seq]`` and the pages each slot holds a reference
    on, ``held[slot]``, in the order of the blocks they stand for from
    block ``lo[slot]`` on.

    A kind that retains every position (``window`` None) reserves a
    slot's whole context at admission: ``lo`` stays 0.  A kind that
    retains a window maps a block when a launch first writes into it
    (``ContinuousBatchingEngine._map_pages``) and gives a block back
    once no row to come can read it (``_recycle``); what it reserves at
    admission is a CLAIM of at most ``bound`` pages, and the sum of the
    live slots' claims never passes the pool.  Then a slot in need of a
    page finds one: the pages no slot holds are at least the claims not
    yet taken up, and each is free or held by the prefix cache alone,
    which gives a window kind's page up on demand."""

    def __init__(self, kind: PageKind, num_pages: int, max_slots: int,
                 pages_per_seq: int, bound: Optional[int]):
        self.kind = kind
        self.window = kind.window
        self.num_pages = int(num_pages)
        self.trash = self.num_pages - 1
        self.alloc = PageAllocator(self.num_pages - 1)
        self.tables = np.full((max_slots, pages_per_seq), -1, np.int32)
        self.held: Dict[int, List[int]] = {}
        self.lo: Dict[int, int] = {}
        self.bound = bound
        self.claim: Dict[int, int] = {}
        self.recycled = 0       # pages given back since the last marker

    def claim_of(self, need: int) -> int:
        """Pages a request of ``need`` blocks reserves of this kind."""
        return need if self.bound is None else min(need, self.bound)

    def release(self, slot: int) -> None:
        self.alloc.release(self.held.pop(slot))
        self.lo.pop(slot, None)
        self.claim.pop(slot, None)
        self.tables[slot] = -1
