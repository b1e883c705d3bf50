"""Fixed-size page allocator + prefix-sharing index for the paged
KV-cache slot pool.

The dense slot pool sized every row to ``max_context``, so pool HBM
was ``max_slots x max_context`` whatever the actual request mix. The
paged pool (the block-table formulation of PAPERS.md's "Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching for
Inference") stores K/V in a global pool of fixed-size pages of
``page_size`` positions each; every slot owns a *page table* — an
int32 index array of ``ceil(max_context / page_size)`` entries — and
the jitted programs gather a slot's logical cache view through it.
Concurrency is then bounded by PAGES, not by worst-case context:
admission reserves only the pages a request's own prompt + budget can
ever touch (``ceil((prompt + n_new [+ gamma + 1]) / page_size)``),
never ``max_context`` worth.

Pages are REFCOUNTED: prefix sharing (:class:`PrefixCache`) lets many
slots — and the cache index itself — hold the same physical page, so
:meth:`PagePool.free` releases one reference and a page returns to
the free list only when its last holder lets go. ``in_use`` counts a
shared page ONCE, however many slots adopted it (the fleet /metrics
aggregation reads these gauges; double-counting a shared system
prompt would report phantom HBM).

This module is the pure-host half: the allocator (free list, refcount
ledger, usage accounting, exhaustion counters) and the prefix index (a
radix tree over ``page_size``-token blocks mapping shared prompt
prefixes to pages, LRU-evicted under allocator pressure). Device-side
page pools are shaped by ``quant/kv.py``'s
:func:`~veles_tpu.quant.kv.block_page_pool`; the jitted gather/scatter
lives in ``serving/engine.py``.

Page 0 is the SINK: it is never allocated, and masked/retired rows in
the fixed-shape programs direct their writes at it (a batched scatter
needs *some* in-bounds target for every lane). Sink content is
garbage by design and no live page table ever points at it for a
position a read mask can reach.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..telemetry.counters import inc


def pages_for(positions: int, page_size: int) -> int:
    """Pages needed to hold ``positions`` cache rows (ceil div)."""
    return max(0, (int(positions) + page_size - 1) // page_size)


def view_ladder(pages_per_slot: int, page_size: int,
                min_bucket: int) -> Tuple[int, ...]:
    """The view lengths THE decode step runs at, in pages, longest
    first: ``pages_per_slot`` and its half, where the half is whole
    pages and no shorter than the smallest prefill bucket;
    ``(pages_per_slot,)`` otherwise. A length is a compiled program
    that its first dispatch builds, and a server's warm-up reaches
    what it has been given: two, so that traffic under half of
    ``max_context`` warms the rung it will run at."""
    whole = int(pages_per_slot)
    half = whole // 2
    if whole % 2 == 0 and half * page_size >= min_bucket:
        return whole, half
    return (whole,)


def view_rung(ladder: Sequence[int], positions: int,
              page_size: int) -> int:
    """The shortest rung of ``ladder`` (pages) whose view holds
    ``positions`` cache rows; the longest (its first) where none
    does."""
    need = pages_for(positions, page_size)
    return min((r for r in ladder if r >= need), default=ladder[0])


def per_shard_kv_heads(n_kv_heads: int, tp: int = 1) -> int:
    """K/V heads each mesh shard STORES per logical page under
    tensor-parallel serving (``serving/engine.py`` ``tp=`` knob).

    The allocator above — page ids, tables, refcounts, ``in_use`` —
    indexes LOGICAL pages only: one page means "``page_size``
    positions of one slot's cache", wherever its head slices live.
    Under ``tp=N`` the device pool's kv-head axis is sharded over the
    ``("model",)`` mesh, so each chip holds ``n_kv_heads / N`` heads
    of every logical page and the HOST-side admission/eviction math
    is identical at every ``tp`` — which is exactly why the scheduler
    can stay shard-agnostic. Raises ValueError on a ragged split
    (a shard holding half a head would change the attention math)."""
    n_kv_heads, tp = int(n_kv_heads), max(1, int(tp))
    if n_kv_heads % tp:
        raise ValueError("kv heads %d %% tp %d != 0 — a ragged "
                         "head shard cannot serve id-exact"
                         % (n_kv_heads, tp))
    return n_kv_heads // tp


class PagePool:
    """Refcounted free-list allocator over ``pages`` usable pages
    (device rows ``1..pages``; row 0 is the sink). Thread-safe; the
    scheduler allocates at admission, the engine allocates growth at
    step boundaries and frees at retirement; the prefix cache and
    adopting slots :meth:`share` pages they did not allocate."""

    def __init__(self, pages: int, page_size: int) -> None:
        if pages < 1:
            raise ValueError("page pool needs >= 1 usable page")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.pages = int(pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        self._free: List[int] = list(range(1, self.pages + 1))
        #: page id -> holders (slots + the prefix index); a page is in
        #: the free list iff it has no entry here
        self._rc: Dict[int, int] = {}
        #: pressure valve: called OUTSIDE the pool lock with the page
        #: shortfall when :meth:`alloc` cannot satisfy a request; the
        #: engine points it at :meth:`PrefixCache.evict` so cached
        #: prefixes are reclaimed LRU-first before anyone is refused
        self.evictor: Optional[Callable[[int], int]] = None

    @property
    def device_rows(self) -> int:
        """Rows the device arrays carry: the usable pages + the sink."""
        return self.pages + 1

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def in_use(self) -> int:
        """Pages with at least one holder — a SHARED page counts once,
        not per adopting slot (satellite fix: the fragmentation gauge
        and fleet ``pages_in_use`` aggregation stay truthful under
        prefix sharing)."""
        with self._lock:
            return self.pages - len(self._free)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._rc.get(int(page), 0)

    def ledger(self) -> Dict[int, int]:
        """Snapshot of the refcount ledger (poisoning/balance tests:
        after all slots retire and the prefix cache clears, this must
        be empty and ``in_use()`` zero)."""
        with self._lock:
            return dict(self._rc)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` page ids (each with refcount 1), or None when the
        pool cannot satisfy the request (exhaustion — counted; the
        caller decides between waiting for retirements and shedding
        503 + Retry-After). Under pressure the :attr:`evictor` is
        asked ONCE to release cached-prefix pages before refusing."""
        n = int(n)
        if n <= 0:
            return []
        evicted = False
        while True:
            with self._lock:
                if len(self._free) >= n:
                    out, self._free = self._free[:n], self._free[n:]
                    for page in out:
                        self._rc[page] = 1
                    break
                shortfall = n - len(self._free)
            if self.evictor is not None and not evicted:
                # outside the lock: the evictor frees pages through
                # free(), which takes the lock itself
                evicted = True
                try:
                    self.evictor(shortfall)
                except Exception:   # noqa: BLE001 — pressure valve only
                    pass
                continue
            inc("veles_serving_pages_exhausted_total")
            return None
        inc("veles_serving_pages_alloc_total", n)
        return out

    def share(self, page: int) -> int:
        """Take one more reference on an allocated page (prefix
        adoption / cache insertion). Raises on a page nobody holds —
        sharing a freed page would alias the next admission's data,
        the exact poisoning the refcount ledger exists to prevent."""
        page = int(page)
        with self._lock:
            rc = self._rc.get(page)
            if rc is None:
                raise ValueError(
                    "page %d is not allocated — cannot share" % page)
            self._rc[page] = rc + 1
            return rc + 1

    def free(self, ids: Sequence[int]) -> None:
        """Release one reference per page; pages whose LAST reference
        dropped return to the free list (counted — the alloc/free
        counters balance against ``in_use``, not against raw
        share/release traffic)."""
        if not ids:
            return
        released = 0
        with self._lock:
            for i in ids:
                page = int(i)
                rc = self._rc.get(page)
                if rc is None:
                    # double free — tolerated like the idempotent slot
                    # retire (shutdown sweeps may race), never counted
                    continue
                if rc > 1:
                    self._rc[page] = rc - 1
                    continue
                del self._rc[page]
                self._free.append(page)
                released += 1
            self._free.sort()
        if released:
            inc("veles_serving_pages_free_total", released)


class _PrefixNode:
    """One cached ``page_size``-token block: the exact tokens (THE
    match key — hashes pick the dict slot, token equality decides, so
    a corrupted index can only degrade to a miss, never to wrong
    tokens), the physical page holding its K/V rows, and the LRU
    stamp."""

    __slots__ = ("tokens", "page", "children", "parent", "last_use")

    def __init__(self, tokens: Tuple[int, ...], page: int,
                 parent: Optional["_PrefixNode"]) -> None:
        self.tokens = tokens
        self.page = page
        self.children: Dict[Tuple[int, ...], "_PrefixNode"] = {}
        self.parent = parent
        self.last_use = 0


class PrefixCache:
    """Radix tree over hashed token blocks (block = ``page_size``
    tokens) mapping shared prompt prefixes to refcounted pages.

    Admission walks the tree over a prompt's full blocks; every
    matched node's page is :meth:`PagePool.share`-adopted into the new
    slot's page table, so the slot's prefill covers only the unmatched
    suffix — a 2k-token system prompt shared by the whole pool costs
    its pages and its prefill FLOPs once. After a prefill completes,
    the slot's own full blocks are :meth:`insert`-ed so the NEXT
    admission shares them.

    The tree holds its own page references (a retired writer's prefix
    outlives it), released by LRU leaf eviction under allocator
    pressure (:meth:`evict` — wired as :attr:`PagePool.evictor`) or
    :meth:`clear`. All mutation happens on the engine's tick thread;
    the lock exists for the /metrics stats reads."""

    def __init__(self, pool: PagePool, page_size: int,
                 max_blocks: Optional[int] = None) -> None:
        self.pool = pool
        self.page_size = int(page_size)
        #: soft block budget: insertions past it evict LRU leaves
        #: first (0/None = bounded only by allocator pressure)
        self.max_blocks = int(max_blocks or 0)
        self._lock = threading.Lock()
        self._root = _PrefixNode((), 0, None)
        self._clock = 0
        self._blocks = 0

    def _blocks_of(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        p = self.page_size
        n = len(tokens) // p
        return [tuple(int(t) for t in tokens[i * p:(i + 1) * p])
                for i in range(n)]

    def match(self, tokens: Sequence[int],
              corrupt=None) -> List[int]:
        """Walk the tree over ``tokens``' full blocks; returns the
        matched pages IN ORDER, each with a reference already taken
        for the caller (the adopting slot owns them like its own
        allocations — :meth:`PagePool.free` at retirement releases).

        ``corrupt`` is the armed ``serve.prefix_match`` fault: when
        set, every candidate block key is damaged before the equality
        check — a corrupted index DEGRADES to a shorter (or empty)
        match and a full prefill, never to wrong tokens, because the
        token comparison is the authority, not the hash."""
        matched: List[int] = []
        with self._lock:
            node = self._root
            self._clock += 1
            for block in self._blocks_of(tokens):
                key = block
                if corrupt is not None:
                    # damage the LOOKUP key the way a rotten index
                    # entry would: the tokens no longer compare equal,
                    # so the walk stops and the suffix prefills fully
                    raw = bytearray()
                    for t in block:
                        raw += int(t).to_bytes(8, "little", signed=True)
                    raw = corrupt.corrupt(bytes(raw))
                    key = tuple(
                        int.from_bytes(raw[i:i + 8], "little",
                                       signed=True)
                        for i in range(0, len(raw) - len(raw) % 8, 8))
                child = node.children.get(key)
                if child is None or child.tokens != block:
                    break
                child.last_use = self._clock
                self.pool.share(child.page)
                matched.append(child.page)
                node = child
        return matched

    def insert(self, tokens: Sequence[int],
               pages: Sequence[int]) -> int:
        """Record ``tokens``' full blocks, backed by the slot's
        ``pages`` (parallel lists: block i lives in ``pages[i]``).
        Blocks already present are only LRU-touched (the tree keeps
        its existing page — two identical prefills must not hold two
        copies); new nodes take their own reference on the slot's
        page, which therefore survives the slot's retirement. Returns
        the number of NEW blocks cached."""
        blocks = self._blocks_of(tokens)
        added = 0
        with self._lock:
            self._clock += 1
            node = self._root
            for i, block in enumerate(blocks):
                if i >= len(pages):
                    break
                child = node.children.get(block)
                if child is None:
                    try:
                        self.pool.share(int(pages[i]))
                    except ValueError:
                        break          # page already gone — stop here
                    child = _PrefixNode(block, int(pages[i]), node)
                    node.children[block] = child
                    self._blocks += 1
                    added += 1
                child.last_use = self._clock
                node = child
        if self.max_blocks and self._blocks > self.max_blocks:
            self.evict(0, over_budget=True)
        return added

    def _leaves(self) -> List[_PrefixNode]:
        out: List[_PrefixNode] = []
        stack = [self._root]
        while stack:
            node = stack.pop()
            kids = list(node.children.values())
            if not kids and node is not self._root:
                out.append(node)
            stack.extend(kids)
        return out

    def evict(self, need_pages: int, over_budget: bool = False) -> int:
        """Drop least-recently-used LEAF blocks (a block with cached
        children anchors their prefix and is never dropped first)
        until ``need_pages`` pages actually returned to the free list
        — or, with ``over_budget``, until the soft block budget holds.
        ONE tree walk seeds a heap of leaves; evicting a leaf can
        only promote its parent, which is pushed as it becomes
        childless — so reclaiming k pages is O(blocks + k log blocks),
        never a re-walk per drop on the allocator-pressure path an
        admission is waiting on. Counted per dropped block. Returns
        pages actually freed."""
        import heapq
        freed = 0
        dropped = 0
        with self._lock:
            heap = [(n.last_use, i, n)
                    for i, n in enumerate(self._leaves())]
            heapq.heapify(heap)
            tie = len(heap)
            while heap:
                if over_budget:
                    if not self.max_blocks \
                            or self._blocks <= self.max_blocks:
                        break
                elif freed >= need_pages:
                    break
                _, _, victim = heapq.heappop(heap)
                parent = victim.parent
                if victim.children or parent is None \
                        or parent.children.get(victim.tokens) \
                        is not victim:
                    continue           # stale heap entry
                parent.children.pop(victim.tokens, None)
                self._blocks -= 1
                dropped += 1
                before = self.pool.free_count()
                self.pool.free([victim.page])
                freed += self.pool.free_count() - before
                if parent is not self._root and not parent.children:
                    heapq.heappush(heap, (parent.last_use, tie,
                                          parent))
                    tie += 1
        if dropped:
            inc("veles_prefix_evictions_total", dropped)
        return freed

    def clear(self) -> None:
        """Release every cached block's page reference (engine stop /
        ledger-balance tests)."""
        with self._lock:
            stack = [self._root]
            pages: List[int] = []
            while stack:
                node = stack.pop()
                kids = list(node.children.values())
                stack.extend(kids)
                if node is not self._root:
                    pages.append(node.page)
            self._root = _PrefixNode((), 0, None)
            self._blocks = 0
        self.pool.free(pages)

    def cached_pages(self) -> List[int]:
        """Every page the index currently references (full blocks by
        construction) — the engine's fragmentation gauge stamps them
        fully occupied."""
        with self._lock:
            out: List[int] = []
            stack = [self._root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if node is not self._root:
                    out.append(node.page)
            return out

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"blocks": self._blocks,
                    "pages": self._blocks}


class _StateNode:
    """One checkpointed ``page_size``-token block of a recurrent
    prompt: the exact tokens (the match key — token equality is the
    authority, same degrade-to-miss contract as :class:`_PrefixNode`)
    and the HOST snapshot of the recurrent state pytree as it stood
    AFTER this block was scanned."""

    __slots__ = ("tokens", "state", "nbytes", "children", "parent",
                 "last_use")

    def __init__(self, tokens: Tuple[int, ...], state, nbytes: int,
                 parent: Optional["_StateNode"]) -> None:
        self.tokens = tokens
        self.state = state
        self.nbytes = int(nbytes)
        self.children: Dict[Tuple[int, ...], "_StateNode"] = {}
        self.parent = parent
        self.last_use = 0


class StateCache:
    """Prefix cache for the O(1)-state lane: a radix tree over
    ``page_size``-token blocks whose payload is a STATE SNAPSHOT, not
    a page.

    A transformer prefix is a range of KV rows, so :class:`PrefixCache`
    shares pages. A recurrent prefix is fully summarized by the state
    vector after its last token, so this tree stores one host-side
    snapshot of the state pytree per block boundary. Admission calls
    :meth:`match` with the prompt: the deepest matched node's snapshot
    is adopted COPY-ON-WRITE — the caller uploads it into its slot's
    state rows and never mutates the host copy — and the slot's scan
    covers only the unmatched suffix. After prefill the slot's own
    block-boundary snapshots are :meth:`insert`-ed so the next
    admission with the same prefix skips the re-scan.

    Snapshots are plain host pytrees (dict of numpy arrays) and own no
    pool pages — eviction is purely the soft ``max_blocks`` budget,
    LRU leaves first (counted as ``veles_o1_state_evictions_total``).
    All mutation happens on the engine's tick thread; the lock exists
    for the /metrics stats reads."""

    def __init__(self, page_size: int,
                 max_blocks: Optional[int] = None) -> None:
        self.page_size = int(page_size)
        self.max_blocks = int(max_blocks or 0)
        self._lock = threading.Lock()
        self._root = _StateNode((), None, 0, None)
        self._clock = 0
        self._blocks = 0
        self._bytes = 0

    def _blocks_of(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        p = self.page_size
        n = len(tokens) // p
        return [tuple(int(t) for t in tokens[i * p:(i + 1) * p])
                for i in range(n)]

    @staticmethod
    def _snapshot_bytes(state) -> int:
        total = 0
        stack = [state]
        while stack:
            node = stack.pop()
            if isinstance(node, dict):
                stack.extend(node.values())
            elif isinstance(node, (list, tuple)):
                stack.extend(node)
            else:
                total += int(getattr(node, "nbytes", 0))
        return total

    def match(self, tokens: Sequence[int], corrupt=None):
        """Walk the tree over ``tokens``' full blocks; returns
        ``(n_tokens_matched, snapshot)`` for the DEEPEST matched node
        (``(0, None)`` on a miss). Unlike the paged cache there is
        nothing per-block to adopt — the last boundary's snapshot
        subsumes all of them.

        ``corrupt`` is the armed ``serve.state_restore`` fault acting
        on the index: every candidate block key is damaged before the
        equality check, so a rotten index DEGRADES to a shorter (or
        empty) match and a longer re-scan — never to a wrong state,
        because token equality is the authority."""
        best = None
        depth = 0
        with self._lock:
            node = self._root
            self._clock += 1
            for block in self._blocks_of(tokens):
                key = block
                if corrupt is not None:
                    raw = bytearray()
                    for t in block:
                        raw += int(t).to_bytes(8, "little", signed=True)
                    raw = corrupt.corrupt(bytes(raw))
                    key = tuple(
                        int.from_bytes(raw[i:i + 8], "little",
                                       signed=True)
                        for i in range(0, len(raw) - len(raw) % 8, 8))
                child = node.children.get(key)
                if child is None or child.tokens != block:
                    break
                child.last_use = self._clock
                best = child.state
                depth += self.page_size
                node = child
        return depth, best

    def insert(self, tokens: Sequence[int], snapshots) -> int:
        """Record ``tokens``' full blocks with their block-boundary
        ``snapshots`` (parallel lists: ``snapshots[i]`` is the state
        after block i's last token — host pytrees the caller no longer
        mutates). Blocks already present are only LRU-touched (first
        writer wins; two identical prefills carry bit-identical states
        anyway, the scan is deterministic). A ``None`` snapshot marks
        a block the caller did NOT re-scan (it was adopted from this
        cache): the existing node is touched, but if eviction dropped
        it meanwhile the walk stops — a node without a real snapshot
        must never exist. Returns NEW blocks cached."""
        blocks = self._blocks_of(tokens)
        added = 0
        with self._lock:
            self._clock += 1
            node = self._root
            for i, block in enumerate(blocks):
                if i >= len(snapshots):
                    break
                child = node.children.get(block)
                if child is None:
                    if snapshots[i] is None:
                        break
                    nbytes = self._snapshot_bytes(snapshots[i])
                    child = _StateNode(block, snapshots[i], nbytes,
                                       node)
                    node.children[block] = child
                    self._blocks += 1
                    self._bytes += nbytes
                    added += 1
                child.last_use = self._clock
                node = child
        if self.max_blocks and self._blocks > self.max_blocks:
            self.evict()
        return added

    def _leaves(self) -> List[_StateNode]:
        out: List[_StateNode] = []
        stack = [self._root]
        while stack:
            node = stack.pop()
            kids = list(node.children.values())
            if not kids and node is not self._root:
                out.append(node)
            stack.extend(kids)
        return out

    def evict(self) -> int:
        """Drop least-recently-used LEAF blocks until the soft block
        budget holds (a block with cached children anchors their
        prefix and is never dropped first). Same one-walk heap shape
        as :meth:`PrefixCache.evict`. Counted per dropped block."""
        import heapq
        dropped = 0
        with self._lock:
            if not self.max_blocks:
                return 0
            heap = [(n.last_use, i, n)
                    for i, n in enumerate(self._leaves())]
            heapq.heapify(heap)
            tie = len(heap)
            while heap and self._blocks > self.max_blocks:
                _, _, victim = heapq.heappop(heap)
                parent = victim.parent
                if victim.children or parent is None \
                        or parent.children.get(victim.tokens) \
                        is not victim:
                    continue           # stale heap entry
                parent.children.pop(victim.tokens, None)
                self._blocks -= 1
                self._bytes -= victim.nbytes
                dropped += 1
                if parent is not self._root and not parent.children:
                    heapq.heappush(heap, (parent.last_use, tie,
                                          parent))
                    tie += 1
        if dropped:
            inc("veles_o1_state_evictions_total", dropped)
        return dropped

    def clear(self) -> None:
        with self._lock:
            self._root = _StateNode((), None, 0, None)
            self._blocks = 0
            self._bytes = 0

    def state_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"blocks": self._blocks,
                    "bytes": self._bytes}
