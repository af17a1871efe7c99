"""Radix prefix cache: cross-request KV reuse for the serving scheduler.

The engine's only KV state was per-sequence — every admission recomputed
its whole prompt even though production chat/RAG traffic is dominated by
shared prefixes (system prompts, few-shot templates, multi-turn history).
This module adds the missing subsystem in the style of SGLang's
RadixAttention (Zheng et al., 2024) over vLLM-shaped block granularity
(Kwon et al., SOSP '23), folded into this engine's fixed-compilation-key
discipline (PAPERS.md annotates both):

  * a RADIX INDEX over token prefixes at fixed block granularity — each
    edge is exactly one ``block_len``-token block (the tree IS
    block-granular, so edges never need splitting and lookup is a dict
    walk), key = the token-id block, value = an on-device block handle;
  * a REFERENCE-COUNTED BLOCK POOL carved from a dedicated
    ``(num_blocks, layers, kv_heads, block_len, head_size)`` K/V arena
    (``Engine.new_prefix_arena``) with LRU eviction of UNREFERENCED
    LEAVES — eviction can never free a block a pinned (in-flight) path
    references, and evicting leaves only keeps the tree prefix-closed;
  * scheduler integration (runtime/scheduler.py): on admission the
    longest cached prefix seeds the slot's cache rows via the jitted,
    donation-safe ``Engine.slot_seed_prefix`` and only the uncached
    suffix prefills; when a slot's prompt finishes prefilling, its
    PROMPT K/V is PUBLISHED back into the tree in blocks
    (``Engine.slot_publish_block``). Prefill-written blocks only —
    decode-step K/V is not guaranteed bitwise-equal to a cold
    prefill's, so publishing a decode extension would void the
    exact-parity guarantee (Scheduler._release_slot_cache).

Correctness invariants (the reason this file is small but subtle):

  * EXACT-TOKEN-MATCH ONLY — an edge matches iff its whole token block
    is identical; K/V stores post-RoPE keys at absolute positions, so a
    block is only valid as the same tokens at the same positions, which
    a prefix walk guarantees by construction.
  * BLOCKS ARE IMMUTABLE ONCE PUBLISHED — publish copies cache -> arena,
    seed copies arena -> cache; nothing ever writes a published block in
    place (a second publish of the same prefix walks the existing node
    and copies nothing).
  * A LOOKUP NEVER COVERS THE WHOLE PROMPT — at least one suffix token
    must prefill so the finishing chunk has real logits to sample from
    (the same ``len - 1`` cap the API server's legacy prefix reuse
    applies).
  * THE ARENA DIES WITH THE ENGINE — ``invalidate()`` drops the whole
    tree; the scheduler calls it on abort, and a supervisor rebuild
    mints a fresh engine + arena + empty tree
    (runtime/resilience.EngineSupervisor._make_sched), so recovered
    generations can never seed from a dead engine's blocks.

Thread model: every method is called from the scheduler's step loop
under its step mutex (admission, publish, retire all happen in-step);
the counters in ``stats`` are plain ints a /stats reader may snapshot
lock-free under the GIL.
"""

from __future__ import annotations

import heapq

import numpy as np

from .stats import PrefixCacheStats


class _Node:
    """One radix edge/node: ``key`` is the block's token tuple, ``block``
    the arena slot holding its K/V. ``refs`` counts in-flight slots
    pinned through this node; ``last_use`` is the LRU clock stamp."""

    __slots__ = ("key", "block", "parent", "children", "refs", "last_use",
                 "epoch")

    def __init__(self, key, block, parent, epoch=0):
        self.key = key
        self.block = block
        self.parent = parent
        self.children: dict = {}
        self.refs = 0
        self.last_use = 0
        # invalidate() generation this node belongs to: a detached
        # depth>=2 node still hangs off its (equally detached) parent,
        # so the parent.children attachment check alone cannot tell it
        # from a live node — the epoch can, in O(1) per invalidate
        self.epoch = epoch


class PrefixCache:
    def __init__(self, engine, *, num_blocks: int, block_len: int,
                 stats: PrefixCacheStats | None = None,
                 transfer: bool = False):
        assert num_blocks >= 1, num_blocks
        assert 1 <= block_len <= engine.seq_len, block_len
        engine.spec.refuse("kv_transfer" if transfer else "prefix_cache")
        self.engine = engine
        # cross-replica KV block transfer (runtime/kv_transfer.py): when
        # armed, warmup() also compiles the block export/import
        # executables so donor serving and cache fills mint ZERO
        # post-warmup keys (--freeze-compiles stays green)
        self.transfer = bool(transfer)
        self.block_len = int(block_len)
        self.num_blocks = int(num_blocks)
        # fixed seed width: ONE compilation key for slot_seed_prefix —
        # every lookup result pads its block_ids up to this
        self.max_seed_blocks = max(engine.seq_len // self.block_len, 1)
        self.arena_k, self.arena_v = engine.new_prefix_arena(
            num_blocks, self.block_len)
        self._root = _Node(None, -1, None)  # sentinel: never evicted
        self._free = list(range(num_blocks))
        # LRU eviction candidates: a LAZY min-heap of
        # (last_use_at_push, seq, node). Entries go stale when the node
        # is re-touched, pinned, extended, or detached — _evict_lru_leaf
        # validates on pop and discards stale ones, so every candidate
        # transition is an O(log n) push instead of an O(nodes) tree
        # scan per allocated block inside the scheduler's step mutex
        self._heap: list = []
        self._seq = 0
        self._tick = 0
        self._epoch = 0
        self.stats = stats or PrefixCacheStats()
        self.stats.num_blocks = self.num_blocks
        self.stats.block_len = self.block_len

    # -- lookup / seed ----------------------------------------------------

    def _walk(self, tokens: list[int], max_blocks: int) -> list[_Node]:
        """Longest cached prefix of `tokens`, whole blocks only (a
        non-block-aligned remainder never matches — partial blocks are
        not indexed), capped at `max_blocks`."""
        bl = self.block_len
        path: list[_Node] = []
        node = self._root
        for i in range(min(len(tokens) // bl, max_blocks)):
            child = node.children.get(tuple(tokens[i * bl: (i + 1) * bl]))
            if child is None:
                break
            path.append(child)
            node = child
        return path

    def match_len(self, tokens: list[int]) -> int:
        """Read-only PEEK at the longest cached prefix (same whole-blocks,
        len-1-capped rule as ``lookup_pin``), for the router's cache-aware
        placement (runtime/router.py): no pin, no LRU touch, no stats —
        a routing probe must not skew hit_rate or protect blocks. Called
        from OUTSIDE the step mutex: the walk only READS children dicts
        (GIL-atomic per access), so a concurrent publish/evict can at
        worst make the answer transiently stale — which costs one
        suboptimal placement, never correctness (the admission's own
        lookup_pin re-walks under the mutex)."""
        usable = max(len(tokens) - 1, 0) // self.block_len
        return len(self._walk(tokens, usable)) * self.block_len

    def lookup_pin(self, tokens: list[int]):
        """Longest cached prefix usable for `tokens`: returns
        (n_tokens, block_ids, pins). The matched path is PINNED
        (refcounted) until the caller unpins — an in-flight slot's
        blocks can never be evicted out from under it. The match is
        capped at len(tokens) - 1 so at least one suffix token prefills
        (the finishing chunk must have real logits to sample)."""
        self._tick += 1
        self.stats.lookups += 1
        usable = max(len(tokens) - 1, 0) // self.block_len
        path = self._walk(tokens, usable)
        if not path:
            return 0, [], ()
        for node in path:
            node.refs += 1
            node.last_use = self._tick
        self.stats.hits += 1
        n = len(path) * self.block_len
        self.stats.tokens_saved += n
        return n, [node.block for node in path], tuple(path)

    def seed_slot(self, row: int, block_ids: list[int]) -> None:
        """Seed slot `row` from `block_ids` via the jitted entry point,
        padding to the fixed width (pad block 0: its writes land beyond
        the real prefix and are overwritten before any query attends
        them — seed_rows_from_blocks documents the invariant)."""
        ids = np.zeros((self.max_seed_blocks,), np.int32)
        ids[: len(block_ids)] = block_ids
        self.engine.slot_seed_prefix(self.arena_k, self.arena_v, row, ids)

    def unpin(self, pins) -> None:
        """Release a lookup_pin path (slot retired/aborted). Tolerates
        nodes an invalidate() already detached — their counters are
        orphaned bookkeeping, never a double-free (the free list is
        rebuilt wholesale on invalidate)."""
        for node in pins:
            node.refs = max(node.refs - 1, 0)
            self._push_candidate(node)  # may just have become evictable

    # -- publish ----------------------------------------------------------

    def publish(self, row: int, tokens: list[int]) -> None:
        """Index slot `row`'s filled K/V under `tokens` (whole blocks
        only). Walks existing nodes for free (dedup — republishing a
        shared prefix copies nothing) and copies only NEW blocks out of
        the cache row into freshly allocated arena slots. Stops at the
        first block the pool cannot serve (publish_drops) — dropping the
        TAIL keeps the tree prefix-closed.

        The walk path is PINNED while publishing: an allocation's
        eviction must never take the node the walk stands on (it would
        attach the next block under a detached parent — an unreachable
        subtree leaking pool slots)."""
        self._tick += 1
        bl = self.block_len
        node = self._root
        path: list[_Node] = []
        try:
            for i in range(min(len(tokens) // bl, self.max_seed_blocks)):
                key = tuple(tokens[i * bl: (i + 1) * bl])
                child = node.children.get(key)
                if child is None:
                    block = self._alloc()
                    if block is None:
                        self.stats.publish_drops += 1
                        return
                    self.arena_k, self.arena_v = (
                        self.engine.slot_publish_block(
                            self.arena_k, self.arena_v, row, i * bl, block))
                    child = _Node(key, block, node, epoch=self._epoch)
                    node.children[key] = child
                    self.stats.blocks_published += 1
                    self.stats.blocks_in_use += 1
                child.refs += 1
                path.append(child)
                child.last_use = self._tick
                node = child
        finally:
            for n in path:
                n.refs = max(n.refs - 1, 0)
            if path:
                self._push_candidate(path[-1])  # the walk's deepest leaf

    # -- cross-replica block transfer (runtime/kv_transfer.py) ------------

    def export_pin(self, tokens: list[int]):
        """Donor side of a cache FILL: the full whole-block matched path
        of ``tokens``, PINNED until the caller unpins — eviction must
        never free a block mid-transfer. Unlike ``lookup_pin`` there is
        NO len-1 cap: the cap exists so a SEEDING slot's finishing chunk
        samples real logits, but an exported block only ever reaches a
        sibling's radix tree, whose own admission lookup re-applies the
        cap. No hit/tokens_saved stats skew either — a transfer is not
        an admission. Returns (n_tokens, block_ids, pins)."""
        self._tick += 1
        path = self._walk(tokens, min(len(tokens) // self.block_len,
                                      self.max_seed_blocks))
        for node in path:
            node.refs += 1
            node.last_use = self._tick
        return (len(path) * self.block_len,
                [node.block for node in path], tuple(path))

    def export_block_host(self, block_id: int):
        """Fetch one arena block pair to host numpy — the bytes a
        BLOCK_DATA frame ships. Must run under the scheduler's step
        mutex like every arena access: a concurrent publish DONATES the
        arena arrays (slot_publish_block), so a reference snapshotted
        outside the mutex could be a deleted buffer by read time."""
        k, v = self.engine.block_export(self.arena_k, self.arena_v,
                                        block_id)
        return np.asarray(k), np.asarray(v)

    def import_path(self, tokens: list[int], start_block: int,
                    blocks: list) -> int:
        """Importer side of a cache FILL: attach fetched block pairs
        ``blocks`` (host (L, KVH, bl, hs) K/V arrays for whole blocks
        ``start_block..``) under the token path of ``tokens``, writing
        each NEW block into a freshly allocated arena slot
        (``Engine.slot_import_block``). Walks existing nodes for free
        (dedup — a racing local publish wins and the shipped bytes for
        that index are discarded); stops at the first block the pool
        cannot serve (dropping the TAIL keeps the tree prefix-closed);
        returns tokens actually imported. If the parent chain below
        ``start_block`` broke since the caller measured its own match
        (local eviction), nothing is attachable prefix-closed and the
        import aborts to 0 — the admission simply re-prefills.

        The walk path is pinned while importing, same as publish: an
        allocation's eviction must never take a node the walk stands
        on."""
        bl = self.block_len
        self._tick += 1
        node = self._root
        imported = 0
        end = min(start_block + len(blocks), self.max_seed_blocks,
                  len(tokens) // bl)
        path: list[_Node] = []
        try:
            for i in range(end):
                key = tuple(tokens[i * bl: (i + 1) * bl])
                child = node.children.get(key)
                if child is None:
                    if i < start_block:
                        return 0  # broken parent chain: unattachable
                    block = self._alloc()
                    if block is None:
                        break  # pool full of pinned/live blocks: drop tail
                    k_np, v_np = blocks[i - start_block]
                    self.arena_k, self.arena_v = (
                        self.engine.slot_import_block(
                            self.arena_k, self.arena_v, k_np, v_np,
                            block))
                    child = _Node(key, block, node, epoch=self._epoch)
                    node.children[key] = child
                    self.stats.blocks_in_use += 1
                    imported += 1
                child.refs += 1
                path.append(child)
                child.last_use = self._tick
                node = child
        finally:
            for n in path:
                n.refs = max(n.refs - 1, 0)
            if path:
                self._push_candidate(path[-1])
        return imported * bl

    def _alloc(self) -> int | None:
        if self._free:
            return self._free.pop()
        return self._evict_lru_leaf()

    def _entry_valid(self, last_use: int, node: _Node) -> bool:
        """Does a heap entry still describe reality? Stale when the node
        was re-touched (last_use moved), pinned, extended into an
        interior node, detached, or belongs to a pre-invalidate()
        epoch — a detached deep node still hangs off its detached
        parent, so the attachment check alone cannot catch it, and
        returning its block would double-allocate a slot the rebuilt
        free list already owns."""
        return (node.epoch == self._epoch
                and node.refs == 0 and not node.children
                and node.parent is not None
                and node.parent.children.get(node.key) is node
                and node.last_use == last_use)

    def _push_candidate(self, node: _Node) -> None:
        """Record `node` as a possible eviction victim. Only attached,
        unreferenced leaves qualify NOW; whether the entry is still
        valid at pop time is re-checked there (lazy invalidation)."""
        if (node.refs == 0 and not node.children
                and self._entry_valid(node.last_use, node)):
            self._seq += 1
            heapq.heappush(self._heap, (node.last_use, self._seq, node))
            if len(self._heap) > max(4 * self.num_blocks, 64):
                # compaction: stale entries are normally discarded only
                # by eviction pops, which never run while the free list
                # keeps serving — on a long-lived server with an ample
                # pool the heap would otherwise grow one entry per
                # request forever. Valid candidates are bounded by
                # num_blocks (leaves), so filtering back down is cheap
                # and amortized over the pushes that grew it.
                seen: set = set()
                kept = []
                for entry in self._heap:
                    lu, _, n = entry
                    if self._entry_valid(lu, n) and id(n) not in seen:
                        seen.add(id(n))
                        kept.append(entry)
                self._heap = kept
                heapq.heapify(self._heap)

    def _evict_lru_leaf(self) -> int | None:
        """Free the least-recently-used UNREFERENCED LEAF's block.
        Leaves-only keeps the tree prefix-closed (an interior block can
        never vanish from under its descendants); lookup_pin pins EVERY
        node on a matched path and publish pins its walk, so no
        in-flight source — and no node the current publish stands on —
        is ever a candidate. Pops the lazy heap until an entry still
        describes reality: re-touched/pinned/extended/detached nodes
        fail the check and are discarded (each was one O(log n) push)."""
        while self._heap:
            last_use, _, node = heapq.heappop(self._heap)
            if not self._entry_valid(last_use, node):
                continue  # stale entry — see _entry_valid
            del node.parent.children[node.key]
            self.stats.evictions += 1
            self.stats.blocks_in_use -= 1
            # the eviction may have exposed its parent as a new leaf
            self._push_candidate(node.parent)
            return node.block
        return None  # everything is pinned or interior: caller drops

    # -- lifecycle --------------------------------------------------------

    def invalidate(self) -> None:
        """Drop the whole tree and reclaim every block. Called when the
        engine generation the arena belongs to is being discarded
        (scheduler abort, supervisor rebuild, close) — restored/recovered
        engines must never seed from blocks a dead engine wrote. The
        arena arrays themselves are reused only through the rebuilt free
        list; in-flight pins reference detached nodes, which unpin()
        tolerates."""
        self._root.children.clear()
        self._free = list(range(self.num_blocks))
        self._heap.clear()
        # bump the epoch so detached survivors (a pinned deep node whose
        # late unpin() re-enqueues it, with its block also on the rebuilt
        # free list) can never pass the eviction validity check again
        self._epoch += 1
        self.stats.blocks_in_use = 0
        self.stats.invalidations += 1

    def warmup(self) -> None:
        """Compile the two arena executables (slot_seed + slot_publish)
        off the serving clock, state-neutrally: the seed writes arena
        bytes into row 0 of a FREE slot (overwritten by its next lease
        before any query attends — the standard invariant; the caller,
        Scheduler.warmup, asserts idleness) and the publish targets a
        block STILL ON THE FREE LIST, so the garbage it writes is
        overwritten by that block's first real allocation before any
        node can reference it. With the free list empty (a re-warm on a
        long-lived full pool — every block then backs a live node whose
        K/V must not be clobbered) the publish is skipped: a full pool
        means publishes already ran, so the executable is compiled."""
        self.seed_slot(0, [])
        if self._free:
            self.arena_k, self.arena_v = self.engine.slot_publish_block(
                self.arena_k, self.arena_v, 0, 0, self._free[-1])
        if self.transfer and self._free:
            # the transfer plane's two executables compile here too (a
            # fill or a donor query must never mint post-warmup keys):
            # export reads a FREE block's garbage, import writes it
            # straight back — state-neutral by the same free-list rule
            # as the publish warmup above
            k, v = self.engine.block_export(self.arena_k, self.arena_v,
                                            self._free[-1])
            self.arena_k, self.arena_v = self.engine.slot_import_block(
                self.arena_k, self.arena_v, np.asarray(k), np.asarray(v),
                self._free[-1])
