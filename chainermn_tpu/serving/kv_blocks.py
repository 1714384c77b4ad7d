"""Host-side paged-KV bookkeeping: the block allocator and cache init.

The device-plane half of paging (pool scatter/gather) lives in
:mod:`chainermn_tpu.ops.paged_kv`; this module owns everything that
may change per request without touching the compiled program:

- :class:`BlockAllocator` — a free-list over physical pool blocks and
  the per-slot block tables. Join/leave/growth mutate numpy state only;
  the tables ride into the jitted step as a traced ``[slots,
  max_blocks]`` int32 argument, so occupancy changes NEVER recompile
  (the engine's structural no-recompile test pins this). ISSUE 7 grows
  it per-block REFCOUNTS: a physical block may appear in several slots'
  tables (cross-request prefix sharing) and in the prefix trie's cache;
  ``release``/``trim`` decrement instead of freeing, and a block
  returns to the free list only when no slot references it and the trie
  no longer caches it.
- :class:`PrefixCache` — a block-granular radix trie over token ids
  (one node = one FULL block's tokens at its exact block index, so a
  cached block is only ever valid at the depth it was written for —
  position encodings are baked into the KV). A joining request adopts
  the longest matching full-block chain and prefills only the unshared
  tail; completed prefills insert their full blocks. Eviction is LRU
  over refcount-0 leaves, driven through the allocator's reclaim hook
  when ``ensure`` would otherwise fail — the trie is a best-effort
  cache that can never starve a live slot.
- :func:`init_serving_cache` — allocate the engine's cache pytree by
  shape evaluation of the model's slot-decode path (zero FLOPs), the
  serving analog of ``models.transformer.init_cache``.

Layout contract (shared with ``ops.paged_kv``): physical block 0 is
SCRATCH — never owned by a slot; released or never-grown table entries
point at it, so stale writes land in a garbage block instead of a
block that may since belong to another request.

Copy-on-write contract (the engine's step wrappers enforce it): a
device-plane WRITE may only target a block that exactly one slot
references and the trie does not cache (:meth:`BlockAllocator
.shared_for_write`); the engine copies the block first
(:func:`chainermn_tpu.ops.paged_kv.copy_block`) and repoints the
writing slot's table row (:meth:`BlockAllocator.cow_replace`) — host
rewrite for the writer only, every other reader (and the trie's cached
copy) untouched. Partial tail blocks are never inserted into the trie,
so COW only ever triggers on the boundary block of a full-prefix hit.
"""

from __future__ import annotations

import heapq
import itertools
import math
from typing import Callable, Optional, Sequence

import numpy as np


class BlockAllocator:
    """Free-list allocator over a paged KV pool.

    ``num_blocks`` counts the WHOLE pool including scratch, matching
    the device pool's leading dimension; ``num_blocks - 1`` blocks are
    allocatable. Allocation failure returns False (the scheduler defers
    admission) — never raises mid-stream.
    """

    SCRATCH = 0

    def __init__(self, num_blocks: int, block_size: int, num_slots: int,
                 max_len: int) -> None:
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is scratch), got "
                f"{num_blocks}"
            )
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_slots = int(num_slots)
        self.max_blocks = math.ceil(max_len / block_size)
        # LIFO free list: recently released blocks are reused first
        # (warm HBM lines on chip; deterministic tables in tests).
        self._free = list(range(self.num_blocks - 1, self.SCRATCH, -1))
        self.tables = np.full((num_slots, self.max_blocks), self.SCRATCH,
                              np.int32)
        self._owned: list[list[int]] = [[] for _ in range(num_slots)]
        #: per-block slot-table reference counts (scratch stays 0).
        #: A block may appear in several slots' tables (prefix sharing);
        #: it returns to the free list only at refcount 0 AND not
        #: trie-cached.
        self.refcounts = np.zeros(self.num_blocks, np.int32)
        #: blocks held by the prefix trie's cache — kept out of the free
        #: list at refcount 0 until evicted (best-effort cache).
        self._cached: set[int] = set()
        #: reclaim hook (set by :class:`PrefixCache`): called with the
        #: block shortfall when ``ensure`` would fail; returns how many
        #: blocks it freed. Live slots can therefore never be starved by
        #: cached-but-unreferenced blocks.
        self.reclaimer: Optional[Callable[[int], int]] = None
        #: capacity twin of the reclaim hook (set by :class:`PrefixCache`
        #: alongside it): how many blocks the hook could free RIGHT NOW.
        #: Strictly less than :meth:`blocks_cached` when a live slot
        #: references a cached chain's descendant — those ancestors never
        #: become evictable leaves.
        self.reclaim_capacity: Optional[Callable[[], int]] = None
        #: bumped on every table mutation — the engine keys its cached
        #: device copy of ``tables`` on it, so the steady-state decode
        #: loop re-uploads only when an admit/grow/release actually
        #: changed a row.
        self.version = 0

    # ------------------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by at least one slot's table (cached-but-
        unreferenced trie blocks are NOT in use — they are reclaimable,
        counted by :meth:`blocks_cached`)."""
        return int((self.refcounts > 0).sum())

    def blocks_cached(self) -> int:
        """Trie-cached blocks no slot references. An upper bound on what
        eviction can free — a cached ancestor whose descendant a live
        slot references is counted here but pinned; the deliverable
        number is the ``reclaim_capacity`` hook."""
        return sum(1 for b in self._cached if self.refcounts[b] == 0)

    def blocks_shared(self) -> int:
        """Blocks referenced by MORE than one slot's table."""
        return int((self.refcounts > 1).sum())

    def utilization(self) -> float:
        """Fraction of the allocatable pool currently owned by slots."""
        denom = self.num_blocks - 1
        return self.blocks_in_use / denom if denom else 0.0

    def blocks_for(self, n_positions: int) -> int:
        """Blocks needed to cover positions ``[0, n_positions)``."""
        return math.ceil(n_positions / self.block_size)

    def can_cover(self, slot: int, n_positions: int) -> bool:
        """Whether :meth:`ensure` for ``n_positions`` would succeed right
        now. Counts only blocks the reclaim hook could ACTUALLY free —
        not every cached refcount-0 block: a cached ancestor whose
        descendant is referenced by a live slot never becomes an
        evictable leaf, so it must not be promised here."""
        need = self.blocks_for(n_positions) - len(self._owned[slot])
        spare = len(self._free)
        if self.reclaim_capacity is not None:
            spare += self.reclaim_capacity()
        return need <= spare

    def owned_blocks(self, slot: int) -> list[int]:
        """``slot``'s physical blocks in table order (a copy)."""
        return list(self._owned[slot])

    def _take_free(self, need: int) -> bool:
        """Whether the free list can supply ``need`` blocks, reclaiming
        cached-but-unreferenced trie blocks (leaf-first LRU, via the
        hook) before giving up. A HOPELESS request — more than free +
        reclaimable — evicts nothing: flushing the hot cache for an
        admission that defers anyway would regress every follower."""
        if need > len(self._free) and self.reclaimer is not None:
            if self.reclaim_capacity is not None:
                if need > len(self._free) + self.reclaim_capacity():
                    return False
            self.reclaimer(need - len(self._free))
        return need <= len(self._free)

    def _unref(self, blk: int) -> None:
        """Drop one slot-table reference; the block returns to the free
        list only when nothing references it and the trie does not
        cache it."""
        self.refcounts[blk] -= 1
        if self.refcounts[blk] < 0:  # pragma: no cover - internal guard
            raise AssertionError(f"block {blk} refcount underflow")
        if self.refcounts[blk] == 0 and blk not in self._cached:
            self._free.append(blk)

    def ensure(self, slot: int, n_positions: int) -> bool:
        """Grow ``slot``'s table to cover positions ``[0, n_positions)``.

        Returns False (state unchanged) when the pool cannot supply the
        missing blocks — all-or-nothing, so a deferred admission leaves
        no half-grown table behind. Before deferring, cached-but-
        unreferenced prefix-trie blocks are reclaimed through the
        allocator's hook (leaf-first LRU), so the best-effort cache can
        never starve a live slot.
        """
        if n_positions > self.max_blocks * self.block_size:
            raise ValueError(
                f"slot {slot}: {n_positions} positions exceed the table "
                f"horizon {self.max_blocks * self.block_size}"
            )
        owned = self._owned[slot]
        need = self.blocks_for(n_positions) - len(owned)
        if need > 0 and not self._take_free(need):
            return False
        if need > 0:
            self.version += 1
        for _ in range(max(0, need)):
            blk = self._free.pop()
            self.refcounts[blk] = 1
            self.tables[slot, len(owned)] = blk
            owned.append(blk)
        return True

    def adopt(self, slot: int, blocks: Sequence[int]) -> None:
        """Append already-filled ``blocks`` to ``slot``'s table (the
        prefix-trie hit path): each gains one reference — nothing is
        popped from the free list, nothing is copied. Callers adopt
        BEFORE :meth:`ensure`-ing the tail, so the table stays
        position-ordered."""
        if not blocks:
            return
        owned = self._owned[slot]
        if len(owned) + len(blocks) > self.max_blocks:
            raise ValueError(
                f"slot {slot}: adopting {len(blocks)} blocks over "
                f"{len(owned)} owned exceeds the table horizon"
            )
        self.version += 1
        for blk in blocks:
            if blk == self.SCRATCH:
                raise ValueError("cannot adopt the scratch block")
            self.refcounts[blk] += 1
            self.tables[slot, len(owned)] = blk
            owned.append(blk)

    def shared_for_write(self, blk: int) -> bool:
        """Whether a device-plane write to ``blk`` must copy first:
        another slot references it, or the prefix trie caches it (a
        write would corrupt the trie's pristine copy for future
        adopters)."""
        return bool(self.refcounts[blk] > 1 or blk in self._cached)

    def alloc_block(self) -> Optional[int]:
        """Pop one free block (refcount 1, unattached to any table) —
        the copy-on-write destination. None on genuine exhaustion
        (after the reclaim hook ran)."""
        if not self._take_free(1):
            return None
        blk = self._free.pop()
        self.refcounts[blk] = 1
        return blk

    def cow_replace(self, slot: int, index: int, new_blk: int) -> int:
        """Repoint table entry ``index`` of ``slot`` at ``new_blk`` (a
        block from :meth:`alloc_block`, already holding the copied
        contents) and drop the old block's reference. Host rewrite for
        the WRITING slot only — every other reader of the old block,
        and the trie's cached copy, are untouched. Returns the old
        physical block id."""
        old = self._owned[slot][index]
        self.version += 1
        self._owned[slot][index] = int(new_blk)
        self.tables[slot, index] = new_blk
        self._unref(old)
        return old

    # ---- trie-cache bookkeeping (driven by PrefixCache) --------------

    def mark_cached(self, blk: int) -> None:
        self._cached.add(int(blk))

    def uncache(self, blk: int) -> None:
        """Drop the trie's hold on ``blk`` (eviction); frees it when no
        slot references it."""
        blk = int(blk)
        self._cached.discard(blk)
        if self.refcounts[blk] == 0:
            self._free.append(blk)

    def trim(self, slot: int, n_positions: int) -> None:
        """Shrink ``slot``'s table to cover no more than positions
        ``[0, n_positions)`` — :meth:`ensure`'s inverse for the tail.
        Freed blocks return to the pool and their table entries point
        back at scratch, so any stale writes they hold become
        unreachable (the :meth:`release` guarantee, per block). The
        engine uses this to make speculative span reservations per-tick
        LEASES: trimming to the committed frontier each tick returns an
        earlier tick's unused extension before it can starve another
        slot. Trimming below the committed history would lose data —
        callers trim to the frontier, never below."""
        owned = self._owned[slot]
        keep = self.blocks_for(n_positions)
        if keep >= len(owned):
            return
        self.version += 1
        while len(owned) > keep:
            blk = owned.pop()
            self.tables[slot, len(owned)] = self.SCRATCH
            self._unref(blk)

    def release(self, slot: int) -> None:
        """Drop ``slot``'s references and point its table back at
        scratch (stale in-flight writes become harmless). Blocks still
        referenced by other slots, or cached by the prefix trie, stay
        out of the free list (the refcount contract); a second release
        of an already-released slot is a no-op (idempotent — no version
        churn)."""
        if self._owned[slot]:
            self.version += 1
        for blk in reversed(self._owned[slot]):
            self._unref(blk)
        self._owned[slot] = []
        self.tables[slot] = self.SCRATCH


class _TrieNode:
    """One full block's tokens at one block depth. ``children`` keys are
    the NEXT block's token tuple; ``block`` is the physical pool block
    holding this node's KV."""

    __slots__ = ("tokens", "block", "children", "parent", "last_used")

    def __init__(self, tokens, block, parent) -> None:
        self.tokens = tokens
        self.block = block
        self.children: dict = {}
        self.parent = parent
        self.last_used = 0


class PrefixCache:
    """Block-granular radix trie over token ids (ISSUE 7 tentpole).

    One node = one FULL block's tokens at its exact depth, so a lookup
    walks the prompt in ``block_size`` chunks from the root: the chain
    of matches is the longest cached prefix, and its physical blocks
    can be adopted verbatim (KV for a given token prefix at given
    positions is deterministic — the engine's equivalence suite pins
    shared == unshared streams bitwise). Partial tail blocks are never
    inserted, which is what confines copy-on-write to the boundary
    block of a full-prefix hit.

    Registers itself as the allocator's reclaim hook: when ``ensure``
    would fail, refcount-0 LEAVES are evicted LRU-first (an interior
    node is never evicted before its descendants, so a cached chain can
    never dangle). Thread-unsafe like the allocator — both are owned by
    the engine's host loop.
    """

    def __init__(self, allocator: BlockAllocator) -> None:
        self.alloc = allocator
        self.block_size = allocator.block_size
        #: per-NAMESPACE trie roots (ISSUE 14: tenant isolation — a
        #: lookup/insert only ever walks its own namespace's tree, so a
        #: cross-tenant block adoption is structurally impossible, not
        #: merely policy). ``None`` is the default namespace
        #: (single-tenant engines never see another).
        self._roots: dict = {
            None: _TrieNode((), BlockAllocator.SCRATCH, None)
        }
        self._clock = itertools.count(1)
        #: number of cached nodes (== cached blocks, the trie-size
        #: gauge), summed across namespaces
        self.n_nodes = 0
        #: lifetime eviction count (bench/dryrun visibility)
        self.evictions = 0
        allocator.reclaimer = self.reclaim
        allocator.reclaim_capacity = self.reclaimable

    def _root_for(self, namespace, create: bool = False):
        root = self._roots.get(namespace)
        if root is None and create:
            root = _TrieNode((), BlockAllocator.SCRATCH, None)
            self._roots[namespace] = root
        return root

    def _chunks(self, tokens: Sequence[int]):
        bs = self.block_size
        for i in range(0, (len(tokens) // bs) * bs, bs):
            yield tuple(int(t) for t in tokens[i:i + bs])

    def lookup(self, tokens: Sequence[int],
               namespace=None) -> list[int]:
        """Physical blocks of the longest cached FULL-block prefix of
        ``tokens`` under ``namespace`` (possibly empty). Touches the
        matched chain's LRU stamps — a hit protects its ancestors from
        eviction ordering."""
        node = self._root_for(namespace)
        if node is None:
            return []
        out: list[int] = []
        stamp = next(self._clock)
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            child.last_used = stamp
            out.append(child.block)
            node = child
        return out

    def match_depth(self, tokens: Sequence[int], namespace=None) -> int:
        """How many FULL blocks of ``tokens`` the trie holds under
        ``namespace`` — a READ-ONLY probe (no LRU stamp: the cluster
        router consults every replica's trie per routing decision, and
        a probe that touched stamps would let mere consideration pin
        chains a real adoption never used). :meth:`lookup` remains the
        adopting walk."""
        node = self._root_for(namespace)
        if node is None:
            return 0
        depth = 0
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            depth += 1
            node = child
        return depth

    def insert(self, tokens: Sequence[int], blocks: Sequence[int],
               namespace=None) -> int:
        """Cache the FULL blocks of a completed prefill under
        ``namespace``: ``blocks[j]`` holds the KV of
        ``tokens[j*bs:(j+1)*bs]``. Chunks already cached
        are left as-is (first writer wins — the existing node's block is
        the one future joins adopt; the inserting slot simply keeps its
        private copy). Returns how many new nodes were cached."""
        node = self._root_for(namespace, create=True)
        added = 0
        stamp = next(self._clock)
        for j, chunk in enumerate(self._chunks(tokens)):
            if j >= len(blocks):
                break
            child = node.children.get(chunk)
            if child is None:
                child = _TrieNode(chunk, int(blocks[j]), node)
                node.children[chunk] = child
                self.alloc.mark_cached(child.block)
                self.n_nodes += 1
                added += 1
            child.last_used = stamp
            node = child
        return added

    def drop_namespace(self, namespace) -> int:
        """Invalidate EVERY cached block under ``namespace`` (ISSUE 14
        review finding: an adapter re-registration changes the weights
        that produced the tenant's cached KV — a later join adopting
        those blocks would silently diverge from ``generate`` under the
        new adapter, so the engine drops the namespace on
        register/evict). Blocks are uncached, not force-freed: a live
        slot still reading one keeps it until release. Returns the
        number of nodes dropped."""
        root = self._roots.pop(namespace, None)
        if root is None:
            return 0
        dropped = 0
        stack = list(root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self.alloc.uncache(node.block)
            dropped += 1
        self.n_nodes -= dropped
        self.evictions += dropped
        if namespace is None:
            # The default namespace always exists (single-tenant
            # engines consult it unconditionally).
            self._root_for(None, create=True)
        return dropped

    def namespace_blocks(self, namespace=None) -> int:
        """Cached nodes under one namespace (the per-tenant trie-size
        probe; the isolation test pins zero overlap between tenants'
        block sets)."""
        root = self._root_for(namespace)
        if root is None:
            return 0
        n, stack = 0, [root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node is not root:
                n += 1
        return n

    def _evictable_leaves(self) -> list[_TrieNode]:
        out = []
        for root in self._roots.values():
            stack = [root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if (node is not root and not node.children
                        and self.alloc.refcounts[node.block] == 0):
                    out.append(node)
        return out

    def reclaimable(self) -> int:
        """Blocks :meth:`reclaim` could free right now: cached nodes
        whose WHOLE subtree is refcount-0. A live descendant pins its
        cached ancestors — they never become evictable leaves — so this
        is strictly tighter than the allocator's ``blocks_cached``
        gauge (the allocator's ``can_cover`` promise reads this)."""
        def walk(node: _TrieNode, root: _TrieNode) -> tuple[int, bool]:
            n, subtree_free = 0, True
            for child in node.children.values():
                cn, cf = walk(child, root)
                n += cn
                subtree_free = subtree_free and cf
            if node is root:
                return n, subtree_free
            if subtree_free and self.alloc.refcounts[node.block] == 0:
                return n + 1, True
            return n, False

        return sum(walk(root, root)[0] for root in self._roots.values())

    def reclaim(self, n: int) -> int:
        """Evict up to ``n`` blocks, LRU leaf first (the allocator's
        ensure-would-fail hook). Evicting a leaf may expose its parent
        as the next candidate — the parent joins the candidate heap
        then, so one trie scan serves the whole batch (refcounts don't
        change during eviction). Returns the blocks actually freed."""
        roots = set(map(id, self._roots.values()))
        heap = [(nd.last_used, id(nd), nd)
                for nd in self._evictable_leaves()]
        heapq.heapify(heap)
        freed = 0
        while freed < n and heap:
            _, _, victim = heapq.heappop(heap)
            del victim.parent.children[victim.tokens]
            self.alloc.uncache(victim.block)
            self.n_nodes -= 1
            self.evictions += 1
            freed += 1
            parent = victim.parent
            if (id(parent) not in roots and not parent.children
                    and self.alloc.refcounts[parent.block] == 0):
                heapq.heappush(
                    heap, (parent.last_used, id(parent), parent))
        return freed


def default_num_blocks(num_slots: int, block_size: int, max_len: int) -> int:
    """Worst-case pool: every slot at ``max_len`` simultaneously, plus
    scratch. Oversubscribe deliberately (smaller ``num_blocks``) when the
    expected resident-token sum is below the worst case — admission then
    defers on pool exhaustion instead of OOMing."""
    return num_slots * math.ceil(max_len / block_size) + 1


def init_serving_cache(model, params, num_slots: int,
                       block_tables: Optional[np.ndarray] = None):
    """Zero-initialised cache pytree for the slot-decode path.

    Pure shape evaluation (``jax.eval_shape``) of one slot-array decode
    step — dense layouts get ``[num_slots, decode_cache_len, kvh, dh]``
    per block, paged layouts get the shared pools. Returns the ``cache``
    collection dict the engine threads through its jitted step.
    """
    import jax
    import jax.numpy as jnp

    dummy = jnp.zeros((num_slots, 1), jnp.int32)
    pos = jnp.zeros((num_slots,), jnp.int32)
    bt = None
    if model.kv_layout == "paged":
        if block_tables is not None:
            bt = jnp.asarray(block_tables, jnp.int32)
        else:
            max_blocks = math.ceil(
                (model.decode_cache_len or model.max_len)
                / model.kv_block_size
            )
            bt = jnp.zeros((num_slots, max_blocks), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.apply(
            params, dummy, train=False, decode=True,
            decode_positions=pos, block_tables=bt, mutable=["cache"],
        )[1]
    )
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), variables
    )["cache"]
