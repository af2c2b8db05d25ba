"""The paged cache's host half: block pool, prefix cache, block tables.

A paged stepwise artifact keeps K/V (or a latent row) in a shared pool
of ``block_size``-token physical blocks; which block a slot's token
lands in is a row of an int32 table the device programs take as an
operand. This module owns that bookkeeping and every decision about
it: which physical block a row writes to, and when a block is shared,
copied, evicted or freed. It is DESIGN section 25's contract (*layer
kinds -> state specs -> allocate / zero / carry / release*) on the host
side; the device half (the donated pool, ``zero_slot``, the block-copy
program) stays with :class:`~.serving_batch.GenerationEngine`, which
hands the copy in as a callable.

- :class:`BlockPool`: refcounted allocator over the physical blocks
  (block 0 the never-read null target).
- :class:`PrefixCache`: token-prefix hash at block granularity, LRU.
- :class:`PagedCache`: the ``[slots, blocks_per_slot]`` table over one
  of each, and the verbs the scheduler calls. It sees slot indices,
  positions, token arrays and block ids, never a slot, a request or a
  device; failure POLICY (which row fails alone, when drafts are
  dropped, deferral) is the scheduler's.

Single-threaded by design: only the scheduler thread calls it.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from .obs.registry import Registry
from .runtime import faults


class BlocksExhaustedError(Exception):
    """The paged cache pool has no free physical block left (even after
    prefix-cache eviction). The one request that needed the block fails
    loudly; the engine keeps serving its neighbors."""


class BlockPool:
    """Host-side refcounted allocator over the physical blocks of a
    paged KV-cache pool.

    Block 0 is the reserved NULL block: never allocated, the target of
    unused/dead block-table entries — whole-block prefill spill and the
    gated dead-row write land there and are never read (the attention
    mask excludes every logical slot past ``pos``). A block returns to
    the free list exactly when its LAST reference drops: slot tables
    and prefix-cache entries each hold one reference, so a shared
    prefix block outlives any single request that mounted it.
    Single-threaded by design — only the scheduler thread touches it.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (the reserved "
                             f"null block + at least one usable), got "
                             f"{num_blocks}")
        self.num_blocks = num_blocks
        self._ref = [0] * num_blocks
        # LIFO free list: recently retired blocks are remounted first;
        # deterministic allocation order (tests rely on it), and holes
        # from mixed-length retirement are served like any other block
        # — physical contiguity is irrelevant, the table indirection IS
        # the defragmenter
        self._free = list(range(num_blocks - 1, 0, -1))
        #: high-water mark of blocks in use — the bytes_resident_peak
        #: observable (per-dtype residency for the bench rows)
        self.peak_in_use = 0

    @classmethod
    def from_bytes(cls, pool_bytes: int, block_bytes: int) -> "BlockPool":
        """Size the pool IN BYTES: as many usable blocks as
        ``block_bytes``-sized K/V payloads fit the budget, plus the
        reserved null block — the sizing rule under which an int8
        cache (half the payload bytes) genuinely doubles the block
        count at fixed HBM. Mirrors ``export_generator``'s
        ``pool_bytes`` math."""
        if block_bytes < 1:
            raise ValueError(f"block_bytes must be >= 1, got "
                             f"{block_bytes}")
        return cls(1 + pool_bytes // block_bytes)

    @property
    def usable(self) -> int:
        return self.num_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.usable - len(self._free)

    def alloc(self, n: int) -> list[int]:
        """``n`` fresh blocks, refcount 1 each — all-or-nothing (a
        caller never holds a partial run)."""
        faults.inject("pool.alloc", detail=f"n={n}")
        if n > len(self._free):
            raise BlocksExhaustedError(
                f"need {n} cache block(s), {len(self._free)} free "
                f"(pool of {self.usable} usable blocks)")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def retain(self, blocks) -> None:
        for b in blocks:
            if self._ref[b] <= 0:
                raise AssertionError(f"retain of free block {b}")
            self._ref[b] += 1

    def release(self, blocks) -> None:
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] < 0:
                raise AssertionError(f"double release of block {b}")
            if self._ref[b] == 0:
                self._free.append(b)

    def refcount(self, block: int) -> int:
        return self._ref[block]


class PrefixCache:
    """Block-granularity prefix reuse: hash of a token prefix -> the
    physical blocks whose K/V bytes ARE that prefix's.

    Entries exist at every full-block boundary of an admitted cold
    prompt (key = its first ``j * block_size`` tokens, value = its
    first ``j`` blocks) plus one EXACT whole-prompt entry when the
    prompt ends mid-block (value includes the partial tail block). The
    left-aligned paged layout makes the cached bytes position-
    independent facts of the token prefix — token i always sits at
    logical slot i — so a hit mounts the blocks by reference (retain),
    no copy. Each entry holds one refcount per block; LRU eviction
    releases entries until the allocator can serve again, and a block
    still mounted by a live slot simply survives its cache eviction.
    """

    def __init__(self, pool: BlockPool, block_size: int, *,
                 registry: Registry | None = None):
        self.pool = pool
        self.block_size = block_size
        # key -> (blocks tuple, covered token count); insertion order
        # doubles as LRU (move_to_end on touch)
        self._entries: OrderedDict[bytes, tuple[tuple[int, ...], int]] \
            = OrderedDict()
        # registry-backed counters (the engine hands in ITS registry so
        # /stats, /metrics and the engine counters stay one source of
        # truth; standalone unit tests get a private one)
        self.registry = registry if registry is not None else Registry()
        self._c_hits = self.registry.counter(
            "serving_prefix_cache_hits_total",
            "admissions served (fully or partially) from cached blocks")
        self._c_misses = self.registry.counter(
            "serving_prefix_cache_misses_total",
            "admissions with no cached prefix (cold prefill)")

    @property
    def hits(self) -> int:
        return self._c_hits.value

    @property
    def misses(self) -> int:
        return self._c_misses.value

    def record_hit(self) -> None:
        self._c_hits.inc()

    def record_miss(self) -> None:
        self._c_misses.inc()

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return np.ascontiguousarray(tokens, np.int32).tobytes()

    def __len__(self) -> int:
        return len(self._entries)

    def lookup(self, tokens: np.ndarray, *,
               record: bool = True) -> tuple[int, tuple[int, ...]]:
        """Longest cached prefix of ``tokens``: ``(n_tokens_hit,
        blocks)`` — the exact whole-prompt entry wins, else the longest
        full-block chain; ``(0, ())`` on a miss. Mounting (refcounting)
        is the caller's move. ``record=False`` skips the hit/miss
        counters — for probes that may not lead to an admission (a
        block-pressure deferral retries the same request every step,
        and one admission must count once)."""
        bs = self.block_size
        p = int(tokens.size)
        probes = [p] + [j * bs for j in range(p // bs, 0, -1)
                        if j * bs != p]
        for n in probes:
            key = self._key(tokens[:n])
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                if record:
                    self._c_hits.inc()
                return n, e[0]
        if record:
            self._c_misses.inc()
        return 0, ()

    def insert(self, tokens: np.ndarray, blocks) -> None:
        """Record a cold prompt's block run: one entry per full-block
        boundary plus the exact whole-prompt entry. Re-inserting a
        known key only touches its LRU position."""
        bs = self.block_size
        p = int(tokens.size)
        ends = sorted({*(j * bs for j in range(1, p // bs + 1)), p})
        for n in ends:
            nb = -(-n // bs)
            key = self._key(tokens[:n])
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            ref = tuple(int(b) for b in blocks[:nb])
            self.pool.retain(ref)
            self._entries[key] = (ref, n)

    def evict(self, need_free: int) -> None:
        """Release LRU entries until ``need_free`` blocks are free (or
        the cache is empty — blocks still mounted by live slots stay
        resident past their entry's eviction)."""
        while self.pool.free_count < need_free and self._entries:
            _, (blocks, _) = self._entries.popitem(last=False)
            self.pool.release(blocks)


class PagedCache:
    """One engine's block tables over its :class:`BlockPool` and
    optional :class:`PrefixCache`.

    ``tables`` is the ``[slots, blocks_per_slot]`` int32 operand of the
    device programs (0 = the null block); a row's nonzero entries each
    hold one reference in ``pool``. A row is named by its slot index, a
    token by its logical position in the row (block ``pos //
    block_size``)."""

    def __init__(self, *, slots: int, blocks_per_slot: int,
                 num_blocks: int, block_size: int, prefix_cache: bool,
                 registry: Registry | None = None):
        self.block_size = block_size
        self.tables = np.zeros((slots, blocks_per_slot), np.int32)
        self.pool = BlockPool(num_blocks)
        self.prefix = (PrefixCache(self.pool, block_size,
                                   registry=registry)
                       if prefix_cache else None)

    def reset(self) -> None:
        """The device pool was rebuilt empty: every table entry and
        cached prefix names bytes that no longer exist. The hit/miss
        counters live in the registry, so the new prefix cache keeps
        counting where the dead one stopped."""
        self.tables[:] = 0
        self.pool = BlockPool(self.pool.num_blocks)
        if self.prefix is not None:
            self.prefix = PrefixCache(self.pool, self.block_size,
                                      registry=self.prefix.registry)

    # ---- admission: a prefix hit ---------------------------------------
    def lookup(self, tokens: np.ndarray) -> tuple[int, tuple[int, ...]]:
        """Longest cached prefix of ``tokens`` as ``(n_tokens_hit,
        blocks)``, uncounted: the probe repeats every step while a
        request is deferred, and :meth:`count` is called once an
        admission OUTCOME."""
        if self.prefix is None:
            return 0, ()
        return self.prefix.lookup(tokens, record=False)

    def mount(self, index: int, blocks) -> None:
        """Row ``index`` takes a reference to each of ``blocks`` (what
        :meth:`lookup` found) as its leading entries."""
        self.pool.retain(blocks)
        self.tables[index, :len(blocks)] = blocks

    def count(self, hit: bool) -> None:
        """One admission outcome, served from cached blocks or not."""
        if self.prefix is None:
            return
        if hit:
            self.prefix.record_hit()
        else:
            self.prefix.record_miss()

    # ---- admission: a cold run -----------------------------------------
    def reserve(self, n: int) -> list[int]:
        """``n`` fresh blocks, all or :class:`BlocksExhaustedError`:
        under pressure the least recently used prefix entries are
        released first, only as many as it takes. The one place blocks
        are taken from the pool."""
        if self.pool.free_count < n and self.prefix is not None:
            self.prefix.evict(n)
        return self.pool.alloc(n)

    def bind(self, index: int, run) -> None:
        """A reserved ``run`` becomes row ``index``'s leading entries."""
        self.tables[index, :len(run)] = run

    def give_back(self, run) -> None:
        """A reserved ``run`` that no row took."""
        self.pool.release(run)

    def publish(self, index: int, tokens: np.ndarray) -> None:
        """Row ``index``'s leading blocks hold ``tokens`` and nothing
        else: enter them in the prefix cache (which then shares them:
        the row's next write into its tail block copies it first)."""
        if self.prefix is not None:
            nb = -(-int(tokens.size) // self.block_size)
            self.prefix.insert(
                tokens, [int(b) for b in self.tables[index, :nb]])

    # ---- decode: the write span ----------------------------------------
    def secure(self, index: int, pos: int, n: int, copy) -> None:
        """Before a program writes positions ``pos .. pos+n-1`` of row
        ``index``: allocate-on-write where a target entry is still the
        null block, copy-on-write where a target block is shared (the
        prefix cache or another row still references it) — a divergence
        must never mutate bytes someone else reads. ``copy(src, dst)``
        copies one physical block on the device; it is called after the
        new block is had and before the table names it, and the shared
        block's reference goes last. Only the FIRST block of a span can
        be shared (anything past the row's own write frontier was never
        cached), but every block gets the same check — the invariant,
        not the current topology, is what the code states."""
        bs, tables = self.block_size, self.tables
        for bi in range(pos // bs, (pos + n - 1) // bs + 1):
            pb = int(tables[index, bi])
            if pb == 0:
                tables[index, bi] = self.reserve(1)[0]
            elif self.pool.refcount(pb) > 1:
                nb = self.reserve(1)[0]
                copy(pb, nb)
                tables[index, bi] = nb
                self.pool.release([pb])

    def secure_all(self, indices, pos) -> bool:
        """The write block of position ``pos[i]`` for every row ``i`` of
        ``indices``: all of them, or none and False. Allocation alone
        (the first half of :meth:`secure`): False where a block is
        shared, which a program would have to copy, where a position
        lies past its row, or where the pool is short (no entry is
        evicted for a step that is only launched early)."""
        bs, need = self.block_size, []
        for i in indices:
            bi = int(pos[i]) // bs
            if bi >= self.tables.shape[1]:
                return False
            pb = int(self.tables[i, bi])
            if pb == 0:
                need.append((i, bi))
            elif self.pool.refcount(pb) > 1:
                return False
        if len(need) > self.pool.free_count:
            return False
        got = self.pool.alloc(len(need)) if need else []
        for at, block in zip(need, got):
            self.tables[at] = block
        return True

    def rewind(self, index: int, pos: int, span_end: int) -> None:
        """After a rejected draft span ``.. span_end`` left row
        ``index``'s next write at ``pos``: a block secured PAST the one
        that holds ``pos`` has only rejected lanes' bytes, which nothing
        will read — its (fresh, refcount-1) reference returns to the
        pool and the entry to the null block. The block holding ``pos``
        is kept: the next program writes into it. Nothing to do where
        the span stayed inside one block."""
        bs = self.block_size
        row = self.tables[index]
        last = min(span_end // bs, row.size - 1)
        for bi in range(pos // bs + 1, last + 1):
            pb = int(row[bi])
            if pb:
                self.pool.release([pb])
                row[bi] = 0

    def release(self, index: int) -> None:
        """Row ``index`` leaves: its references are dropped (a block
        shared with the prefix cache or another row survives to its
        LAST release) and the row goes back to the null block."""
        row = self.tables[index]
        ids = [int(b) for b in row if b]
        if ids:
            self.pool.release(ids)
        row[:] = 0

    # ---- gauges and /stats ---------------------------------------------
    def occupancy(self) -> tuple[int, int, int, int | None]:
        """``(free, in use, most ever in use, prefix entries)`` in
        blocks; entries ``None`` without a prefix cache."""
        return (self.pool.free_count, self.pool.in_use,
                self.pool.peak_in_use,
                None if self.prefix is None else len(self.prefix))
