"""The paged cache's host half alone (``serving_cache.PagedCache``): no
engine, no device. One parametrised test a verb; the engine-level tests
of the same ground (``test_paged_serving``, ``test_chunk_behind_step``,
``test_step_behind_step``, ``test_serving_spec``) are the proof that the
scheduler still calls them as it did."""

import numpy as np
import pytest

from distributed_tensorflow_example_tpu.serving_cache import (
    BlocksExhaustedError, PagedCache)

BS = 4


def make(num_blocks=12, *, prefix=True, slots=3, blocks_per_slot=4):
    return PagedCache(slots=slots, blocks_per_slot=blocks_per_slot,
                      num_blocks=num_blocks, block_size=BS,
                      prefix_cache=prefix)


def row(cache, index):
    return [int(b) for b in cache.tables[index]]


def admit(cache, index, tokens):
    """A cold admission's calls, as the scheduler makes them."""
    tokens = np.asarray(tokens, np.int32)
    run = cache.reserve(-(-tokens.size // BS))
    cache.bind(index, run)
    cache.publish(index, tokens)
    return run


def no_copy(src, dst):
    raise AssertionError(f"copy({src}, {dst}) of an unshared block")


@pytest.mark.parametrize("pos, n, fresh", [
    (0, 1, [0]),            # the row's first write
    (3, 1, [0]),            # the last lane of a block
    (3, 2, [0, 1]),         # a span over a block boundary: once a block
    (2, 7, [0, 1, 2]),      # a span over two boundaries
    (4, 4, [1]),            # a whole block, the row's second
])
def test_secure_allocates_once_a_block(pos, n, fresh):
    cache = make(prefix=False)
    cache.secure(0, pos, n, no_copy)
    got = row(cache, 0)
    assert [bi for bi, b in enumerate(got) if b] == fresh
    assert cache.pool.in_use == len(fresh)
    assert all(cache.pool.refcount(got[bi]) == 1 for bi in fresh)
    # the span is secured: doing it again takes nothing more
    cache.secure(0, pos, n, no_copy)
    assert row(cache, 0) == got and cache.pool.in_use == len(fresh)


@pytest.mark.parametrize("sharer", ["prefix cache", "another row"])
def test_secure_copies_a_shared_block_once_before_the_table_changes(
        sharer):
    cache = make(prefix=sharer == "prefix cache")
    tokens = np.arange(6, dtype=np.int32)         # one block and a half
    run = admit(cache, 0, tokens)
    if sharer == "another row":
        cache.mount(1, run)
    shared = run[1]
    assert cache.pool.refcount(shared) == 2
    calls = []

    def copy(src, dst):
        # the new block is had, the table still names the shared one
        calls.append((src, dst, row(cache, 0)[1],
                      cache.pool.refcount(src), cache.pool.refcount(dst)))

    cache.secure(0, 6, 3, copy)                   # lanes 6..8: blocks 1, 2
    (src, dst, named, src_refs, dst_refs), = calls
    assert (src, named, src_refs, dst_refs) == (shared, shared, 2, 1)
    got = row(cache, 0)
    assert got[0] == run[0] and got[1] == dst and got[2] not in (0, dst)
    assert cache.pool.refcount(shared) == 1       # the sharer's alone
    assert cache.pool.refcount(dst) == 1
    cache.secure(0, 6, 3, no_copy)                # now the row's own


@pytest.mark.parametrize("pos, span_end, kept", [
    (5, 6, [0, 1]),         # the span stayed inside block 1: nothing
    (3, 6, [0]),            # next write in block 0: block 1 goes
    (4, 9, [0, 1]),         # next write opens block 1: it is kept
    (1, 11, [0]),           # two trailing blocks go
    (9, 99, [0, 1, 2]),     # a span end past the row is clipped
])
def test_rewind_frees_only_blocks_past_the_next_write(pos, span_end, kept):
    cache = make(prefix=False)
    cache.secure(0, 0, min(span_end + 1, 4 * BS), no_copy)
    before = row(cache, 0)
    cache.rewind(0, pos, span_end)
    got = row(cache, 0)
    assert [bi for bi, b in enumerate(got) if b] == kept
    assert [got[bi] for bi in kept] == [before[bi] for bi in kept]
    assert cache.pool.in_use == len(kept)


@pytest.mark.parametrize("case, ok", [
    ("every row opens a block", True),
    ("blocks already held", True),
    ("one row's block is shared", False),
    ("the pool is one block short", False),
    ("one position lies past its row", False),
])
def test_secure_all_is_all_or_none(case, ok):
    cache = make(num_blocks=6, prefix=False)      # 5 usable
    pos = np.zeros((3,), np.int32)
    if case == "every row opens a block":
        pos[:] = [0, 4, 8]
    elif case == "blocks already held":
        for i in range(3):
            cache.secure(i, 0, 1, no_copy)
        pos[:] = [1, 2, 3]
    elif case == "one row's block is shared":
        cache.secure(0, 0, 1, no_copy)
        cache.mount(1, [int(cache.tables[0, 0])])
        pos[:] = [4, 1, 0]                         # rows 0, 2 would open
    elif case == "the pool is one block short":
        spare = cache.pool.alloc(3)
        pos[:] = [0, 0, 0]
        assert spare
    else:
        pos[:] = [0, 0, 4 * BS]
    tables, free = cache.tables.copy(), cache.pool.free_count
    assert cache.secure_all(range(3), pos) is ok
    if ok:
        for i in range(3):
            block = int(cache.tables[i, int(pos[i]) // BS])
            assert block and cache.pool.refcount(block) == 1
    else:
        assert np.array_equal(cache.tables, tables)
        assert cache.pool.free_count == free


@pytest.mark.parametrize("n, evicted", [
    (2, 0),                 # the pool serves it: nothing is evicted
    (3, 1),                 # one entry short: the least recently used
    (4, 2),                 # two short: the two oldest, not the third
    (5, 3),                 # the whole pool: every entry goes
])
def test_reserve_evicts_least_recently_used_and_no_more(n, evicted):
    cache = make(num_blocks=6)                    # 5 usable
    prompts = [np.full((BS,), t, np.int32) for t in (7, 8, 9)]
    for tokens in prompts:                        # a block each, then the
        admit(cache, 0, tokens)                   # row leaves: the cache's
        cache.release(0)
    assert cache.lookup(prompts[0])[0] == BS      # touched: now the newest
    order = [prompts[1], prompts[2], prompts[0]]  # least recent first
    run = cache.reserve(n)
    assert len(run) == n == len(set(run)) and 0 not in run
    left = [cache.lookup(t)[0] == BS for t in order]
    assert left == [False] * evicted + [True] * (3 - evicted)
    assert cache.occupancy()[3] == 3 - evicted


def test_reserve_raises_when_eviction_cannot_serve():
    cache = make(num_blocks=4)                    # 3 usable
    run = admit(cache, 0, np.arange(2 * BS, dtype=np.int32))
    with pytest.raises(BlocksExhaustedError):
        cache.reserve(2)                          # row 0 still holds two
    # the cache's entries went for it; the row's blocks did not
    assert cache.occupancy()[3] == 0 and row(cache, 0)[:2] == run
    assert all(cache.pool.refcount(b) == 1 for b in run)
    cache.give_back(cache.reserve(1))
    assert cache.pool.free_count == 1


@pytest.mark.parametrize("holder", ["nobody", "another row",
                                    "prefix cache"])
def test_release_leaves_a_block_someone_else_holds(holder):
    cache = make(prefix=holder == "prefix cache")
    tokens = np.arange(2 * BS, dtype=np.int32)
    run = admit(cache, 0, tokens)
    if holder == "another row":
        cache.mount(1, run[:1])
    cache.secure(0, 2 * BS, 1, no_copy)            # a third, unshared
    own = row(cache, 0)[2]
    cache.release(0)
    assert row(cache, 0) == [0, 0, 0, 0]
    assert cache.pool.refcount(own) == 0
    # an entry a block boundary: the first block is in two of them
    want = {"nobody": [0, 0], "another row": [1, 0],
            "prefix cache": [2, 1]}[holder]
    assert [cache.pool.refcount(b) for b in run] == want
    assert cache.pool.in_use == sum(1 for refs in want if refs)
    if holder == "another row":
        cache.release(1)
        assert cache.pool.in_use == 0


@pytest.mark.parametrize("prompt_len, hit", [
    (2 * BS, 2 * BS),       # the exact prompt
    (2 * BS + 3, 2 * BS),   # a longer one: its whole blocks
    (BS + 1, BS),           # a shorter one: the first block's entry
    (BS - 1, 0),            # less than a block of it: a miss
])
def test_lookup_and_mount_share_the_published_blocks(prompt_len, hit):
    cache = make()
    tokens = np.arange(100, 100 + 3 * BS, dtype=np.int32)
    run = admit(cache, 0, tokens[:2 * BS])
    n_hit, blocks = cache.lookup(tokens[:prompt_len])
    assert n_hit == hit and list(blocks) == run[:hit // BS]
    # a probe counts nothing: the outcome does, once
    assert (cache.prefix.hits, cache.prefix.misses) == (0, 0)
    if hit:
        refs = [cache.pool.refcount(b) for b in blocks]
        cache.mount(1, blocks)
        assert row(cache, 1)[:len(blocks)] == list(blocks)
        assert [cache.pool.refcount(b) for b in blocks] == [
            r + 1 for r in refs]
    cache.count(hit=bool(hit))
    assert (cache.prefix.hits, cache.prefix.misses) == (
        (1, 0) if hit else (0, 1))


def test_without_a_prefix_cache_nothing_is_shared_or_counted():
    cache = make(prefix=False)
    tokens = np.arange(BS, dtype=np.int32)
    run = admit(cache, 0, tokens)
    assert cache.lookup(tokens) == (0, ())
    cache.count(hit=False)
    assert cache.pool.refcount(run[0]) == 1 and cache.prefix is None
    assert cache.occupancy() == (cache.pool.usable - 1, 1, 1, None)


def test_reset_forgets_every_block_and_keeps_the_counters():
    cache = make()
    tokens = np.arange(BS, dtype=np.int32)
    admit(cache, 0, tokens)
    cache.count(hit=False)
    tables = cache.tables
    cache.reset()
    assert cache.tables is tables and not tables.any()
    assert cache.occupancy() == (cache.pool.usable, 0, 0, 0)
    assert cache.lookup(tokens) == (0, ())
    assert cache.prefix.misses == 1
