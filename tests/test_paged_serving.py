"""Block-paged KV-cache pool + shared-prefix reuse (round 10):

- paged decode attention (XLA gather path BITWISE vs the slab
  reference, Pallas scalar-prefetch kernel vs the gather path),
- paged model methods (decode step bitwise vs slab on equal logical
  contents; paged prefill vs the monolithic oracle),
- BlockPool / PrefixCache / RetryAfterEstimator units (refcounts,
  exhaustion, fragmentation, LRU eviction, EMA math),
- GenerationEngine on paged artifacts: cold/greedy parity vs the
  single-request oracle, exact-hit and divergent-suffix prefix reuse
  with ZERO prefill dispatches, copy-on-write on divergence,
  mid-decode block exhaustion failing ONE request loudly while
  neighbors finish, >= 2x admitted concurrency vs the slab slot count
  at equal pool bytes, and block-level /stats.
"""

import importlib
import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.ops.pallas.decode_attention import (
    decode_attention, paged_decode_attention, paged_tile_friendly)
from distributed_tensorflow_example_tpu.serving import (export_generator,
                                                        load_stepwise)
from distributed_tensorflow_example_tpu.serving_batch import (
    GenerationEngine, RetryAfterEstimator)
from distributed_tensorflow_example_tpu.serving_cache import (
    BlockPool, BlocksExhaustedError, PrefixCache)
from distributed_tensorflow_example_tpu.serving_http import PredictServer

# the module, not the same-named function ops.pallas re-exports
decode_mod = importlib.import_module(
    "distributed_tensorflow_example_tpu.ops.pallas.decode_attention")

PROMPT_LEN = 8
MAX_NEW = 5
SLOTS = 4
BLOCK = 4


# ---------------------------------------------------------------------------
# kernel / op level
# ---------------------------------------------------------------------------

def _rand_pool(rs, n, bs, h, d):
    """K and V pools in the one paged layout: [N, Bs, H*D], a token's
    heads side by side."""
    return (rs.randn(n, bs, h * d).astype(np.float32),
            rs.randn(n, bs, h * d).astype(np.float32))


def test_paged_xla_gather_bitwise_matches_slab_reference():
    """Equal logical contents -> the gather path IS the slab path,
    bit for bit (the paged byte-parity foundation)."""
    rs = np.random.RandomState(0)
    b, h, d, bs, nb = 3, 4, 32, 4, 3
    n = 1 + b * nb
    kp, vp = _rand_pool(rs, n, bs, h, d)
    q = rs.randn(b, h, d).astype(np.float32)
    bt = rs.permutation(np.arange(1, n))[:b * nb].reshape(b, nb)
    bt = bt.astype(np.int32)
    pos = np.array([2, 7, 11], np.int32)
    pad = np.array([0, 1, 0], np.int32)
    ks = kp[bt].reshape(b, nb * bs, h, d)
    vs = vp[bt].reshape(b, nb * bs, h, d)
    want = decode_attention(jnp.asarray(q), jnp.asarray(ks),
                            jnp.asarray(vs), pos=jnp.asarray(pos),
                            pad=jnp.asarray(pad), impl="xla")
    got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), block_tables=bt,
                                 pos=pos, pad=pad, impl="xla")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_paged_kernel_matches_gather_reference():
    """The scalar-prefetch kernel (interpret mode off-TPU) against the
    gather reference at a tile-friendly shape, including a row whose
    table holds null/stale entries past its pos."""
    rs = np.random.RandomState(1)
    b, h, d, bs, nb = 2, 2, 64, 128, 3
    assert paged_tile_friendly(bs, h, d)
    n = 1 + b * nb
    q = rs.randn(b, h, d).astype(np.float32)
    kp, vp = _rand_pool(rs, n, bs, h, d)
    bt = np.arange(1, 1 + b * nb, dtype=np.int32).reshape(b, nb)
    bt[0, 2] = 0                    # beyond pos: never read
    pos = np.array([130, 380], np.int32)
    pad = np.array([3, 0], np.int32)
    want = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), block_tables=bt,
                                  pos=pos, pad=pad, impl="xla")
    got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), block_tables=bt,
                                 pos=pos, pad=pad, impl="pallas")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-6, atol=2e-6)


def test_paged_kernel_matches_gather_on_verify_expanded_rows():
    """The speculative verify path (round 16) presents paged attention
    with ROW-EXPANDED queries: lanes (b, j) sit at consecutive
    positions pos_b + j and SHARE row b's block table. Both impls must
    agree on exactly that shape — the kernel's scalar-prefetch index
    maps see repeated table rows and per-lane pos, the gather
    reference sees them as ordinary independent rows."""
    rs = np.random.RandomState(2)
    b, kk, h, d, bs, nb = 2, 4, 2, 64, 128, 3
    assert paged_tile_friendly(bs, h, d)
    n = 1 + b * nb
    kp, vp = _rand_pool(rs, n, bs, h, d)
    q = rs.randn(b * kk, h, d).astype(np.float32)
    bt = np.arange(1, 1 + b * nb, dtype=np.int32).reshape(b, nb)
    bt_e = np.repeat(bt, kk, axis=0)
    pos = (np.array([[100], [250]], np.int32)
           + np.arange(kk, dtype=np.int32)[None]).reshape(-1)
    pad = np.repeat(np.array([3, 0], np.int32), kk)
    want = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), block_tables=bt_e,
                                  pos=pos, pad=pad, impl="xla")
    got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), block_tables=bt_e,
                                 pos=pos, pad=pad, impl="pallas")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-6, atol=2e-6)
    # the expanded call is ALSO exactly the per-lane single-query call
    # — lane independence is what the verify program's exactness rides
    for b_i in range(b):
        for j in range(kk):
            r = b_i * kk + j
            one = paged_decode_attention(
                jnp.asarray(q[r:r + 1]), jnp.asarray(kp),
                jnp.asarray(vp), block_tables=bt[b_i:b_i + 1],
                pos=pos[r:r + 1], pad=pad[r:r + 1], impl="xla")
            np.testing.assert_array_equal(np.asarray(want[r]),
                                          np.asarray(one[0]))


def _kernel_case(name):
    """Inputs of one case of ``test_paged_kernel_cases``: (q, k_pool,
    v_pool, block_tables, pos, pad) and, where the kernel's pool differs
    from the reference's (NaN bytes nothing may read), the reference's
    pools."""
    rs = np.random.RandomState(sum(map(ord, name)))
    h, d = (4, 128) if "4x128" in name else (4, 96) if "4x96" in name \
        else (12, 64)
    bs, nb = 128, 6 if "six" in name else 3
    b = 3
    n = 1 + b * nb
    kp, vp = _rand_pool(rs, n, bs, h, d)
    bt = rs.permutation(np.arange(1, n)).astype(np.int32).reshape(b, nb)
    pos = np.array([130, nb * bs - 1, 5], np.int32)
    pad = np.zeros(b, np.int32)
    ref = None
    if name.startswith("pos"):          # the block edges, in every row
        pos[:] = {"pos0": 0, "pos127": 127, "pos128": 128,
                  "poslast": nb * bs - 1}[name.split("_")[0]]
    elif name.startswith("pad"):
        pad[:] = [3, 140, 5]            # a whole masked block; pad == pos
    elif name.startswith("nulltable"):  # rows that are not alive
        bt[0] = bt[2] = 0
        pos[[0, 2]] = 0
    elif name.startswith("nan"):
        # bytes nothing may read: the blocks of entries past pos (row 0:
        # entry 2; row 2: entries 1, 2) and the null block
        ref = (kp.copy(), vp.copy())
        for blk in (0, bt[0, 2], bt[2, 1], bt[2, 2]):
            kp[blk] = vp[blk] = np.nan
    q = rs.randn(b, h, d).astype(np.float32)
    if name.startswith("kfold"):        # a verify program's expanded rows
        kk = 4
        q = rs.randn(b * kk, h, d).astype(np.float32)
        bt = np.repeat(bt, kk, axis=0)
        pos = (np.array([100, 250, 126], np.int32)[:, None]
               + np.arange(kk, dtype=np.int32)[None]).reshape(-1)
        pad = np.repeat(np.array([3, 0, 0], np.int32), kk)
    return (q, kp, vp, bt, pos, pad), ref


@pytest.mark.parametrize("name", [
    "mixed_12x64", "mixed_4x128", "mixed_4x96", "mixed_six_12x64",
    "pos0_12x64", "pos127_12x64", "pos128_12x64", "poslast_12x64",
    "pos0_4x128", "poslast_six_4x128", "pad_12x64", "pad_4x128",
    "nulltable_12x64", "nulltable_six_4x128", "nan_12x64", "nan_4x128",
    "kfold_12x64", "kfold_six_4x128"])
def test_paged_kernel_cases(name):
    """The kernel (interpret mode off-TPU) against the gather reference:
    12 heads x 64 and 4 x 128 (and 4 x 96: the query tile keeps any head
    size apart), pos on the block edges, a pad window, rows that are not
    alive (every entry the null block, pos 0), blocks past pos and a null
    block full of NaN (entries past pos are neither fetched nor
    computed: exact 0), a verify program's K-fold rows at consecutive
    pos over one table row."""
    (q, kp, vp, bt, pos, pad), ref = _kernel_case(name)
    assert paged_tile_friendly(kp.shape[1], *q.shape[1:])
    rk, rv = ref or (kp, vp)
    want = paged_decode_attention(jnp.asarray(q), jnp.asarray(rk),
                                  jnp.asarray(rv), block_tables=bt,
                                  pos=pos, pad=pad, impl="xla")
    got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), block_tables=bt,
                                 pos=pos, pad=pad, impl="pallas")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=3e-6, atol=3e-6)


@pytest.mark.parametrize("entries", [1, 2, 3, 6])
def test_paged_kernel_same_result_at_every_schedule(entries):
    """1, 2, 3 or 6 table entries a grid step: the same online softmax in
    the same order, so the same bytes as the schedule's own choice."""
    (q, kp, vp, bt, pos, pad), _ = _kernel_case("mixed_six_12x64")
    args = tuple(map(jnp.asarray, (q, kp, vp, bt, pos, pad)))
    want = decode_mod._paged_dispatch(*args)
    got = decode_mod._paged_dispatch(
        *args, schedule=decode_mod.PagedSchedule(entries, 0))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_kernel_reads_every_layer_where_it_lies(dtype):
    """The flat [L*N, Bs, H*D] view with ``block_tables + i * N``: layer
    i's call equals the call on layer i's own pool, bf16 pools under a
    bf16 query (MXU operands stay bf16) included."""
    rs = np.random.RandomState(7)
    layers, b, h, d, bs, nb = 3, 2, 12, 64, 128, 3
    n = 1 + b * nb
    kp = jnp.asarray(rs.randn(layers, n, bs, h * d), dtype)
    vp = jnp.asarray(rs.randn(layers, n, bs, h * d), dtype)
    q = jnp.asarray(rs.randn(b, h, d), dtype)
    bt = np.arange(1, 1 + b * nb, dtype=np.int32).reshape(b, nb)
    pos = np.array([200, 383], np.int32)
    pad = np.zeros(b, np.int32)
    tol = 2e-2 if dtype == "bfloat16" else 3e-6
    for i in range(layers):
        got = paged_decode_attention(
            q, kp.reshape(layers * n, bs, h * d),
            vp.reshape(layers * n, bs, h * d), block_tables=bt + i * n,
            pos=pos, pad=pad, impl="pallas")
        assert got.dtype == jnp.dtype(dtype)
        own = paged_decode_attention(q, kp[i], vp[i], block_tables=bt,
                                     pos=pos, pad=pad, impl="pallas")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(own))
        want = paged_decode_attention(q, kp[i], vp[i], block_tables=bt,
                                      pos=pos, pad=pad, impl="xla")
        np.testing.assert_allclose(
            np.asarray(want, np.float32), np.asarray(got, np.float32),
            rtol=tol, atol=tol)


@pytest.mark.parametrize("entries", [1, 2, 3, 6])
def test_fetch_table_names_what_the_operand_already_holds(entries):
    """A live entry names its own block; a dead one the block the same
    operand (entry index modulo ``entries``) held a grid step earlier,
    in grid order, so a dead entry is never fetched."""
    rs = np.random.RandomState(entries)
    b, nb, bs = 9, 6, 128
    bt = rs.randint(1, 500, (b, nb)).astype(np.int32)
    pos = rs.randint(0, nb * bs, b).astype(np.int32)
    pos[[2, 3]] = 0
    got = np.asarray(decode_mod._fetch_table(
        jnp.asarray(bt), jnp.asarray(pos), entries, bs))
    held = {e: bt[0, e] for e in range(entries)}     # before any fetch
    for r in range(b):
        for j in range(nb):
            e = j % entries
            if j * bs <= pos[r]:
                held[e] = bt[r, j]
            assert got[r, j] == held[e], (r, j)


#: (rows, heads, head_dim, block_size, table width, pool dtype): what the
#: GPT-2 cells serve (decode, a verify program's 4-fold rows, int8 pools)
#: and every shape ``experiments/flash_sweep.py paged`` times
_READ_SHAPES = [(rows, h, d, 128, 6, dt)
                for rows in (16, 64, 128, 256)
                for h, d in ((12, 64), (6, 128))
                for dt in ("bfloat16", "int8")]


def test_paged_schedule_fits_vmem_and_falls_back_conservatively():
    for rows, h, d, bs, nb, dt in _READ_SHAPES:
        sch = decode_mod.paged_schedule(rows, h, d, bs, nb, dt)
        assert sch.entries == nb                     # one grid step a row
        assert sch.vmem_bytes == decode_mod._paged_vmem_bytes(
            sch.entries, h, d, bs, dt) <= decode_mod._PAGED_VMEM_BUDGET
        assert sch.describe(rows, nb) == {
            "kernel": "paged_decode_attn", "entries_per_step": nb,
            "grid": [rows, 1], "vmem_bytes": sch.vmem_bytes}
    # shapes no sweep read: the same rule, down to one entry a step
    assert decode_mod.paged_schedule(64, 12, 64, 128, 7).entries == 7
    assert decode_mod.paged_schedule(64, 12, 64, 128, 34).entries == 2
    assert decode_mod.paged_schedule(64, 12, 64, 128, 13).entries == 1
    wide = decode_mod.paged_schedule(8, 32, 128, 128, 8)      # 4096 lanes
    assert 1 <= wide.entries < 8
    assert wide.vmem_bytes <= decode_mod._PAGED_VMEM_BUDGET
    # a block that cannot fit: the predicate says so, the rule refuses
    assert not paged_tile_friendly(128, 96, 128)
    with pytest.raises(ValueError, match="VMEM"):
        decode_mod.paged_schedule(8, 96, 128, 128, 8)


def test_schedule_log_names_what_a_program_was_traced_with():
    (q, kp, vp, bt, pos, pad), _ = _kernel_case("mixed_six_12x64")
    args = tuple(map(jnp.asarray, (q, kp, vp)))
    kw = dict(block_tables=bt, pos=pos, pad=pad)
    with decode_mod.schedule_log() as seen:
        jax.eval_shape(lambda *a: paged_decode_attention(
            *a, impl="pallas", **kw), *args)
        jax.eval_shape(lambda *a: paged_decode_attention(
            *a, impl="pallas", **kw), *args)          # a layer scan: once
    sch = decode_mod.paged_schedule(3, 12, 64, 128, 6, jnp.float32)
    assert seen == [sch.describe(3, 6)]
    with decode_mod.schedule_log() as seen:
        paged_decode_attention(*args, impl="xla", **kw)
    assert seen == [{"kernel": "xla"}]
    paged_decode_attention(*args, impl="xla", **kw)    # no log: no-op


def test_paged_kernel_rejects_unfriendly_shapes():
    q = jnp.zeros((1, 2, 32))
    kp = jnp.zeros((2, 4, 2 * 32))
    with pytest.raises(ValueError, match="block_size"):
        paged_decode_attention(q, kp, kp, block_tables=np.zeros(
            (1, 1), np.int32), pos=np.zeros(1, np.int32),
            pad=np.zeros(1, np.int32), impl="pallas")


@pytest.fixture(scope="module")
def tiny_model():
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    return m, m.init(jax.random.key(0))


@pytest.mark.parametrize("dead_on_null", [False, True])
def test_paged_decode_step_bitwise_matches_slab(tiny_model, dead_on_null):
    """decode_step_batched_paged (the pools flat, carried through the
    layer scan, layer i read at row i * N of the flat view) ==
    decode_step_batched on the slab, bit for bit on equal logical
    contents: logits AND every byte of the pools, the dead row's
    blocks, the null block and the blocks no table names included.
    ``dead_on_null``: the dead row's table names the null block 0, as
    the engine leaves a retired slot's."""
    m, params = tiny_model
    c = m.cfg
    rs = np.random.RandomState(2)
    b, bs, nb = 3, 4, 3
    t = nb * bs
    l, h, d = c.layers, c.heads, m.head_dim
    n = 2 + b * nb                          # null + the runs + one spare
    slab = {x: rs.randn(l, b, t, h, d).astype(np.float32)
            for x in ("k", "v")}
    bt = (1 + np.arange(b * nb).reshape(b, nb)).astype(np.int32)
    pools = {}
    for x in ("k", "v"):
        pool = rs.randn(l, n, bs, h * d).astype(np.float32)
        for bb in range(b):
            for j in range(nb):
                pool[:, bt[bb, j]] = slab[x][
                    :, bb, j * bs:(j + 1) * bs].reshape(l, bs, h * d)
        pools[x] = jnp.asarray(pool)
    slabj = {x: jnp.asarray(v) for x, v in slab.items()}
    stacked = m.stack_decode_params(params)
    tok = jnp.asarray(rs.randint(0, c.vocab_size, (b,)), jnp.int32)
    pos = jnp.asarray([3, 7, 11], jnp.int32)
    pad = jnp.zeros((b,), jnp.int32)
    alive = jnp.asarray([1, 1, 0], jnp.int32)
    lg_s, new_s = m.decode_step_batched(params, stacked, slabj, tok,
                                        pos, pad, alive,
                                        decode_attention="xla")
    bt_p = bt.copy()
    if dead_on_null:
        bt_p[2] = 0
    lg_p, new_p = m.decode_step_batched_paged(params, stacked, pools,
                                              bt_p, tok, pos, pad, alive,
                                              decode_attention="xla")
    live = slice(0, 2) if dead_on_null else slice(None)
    np.testing.assert_array_equal(np.asarray(lg_s)[live],
                                  np.asarray(lg_p)[live])
    for x in ("k", "v"):
        got = np.asarray(new_p[x])
        assert got.shape == (l, n, bs, h * d)
        gathered = got[:, bt].reshape(l, b, t, h, d)
        np.testing.assert_array_equal(gathered, np.asarray(new_s[x]))
        # outside the two live rows' written token rows not a byte
        # moved: null block, spare block, the dead row's run
        want = np.asarray(pools[x]).copy()
        for bb in (0, 1):
            pb, off = bt[bb, int(pos[bb]) // bs], int(pos[bb]) % bs
            want[:, pb, off] = got[:, pb, off]
        np.testing.assert_array_equal(got, want)


def test_paged_prefill_matches_oracle_and_writes_blocks(tiny_model):
    """paged_prefill's first-token pick equals the monolithic ragged
    oracle's, and the written blocks hold the left-aligned prefill
    K/V."""
    m, params = tiny_model
    c = m.cfg
    l, h, d = c.layers, c.heads, m.head_dim
    rs = np.random.RandomState(3)
    p = 6
    prompt = rs.randint(0, c.vocab_size, (p,)).astype(np.int32)
    ids = np.zeros((1, PROMPT_LEN), np.int32)
    mask = np.zeros((1, PROMPT_LEN), np.int32)
    ids[0, :p] = prompt
    mask[0, :p] = 1
    tr = np.array([2, 4], np.int32)
    kp = jnp.zeros((l, 6, BLOCK, h * d), jnp.float32)
    vp = jnp.zeros((l, 6, BLOCK, h * d), jnp.float32)
    logits, kp2, vp2 = m.paged_prefill(params, jnp.asarray(ids),
                                       jnp.asarray(mask), kp, vp,
                                       jnp.asarray(tr))
    last_h, _, _ = m.ragged_prefill(params, jnp.asarray(ids),
                                    jnp.asarray(mask), PROMPT_LEN)
    want = m.lm_logits(params, last_h[:, None])[:, 0]
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(want[0]))
    # written blocks = the left-aligned prefill's own K/V
    hfull, caches = m._prefill_full(
        params, jnp.asarray(np.where(mask, ids, 0)), 2 * BLOCK,
        mask=jnp.asarray(mask),
        pos_ids=jnp.arange(PROMPT_LEN, dtype=jnp.int32)[None])
    kv = m._stack_caches(caches)
    for x, pool in (("k", kp2), ("v", vp2)):
        want_blocks = np.asarray(kv[x])[:, 0].reshape(l, 2, BLOCK, h * d)
        np.testing.assert_array_equal(np.asarray(pool)[:, tr],
                                      want_blocks)


# ---------------------------------------------------------------------------
# allocator / cache / estimator units
# ---------------------------------------------------------------------------

def test_block_pool_alloc_release_refcount():
    bp = BlockPool(6)                       # 5 usable + null
    assert bp.usable == 5 and bp.free_count == 5
    run = bp.alloc(3)
    assert len(set(run)) == 3 and 0 not in run
    assert bp.free_count == 2
    bp.retain(run[:1])                      # shared with a second owner
    bp.release(run)
    # the shared block survives its first release...
    assert bp.free_count == 4
    assert bp.refcount(run[0]) == 1
    bp.release(run[:1])                     # ...and frees at the LAST
    assert bp.free_count == 5


def test_block_pool_exhaustion_is_all_or_nothing():
    bp = BlockPool(4)
    bp.alloc(2)
    with pytest.raises(BlocksExhaustedError):
        bp.alloc(2)
    assert bp.free_count == 1               # nothing partially taken


def test_block_pool_fragmentation_after_mixed_retirement():
    """Release a non-contiguous subset; the next alloc serves from the
    holes — physical contiguity is irrelevant under table
    indirection."""
    bp = BlockPool(9)
    run = bp.alloc(8)
    odd = run[1::2]
    bp.release(odd)
    assert bp.free_count == 4
    again = bp.alloc(4)
    assert sorted(again) == sorted(odd)
    assert bp.free_count == 0
    with pytest.raises(BlocksExhaustedError):
        bp.alloc(1)


def test_block_pool_double_release_raises():
    bp = BlockPool(3)
    run = bp.alloc(1)
    bp.release(run)
    with pytest.raises(AssertionError, match="double release"):
        bp.release(run)
    with pytest.raises(AssertionError, match="retain of free"):
        bp.retain(run)


def test_prefix_cache_longest_match_and_lru_eviction():
    bp = BlockPool(10)
    pc = PrefixCache(bp, block_size=4)
    toks = np.arange(100, 110, dtype=np.int32)      # 10 tokens
    run = bp.alloc(3)                               # ceil(10/4)
    pc.insert(toks, run)
    # entries: 4-token chain, 8-token chain, exact 10-token
    assert len(pc) == 3
    n, blocks = pc.lookup(toks)                     # exact wins
    assert n == 10 and list(blocks) == run
    n, blocks = pc.lookup(np.concatenate([toks[:7], [999]]).astype(np.int32))
    assert n == 4 and list(blocks) == run[:1]       # longest chain
    n, _ = pc.lookup(np.array([1, 2, 3], np.int32))
    assert n == 0
    assert pc.hits == 2 and pc.misses == 1
    # record=False probes (the engine's block-pressure deferral loop)
    # leave the counters alone — one admission counts exactly once
    pc.lookup(toks, record=False)
    pc.lookup(np.array([1, 2, 3], np.int32), record=False)
    assert pc.hits == 2 and pc.misses == 1
    # eviction: release the owner's refs, then evict — blocks free
    # only when the LAST reference (the cache's) is dropped
    bp.release(run)
    assert bp.free_count == 6                       # cache still holds
    pc.evict(9)
    assert bp.free_count == 9 and len(pc) == 0


def test_retry_after_estimator_ema_math():
    est = RetryAfterEstimator(alpha=0.5)
    assert est.estimate(10) == 1.0                  # no signal yet
    est.observe(0.10)
    assert est.ema_step_s == pytest.approx(0.10)
    est.observe(0.20)
    assert est.ema_step_s == pytest.approx(0.15)
    est.observe(0.05)
    assert est.ema_step_s == pytest.approx(0.10)
    # steps-to-free and queue waves scale the estimate
    assert est.estimate(4) == pytest.approx(0.4)
    assert est.estimate(4, queue_ahead=8, slots=4) \
        == pytest.approx(0.4 * 3)
    assert est.estimate(0.1) == pytest.approx(0.1)  # floor


# ---------------------------------------------------------------------------
# engine level (paged artifacts)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_dir(tmp_path_factory, tiny_model):
    """One roomy paged export shared module-wide (48 blocks, so prefix
    entries never get evicted mid-test)."""
    d = str(tmp_path_factory.mktemp("paged"))
    m, params = tiny_model
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=SLOTS, paged=True,
                     block_size=BLOCK, num_blocks=48,
                     platforms=("cpu",))
    return d


def test_export_and_stats_name_the_attention_schedule(
        tmp_path, paged_dir, tiny_model, monkeypatch):
    """``export.json`` ``stepwise.decode.attn_schedule`` and ``/stats``
    ``attn_schedule``: what each program's paged decode attention was
    traced with. An export off the TPU holds the gather; one for the TPU
    alone, made where the backend says it is one (held so here: the
    programs are lowered for the chip and never run), names the kernel's
    schedule, the verify program at its K-fold rows."""
    import os
    with open(os.path.join(paged_dir, "export.json")) as f:
        meta = json.load(f)
    assert meta["stepwise"]["decode"]["attn_schedule"] == {
        "decode": {"kernel": "xla"}}
    m, params = tiny_model                      # 4 heads x 32 = 128 lanes
    d = str(tmp_path)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=SLOTS, paged=True, block_size=128,
                     spec_tokens=2, platforms=("tpu",))
    with open(os.path.join(d, "export.json")) as f:
        got = json.load(f)["stepwise"]["decode"]["attn_schedule"]
    sch = decode_mod.paged_schedule(SLOTS, 4, 32, 128, 1, jnp.float32)
    assert sch.entries == 1
    assert got == {"decode": sch.describe(SLOTS, 1),
                   "verify": sch.describe(SLOTS * 2, 1)}
    monkeypatch.undo()
    eng = GenerationEngine(load_stepwise(d), prefix_cache=False)
    assert eng.stats()["attn_schedule"] == got


def _oracle(m, params, prompt, max_new=MAX_NEW, **kw):
    ids = np.zeros((1, PROMPT_LEN), np.int32)
    mask = np.zeros((1, PROMPT_LEN), np.int32)
    ids[0, :prompt.size] = prompt
    mask[0, :prompt.size] = 1
    return np.asarray(m.generate(params, jnp.asarray(ids), max_new,
                                 prompt_mask=jnp.asarray(mask),
                                 **kw))[0].tolist()


def _prompts(n, seed=0, lo=1, hi=PROMPT_LEN):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 1000, (int(rs.randint(lo, hi + 1)),)
                       ).astype(np.int32) for _ in range(n)]


def _drain(eng):
    """Drive the engine synchronously (no scheduler thread): admission
    + shared steps until idle — deterministic order for the allocator
    tests."""
    for _ in range(10_000):
        eng._admit()
        if not eng._live:
            if not eng._queue:
                return
            continue
        eng._shared_step()
    raise AssertionError("engine did not drain")


def test_paged_cold_greedy_parity(paged_dir, tiny_model):
    """Cold paged serving is byte-identical to the single-request
    oracle for a full mixed-length concurrent wave."""
    m, params = tiny_model
    prompts = _prompts(SLOTS * 2, seed=10)
    eng = GenerationEngine(load_stepwise(paged_dir))
    assert eng.paged
    futs = [eng.submit(p) for p in prompts]
    eng.start()
    try:
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.close()
    for p, g in zip(prompts, got):
        assert g == _oracle(m, params, p)


def test_each_program_compiles_once_across_requests(paged_dir):
    """Sequential requests ride ONE executable per program: the pool
    the engine starts from must key the jit cache exactly like the
    pool a program hands back. (Found on the chip in PR 21: the fresh
    pool was uncommitted, so the second request ever served paid a
    second full compile of the prefill program — 33 s of
    time-to-first-token at GPT-2-small.)"""
    sw = load_stepwise(paged_dir)
    eng = GenerationEngine(sw, prefix_cache=False).start()
    try:
        for p in _prompts(3, seed=21):
            eng.generate(p, timeout=120)
    finally:
        eng.close()
    assert sw._prefill._cache_size() == 1
    assert sw._decode._cache_size() == 1


def test_exact_prefix_hit_skips_prefill_and_keeps_parity(paged_dir,
                                                         tiny_model):
    """Resubmitting known prompts costs ZERO prefill dispatches (the
    headline claim) and stays byte-identical — including the
    copy-on-write protecting the cached tail block."""
    m, params = tiny_model
    prompts = _prompts(4, seed=11)
    eng = GenerationEngine(load_stepwise(paged_dir))
    futs = [eng.submit(p) for p in prompts]
    eng.start()
    try:
        first = [f.result(timeout=120) for f in futs]
        pre = eng.prefills
        second = [eng.submit(p).result(timeout=120) for p in prompts]
        third = [eng.submit(p).result(timeout=120) for p in prompts]
    finally:
        eng.close()
    assert eng.prefills == pre, "repeat prompts must not prefill"
    for p, a, b, c in zip(prompts, first, second, third):
        want = _oracle(m, params, p)
        assert a == want and b == want and c == want
    s = eng.stats()
    assert s["prefix_cache_hits"] >= 8
    assert s["prefill_tokens_saved"] > 0


def test_divergent_suffix_reuses_prefix_blocks(paged_dir, tiny_model):
    """Shared system prefix + different user suffixes: later requests
    mount the prefix blocks (no prefill) and teacher-force only their
    own suffix; outputs match the cold oracle byte for byte."""
    m, params = tiny_model
    rs = np.random.RandomState(12)
    sysp = rs.randint(0, 1000, (BLOCK,)).astype(np.int32)
    suffixes = [rs.randint(0, 1000, (k,)).astype(np.int32)
                for k in (1, 2, 3)]
    prompts = [np.concatenate([sysp, s]) for s in suffixes]
    eng = GenerationEngine(load_stepwise(paged_dir))
    eng.start()
    try:
        first = eng.submit(prompts[0]).result(timeout=120)
        pre = eng.prefills
        rest = [eng.submit(p).result(timeout=120) for p in prompts[1:]]
    finally:
        eng.close()
    assert eng.prefills == pre, "prefix hits must not prefill"
    for p, g in zip(prompts, [first] + rest):
        assert g == _oracle(m, params, p)


def test_partial_hit_prompt_gets_cached_for_exact_repeat(paged_dir,
                                                         tiny_model):
    """A prompt admitted via a PARTIAL prefix hit is inserted into the
    cache once its teacher-forced suffix lands, so an identical repeat
    exact-hits (re-feeds only the last token) instead of re-forcing
    the suffix forever."""
    m, params = tiny_model
    rs = np.random.RandomState(22)
    sysp = rs.randint(0, 1000, (BLOCK,)).astype(np.int32)
    u1 = rs.randint(0, 1000, (2,)).astype(np.int32)
    u2 = rs.randint(0, 1000, (3,)).astype(np.int32)
    p2 = np.concatenate([sysp, u2])
    eng = GenerationEngine(load_stepwise(paged_dir))
    eng.submit(np.concatenate([sysp, u1]))      # cold: caches sysp chain
    _drain(eng)
    f2 = eng.submit(p2)                         # partial hit on sysp
    _drain(eng)
    saved_before = eng.prefill_tokens_saved
    f3 = eng.submit(p2)                         # must now EXACT-hit
    _drain(eng)
    assert eng.prefill_tokens_saved - saved_before == p2.size - 1, (
        "identical repeat of a partial-hit prompt should exact-hit "
        "(re-feed only the last token)")
    want = _oracle(m, params, p2)
    assert f2.result(timeout=5) == want
    assert f3.result(timeout=5) == want
    eng.close()


def test_cow_on_divergence_protects_cached_blocks(paged_dir,
                                                  tiny_model):
    """An exact-hit request writes its first generated token INTO the
    shared tail block's successor slot — the engine must copy first
    (cow_copies advances) and the cached bytes must stay pure: a third
    identical request still matches the oracle."""
    m, params = tiny_model
    prompt = _prompts(1, seed=13, lo=5, hi=7)[0]     # partial tail block
    assert prompt.size % BLOCK != 0
    eng = GenerationEngine(load_stepwise(paged_dir))
    f1 = eng.submit(prompt)
    _drain(eng)
    cow0 = eng.cow_copies
    f2 = eng.submit(prompt)
    _drain(eng)
    assert eng.cow_copies > cow0, (
        "exact-hit divergence must copy-on-write the shared tail block")
    f3 = eng.submit(prompt)
    _drain(eng)
    want = _oracle(m, params, prompt)
    # all three resolved identically (cached bytes unpolluted)
    for f in (f1, f2, f3):
        assert f.result(timeout=5) == want
    eng.close()


@pytest.mark.parametrize("chunk", [0, BLOCK])
def test_served_tokens_survive_hit_cow_and_chunked_prefill(tmp_path,
                                                           tiny_model,
                                                           chunk):
    """One engine over the flat pool, every path that writes or copies
    it: a cold prefill (monolithic, or in whole-block chunks read back
    through the table), an exact prefix hit whose first token
    copies-on-write the shared tail block, and a divergent suffix
    teacher-forced onto mounted prefix blocks. Each request returns the
    single-request oracle's tokens."""
    m, params = tiny_model
    d = str(tmp_path / "art")
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=SLOTS, paged=True,
                     block_size=BLOCK, num_blocks=48, prefill_chunk=chunk,
                     platforms=("cpu",))
    rs = np.random.RandomState(31)
    base = rs.randint(0, 1000, (BLOCK + 2,)).astype(np.int32)
    other = np.concatenate([base[:BLOCK],
                            rs.randint(0, 1000, (3,)).astype(np.int32)])
    eng = GenerationEngine(load_stepwise(d),
                           prefill_chunk_tokens=chunk).start()
    try:
        for prompt in (base, base, other):
            assert eng.submit(prompt).result(timeout=120) == _oracle(
                m, params, prompt)
        s = eng.stats()
    finally:
        eng.close()
    assert s["prefills"] + s["prefill_chunks"] > 0
    assert (s["prefill_chunks"] > 0) == bool(chunk)
    assert s["prefix_cache_hits"] >= 2 and s["cow_copies"] >= 1
    assert len(s["pool_shape"]) == 4


def test_block_exhaustion_fails_one_request_loudly(tmp_path,
                                                   tiny_model):
    """Mid-decode block exhaustion: the request that cannot get a
    block fails with a clear error; its neighbor keeps its blocks and
    finishes byte-identical to the oracle."""
    m, params = tiny_model
    d = str(tmp_path / "tight")
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=8, batch_size=1, ragged=True,
                     stepwise=True, slots=2, paged=True,
                     block_size=BLOCK, num_blocks=6,   # 5 usable
                     platforms=("cpu",))
    eng = GenerationEngine(load_stepwise(d), prefix_cache=False)
    pa, pb = _prompts(2, seed=14, lo=4, hi=4)
    fa = eng.submit(pa, max_new=8)      # needs 3 blocks over its life
    fb = eng.submit(pb, max_new=8)      # the 6th block does not exist
    _drain(eng)
    assert fa.result(timeout=5) == _oracle(m, params, pa, max_new=8)
    with pytest.raises(BlocksExhaustedError, match="mid-decode"):
        fb.result(timeout=5)
    # the engine still serves: a fresh short request completes
    fc = eng.submit(pa, max_new=1)
    _drain(eng)
    assert fc.result(timeout=5) == _oracle(m, params, pa, max_new=1)
    eng.close()


def test_block_pressure_defers_admission_until_retirement(tmp_path,
                                                          tiny_model):
    """Admission is driven by BLOCK availability, not slot count: a
    request that cannot get its block run waits at the queue head and
    admits after a retirement frees blocks — no deadlock, no loss."""
    m, params = tiny_model
    d = str(tmp_path / "tiny_pool")
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=2, batch_size=1, ragged=True,
                     stepwise=True, slots=2, paged=True,
                     block_size=BLOCK, num_blocks=4,    # 3 usable
                     platforms=("cpu",))
    eng = GenerationEngine(load_stepwise(d), prefix_cache=False)
    big = _prompts(1, seed=15, lo=PROMPT_LEN, hi=PROMPT_LEN)[0]
    ok = _prompts(1, seed=16, lo=2, hi=2)[0]
    # occupy 2 of 3 blocks so the 2-block prompt cannot fit...
    f_big = eng.submit(big, max_new=1)
    _drain(eng)
    assert f_big.result(timeout=5)      # fits alone (2 blocks + 1 spare)
    # now the unservable case: pool smaller than one prompt's run is
    # impossible by export validation, so exercise the deferral path:
    # a long-lived request holds blocks; a queued one waits, then runs
    f1 = eng.submit(big, max_new=2)
    f2 = eng.submit(big, max_new=2)
    _drain(eng)
    assert f1.result(timeout=5) == f2.result(timeout=5) \
        == _oracle(m, params, big, max_new=2)
    eng.close()


def test_paged_capacity_2x_slab_at_equal_pool_bytes(tmp_path,
                                                    tiny_model):
    """THE capacity claim: at equal pool bytes, paged admission holds
    >= 2x the slab slot count of short concurrent requests (slab
    reserves slots x T; paged reserves actual residency)."""
    m, params = tiny_model
    slab_slots = 2
    total = PROMPT_LEN + MAX_NEW                     # 13
    blocks_per_slot = -(-total // BLOCK)             # 4
    usable = slab_slots * blocks_per_slot            # slab bytes, blocks
    d = str(tmp_path / "cap")
    export_generator(m, params, d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=4 * slab_slots, paged=True,
                     block_size=BLOCK, num_blocks=1 + usable,
                     platforms=("cpu",))
    eng = GenerationEngine(load_stepwise(d), prefix_cache=False)
    # short prompts: 1 block each
    for p in _prompts(4 * slab_slots, seed=17, lo=2, hi=3):
        eng.submit(p, max_new=MAX_NEW)
    eng._admit()
    admitted = len(eng._live)
    assert admitted >= 2 * slab_slots, (
        f"paged pool admitted {admitted} concurrent requests; the slab "
        f"pool of equal bytes holds {slab_slots}")
    assert admitted == usable                        # 1 block per prompt
    eng.close()


def test_paged_stats_block_observability(paged_dir):
    eng = GenerationEngine(load_stepwise(paged_dir))
    eng.submit(_prompts(1, seed=18)[0])
    _drain(eng)
    s = eng.stats()
    for key in ("blocks_total", "blocks_free", "bytes_resident",
                "prefix_cache_hits", "prefix_cache_misses",
                "prefill_tokens_saved", "cow_copies", "block_size"):
        assert key in s, key
    assert s["paged"] is True
    # the one pool layout: [L, N, Bs, H*D], as export.json records it
    with open(f"{paged_dir}/export.json") as f:
        assert s["pool_shape"] == json.load(f)["stepwise"]["pool_shape"]
    assert s["pool_shape"] == [2, 48, BLOCK, 128]
    assert s["blocks_total"] == 47
    assert 0 <= s["blocks_free"] <= s["blocks_total"]
    resident = s["blocks_total"] - s["blocks_free"]
    assert s["bytes_resident"] == resident * eng._block_bytes
    eng.close()


def test_shared_block_freed_only_at_last_release(paged_dir, tiny_model):
    """Engine-level refcount contract: a block shared by the prefix
    cache and TWO mounted slots survives cache eviction and the first
    retirement; it frees only when the last owner lets go."""
    m, params = tiny_model
    rs = np.random.RandomState(19)
    sysp = rs.randint(0, 1000, (BLOCK,)).astype(np.int32)   # 1 full block
    eng = GenerationEngine(load_stepwise(paged_dir))
    eng.submit(sysp, max_new=1)
    _drain(eng)                                  # cold: caches the block
    free_with_cache = eng.cache.pool.free_count
    blk = None
    for (blocks, n) in eng.cache.prefix._entries.values():
        if n == BLOCK:
            blk = blocks[0]
    assert blk is not None
    assert eng.cache.pool.refcount(blk) == 1                 # cache only
    # two hit admissions mount it (no steps run yet)
    a = np.concatenate([sysp, rs.randint(0, 1000, (1,)).astype(np.int32)])
    b = np.concatenate([sysp, rs.randint(0, 1000, (2,)).astype(np.int32)])
    fa, fb = eng.submit(a), eng.submit(b)
    eng._admit()
    assert eng.cache.pool.refcount(blk) == 3
    eng.cache.prefix.evict(10 ** 9)                      # drop ALL entries
    assert eng.cache.pool.refcount(blk) == 2                 # slots still hold
    assert eng.cache.pool.free_count < eng.cache.pool.usable
    _drain(eng)                                          # both retire
    # the retired slots re-inserted their (partial-hit) prompts, so
    # the cache again holds blk — drop it to see the LAST release free
    eng.cache.prefix.evict(10 ** 9)
    assert eng.cache.pool.refcount(blk) == 0                 # last release
    assert eng.cache.pool.free_count == eng.cache.pool.usable
    assert fa.result(timeout=5) == _oracle(m, params, a)
    assert fb.result(timeout=5) == _oracle(m, params, b)
    eng.close()


def test_http_paged_end_to_end_parity_and_stats(paged_dir):
    """The REST layer over a paged artifact: auto scheduler on,
    concurrent posts byte-identical to --scheduler off, /stats carries
    the block keys, and --prefix_cache off serves cold."""
    n = 6
    prompts = _prompts(n, seed=20)
    results: list = [None] * n

    def post(port, name, payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/{name}:generate",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())

    with PredictServer(paged_dir) as srv:
        assert srv.scheduler == "on" and srv.engine.paged

        def worker(i):
            results[i] = post(
                srv.port, srv.name,
                {"inputs": {"input_ids": [prompts[i].tolist()]}}
            )["generations"][0]

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/stats") as r:
            stats = json.loads(r.read())["generate"]
    assert stats["paged"] is True
    assert stats["blocks_total"] > 0
    assert stats["requests_done"] == n

    with PredictServer(paged_dir, scheduler="off") as srv:
        for i, p in enumerate(prompts):
            ids = np.zeros((PROMPT_LEN,), np.int32)
            mask = np.zeros((PROMPT_LEN,), np.int32)
            ids[:p.size] = p
            mask[:p.size] = 1
            want = post(srv.port, srv.name,
                        {"inputs": {"input_ids": [ids.tolist()],
                                    "prompt_mask": [mask.tolist()]}}
                        )["generations"][0]
            assert results[i] == want, f"request {i} diverged"

    with PredictServer(paged_dir, prefix_cache=False) as srv:
        assert srv.engine.cache.prefix is None
        got = post(srv.port, srv.name,
                   {"inputs": {"input_ids": [prompts[0].tolist()]}}
                   )["generations"][0]
        assert got == results[0]


def test_engine_retry_after_uses_measured_steps(paged_dir):
    """After real steps the 429 Retry-After reflects the measured EMA,
    not the old queue-depth guess."""
    eng = GenerationEngine(load_stepwise(paged_dir))
    assert eng._retry_after() == 1.0          # no signal yet
    eng.submit(_prompts(1, seed=21, lo=4, hi=6)[0], max_new=MAX_NEW)
    _drain(eng)
    assert eng._retry.ema_step_s is not None
    assert eng._retry_after() >= 0.1
    eng.close()
