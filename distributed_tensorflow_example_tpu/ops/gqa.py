"""Grouped-query attention over two caches of one request: every earlier
row's K and V in a paged pool behind block tables, and the last
``window`` rows in a ring a slot.

A token keeps ``KVH`` key and value heads of ``D`` values, side by side
in one row of ``KVH x D`` values of each cache (whole 128-lane tiles at
D = 128), and ``G = H / KVH`` query heads read KV head ``h // G``. The
head counts are the caller's, a layer kind at a time (Laguna-S-2.1's
full layers bring 48 query heads, its window layers 72, over the same 8
KV heads); rotary positions are on q and k before they arrive.

Two caches, two pairs of forwards (a chunk of one prompt, one token a
slot):

- the paged pool ``[N, Bs, KVH x D]`` (a layer's blocks ``N`` apart in
  the flat view): :func:`gqa_prefill_attention` (the chunk's rows against
  the slot's earlier blocks and the chunk's own, causal) and
  :func:`paged_gqa_decode_attention` (one query row a slot against its
  live blocks: on the chip the Pallas kernel ``paged_gqa_attn``, every
  query head at once under one masked query tile, a KV head's lanes of a
  block serving its ``G`` query heads);
- the ring ``[slots, rows, KVH x D]`` (row ``p % rows`` holds position
  ``p``; ``ops/mla.ring_rows``, ``ring_positions``):
  :func:`gqa_window_prefill_attention` (a band: a tile of queries
  against the rows that end with its own, the ring's before the chunk's)
  and :func:`gqa_window_decode_attention` (over the ring).

The chunk's two forms are one kernel on the chip (``gqa_chunk_attn``:
grid (query heads, query tiles, key tiles) over 128-row blocks named by
a table, the causal and the window mask from positions, tiles outside
the band neither fetched nor computed) and one tile loop in XLA
elsewhere (the kernel's oracle).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla import NEG_INF, ring_positions
from .pallas.decode_attention import (_fetch_table, _log_schedule,
                                      _tile_rows)

#: table entries one grid step of ``paged_gqa_attn`` visits (a divisor
#: of the table's width is taken: :func:`gqa_entries`). Read on the chip
#: at 24 rows x 48 heads over 8 KV heads x 128, a table of 128 blocks
#: (``benchmark/records/pr41/attn_sweep.jsonl``): 2 / 4 / 8 / 16 entries
#: 1.27 / 1.23 / 1.27 / 1.34 ms at the cell's mix of contexts, 0.59 / 0.61
#: / 0.68 / 0.78 at 2 k rows, 2.31 / 2.15 / 2.13 / 2.14 at 16 k (the live
#: rows' bytes over 819 GB/s: 0.91, 0.25, 1.97); ``paged_block_attn``
#: with one lane (one block a step) 2.00, 0.88, 3.73
_ENTRIES_A_STEP = 4
#: no window: every earlier row
_NO_WINDOW = 1 << 30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies of a ``dim``-wide rotation, [dim / 2]
    float32: pair ``i``'s plain frequency ``theta^(-2i / dim)`` where it
    turns more than ``beta_fast`` times within the ``original`` context,
    that frequency over ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between the two in ``i``."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(n):        # the pair that turns n times in `original`
        return dim * math.log(original / (n * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return ((1.0 / (factor * pos)) * ramp
            + (1.0 / pos) * (1.0 - ramp)).astype(np.float32)


def _split(q, kv_heads: int):
    """[.., H, D] -> [.., KVH, G, D]."""
    *lead, h, d = q.shape
    return q.reshape(*lead, kv_heads, h // kv_heads, d)


def _own(heads: int, kv_heads: int):
    """[H, KVH] bool: query head h reads KV head ``h // G``."""
    return (jnp.arange(heads)[:, None] // (heads // kv_heads)
            == jnp.arange(kv_heads)[None, :])


def _query_tile(q, kv_heads: int):
    """The masked query tile: ``q`` [B, H, D] -> [B, H, KVH x D], head
    h's values in the lanes of its KV head and zeros elsewhere, so that
    ONE product with a cache row ``[KVH x D]`` as it lies gives each
    head its own score (the zeros contribute exact 0)."""
    b, h, d = q.shape
    return jnp.where(_own(h, kv_heads)[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(b, h, kv_heads * d)


def _own_lanes(out, kv_heads: int):
    """The way back: ``out`` [B, H, KVH x D] -> [B, H, D], row h keeps
    the lanes of its KV head."""
    b, h, w = out.shape
    return jnp.sum(jnp.where(_own(h, kv_heads)[None, :, :, None],
                             out.reshape(b, h, kv_heads, w // kv_heads),
                             0.0), axis=2)


# ---------------------------------------------------------------------------
# one token a slot against the paged pool
# ---------------------------------------------------------------------------

def gqa_entries(table_width: int) -> int:
    """Table entries a grid step: the most up to ``_ENTRIES_A_STEP``
    that divide the table's width."""
    return max(e for e in range(1, _ENTRIES_A_STEP + 1)
               if table_width % e == 0)


def paged_gqa_friendly(block_size: int, head_dim: int) -> bool:
    """The pool shapes the kernel takes: whole [block_size, KVH x D]
    blocks in whole tiles."""
    return block_size % 128 == 0 and head_dim % 128 == 0


def xla_paged_gqa_decode_attention(q, k_pool, v_pool, *, block_tables, pos):
    """Reference path: gather each slot's block run and attend plainly."""
    b, h, d = q.shape
    bs, w = k_pool.shape[1], k_pool.shape[2]
    kvh = w // d
    bt = jnp.asarray(block_tables, jnp.int32)
    nb = bt.shape[1]
    k = k_pool[bt].reshape(b, nb * bs, kvh, d)
    v = v_pool[bt].reshape(b, nb * bs, kvh, d)
    s = jnp.einsum("bhgd,bthd->bhgt", _split(q, kvh), k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    live = jnp.arange(nb * bs)[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, NEG_INF),
                       axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, d)


def _decode_kernel(ft_ref, pos_ref, q_ref, *rest, entries: int,
                   block_size: int, sm_scale: float):
    """Grid (B, NB / entries): a step holds ``entries`` consecutive table
    entries of row b, each a whole [block_size, KVH x D] K and V block of
    the pool as it lies. Row h of the [Hp, KVH x D] query tile holds query
    head h in the lanes of ITS KV head (``h // G``) and zeros elsewhere,
    so one matmul against the block gives every query head its own
    scores, and row h of the context accumulator is meaningful in those
    lanes, which the caller keeps. An entry past ``pos`` computes nothing
    and fetched nothing (``decode_attention._fetch_table``)."""
    n = entries
    k_refs, v_refs = rest[:n], rest[n:2 * n]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * n:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                            # [Hp, W]
    for e in range(n):              # static: the step's table entries
        first = (j * n + e) * block_size

        @pl.when(first <= pos)
        def _compute(e=e, first=first):
            s = lax.dot_general(
                q, k_refs[e][0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [Hp, Bs]
            kpos = first + lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1)
            live = kpos <= pos
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            vblk = v_refs[e][0]
            acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # position `pos` is always live, so l > 0
        o_ref[0] = acc_ref[...] / l_ref[...]


def _decode_dispatch(q, k_pool, v_pool, block_tables, pos, entries: int):
    _, bs, w = k_pool.shape
    b, h, d = q.shape
    kvh = w // d
    nb = block_tables.shape[1]
    hp = _tile_rows(h)
    tile = jnp.pad(_query_tile(q, kvh), ((0, 0), (0, hp - h), (0, 0))
                   ).astype(k_pool.dtype)

    def table_map(e):
        return lambda bb, jj, ft, pos_s: (ft[bb, jj * entries + e], 0, 0)

    def q_map(bb, jj, ft, pos_s):
        return (bb, 0, 0)

    blocks = [pl.BlockSpec((1, bs, w), table_map(e)) for e in range(entries)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # fetch table, pos
        grid=(b, nb // entries),
        in_specs=[pl.BlockSpec((1, hp, w), q_map)] + blocks + blocks,
        out_specs=pl.BlockSpec((1, hp, w), q_map),
        scratch_shapes=[pltpu.VMEM((hp, 1), jnp.float32),
                        pltpu.VMEM((hp, 1), jnp.float32),
                        pltpu.VMEM((hp, w), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_decode_kernel, entries=entries, block_size=bs,
                          sm_scale=1.0 / math.sqrt(d)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="paged_gqa_attn",
        interpret=_interpret(),
    )(_fetch_table(block_tables, pos, entries, bs), pos, tile,
      *([k_pool] * entries), *([v_pool] * entries))
    return _own_lanes(out[:, :h], kvh)


def paged_gqa_decode_attention(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, *, block_tables, pos,
                               impl: str = "auto",
                               entries: int | None = None) -> jax.Array:
    """One query row a slot against its live blocks of the paged pool.

    ``q``: [B, H, D]; ``k_pool`` / ``v_pool``: [N, Bs, KVH x D], a
    token's KV heads side by side (the caller may hand every layer's
    blocks at once with ``block_tables + i * N``); ``block_tables``
    [B, NB]; ``pos`` [B]: row b attends to logical positions
    ``0 .. pos[b]`` (its own row already written). Returns [B, H, D]
    float32.

    ``impl``: ``"auto"`` takes the kernel (``paged_gqa_attn`` in a
    capture) on a TPU where :func:`paged_gqa_friendly` holds,
    ``"pallas"`` forces it (interpreted off the TPU), ``"xla"`` gathers
    the run and attends plainly. ``entries``: table entries a grid step
    (the sweep's lever; :func:`gqa_entries` of the table's width)."""
    b, h, d = q.shape
    if k_pool.ndim != 3 or k_pool.shape[2] % d or h % (
            k_pool.shape[2] // d) or v_pool.shape != k_pool.shape:
        raise ValueError(f"pool shapes {k_pool.shape}/{v_pool.shape} are "
                         f"not [N, Bs, KVH x {d}] for q {q.shape}")
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown decode attention impl {impl!r}")
    bs = k_pool.shape[1]
    kvh = k_pool.shape[2] // d
    bt = jnp.asarray(block_tables, jnp.int32)
    posb = jnp.clip(jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,)),
        0, bt.shape[1] * bs - 1)
    friendly = paged_gqa_friendly(bs, d)
    if impl == "pallas" and not friendly:
        raise ValueError(
            f"paged_gqa_attn needs block_size % 128 == 0 and a head dim "
            f"that is a multiple of 128, got block_size={bs} D={d} "
            "(impl='auto' falls back to XLA)")
    with jax.named_scope("paged_gqa_attn"):
        if friendly and (impl == "pallas" or (
                impl == "auto" and jax.default_backend() == "tpu")):
            n = entries or gqa_entries(bt.shape[1])
            _log_schedule({"kernel": "paged_gqa_attn",
                           "entries_per_step": n,
                           "grid": [b, bt.shape[1] // n],
                           "query_heads": h, "kv_heads": kvh})
            return _decode_dispatch(q, k_pool, v_pool, bt, posb, n)
        _log_schedule({"kernel": "xla", "query_heads": h, "kv_heads": kvh})
        return xla_paged_gqa_decode_attention(
            q, k_pool, v_pool, block_tables=bt, pos=posb)


# ---------------------------------------------------------------------------
# a chunk of one prompt: causal over a table's blocks, or a band of them
# ---------------------------------------------------------------------------

def chunk_tile_friendly(t: int, block_size: int, head_dim: int,
                        table_blocks: int, tile: int) -> bool:
    """Shapes the TPU compiler takes for ``gqa_chunk_attn``: 128-row
    blocks, a chunk and a table of whole ``tile``-row tiles, lane-aligned
    heads."""
    return (block_size == 128 and tile % 128 == 0 and t % tile == 0
            and head_dim % 128 == 0
            and table_blocks % (tile // 128) == 0)


def _chunk_kernel(table_ref, at_ref, q_ref, *rest, blocks: int, tile: int,
                  window: int):
    """Grid (H, T / tile, W / tile): one query head's tile of rows
    against a key tile of ``blocks`` blocks of its KV head a step, an
    online softmax over the key tiles. ``at_ref``: the first query's and
    the first key row's positions. A key tile outside the query tile's
    band (after its last row, or wholly before its first row's window)
    names the nearest live one again (no DMA) and computes nothing."""
    k_refs, v_refs = rest[:blocks], rest[blocks:2 * blocks]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * blocks:]
    i, j = pl.program_id(1), pl.program_id(2)
    q0 = at_ref[0] + i * tile               # the tile's first query
    k0 = at_ref[1] + j * tile               # the tile's first key row

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((k0 <= q0 + tile - 1) & (k0 + tile - 1 > q0 - window))
    def _compute():
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)     # [tile, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)
        s = lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        qp = q0 + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        kp = k0 + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        live = (kp <= qp) & (kp > qp - window) & (kp >= 0)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # every row attends to itself at least, so l > 0
        o_ref[0] = acc_ref[...] / l_ref[...]


def _chunk_dispatch(q, k_pool, v_pool, table, q_start, k_start, *,
                    window: int, tile: int):
    t, h, d = q.shape
    bs, w = k_pool.shape[1], k_pool.shape[2]
    kvh = w // d
    grp = h // kvh
    g = tile // bs
    tiles = table.shape[0] // g
    dtype = k_pool.dtype
    # head-major, as XLA lays the rotated [T, H, D] rows out anyway (read
    # and written as lane blocks of [T, H x D] rows the compiled chunk
    # held ten more copies of them: described v5e, PR 41)
    qh = (q.astype(jnp.float32) / math.sqrt(d)).astype(dtype
                                                      ).transpose(1, 0, 2)
    at = jnp.stack([jnp.asarray(q_start, jnp.int32),
                    jnp.asarray(k_start, jnp.int32)])

    def live_tile(ii, jj, at_s):
        """``jj`` held to the key tiles query tile ``ii`` can see."""
        q0 = at_s[0] + ii * tile - at_s[1]      # relative to key row 0
        hi = (q0 + tile - 1) // tile
        lo = jnp.maximum(q0 - window + 1, 0) // tile
        return jnp.clip(jj, lo, jnp.minimum(hi, tiles - 1))

    def q_map(hh, ii, jj, table_s, at_s):
        return (hh, ii, 0)

    def kv_map(hh, ii, jj, table_s, at_s, *, k):
        return (table_s[live_tile(ii, jj, at_s) * g + k], 0, hh // grp)

    kv_specs = [pl.BlockSpec((1, bs, d), functools.partial(kv_map, k=k))
                for k in range(g)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # table, positions
        grid=(h, t // tile, tiles),
        in_specs=[pl.BlockSpec((1, tile, d), q_map)] + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((1, tile, d), q_map),
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32),
                        pltpu.VMEM((tile, 1), jnp.float32),
                        pltpu.VMEM((tile, d), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, blocks=g, tile=tile, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        name="gqa_chunk_attn",
        interpret=_interpret(),
    )(jnp.asarray(table, jnp.int32), at, qh, *([k_pool] * g),
      *([v_pool] * g))
    return out.transpose(1, 0, 2)


def _chunk_xla(q, k_pool, v_pool, table, q_start, k_start, *, window: int,
               tile: int):
    """The same in XLA: a loop over the key tiles the chunk can see, every
    query row against each (the kernel's oracle, and the path off the
    TPU)."""
    t, h, d = q.shape
    bs, w = k_pool.shape[1], k_pool.shape[2]
    kvh = w // d
    dtype = k_pool.dtype
    tb = tile // bs
    qs = _split((q.astype(jnp.float32) / math.sqrt(d)).astype(dtype), kvh)
    qp = q_start + jnp.arange(t)
    rel = q_start - k_start                 # the first query's key row

    def one(j, carry):
        m, l, acc = carry
        ids = lax.dynamic_slice_in_dim(table, j * tb, tb)
        k = k_pool[ids].reshape(tile, kvh, d)
        v = v_pool[ids].reshape(tile, kvh, d)
        s = jnp.einsum("qhgd,khd->hgqk", qs, k,
                       preferred_element_type=jnp.float32)
        kp = k_start + j * tile + jnp.arange(tile)
        live = ((kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
                & (kp[None, :] > qp[:, None] - window))[None, None]
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hgqk,khd->hgqd", p.astype(dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    grp = h // kvh
    init = (jnp.full((kvh, grp, t), NEG_INF, jnp.float32),
            jnp.zeros((kvh, grp, t), jnp.float32),
            jnp.zeros((kvh, grp, t, d), jnp.float32))
    lo = jnp.maximum(rel - window + 1, 0) // tile
    hi = (rel + t - 1) // tile + 1
    _, l, acc = lax.fori_loop(lo, hi, one, init)
    return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(t, h, d)


def _chunk_attention(q, k_pool, v_pool, table, q_start, k_start, *,
                     window: int, tile: int, impl: str):
    t, _, d = q.shape
    bs = k_pool.shape[1]
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown chunk attention impl {impl!r}")
    table = jnp.asarray(table, jnp.int32)
    friendly = chunk_tile_friendly(t, bs, d, table.shape[0], tile)
    if impl == "pallas" and not friendly:
        raise ValueError(
            f"gqa_chunk_attn needs 128-row blocks, a chunk and a table of "
            f"whole {tile}-row tiles and a head dim that is a multiple of "
            f"128, got T={t} block_size={bs} D={d} table={table.shape[0]}")
    if friendly and (impl == "pallas" or (
            impl == "auto" and jax.default_backend() == "tpu")):
        return _chunk_dispatch(q, k_pool, v_pool, table, q_start, k_start,
                               window=window, tile=tile)
    tile = min(tile, t)
    if tile % bs or t % tile or (table.shape[0] * bs) % tile:
        raise ValueError(f"a chunk of {t} rows, key tiles of {tile} and "
                         f"{table.shape[0]} blocks of {bs} do not nest")
    return _chunk_xla(q, k_pool, v_pool, table, q_start, k_start,
                      window=window, tile=tile)


def gqa_prefill_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, table_row, start, *,
                          key_tile: int = 1024,
                          impl: str = "auto") -> jax.Array:
    """A chunk's queries against the slot's earlier rows and the chunk's
    own, causal: ``q`` [T, H, D] (positions ``start .. start + T - 1``,
    ``start`` a multiple of T), ``k_pool`` / ``v_pool`` [N, Bs, KVH x D]
    with the chunk's own rows already in its blocks, ``table_row`` the
    slot's blocks. Returns [T, H, D] float32. ``impl``: ``"auto"`` (the
    kernel ``gqa_chunk_attn`` on a TPU where
    :func:`chunk_tile_friendly` holds), ``"pallas"`` or ``"xla"``."""
    with jax.named_scope("gqa_chunk_attn"):
        return _chunk_attention(q, k_pool, v_pool, table_row, start, 0,
                                window=_NO_WINDOW, tile=key_tile, impl=impl)


def gqa_window_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                 ring_k: jax.Array, ring_v: jax.Array,
                                 start, *, window: int, tile: int = 512,
                                 block_size: int = 128,
                                 impl: str = "auto") -> jax.Array:
    """A chunk's queries against the last ``window`` rows each: ``q``
    [T, H, D], ``k`` / ``v`` [T, KVH x D] the chunk's own rows,
    ``ring_k`` / ``ring_v`` [rows, KVH x D] the slot's ring as the chunks
    before left it (row ``p % rows`` holds position ``p``). The keys are
    the ring's last ``prev`` rows in the order of their positions and
    then the chunk's (``prev`` = the window's reach before the chunk, up
    to whole tiles where the ring holds as many), laid out as blocks of
    a pool of their own, so that the band is the same kernel's (or the
    same loop's) as the causal form. Returns [T, H, D] float32."""
    t = q.shape[0]
    rows = ring_k.shape[0]
    tile = min(tile, t)
    prev = min(-(-(window - 1) // tile) * tile, rows)
    if prev % tile or t % tile:
        # a ring that is no whole tile: one tile over the keys' width
        prev, tile = min(window - 1, rows), t
    before = start - prev + jnp.arange(prev)                # may be < 0
    bs = min(block_size, tile)
    pad = -(prev + t) % tile

    def keys(ring, own):
        x = jnp.concatenate([ring[before % rows], own,
                             jnp.zeros((pad, own.shape[1]), own.dtype)])
        return x.reshape(-1, bs, own.shape[1])

    kk, vv = keys(ring_k, k), keys(ring_v, v)
    with jax.named_scope("gqa_window_attn"):
        return _chunk_attention(
            q, kk, vv, jnp.arange(kk.shape[0], dtype=jnp.int32), start,
            start - prev, window=window, tile=tile, impl=impl)


def gqa_window_decode_attention(q: jax.Array, ring_k: jax.Array,
                                ring_v: jax.Array, pos, *, window: int
                                ) -> jax.Array:
    """One query row a slot over its ring: ``q`` [B, H, D], ``ring_k`` /
    ``ring_v`` [B, rows, KVH x D] with each slot's own row written at
    ``pos % rows``, ``pos`` [B]. Returns [B, H, D] float32. The rows are
    read as they lie, every KV head at once under the masked query tile
    (a product by KV head wants the ring transposed: a copy of it a layer
    a step)."""
    d = q.shape[2]
    rows, kvh = ring_k.shape[1], ring_k.shape[2] // d
    with jax.named_scope("gqa_window_attn"):
        held = jax.vmap(lambda p: ring_positions(p, rows))(pos)
        live = (held >= 0) & (held > pos[:, None] - window)
        s = jnp.einsum("bhw,bkw->bhk", _query_tile(q, kvh), ring_k,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(live[:, None, :], s, NEG_INF), axis=-1)
        return _own_lanes(jnp.einsum(
            "bhk,bkw->bhw", p.astype(ring_v.dtype), ring_v,
            preferred_element_type=jnp.float32), kvh)
