"""Multi-head latent attention (MLA) over a paged latent cache.

A token keeps one latent row ``[c_kv ; k_pe]`` (``kv_lora_rank`` normed
values and ``qk_rope_head_dim`` values every head shares), not K and V
per head, stored in a row of ``R`` values: whole 128-lane tiles, zeros
past the latent (576 -> 640 for rank 512 + 64; 1,088 -> 1,152 for rank
1,024 + 64). The geometry (heads, rank, head sizes, ``R``) is the
caller's, a layer kind at a time: nothing here fixes one. Whether
``k_pe`` and the queries' last ``pe`` values carry rotary positions is
the caller's too (``models/decoder.py`` rotates them BEFORE the row is
written, or not at all: Kimi's ``mla_use_nope``); attention reads them
as they lie.

Three caches, three pairs of forwards (a chunk of one prompt, one token a
slot):

- every earlier row, in a paged pool ``[N, Bs, R]`` behind block tables (a
  layer's blocks ``N`` apart in the flat view):
  :func:`mla_prefill_attention` / :func:`mla_chunk_attention` (per-head
  K and V from the latent, ``[k_nope_h ; v_h] = W_kvb c_kv``, a tile of
  rows at a time: an XLA loop / the kernel ``mla_chunk_attn``) and
  :func:`mla_decode_attention` (absorbed: ``W_kvb`` folded into the query
  and the output, each live row read once for all heads; on the chip the
  Pallas kernel ``paged_latent_attn`` walks a slot's blocks,
  ``_BLOCKS_A_STEP`` a grid step);
- a SELECTED set of the earlier rows in the same pool (``ops/dsa.py``
  chooses it): :func:`mla_masked_prefill_attention` (dense under the
  selection's mask: on the chip the Pallas kernel ``dsa_selected_attn``,
  a head's K, V and scores of a tile made and kept in the core;
  elsewhere the same tile loop in XLA) and :func:`mla_gathered_attention`
  (absorbed, over the gathered rows). The two are one mathematics; a
  chunk's thousand rows share the dense tiles' K and V, one row a slot
  gathers its own 2,048;
- the last ``window`` rows, in a ring a slot ``[slots, ring, R]``:
  :func:`mla_window_prefill_attention` (a band: each tile of
  ``window - 1`` queries against the ``2 (window - 1)`` rows it can see)
  and :func:`mla_window_decode_attention` (absorbed, over the ring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: pool blocks one grid step of the decode kernel visits: a grid step
#: costs ~0.35 us whether it computes or not, and a slot's table has a
#: block for every 128 tokens of the longest context the engine admits
_BLOCKS_A_STEP = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def latent_row(rank: int, pe: int) -> int:
    """Values a stored latent row holds: ``rank + pe`` and zeros up to
    whole 128-lane tiles. (The chip lays a [.., Bs, 576] array out
    tokens-minor so as not to pad it, and every program that reads it
    rows-minor then copies the whole pool.)"""
    return -(-(rank + pe) // 128) * 128


def latent_tile_friendly(block_size: int, heads: int, rank: int,
                         blocks_per_slot: int) -> bool:
    """Shapes the TPU compiler takes for the decode kernel, whatever the
    layer kind's geometry (the row's width is the pool's last axis)."""
    return (block_size % 128 == 0 and heads % 8 == 0 and rank % 128 == 0
            and blocks_per_slot % _BLOCKS_A_STEP == 0)


# ---------------------------------------------------------------------------
# prefill: per-head K and V from the latent, a tile at a time
# ---------------------------------------------------------------------------

def mla_prefill_attention(q: jax.Array, pool: jax.Array, table_row, start,
                          w_kvb: jax.Array, *, rank: int, nope: int,
                          pe: int, v_dim: int, scale: float) -> jax.Array:
    """``q`` [T, H, nope + pe] the chunk's queries (positions ``start ..
    start + T - 1``, ``start`` a multiple of ``T``); ``pool`` [N, Bs, R]
    one layer's latent blocks with this chunk's rows already written;
    ``table_row`` [NB] the request's blocks; ``w_kvb`` [rank, H, nope +
    v_dim]. Causal. Returns [T, H, v_dim] float32."""
    t, h, _ = q.shape
    bs = pool.shape[1]
    if t % bs:
        raise ValueError(f"a chunk of {t} rows is not whole blocks of {bs}")
    tb = t // bs
    dtype = pool.dtype
    q_nope = (q[..., :nope].astype(jnp.float32) * scale).astype(dtype)
    q_pe = (q[..., nope:nope + pe].astype(jnp.float32) * scale
            ).astype(dtype)
    qpos = start + jnp.arange(t)
    table_row = jnp.asarray(table_row, jnp.int32)

    def tile(j, carry):
        m, l, acc = carry
        ids = lax.dynamic_slice_in_dim(table_row, j * tb, tb)
        lat = pool[ids].reshape(t, -1)                      # [T, R]
        kv = jnp.einsum("sc,chd->shd", lat[:, :rank], w_kvb.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :nope],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhd,kd->hqk", q_pe, lat[:, rank:rank + pe],
                          preferred_element_type=jnp.float32))
        kpos = j * t + jnp.arange(t)
        live = (kpos[None, :] <= qpos[:, None])[None]       # [1, T, T]
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    with jax.named_scope("mla_prefill_attn"):
        init = (jnp.full((h, t), NEG_INF, jnp.float32),
                jnp.zeros((h, t), jnp.float32),
                jnp.zeros((h, t, v_dim), jnp.float32))
        _, l, acc = lax.fori_loop(0, start // t + 1, tile, init)
        return (acc / l[..., None]).transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# decode: absorbed, against the latent rows
# ---------------------------------------------------------------------------

def xla_latent_attention(q, pool, *, block_tables, last, rank: int):
    """Reference path: gather each slot's rows and attend plainly."""
    bt = jnp.asarray(block_tables, jnp.int32)
    b, nb = bt.shape
    bs = pool.shape[1]
    lat = pool[bt].reshape(b, nb * bs, -1)                  # [B, T, R]
    s = jnp.einsum("bhr,btr->bht", q, lat,
                   preferred_element_type=jnp.float32)
    live = jnp.arange(nb * bs)[None, :] <= last[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btc->bhc", p.astype(lat.dtype), lat[..., :rank],
                      preferred_element_type=jnp.float32)


def _latent_kernel(bt_ref, last_ref, q_ref, *rest, block_size: int,
                   rank: int, blocks: int):
    """Grid (B, NB / blocks): ``blocks`` latent blocks of the slot's run a
    step, all heads' rows resident; online softmax over the run. A block
    past the slot's last live one names that one again (no DMA) and
    computes nothing."""
    lat_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:blocks], rest[blocks:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    last = last_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for g in range(blocks):                 # static: blocks a grid step
        first = (j * blocks + g) * block_size

        @pl.when(first <= last)
        def _compute(g=g, first=first):
            lat = lat_refs[g][0]                            # [Bs, R]
            s = lax.dot_general(q_ref[0], lat, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            kpos = first + lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1)
            live = kpos <= last
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(lat.dtype), lat[:, :rank],
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # logical slot 0 is live for every slot, so l > 0
        o_ref[0] = acc_ref[...] / l_ref[...]


def _latent_dispatch(q, pool, block_tables, last, rank: int):
    b, h, r = q.shape
    bs = pool.shape[1]
    nb = block_tables.shape[1]
    g = _BLOCKS_A_STEP

    def lat_map(bb, jj, bt, last_s, *, k):
        return (bt[bb, jnp.minimum(jj * g + k, last_s[bb] // bs)], 0, 0)

    def q_map(bb, jj, bt, last_s):
        return (bb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, last
        grid=(b, nb // g),
        in_specs=[pl.BlockSpec((1, h, r), q_map)] + [
            pl.BlockSpec((1, bs, r), functools.partial(lat_map, k=k))
            for k in range(g)],
        out_specs=pl.BlockSpec((1, h, rank), q_map),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, rank), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_latent_kernel, block_size=bs, rank=rank,
                          blocks=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_latent_attn",
        interpret=_interpret(),
    )(block_tables, last, q, *([pool] * g))


def mla_decode_attention(q: jax.Array, pool: jax.Array, *, block_tables,
                         last, rank: int, impl: str = "auto") -> jax.Array:
    """One absorbed query a slot against its latent rows.

    ``q`` [B, H, R] (``[W_kb^T q_nope ; q_pe]``, already scaled) in the
    pool's dtype; ``pool`` [N, Bs, R] (the slot's own row written);
    ``block_tables`` [B, NB]; ``last`` [B] the last logical slot each row
    sees. Returns [B, H, rank] float32: the weighted sum of ``c_kv``
    rows, which ``W_vb`` turns into the heads' output.

    ``impl``: ``"auto"`` takes the kernel on a TPU where
    :func:`latent_tile_friendly` holds, ``"pallas"`` forces it
    (interpreted off the TPU), ``"xla"`` gathers."""
    b, h, r = q.shape
    bs = pool.shape[1]
    if pool.ndim != 3 or pool.shape[2] != r:
        raise ValueError(f"pool shape {pool.shape} is not [N, Bs, {r}]")
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown latent attention impl {impl!r}")
    bt = jnp.asarray(block_tables, jnp.int32)
    friendly = latent_tile_friendly(bs, h, rank, bt.shape[1])
    if impl == "pallas" and not friendly:
        raise ValueError(
            "the latent attention kernel needs block_size % 128 == 0, "
            f"heads % 8 == 0, rank % 128 == 0 and a table of whole "
            f"{_BLOCKS_A_STEP}-block steps, got block_size={bs} H={h} "
            f"rank={rank} NB={bt.shape[1]}")
    last = jnp.clip(jnp.asarray(last, jnp.int32), 0, bt.shape[1] * bs - 1)
    with jax.named_scope("mla_decode_attn"):
        if friendly and (impl == "pallas" or (
                impl == "auto" and jax.default_backend() == "tpu")):
            return _latent_dispatch(q, pool, bt, last, rank)
        return xla_latent_attention(q, pool, block_tables=bt, last=last,
                                    rank=rank)


# ---------------------------------------------------------------------------
# a selected set of the earlier rows (ops/dsa.py chooses it)
# ---------------------------------------------------------------------------

def selected_tile_friendly(t: int, block_size: int, rank: int, nope: int,
                           pe: int, v_dim: int, table_blocks: int,
                           width: int, tile: int = 1024) -> bool:
    """Shapes the TPU compiler takes for the selected-attention kernel: a
    chunk of whole key tiles (``tile`` latent rows a grid step), 128-row
    blocks, lane-aligned head parts, a table of whole tiles as wide as
    the mask."""
    return (block_size == 128 and tile % 128 == 0 and t % tile == 0
            and rank % 128 == 0 and nope % 128 == 0 and pe % 64 == 0
            and v_dim % 128 == 0 and table_blocks % (tile // 128) == 0
            and width == table_blocks * block_size)


def _selected_kernel(table_ref, live_ref, q_ref, w_ref, mask_ref, *rest,
                     blocks: int, rank: int, nope: int, pe: int):
    """Grid (H, W / tile): one head's queries (the whole chunk) against a
    tile of ``blocks`` latent blocks a step; K and V of the head are made
    from the tile's latent rows here, scores are masked by the
    selection's tile, an online softmax runs over the tiles. A tile past
    the chunk's own names the last live one again (no DMA) and computes
    nothing."""
    lat_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:blocks], rest[blocks:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < live_ref[0])
    def _compute():
        lat = jnp.concatenate([r[0] for r in lat_refs], axis=0)  # [tile, R]
        kv = jnp.dot(lat[:, :rank], w_ref[0],
                     preferred_element_type=jnp.float32).astype(lat.dtype)
        q = q_ref[0]                                        # [T, nope + pe]
        dims = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(q[:, :nope], kv[:, :nope], dims,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(q[:, nope:], lat[:, rank:rank + pe], dims,
                               preferred_element_type=jnp.float32))
        live = mask_ref[...].astype(jnp.float32) > 0.0      # [T, tile]
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(lat.dtype), kv[:, nope:],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # every row attends to one row at least, so l > 0
        o_ref[0] = acc_ref[...] / l_ref[...]


def _selected_dispatch(q, pool, table_row, start, w_kvb, allowed, *,
                       rank: int, nope: int, pe: int, v_dim: int,
                       scale: float, tile: int):
    t, h, _ = q.shape
    bs, r = pool.shape[1], pool.shape[2]
    dtype = pool.dtype
    g = tile // bs
    tiles = allowed.shape[1] // tile
    qh = (q.astype(jnp.float32) * scale).astype(dtype).transpose(1, 0, 2)
    wh = w_kvb.astype(dtype).transpose(1, 0, 2)         # [H, rank, nope+v]
    live = jnp.reshape((start + t) // tile, (1,)).astype(jnp.int32)

    def head_map(hh, jj, table, live_s):
        return (hh, 0, 0)

    def lat_map(hh, jj, table, live_s, *, k):
        return (table[jnp.minimum(jj, live_s[0] - 1) * g + k], 0, 0)

    def mask_map(hh, jj, table, live_s):
        return (0, jnp.minimum(jj, live_s[0] - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # table_row, live tiles
        grid=(h, tiles),
        in_specs=[pl.BlockSpec((1, t, nope + pe), head_map),
                  pl.BlockSpec((1, rank, nope + v_dim), head_map),
                  pl.BlockSpec((t, tile), mask_map)] + [
            pl.BlockSpec((1, bs, r), functools.partial(lat_map, k=k))
            for k in range(g)],
        out_specs=pl.BlockSpec((1, t, v_dim), head_map),
        scratch_shapes=[pltpu.VMEM((t, 1), jnp.float32),
                        pltpu.VMEM((t, 1), jnp.float32),
                        pltpu.VMEM((t, v_dim), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_selected_kernel, blocks=g, rank=rank, nope=nope,
                          pe=pe),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, t, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        name="dsa_selected_attn",
        interpret=_interpret(),
    )(jnp.asarray(table_row, jnp.int32), live, qh, wh,
      allowed.astype(jnp.int8), *([pool] * g))
    return out.transpose(1, 0, 2)


def mla_masked_prefill_attention(q: jax.Array, pool: jax.Array, table_row,
                                 start, w_kvb: jax.Array, allowed: jax.Array,
                                 *, rank: int, nope: int, pe: int,
                                 v_dim: int, scale: float,
                                 key_tile: int = 1024,
                                 impl: str = "auto") -> jax.Array:
    """:func:`mla_prefill_attention` under a mask: ``allowed`` [T, W] bool
    says which of the request's first ``W`` rows each query attends to
    (causal already; at least one a row).

    ``impl``: ``"auto"`` takes the kernel (``dsa_selected_attn`` in a
    capture: a head's scores of a tile never leave the core) on a TPU
    where :func:`selected_tile_friendly` holds, ``"pallas"`` forces it
    (interpreted off the TPU), ``"xla"`` runs the dense tile loop (128
    heads' scores a tile are ``H x T x key_tile`` float32, written and
    read again). ``key_tile``: latent rows a step of either (1,024: the
    kernel read 7.9 / 11.7 / 19.2 / 33.8 ms a layer at 4 / 8 / 16 / 32 k
    rows of context, 9.2 / 14.5 / 25.1 / 45.3 at 512, 13.6 / 22.7 / 40.7 /
    76.3 at 256, the XLA loop 20.7 / 39.3 / 77.2 / 152.6: PERF.md)."""
    t, h, _ = q.shape
    bs = pool.shape[1]
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown selected attention impl {impl!r}")
    table_row = jnp.asarray(table_row, jnp.int32)
    friendly = selected_tile_friendly(t, bs, rank, nope, pe, v_dim,
                                      table_row.shape[0], allowed.shape[1],
                                      key_tile)
    if impl == "pallas" and not friendly:
        raise ValueError(
            "the selected attention kernel needs 128-row blocks, a chunk "
            f"and a table of whole {key_tile}-row tiles as wide as the "
            f"mask and lane-aligned head parts, got T={t} block_size={bs} "
            f"rank={rank} nope={nope} pe={pe} v={v_dim} "
            f"table={table_row.shape[0]} mask width={allowed.shape[1]}")
    if friendly and (impl == "pallas" or (
            impl == "auto" and jax.default_backend() == "tpu")):
        with jax.named_scope("dsa_selected_attn"):
            return _selected_dispatch(
                q, pool, table_row, start, w_kvb, allowed, rank=rank,
                nope=nope, pe=pe, v_dim=v_dim, scale=scale, tile=key_tile)
    tile = min(key_tile, t)
    if tile % bs or t % tile:
        raise ValueError(f"a chunk of {t} rows, key tiles of {tile} and "
                         f"blocks of {bs} do not nest")
    tb = tile // bs
    dtype = pool.dtype
    q_nope = (q[..., :nope].astype(jnp.float32) * scale).astype(dtype)
    q_pe = (q[..., nope:nope + pe].astype(jnp.float32) * scale
            ).astype(dtype)

    def one(j, carry):
        m, l, acc = carry
        ids = lax.dynamic_slice_in_dim(table_row, j * tb, tb)
        lat = pool[ids].reshape(tile, -1)                   # [tile, R]
        kv = jnp.einsum("sc,chd->shd", lat[:, :rank], w_kvb.astype(dtype),
                        preferred_element_type=jnp.float32).astype(dtype)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :nope],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhd,kd->hqk", q_pe, lat[:, rank:rank + pe],
                          preferred_element_type=jnp.float32))
        live = lax.dynamic_slice_in_dim(allowed, j * tile, tile,
                                        axis=1)[None]       # [1, T, tile]
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    with jax.named_scope("dsa_selected_attn"):
        init = (jnp.full((h, t), NEG_INF, jnp.float32),
                jnp.zeros((h, t), jnp.float32),
                jnp.zeros((h, t, v_dim), jnp.float32))
        _, l, acc = lax.fori_loop(0, (start + t) // tile, one, init)
        return (acc / l[..., None]).transpose(1, 0, 2)


def mla_gathered_attention(q: jax.Array, pool: jax.Array, *, block_tables,
                           positions, chosen, rank: int) -> jax.Array:
    """One absorbed query a slot over the rows chosen for it. ``q``
    [B, H, R] as :func:`mla_decode_attention` takes it; ``pool`` [N, Bs, R];
    ``positions`` [B, K] logical positions in the slot's context,
    ``chosen`` [B, K] which of them count (at least one a row). Returns
    [B, H, rank] float32."""
    bt = jnp.asarray(block_tables, jnp.int32)
    n, bs, r = pool.shape
    with jax.named_scope("dsa_selected_attn"):
        rows = jnp.take_along_axis(bt, positions // bs, axis=1) * bs \
            + positions % bs                                # [B, K]
        lat = pool.reshape(n * bs, r)[rows]                 # [B, K, R]
        s = jnp.einsum("bhr,bkr->bhk", q, lat,
                       preferred_element_type=jnp.float32)
        s = jnp.where(chosen[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhk,bkc->bhc", p.astype(lat.dtype),
                          lat[..., :rank],
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the last `window` rows, in a ring a slot
# ---------------------------------------------------------------------------

def ring_rows(window: int) -> int:
    """Rows of a slot's ring: the window, up to whole 16-row tiles."""
    return -(-window // 16) * 16


def ring_positions(last, rows: int):
    """The position each of a ring's ``rows`` rows holds once position
    ``last`` is written (row ``p % rows`` holds ``p``); negative: never
    written."""
    j = jnp.arange(rows)
    return last - (last - j) % rows


def mla_window_prefill_attention(q: jax.Array, lat: jax.Array,
                                 ring: jax.Array, start, w_kvb: jax.Array,
                                 *, window: int, rank: int, nope: int,
                                 pe: int, v_dim: int, scale: float
                                 ) -> jax.Array:
    """A chunk's queries against the last ``window`` rows each:
    ``q`` [T, H, nope + pe] (positions ``start .. start + T - 1``),
    ``lat`` [T, R] the chunk's own latent rows, ``ring`` [rows, R] the
    slot's ring as the chunks before left it (row ``p % rows`` holds
    position ``p``; :func:`ring_rows` of the window, so it holds the
    ``window - 1`` rows before the chunk). A tile of ``window - 1``
    queries sees the ``2 (window - 1)`` rows that end with its own.
    Returns [T, H, v_dim] float32."""
    t, h, _ = q.shape
    prev = min(window - 1, ring.shape[0])   # the ring holds no more
    tq = prev if prev and t % prev == 0 else t
    dtype = lat.dtype
    before = start - prev + jnp.arange(prev)                # may be < 0
    rows = jnp.concatenate([ring[before % ring.shape[0]], lat])
    kpos = jnp.concatenate([before, start + jnp.arange(t)])
    kv = jnp.einsum("sc,chd->shd", rows[:, :rank], w_kvb.astype(dtype),
                    preferred_element_type=jnp.float32).astype(dtype)
    k_pe = rows[:, rank:rank + pe]
    q_nope = (q[..., :nope].astype(jnp.float32) * scale).astype(dtype)
    q_pe = (q[..., nope:nope + pe].astype(jnp.float32) * scale
            ).astype(dtype)
    out = []
    with jax.named_scope("mla_window_attn"):
        for a in range(0, t, tq):           # static: 2 tiles at 1,024 / 512
            ks = slice(a, a + tq + prev)
            s = (jnp.einsum("qhd,khd->hqk", q_nope[a:a + tq],
                            kv[ks, :, :nope],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("qhd,kd->hqk", q_pe[a:a + tq], k_pe[ks],
                              preferred_element_type=jnp.float32))
            qp = start + a + jnp.arange(tq)
            kp = kpos[ks]
            live = ((kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
                    & (kp[None, :] > qp[:, None] - window))[None]
            p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
            out.append(jnp.einsum("hqk,khd->qhd", p.astype(dtype),
                                  kv[ks, :, nope:],
                                  preferred_element_type=jnp.float32))
        return jnp.concatenate(out)


def ring_after_chunk(ring: jax.Array, lat: jax.Array, start, n_valid
                     ) -> jax.Array:
    """The ring once the chunk's first ``n_valid`` rows (positions from
    ``start``) are in it: row ``j`` takes the latest position ``p`` with
    ``p % rows == j`` if the chunk holds it, and stays otherwise."""
    p = ring_positions(start + n_valid - 1, ring.shape[0])
    mine = (p >= start) & (n_valid > 0)
    new = lat[jnp.clip(p - start, 0, lat.shape[0] - 1)]
    return jnp.where(mine[:, None], new, ring)


def mla_window_decode_attention(q: jax.Array, ring: jax.Array, pos, *,
                                window: int, rank: int) -> jax.Array:
    """One absorbed query a slot over its ring: ``q`` [B, H, R], ``ring``
    [B, rows, R] with each slot's own row written at ``pos % rows``,
    ``pos`` [B]. Returns [B, H, rank] float32."""
    with jax.named_scope("mla_window_attn"):
        held = jax.vmap(lambda p: ring_positions(p, ring.shape[1]))(pos)
        live = (held >= 0) & (held > pos[:, None] - window)
        s = jnp.einsum("bhr,bkr->bhk", q, ring,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(live[:, None, :], s, NEG_INF), axis=-1)
        return jnp.einsum("bhk,bkc->bhc", p.astype(ring.dtype),
                          ring[..., :rank],
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# a chunk against every earlier row, dense: the scores of a tile kept in
# the core (below everything the other decoders' programs were traced
# from: a Mosaic kernel's serialized body carries its source lines)
# ---------------------------------------------------------------------------

def chunk_tile_friendly(t: int, block_size: int, rank: int, nope: int,
                        pe: int, v_dim: int, table_blocks: int,
                        tile: int = 1024) -> bool:
    """Shapes the TPU compiler takes for ``mla_chunk_attn``: a chunk and
    a table of whole key tiles (``tile`` latent rows a grid step),
    128-row blocks, lane-aligned head parts."""
    return (block_size == 128 and tile % 128 == 0 and t % tile == 0
            and rank % 128 == 0 and nope % 128 == 0 and pe % 64 == 0
            and v_dim % 128 == 0 and table_blocks % (tile // 128) == 0)


def _chunk_kernel(table_ref, at_ref, q_ref, w_ref, *rest, blocks: int,
                  tile: int, rank: int, nope: int, pe: int):
    """Grid (H, W / tile): one head's queries (the whole chunk) against a
    tile of ``blocks`` latent blocks a step; K and V of the head are made
    from the tile's latent rows here, an online softmax runs over the
    tiles. ``at_ref``: the chunk's first position and the tiles it can
    see. A tile wholly before the chunk needs no mask; one that holds
    rows of the chunk is masked from positions; one past the chunk names
    the last live one again (no DMA) and computes nothing."""
    lat_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:blocks], rest[blocks:]
    j = pl.program_id(1)
    start, live_tiles = at_ref[0], at_ref[1]
    k0 = j * tile

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(masked: bool):
        lat = jnp.concatenate([r[0] for r in lat_refs], axis=0)  # [tile, R]
        kv = jnp.dot(lat[:, :rank], w_ref[0],
                     preferred_element_type=jnp.float32).astype(lat.dtype)
        q = q_ref[0]                                        # [T, nope + pe]
        dims = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(q[:, :nope], kv[:, :nope], dims,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(q[:, nope:], lat[:, rank:rank + pe], dims,
                               preferred_element_type=jnp.float32))
        if masked:
            qp = start + lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0)
            kp = k0 + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            live = kp <= qp
            s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(live, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(lat.dtype), kv[:, nope:],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(k0 + tile <= start)
    def _before():
        attend(False)

    @pl.when((k0 + tile > start) & (j < live_tiles))
    def _own():
        attend(True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # every row attends to itself at least, so l > 0
        o_ref[0] = acc_ref[...] / l_ref[...]


def _chunk_dispatch(q, pool, table_row, start, w_kvb, *, rank: int,
                    nope: int, pe: int, v_dim: int, scale: float,
                    tile: int):
    t, h, _ = q.shape
    bs, r = pool.shape[1], pool.shape[2]
    dtype = pool.dtype
    g = tile // bs
    tiles = table_row.shape[0] // g
    qh = (q.astype(jnp.float32) * scale).astype(dtype).transpose(1, 0, 2)
    wh = w_kvb.astype(dtype).transpose(1, 0, 2)         # [H, rank, nope+v]
    start = jnp.asarray(start, jnp.int32)
    at = jnp.stack([start, (start + t + tile - 1) // tile])

    def head_map(hh, jj, table, at_s):
        return (hh, 0, 0)

    def lat_map(hh, jj, table, at_s, *, k):
        return (table[jnp.minimum(jj, at_s[1] - 1) * g + k], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # table_row, (start, live tiles)
        grid=(h, tiles),
        in_specs=[pl.BlockSpec((1, t, nope + pe), head_map),
                  pl.BlockSpec((1, rank, nope + v_dim), head_map)] + [
            pl.BlockSpec((1, bs, r), functools.partial(lat_map, k=k))
            for k in range(g)],
        out_specs=pl.BlockSpec((1, t, v_dim), head_map),
        scratch_shapes=[pltpu.VMEM((t, 1), jnp.float32),
                        pltpu.VMEM((t, 1), jnp.float32),
                        pltpu.VMEM((t, v_dim), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, blocks=g, tile=tile, rank=rank,
                          nope=nope, pe=pe),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, t, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        name="mla_chunk_attn",
        interpret=_interpret(),
    )(jnp.asarray(table_row, jnp.int32), at, qh, wh, *([pool] * g))
    return out.transpose(1, 0, 2)


def mla_chunk_attention(q: jax.Array, pool: jax.Array, table_row, start,
                        w_kvb: jax.Array, *, rank: int, nope: int, pe: int,
                        v_dim: int, scale: float, key_tile: int = 1024,
                        impl: str = "auto") -> jax.Array:
    """:func:`mla_prefill_attention` (same arguments, same result: a
    chunk's queries against every earlier row and its own, causal, K and
    V expanded from the latent) with the scores of a tile kept in the
    core. ``impl``: ``"auto"`` takes the kernel (``mla_chunk_attn`` in a
    capture) on a TPU where :func:`chunk_tile_friendly` holds,
    ``"pallas"`` forces it (interpreted off the TPU), ``"xla"`` runs
    :func:`mla_prefill_attention`'s tile loop. ``key_tile``: latent rows
    a grid step."""
    t = q.shape[0]
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown chunk attention impl {impl!r}")
    table_row = jnp.asarray(table_row, jnp.int32)
    tile = min(key_tile, t)
    friendly = chunk_tile_friendly(t, pool.shape[1], rank, nope, pe, v_dim,
                                   table_row.shape[0], tile)
    if impl == "pallas" and not friendly:
        raise ValueError(
            "the chunk attention kernel needs 128-row blocks, a chunk and "
            f"a table of whole {tile}-row tiles and lane-aligned head "
            f"parts, got T={t} block_size={pool.shape[1]} rank={rank} "
            f"nope={nope} pe={pe} v={v_dim} table={table_row.shape[0]}")
    if friendly and (impl == "pallas" or (
            impl == "auto" and jax.default_backend() == "tpu")):
        with jax.named_scope("mla_chunk_attn"):
            return _chunk_dispatch(q, pool, table_row, start, w_kvb,
                                   rank=rank, nope=nope, pe=pe, v_dim=v_dim,
                                   scale=scale, tile=tile)
    return mla_prefill_attention(q, pool, table_row, start, w_kvb, rank=rank,
                                 nope=nope, pe=pe, v_dim=v_dim, scale=scale)


def decode_schedule(rows: int, heads: int, rank: int, block_size: int,
                    blocks_per_slot: int, impl: str = "auto") -> dict:
    """What :func:`mla_decode_attention` runs for these shapes, as an
    exporter keeps it (``export.json`` ``stepwise.decode.attn_schedule``):
    the kernel with its blocks a grid step and its grid, or the
    gather."""
    if latent_tile_friendly(block_size, heads, rank, blocks_per_slot) and (
            impl == "pallas" or (impl == "auto"
                                 and jax.default_backend() == "tpu")):
        return {"kernel": "paged_latent_attn",
                "blocks_per_step": _BLOCKS_A_STEP,
                "grid": [rows, blocks_per_slot // _BLOCKS_A_STEP],
                "heads": heads}
    return {"kernel": "xla", "heads": heads}
