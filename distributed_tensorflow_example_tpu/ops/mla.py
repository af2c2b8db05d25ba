"""Multi-head latent attention (MLA) over a paged latent cache.

A token keeps one latent row ``[c_kv ; k_pe]`` (``kv_lora_rank`` normed
values and ``qk_rope_head_dim`` values every head shares; 576 values for
512 + 64, in a row of ``R`` = 640: whole 128-lane tiles, zeros past the
576), not K and V per head. The pool is ``[N, Bs, R]`` blocks behind
block tables, a layer's blocks ``N`` apart in the flat view, as the K/V
pools of the other served decoders are.

- :func:`mla_prefill_attention`: a prompt chunk's queries against the
  latent rows written so far (this chunk's among them). Per-head K and V
  are made from the latent a tile of rows at a time inside the loop
  (``[k_nope_h ; v_h] = W_kvb c_kv``) and an online softmax runs over the
  tiles, of which only those up to the chunk's own are visited: the trip
  count is ``start / T + 1``, not the pool's capacity.
- :func:`mla_decode_attention`: one query a slot in the absorbed form,
  ``W_kvb`` folded into the query and the output, so scores and the
  weighted sum are taken against the latent rows themselves: each live
  row is read once, ``R`` values, for all heads. On the chip a Pallas
  kernel (``paged_latent_attn`` in a capture) walks a slot's blocks
  through its table, ``_BLOCKS_A_STEP`` blocks a grid step; elsewhere
  the rows are gathered and attended in XLA.

``mla_use_nope``: no rotary rotation anywhere; the ``k_pe`` values are
plain extra dimensions.
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


def latent_tile_friendly(block_size: int, heads: int, rank: int,
                         blocks_per_slot: int) -> bool:
    """Shapes the TPU compiler takes for the decode kernel."""
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
