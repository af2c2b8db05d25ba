"""A learned sparse selection over a paged cache: the indexer.

Beside its latent row (``ops/mla.py``) a token of a full-attention layer
keeps one INDEX KEY ``kI`` of ``D`` values in a second paged pool
``[N, Bs, D]`` behind the same block tables. A query row scores every
earlier token with ``J`` small heads,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),      s <= t,

and attends only to the ``k`` tokens of largest ``I[t, :]`` (all of them
while ``t < k``). Three pieces, each one form, all XLA (what the chip read
for each is in PERF.md; the attention over the selected set is a kernel,
``ops/mla.py``):

- :func:`chunk_scores` / :func:`step_scores`: the scores of a prompt
  chunk's rows, or of one row a slot, against the index keys the request
  holds, into ``[rows, T]`` float32 with ``-inf`` where ``s > t``. The
  ``J`` heads are reduced as they are made, a tile of keys at a time:
  ``[rows, J, T]`` never exists.
- :func:`top_k_mask`: the selected SET of every row as a mask, without
  sorting: the k-th largest score of a row is found by bisection on the
  scores' bits (32 counting passes over the share of ``[rows, T]`` that
  the chunk's context reaches), ties at it go to the lower positions, as
  ``lax.top_k`` orders them. What a chunk's attention runs under
  (``ops/mla.mla_masked_prefill_attention``).
- :func:`top_k_rows`: the selected positions of one row a slot, by
  ``lax.top_k`` (a sort of ``slots`` rows, not of a chunk's thousand):
  what the one-token forward gathers (``ops/mla.mla_gathered_attention``).

Both give the same set for the same scores; the reference
(``benchmark/reference/dots3-note-prev.py``) takes a plain ``top_k``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -jnp.inf
#: index keys one step of the score loops visits
KEY_TILE = 512


def _head_sum(q, w, keys):
    """``sum_j w[r, j] relu(q[r, j] . keys[s])``: ``q`` [R, J, D], ``w``
    [R, J] float32, ``keys`` [S, D] -> [R, S] float32."""
    r, j, d = q.shape
    s = jnp.dot(q.reshape(r * j, d), keys.T,
                preferred_element_type=jnp.float32).reshape(r, j, -1)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def chunk_scores(q: jax.Array, w: jax.Array, pool: jax.Array, table_row,
                 start, *, width: int) -> jax.Array:
    """A chunk's index scores. ``q`` [C, J, D] the rows' index queries
    (positions ``start .. start + C - 1``, ``start`` a multiple of C) in
    the pool's dtype, ``w`` [C, J] float32, ``pool`` [N, Bs, D] one
    layer's index keys with this chunk's already written, ``table_row``
    the request's blocks (``width / Bs`` of them at least). Returns
    [C, width] float32, ``-inf`` where the key is later than the row.
    Only the tiles up to the chunk's own are visited."""
    c = q.shape[0]
    bs = pool.shape[1]
    tile = min(KEY_TILE, c)
    if tile % bs or c % tile or width % c:
        raise ValueError(f"chunk {c}, key tile {tile}, width {width} and "
                         f"block size {bs} do not nest")
    tb = tile // bs
    table_row = jnp.asarray(table_row, jnp.int32)
    qpos = start + jnp.arange(c)

    def one(i, out):
        ids = lax.dynamic_slice_in_dim(table_row, i * tb, tb)
        keys = pool[ids].reshape(tile, -1)
        s = _head_sum(q, w, keys)
        kpos = i * tile + jnp.arange(tile)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF)
        return lax.dynamic_update_slice_in_dim(out, s, i * tile, axis=1)

    with jax.named_scope("dsa_index_scores"):
        return lax.fori_loop(0, (start + c) // tile, one,
                             jnp.full((c, width), NEG_INF, jnp.float32))


def step_scores(q: jax.Array, w: jax.Array, pool: jax.Array, block_tables,
                pos) -> jax.Array:
    """One row a slot: ``q`` [S, J, D], ``w`` [S, J], ``block_tables``
    [S, NB], ``pos`` [S] each row's own position (its key written).
    Returns [S, NB * Bs] float32, ``-inf`` past ``pos``."""
    bt = jnp.asarray(block_tables, jnp.int32)
    s, nb = bt.shape
    bs = pool.shape[1]
    with jax.named_scope("dsa_index_scores"):
        keys = pool[bt].reshape(s, nb * bs, -1)             # [S, T, D]
        sc = jnp.einsum("sjd,std->sjt", q, keys,
                        preferred_element_type=jnp.float32)
        sc = jnp.sum(jax.nn.relu(sc) * w[:, :, None], axis=1)
        live = jnp.arange(nb * bs)[None, :] <= pos[:, None]
        return jnp.where(live, sc, NEG_INF)


def _order_bits(x):
    """float32 -> uint32 whose order is the floats' (``-0.0`` as
    ``0.0``); ``-inf`` maps to 0x007fffff, every finite value above it."""
    b = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(0x80000000)


def top_k_mask(scores: jax.Array, k: int, *, live=None) -> jax.Array:
    """[R, T] float32 scores (``-inf`` = not a candidate) -> [R, T] bool:
    each row's ``k`` largest candidates (all of them where a row has
    fewer), equal scores at the k-th taken from the lower positions
    (``-0.0`` equals ``0.0``).

    ``live`` (a traced scalar): no column from ``live`` on holds a
    candidate (a chunk's context ends there). The 32 counting passes then
    visit the narrowest of an eighth, a quarter, a half or the whole of
    the width that holds ``live`` columns, and none at all while ``live
    <= k``: the cost follows the context, not the table's width."""
    t = scores.shape[-1]
    if live is None:
        with jax.named_scope("dsa_select"):
            return _bisect(scores, k)
    widths = sorted({t // d for d in (8, 4, 2, 1) if t % d == 0})

    def within(w):
        def run(sc):
            return jnp.pad(_bisect(sc[:, :w], k), ((0, 0), (0, t - w)))
        return run

    live = jnp.asarray(live, jnp.int32)
    with jax.named_scope("dsa_select"):
        branch = jnp.where(live <= k, 0, 1 + sum(
            (live > w).astype(jnp.int32) for w in widths[:-1]))
        return lax.switch(branch, [lambda sc: sc > NEG_INF,
                                   *(within(w) for w in widths)], scores)


def _bisect(scores: jax.Array, k: int) -> jax.Array:
    u = _order_bits(scores)
    u = jnp.where(scores == NEG_INF, jnp.uint32(0), u)

    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        n = jnp.sum(u >= cand, axis=-1, keepdims=True)
        return jnp.where(n >= k, cand, tau)

    # the largest tau with at least k scores >= tau (0: fewer than k
    # candidates, and every candidate is taken)
    tau = lax.fori_loop(0, 32, bit,
                        jnp.zeros((scores.shape[0], 1), jnp.uint32))
    above = u > tau
    at = (u == tau) & (u > 0)
    room = k - jnp.sum(above, axis=-1, keepdims=True)

    def ties(_):
        return above | (at & (jnp.cumsum(at, axis=-1) <= room))

    def no_ties(_):
        return above | at

    crowded = jnp.any(jnp.sum(at, axis=-1, keepdims=True) > room)
    return lax.cond(crowded, ties, no_ties, None)


def top_k_rows(scores: jax.Array, k: int):
    """[S, T] scores -> (positions [S, k] int32, chosen [S, k] bool): each
    row's ``k`` largest, ``chosen`` false where the row has fewer
    candidates than ``k``."""
    with jax.named_scope("dsa_select"):
        # -0.0 as 0.0, as top_k_mask counts it
        vals, idx = lax.top_k(jnp.where(scores == 0, 0.0, scores),
                              min(k, scores.shape[-1]))
        return idx.astype(jnp.int32), vals > NEG_INF
