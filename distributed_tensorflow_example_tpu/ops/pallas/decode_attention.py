"""Single-query decode attention over the KV-cache slab as one Pallas kernel.

The decode step's attention is the per-op latency floor's biggest owner
after layernorm (PROFILE_r05_decode: ~36 attention fusions at ~15 µs per
token-step — mask build, score, softmax, context as separate small XLA
fusions on [B, 1, H, D]-sized tensors). This kernel collapses that chain
into ONE program per (batch row, head): it reads K/V straight from the
[B, T, H, D] cache slab (no transpose, no repacking — the BlockSpec
index map picks the head plane), builds the ragged ``pad``/``pos``
validity mask from scalars in SMEM with an in-kernel iota, and runs the
f32 softmax + context matmul in VMEM. One kernel per layer per token
step instead of ~4-6.

Numerics mirror ``ops.attention.multi_head_attention(impl="xla")``:
scores in f32 scaled by 1/sqrt(D), NEG_INF masking (exp underflows to
exactly 0 — slot ``pos`` is always valid, so no fully-masked rows
exist), probabilities cast to the value dtype before the context matmul
with f32 accumulation.

Scope: the kernel path needs tiles the TPU compiler accepts
(:func:`tile_friendly`). Every K/V block is carved from the
[B, T, H·D] view of the cache, so its lane width must be a multiple of
128: a head dim that is one (D % 128 == 0) is one block per head; at
D == 64 — GPT-2's — one block holds a PAIR of adjacent heads (the head
count must be even) and the kernel keeps the two apart with a lane mask
on the query rows. The score row's lane dim is the cache length
(T % 128 == 0). Anything else takes the XLA path (which the ``"loop"``
decode impl uses anyway). Off-TPU the kernel runs in Pallas interpret
mode so CPU tests exercise the same code path (same recipe as
flash_attention); tests/test_tpu_compile.py compiles it for a described
v5e at GPT-2 widths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF, multi_head_attention


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _group(head_dim: int) -> int:
    """Heads per K/V block: as many as fill one 128-lane tile."""
    return max(1, 128 // head_dim)


def _head_dim_ok(heads: int, head_dim: int) -> bool:
    return head_dim % 128 == 0 or (head_dim == 64 and heads % 2 == 0)


def tile_friendly(total: int, heads: int, head_dim: int) -> bool:
    """Exactly the slab shapes the TPU compiler accepts for this kernel:
    the [g, T] score rows put T in the lane dim (128-multiples) and each
    [T, g·D] K/V block must be 128 lanes wide — D a multiple of 128, or
    D == 64 with an even head count (two heads per block)."""
    return total % 128 == 0 and _head_dim_ok(heads, head_dim)


def _own_lanes(group: int, head_dim: int):
    """[g, g·D] bool: row r owns the lanes of the r-th head in the
    block. Comparisons only — no vector integer division."""
    shape = (group, group * head_dim)
    lo = lax.broadcasted_iota(jnp.int32, shape, 0) * head_dim
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= lo) & (lane < lo + head_dim)


def _split_heads(q, group: int, head_dim: int):
    """[1, g·D] query lanes -> [g, g·D] rows, row r zero outside head
    r's lanes, so ONE lane-aligned matmul against the [T, g·D] K block
    yields each head's own scores (the zeros contribute exact 0)."""
    if group == 1:
        return q
    return jnp.where(_own_lanes(group, head_dim), q, 0.0)


def _merge_heads(ctx, group: int, head_dim: int):
    """[g, g·D] per-row contexts -> the [1, g·D] output lanes: row r's
    matmul against the whole V block is only meaningful in head r's
    lanes; keep those."""
    if group == 1:
        return ctx
    return jnp.sum(jnp.where(_own_lanes(group, head_dim), ctx, 0.0),
                   axis=0, keepdims=True)


def _kernel(pos_ref, pad_ref, q_ref, k_ref, v_ref, o_ref, *,
            total: int, head_dim: int, sm_scale: float):
    b = pl.program_id(0)
    g = _group(head_dim)
    q = _split_heads(q_ref[0].astype(jnp.float32), g, head_dim)  # [g, W]
    k = k_ref[0].astype(jnp.float32)                    # [T, W]
    v = v_ref[0]                                        # [T, W]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    # ragged pad/pos mask fused in: slot j of row b is live iff
    # pad_b <= j <= pos_b (pos_b = the slot row b's current token sits
    # at — per-row since the continuous-batching engine, where slots
    # admitted at different times sit at different depths)
    kpos = lax.broadcasted_iota(jnp.int32, (1, total), 1)
    live = (kpos <= pos_ref[b]) & (kpos >= pad_ref[b])
    s = jnp.where(live, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)                                  # masked -> exact 0
    probs = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(v.dtype)
    ctx = lax.dot_general(probs, v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    o_ref[0] = _merge_heads(ctx, g, head_dim).astype(o_ref.dtype)


def _dispatch(q, k, v, pos, pad):
    """Grid (B, H/g); per program ONE [T, g·D] K/V block of the cache
    slab — g = 1 head where D fills the lanes, 2 heads at D == 64.

    Mosaic tiling note: the block is carved out of the [B, T, H·D]
    *view* of the slab (a free, contiguous reshape), so its trailing
    2-D tile is [T, g·D]: sublane T equals the array dim and lane g·D
    is a 128-multiple — the two things the TPU lowering asks of a
    block. q and the output ride the same view as [B, 1, H·D] rows.
    Blocking the 4-D [B, T, H, D] layout directly would put a size-1
    tile against the H dim (neither 8-divisible nor the array dim),
    and a bare [T, 64] block of the view a 64-lane tile against
    H·D: both pass interpret mode and are refused by the compiler."""
    b, t, h, d = k.shape
    g = _group(d)
    w = g * d
    row = pl.BlockSpec((1, 1, w), lambda bb, hh: (bb, 0, hh))
    slab = pl.BlockSpec((1, t, w), lambda bb, hh: (bb, 0, hh))
    out = pl.pallas_call(
        functools.partial(_kernel, total=t, head_dim=d,
                          sm_scale=1.0 / math.sqrt(d)),
        grid=(b, h // g),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # pos [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),               # pad [B]
            row, slab, slab,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="decode_attn",
        interpret=_interpret(),
    )(pos, pad, q.reshape(b, 1, h * d), k.reshape(b, t, h * d),
      v.reshape(b, t, h * d))
    return out.reshape(b, h, d)


def xla_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         pos, pad) -> jax.Array:
    """Reference path: the exact ``multi_head_attention(impl="xla")``
    call the ``"loop"`` decode step makes — the kernel's parity oracle
    and the fallback for tile-unfriendly shapes."""
    total = k.shape[1]
    slots = jnp.arange(total, dtype=jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos[:, None] if pos.ndim == 1 else pos
    live = (slots[None, :] <= pos_b) & (slots[None, :] >= pad[:, None])
    ctx = multi_head_attention(q[:, None], k, v,
                               mask=live[:, None, None, :], impl="xla")
    return ctx[:, 0]


# ---------------------------------------------------------------------------
# block-paged decode attention (round 10): K/V live in a shared block pool
# [N, block_size, H*D] (a token's heads side by side in the lane dimension,
# the layout the kernel's blocks are carved from as they lie) instead of
# per-slot slabs; each row's logical cache is the run of physical blocks its
# block-table row names. Both impls gather THROUGH the table: the XLA
# fallback with one advanced-indexing gather (then the exact slab reference
# math), the kernel with scalar-prefetch index maps (the block id is read
# from SMEM before each K/V block's DMA is issued — no gathered [B, T, H, D]
# tensor ever exists).
#
# K-query speculative verify (round 16): the verify program presents BOTH
# impls with row-expanded queries — K lanes of one slot become K rows at
# consecutive `pos` values sharing one block-table row (repeated in
# `block_tables`). Neither impl needs a special case: rows are independent
# by construction, which is exactly the property the engine's exact-accept
# rule rides; kernel-vs-gather parity on the expanded shape is pinned in
# tests/test_paged_serving.py.
# ---------------------------------------------------------------------------

def paged_tile_friendly(block_size: int, heads: int,
                        head_dim: int) -> bool:
    """Exactly the pool shapes the TPU compiler accepts for the paged
    kernel: each score row is [g, block_size] (block_size in the lane
    dim — 128-multiples) over the same 128-lane K/V blocks as the slab
    kernel (:func:`tile_friendly`)."""
    return block_size % 128 == 0 and _head_dim_ok(heads, head_dim)


def xla_paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, *, block_tables,
                               pos, pad, k_scale=None,
                               v_scale=None) -> jax.Array:
    """Reference path: gather each row's block run out of the flat
    [N, Bs, H*D] pool (one advanced-indexing gather, heads split after
    it -> the row's [T, H, D] logical cache, with
    T = blocks_per_row * block_size) and run the exact slab reference.
    Bitwise equal to the slab path on equal logical contents — the
    paged byte-parity oracle.

    int8 pools (``k_scale``/``v_scale`` [N, Bs] f32 per-row scales):
    the gather additionally dequantizes each row — f32 multiply, cast
    to the query dtype — before the slab reference math (the kernel
    path's parity oracle for the quantized cache)."""
    _, h, d = q.shape
    bs = k_pool.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    b, nb = bt.shape

    def gather(pool, scale):
        g = pool[bt]                                # [B, NB, Bs, H*D]
        if scale is not None:
            g = (g.astype(jnp.float32)
                 * scale[bt][..., None]).astype(q.dtype)
        return g.reshape(b, nb * bs, h, d)

    return xla_decode_attention(q, gather(k_pool, k_scale),
                                gather(v_pool, v_scale), pos=pos,
                                pad=pad)


def _paged_kernel(bt_ref, pos_ref, pad_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, head_dim: int, sm_scale: float,
                  quant: bool):
    """Grid (B, H/g, NB): one [block_size, g·D] K/V block per step,
    gathered through the block table by the index maps (scalar
    prefetch). The softmax runs online over the NB dimension (per-head
    m/l/acc scratch persists across the revisited output block);
    masked slots are zeroed explicitly so never-written pool blocks
    (incl. the engine's null block) contribute exact 0 regardless of
    their bytes.

    ``quant=True`` (int8 pools): two extra [1, 1, Bs] scale-row inputs
    follow v. The dequant is fused ALGEBRAICALLY — K's per-row scale
    multiplies the score COLUMNS (q·(k·s)ᵀ = (q·kᵀ)·s, broadcast along
    the [g, Bs] score rows) and V's scale folds into the probabilities
    before the context matmul (p·(v·s) = (p·s)·v) — so no dequantized
    [Bs, g·D] tile is ever materialized and no transpose of the scale
    row is needed."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    g = _group(head_dim)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _split_heads(q_ref[0].astype(jnp.float32), g, head_dim)  # [g, W]
    k = k_ref[0].astype(jnp.float32)                    # [Bs, W]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    if quant:
        s = s * ks_ref[0]                               # [1, Bs] scales
    kpos = j * block_size + lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    live = (kpos <= pos_ref[b]) & (kpos >= pad_ref[b])
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_ref[...]                                 # [g, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # explicit zeroing (not exp underflow): with the finite NEG_INF fill
    # an all-masked block would otherwise see exp(NEG_INF - NEG_INF) = 1
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)        # [g, Bs]
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        pv = p * vs_ref[0]                              # fold V scales
        vblk = v_ref[0].astype(jnp.float32)
    else:
        pv = p.astype(v_ref.dtype)
        vblk = v_ref[0]
    acc_ref[...] = (acc_ref[...] * alpha
                    + lax.dot_general(
                        pv, vblk,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # slot `pos` is always live, so l >= exp(0) > 0
        o_ref[0] = _merge_heads(acc_ref[...] / l_ref[...], g,
                                head_dim).astype(o_ref.dtype)


def _paged_dispatch(q, k_pool, v_pool, block_tables, pos, pad,
                    k_scale=None, v_scale=None):
    """Grid (B, H/g, NB); per program ONE [Bs, g·D] K/V block of the
    pool, selected by the block table via scalar-prefetch index maps.
    The pool is [N, Bs, H·D] as it lies (the slab kernel's view, here
    the layout itself: no reshape, so no relayout of the pool a call),
    every tile one the compiler accepts. int8 pools additionally stream
    the matching [1, Bs] scale row per block ([N, 1, Bs] view so the
    singleton tile dim matches its array dim)."""
    n, bs, _ = k_pool.shape
    _, h, d = q.shape
    b, nb = block_tables.shape
    quant = k_scale is not None
    g = _group(d)
    w = g * d

    def kv_map(bb, hh, jj, bt, pos_s, pad_s):
        return (bt[bb, jj], 0, hh)

    def scale_map(bb, hh, jj, bt, pos_s, pad_s):
        return (bt[bb, jj], 0, 0)

    def q_map(bb, hh, jj, bt, pos_s, pad_s):
        return (bb, 0, hh)

    in_specs = [
        pl.BlockSpec((1, 1, w), q_map),
        pl.BlockSpec((1, bs, w), kv_map),
        pl.BlockSpec((1, bs, w), kv_map),
    ]
    operands = [q.reshape(b, 1, h * d), k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, bs), scale_map)] * 2
        operands += [k_scale.reshape(n, 1, bs).astype(jnp.float32),
                     v_scale.reshape(n, 1, bs).astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # block_tables, pos, pad
        grid=(b, h // g, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, w), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),            # running max
            pltpu.VMEM((g, 1), jnp.float32),            # running sum
            pltpu.VMEM((g, w), jnp.float32),            # context acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, head_dim=d,
                          sm_scale=1.0 / math.sqrt(d), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, 1, h * d), q.dtype if quant else v_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="paged_decode_attn",
        interpret=_interpret(),
    )(block_tables, pos, pad, *operands)
    return out.reshape(b, h, d)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, *, block_tables, pos, pad,
                           k_scale=None, v_scale=None,
                           impl: str = "auto") -> jax.Array:
    """One-query attention against the block-paged cache pool.

    ``q``: [B, H, D] (heads and head size are read from it);
    ``k_pool``/``v_pool``: [N, block_size, H*D] shared physical blocks,
    a token's heads side by side (the caller may hand every layer's
    blocks at once, ``[L*N, Bs, H*D]`` with ``block_tables + i * N``:
    the kernel then reads layer i where it lies); ``block_tables``:
    [B, NB] int32 — row b's logical slot j lives in
    ``pool[block_tables[b, j // Bs], j % Bs]``;
    ``pos``/``pad``: [B] int32, the same live-window semantics as the
    slab path (row b attends to logical slots ``pad_b <= j <= pos_b``).
    Returns [B, H, D] context.

    int8 KV cache: pass the pools as int8 plus ``k_scale``/``v_scale``
    ([N, Bs] f32 per-token-row scales) — BOTH impls fuse the dequant
    into the gather (the kernel algebraically, the XLA path on the
    gathered rows); the context dtype is then the QUERY's dtype. The
    scales and the int8 pools travel together: one without the other
    is a loud error, never a silent garbage read.

    ``impl`` as in :func:`decode_attention`; the kernel path needs
    :func:`paged_tile_friendly` shapes, anything else falls back to the
    gather + slab-reference XLA path.
    """
    b, h, d = q.shape
    if k_pool.ndim != 3 or k_pool.shape[2] != h * d \
            or v_pool.shape != k_pool.shape:
        raise ValueError(f"pool shapes {k_pool.shape}/{v_pool.shape} are "
                         f"not [N, Bs, {h * d}] for q {q.shape}")
    n, bs, _ = k_pool.shape
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown decode attention impl {impl!r}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together "
                         "(int8 pools carry one scale row per cached "
                         "token for BOTH k and v)")
    if k_scale is not None:
        if k_pool.dtype != jnp.int8 or v_pool.dtype != jnp.int8:
            raise ValueError(
                f"k_scale/v_scale describe int8 pools, got pool dtype "
                f"{k_pool.dtype}/{v_pool.dtype}")
        if tuple(k_scale.shape) != (n, bs) \
                or tuple(v_scale.shape) != (n, bs):
            raise ValueError(
                f"scale shape {tuple(k_scale.shape)}/"
                f"{tuple(v_scale.shape)} != per-row ({n}, {bs}) from "
                f"pool {k_pool.shape}")
    elif k_pool.dtype == jnp.int8:
        raise ValueError("int8 pools need k_scale/v_scale — attending "
                         "over raw int8 bytes would silently produce "
                         "garbage context")
    bt = jnp.asarray(block_tables, jnp.int32)
    if bt.ndim != 2 or bt.shape[0] != b:
        raise ValueError(f"block_tables shape {bt.shape} != ({b}, NB)")
    friendly = paged_tile_friendly(bs, h, d)
    if impl == "pallas" and not friendly:
        raise ValueError(
            f"paged decode_attention kernel needs block_size % 128 == 0 "
            f"and a head dim of 64 (even head count) or a multiple of "
            f"128, got block_size={bs} H={h} D={d} (use impl='auto' for "
            "the XLA fallback)")
    use_kernel = friendly and (
        impl == "pallas"
        or (impl == "auto" and jax.default_backend() == "tpu"))
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    padb = jnp.broadcast_to(jnp.asarray(pad, jnp.int32).reshape(-1), (b,))
    if not use_kernel:
        return xla_paged_decode_attention(q, k_pool, v_pool,
                                          block_tables=bt, pos=posb,
                                          pad=padb, k_scale=k_scale,
                                          v_scale=v_scale)
    return _paged_dispatch(q, k_pool, v_pool, bt, posb, padb,
                           k_scale=k_scale, v_scale=v_scale)


# ---------------------------------------------------------------------------
# Block steps over grouped KV heads (block-diffusion decoders)
#
# A block step runs B lanes a slot, and a KV head is shared by a group of
# query heads, so a slot brings R = group x B query rows to each KV head.
# Every one of them sees the same window: the block is bidirectional
# inside and causal across, so row (slot, lane) attends to the logical
# slots <= last[slot], the block's last position. That makes the mask one
# scalar a slot, and the kernel a single pass over the slot's live pool
# blocks with all its KV heads' rows resident.
# ---------------------------------------------------------------------------

def block_tile_friendly(block_size: int, rows: int, head_dim: int) -> bool:
    """The shapes the TPU compiler takes for :func:`paged_block_attention`'s
    kernel: K/V blocks carved [block_size, KVH*D] from the pool's view
    (D a multiple of 128 lanes a head), score rows [R, block_size], and R
    query rows a KV head in whole sublane tiles."""
    return block_size % 128 == 0 and head_dim % 128 == 0 and rows % 8 == 0


def xla_paged_block_attention(q, k_pool, v_pool, *, block_tables, last):
    """Reference path: gather each slot's block run and attend plainly."""
    _, kvh, _, d = q.shape
    bs = k_pool.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    b, nb = bt.shape
    k = k_pool[bt].reshape(b, nb * bs, kvh, d)
    v = v_pool[bt].reshape(b, nb * bs, kvh, d)
    s = jnp.einsum("bhrd,bthd->bhrt", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    live = jnp.arange(nb * bs)[None, :] <= last[:, None]       # [B, T]
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrt,bthd->bhrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _block_kernel(bt_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_size: int, kv_heads: int,
                  rows: int, head_dim: int, sm_scale: float):
    """Grid (B, NB): one [block_size, KVH*D] K/V block of the slot's run a
    step, every KV head's R query rows resident; online softmax over the
    NB dimension. A step past the slot's last live block names that block
    again (no DMA) and computes nothing."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    last = last_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_size <= last)
    def _compute():
        kpos = j * block_size + lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        live = kpos <= last
        for h in range(kv_heads):           # static: KVH heads a block
            r0, c0 = h * rows, h * head_dim
            q = q_ref[0, r0:r0 + rows, :]                     # [R, D]
            k = k_ref[0, :, c0:c0 + head_dim]                 # [Bs, D]
            v = v_ref[0, :, c0:c0 + head_dim]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * sm_scale
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[r0:r0 + rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r0:r0 + rows, :] = (l_ref[r0:r0 + rows, :] * alpha
                                      + jnp.sum(p, axis=-1, keepdims=True))
            acc_ref[r0:r0 + rows, :] = (
                acc_ref[r0:r0 + rows, :] * alpha
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))
            m_ref[r0:r0 + rows, :] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # logical slot 0 is live for every slot, so l > 0
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _block_dispatch(q, k_pool, v_pool, block_tables, last):
    bs = k_pool.shape[1]
    b, kvh, r, d = q.shape
    nb = block_tables.shape[1]

    def kv_map(bb, jj, bt, last_s):
        return (bt[bb, jnp.minimum(jj, last_s[bb] // bs)], 0, 0)

    def q_map(bb, jj, bt, last_s):
        return (bb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, last
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, kvh * r, d), q_map),
                  pl.BlockSpec((1, bs, kvh * d), kv_map),
                  pl.BlockSpec((1, bs, kvh * d), kv_map)],
        out_specs=pl.BlockSpec((1, kvh * r, d), q_map),
        scratch_shapes=[pltpu.VMEM((kvh * r, 1), jnp.float32),
                        pltpu.VMEM((kvh * r, 1), jnp.float32),
                        pltpu.VMEM((kvh * r, d), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_block_kernel, block_size=bs, kv_heads=kvh,
                          rows=r, head_dim=d, sm_scale=1.0 / math.sqrt(d)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh * r, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_block_attn",
        interpret=_interpret(),
    )(block_tables, last, q.reshape(b, kvh * r, d), k_pool, v_pool)
    return out.reshape(b, kvh, r, d)


def paged_block_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, *, block_tables, last,
                          impl: str = "auto") -> jax.Array:
    """A block step's attention against the block-paged pool, grouped KV
    heads.

    ``q``: [B, KVH, R, D], the R = (query heads a KV head) x (lanes a
    block) rows each KV head of slot b answers; ``k_pool``/``v_pool``:
    [N, block_size, KVH * D] (the lanes' own K/V already written), a
    token's heads side by side in the lane dimension: the layout the
    kernel's [block_size, KVH * D] blocks are carved from as they lie
    (a [.., KVH, D] pool is tiled by (KVH, D) in HBM, and handing it to
    the kernel costs a copy of the whole pool a call);
    ``block_tables``: [B, NB] int32; ``last``: [B] int32, the last
    logical slot the block's rows see (``pos | (lanes - 1)``): all R rows
    of a slot attend to logical slots ``0 .. last[b]``. Returns
    [B, KVH, R, D] in ``q``'s dtype. MXU operands stay in the inputs'
    dtype; scores, softmax and accumulation are float32.

    ``impl`` as in :func:`paged_decode_attention`: the kernel
    (``paged_block_attn`` in a capture) needs :func:`block_tile_friendly`
    shapes; anything else gathers the run and attends in XLA.
    """
    b, kvh, r, d = q.shape
    bs = k_pool.shape[1]
    if k_pool.ndim != 3 or k_pool.shape[2] != kvh * d:
        raise ValueError(f"pool shape {k_pool.shape} is not [N, Bs, "
                         f"{kvh * d}] for q {q.shape}")
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown decode attention impl {impl!r}")
    bt = jnp.asarray(block_tables, jnp.int32)
    if bt.ndim != 2 or bt.shape[0] != b:
        raise ValueError(f"block_tables shape {bt.shape} != ({b}, NB)")
    friendly = block_tile_friendly(bs, r, d)
    if impl == "pallas" and not friendly:
        raise ValueError(
            f"paged_block_attention's kernel needs block_size % 128 == 0, "
            f"a head dim that is a multiple of 128 and rows % 8 == 0, got "
            f"block_size={bs} R={r} D={d} (impl='auto' falls back to XLA)")
    last = jnp.clip(jnp.broadcast_to(
        jnp.asarray(last, jnp.int32).reshape(-1), (b,)),
        0, bt.shape[1] * bs - 1)
    if friendly and (impl == "pallas" or (
            impl == "auto" and jax.default_backend() == "tpu")):
        return _block_dispatch(q, k_pool, v_pool, bt, last)
    return xla_paged_block_attention(q, k_pool, v_pool, block_tables=bt,
                                     last=last)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     pos, pad, impl: str = "auto") -> jax.Array:
    """One-query attention against the cache slab.

    ``q``: [B, H, D] (the current token's heads); ``k``/``v``:
    [B, T, H, D] cache slabs (slot ``pos`` already written); ``pos``:
    int32 cache slot of the current token — a scalar (one shared decode
    depth, the ``generate`` loop) or a [B] vector (per-row depths, the
    continuous-batching engine where slots join mid-flight); ``pad``:
    [B] int32 per-row dead-slot count (ragged prompts). Returns
    [B, H, D] context.

    ``impl``: ``"auto"`` takes the kernel on TPU when
    :func:`tile_friendly` holds and the XLA path otherwise; ``"pallas"``
    forces the kernel (interpret mode off-TPU — the CPU test path);
    ``"xla"`` forces the reference.
    """
    b, t, h, d = k.shape
    if q.shape != (b, h, d):
        raise ValueError(f"q shape {q.shape} != {(b, h, d)} from cache "
                         f"{k.shape}")
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown decode attention impl {impl!r}")
    friendly = tile_friendly(t, h, d)
    if impl == "pallas" and not friendly:
        raise ValueError(
            f"decode_attention kernel needs T % 128 == 0 and a head dim "
            f"of 64 (even head count) or a multiple of 128, got T={t} "
            f"H={h} D={d} (use impl='auto' for the XLA fallback)")
    use_kernel = friendly and (
        impl == "pallas"
        or (impl == "auto" and jax.default_backend() == "tpu"))
    if not use_kernel:
        return xla_decode_attention(q, k, v, pos=pos, pad=pad)
    # kernel reads one pos per row from SMEM; broadcast a scalar pos
    posb = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    return _dispatch(q, k, v, posb, pad.astype(jnp.int32))
