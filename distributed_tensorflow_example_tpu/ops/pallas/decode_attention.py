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
(:func:`tile_friendly`). Every K/V block of the SLAB kernel is carved
from the [B, T, H·D] view of the cache, so its lane width must be a
multiple of 128: a head dim that is one (D % 128 == 0) is one block per
head; at D == 64 — GPT-2's — one block holds a PAIR of adjacent heads
(the head count must be even) and the kernel keeps the two apart with a
lane mask on the query rows. The score row's lane dim is the cache
length (T % 128 == 0). Anything else takes the XLA path (which the
``"loop"`` decode impl uses anyway). The PAGED kernel (the served one,
further down) takes whole [Bs, H·D] blocks of the pool, every head at
once, and walks only a row's live blocks
(:func:`paged_tile_friendly`, :func:`paged_schedule`). Off-TPU the
kernels run in Pallas interpret mode so CPU tests exercise the same code
path (same recipe as flash_attention); tests/test_tpu_compile.py
compiles them for a described v5e at GPT-2 widths.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import NamedTuple

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


def _own_lanes(group: int, head_dim: int, width: int | None = None):
    """[g, g·D] bool ([g, width] where given): row r owns the lanes of
    the r-th head in the block. Comparisons only — no vector integer
    division."""
    shape = (group, group * head_dim if width is None else width)
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
# the layout the kernel's blocks are taken from as they lie) instead of
# per-slot slabs; each row's logical cache is the run of physical blocks its
# block-table row names. Both impls gather THROUGH the table: the XLA
# fallback with one advanced-indexing gather (then the exact slab reference
# math), the kernel with scalar-prefetch index maps (the block id is read
# from SMEM before each K/V block's DMA is issued — no gathered [B, T, H, D]
# tensor ever exists).
#
# The kernel's grid (PR 34) is (rows, NB / entries): a grid step holds
# `entries` consecutive table entries of one row, each a WHOLE [Bs, H*D]
# block, every head at once under one masked [H, H*D] query tile; entries
# past `pos` neither compute (pl.when) nor fetch (the fetch table names the
# block the operand already holds); `paged_schedule` chooses `entries` from
# the static shapes. A grid step has a price whatever it does (~0.4 us on a
# v5e), and the (B, H/g, NB) grid this replaced paid it 2,304 times a call
# for GPT-2's 64 rows.
#
# K-query speculative verify (round 16): the verify program presents BOTH
# impls with row-expanded queries — K lanes of one slot become K rows at
# consecutive `pos` values sharing one block-table row (repeated in
# `block_tables`). Neither impl needs a special case: rows are independent
# by construction, which is exactly the property the engine's exact-accept
# rule rides; kernel-vs-gather parity on the expanded shape is pinned in
# tests/test_paged_serving.py.
# ---------------------------------------------------------------------------

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


#: what :func:`paged_schedule` lets the kernel's VMEM estimate reach: the
#: compiler's default scoped limit (16 MiB on a v5e) less room for what
#: the estimate does not count
_PAGED_VMEM_BUDGET = 12 * 2**20
#: the most table entries a grid step takes (the body is unrolled once an
#: entry; GPT-2's tables hold 6, the widest the sweep read)
_PAGED_ENTRIES_MAX = 8


def _tile_rows(heads: int) -> int:
    """Rows of the query tile and its accumulator: the heads, padded to
    whole 16-row sublane tiles (bfloat16 packs 16 rows a tile)."""
    return -(-heads // 16) * 16


class PagedSchedule(NamedTuple):
    """How one ``paged_decode_attn`` call walks the block tables:
    ``entries`` consecutive table entries a grid step (a divisor of the
    table's width) on a grid ``(rows, width // entries)``, and the VMEM
    the estimate gives it."""
    entries: int
    vmem_bytes: int

    def describe(self, rows: int, width: int) -> dict:
        """What ``export.json`` and ``/stats`` say of a traced program."""
        return {"kernel": "paged_decode_attn",
                "entries_per_step": self.entries,
                "grid": [rows, width // self.entries],
                "vmem_bytes": self.vmem_bytes}


def _paged_vmem_bytes(entries: int, heads: int, head_dim: int,
                      block_size: int, pool_dtype) -> int:
    """Estimate of the kernel's VMEM at ``entries`` table entries a grid
    step: each entry's K and V block [Bs, H*D] double-buffered in the
    pool's dtype (an int8 pool's [1, Bs] scale rows pad to a sublane
    tile), one entry's float32 temporaries (the [H, Bs] scores,
    probabilities and select; an int8 block's float32 V copy), the
    [H, H*D] query tile, accumulator and its update, the statistics and
    the q / output rows."""
    hp, w = _tile_rows(heads), heads * head_dim
    item = jnp.dtype(pool_dtype).itemsize
    blocks = entries * 2 * 2 * block_size * w * item
    if item == 1:
        blocks += entries * 2 * 2 * 8 * block_size * 4
    tmps = 4 * (4 * hp * block_size + 3 * hp * w
                + (block_size * w if item == 1 else 0))
    return blocks + tmps + 4 * 2 * hp * 128 + 4 * 4 * w


def paged_tile_friendly(block_size: int, heads: int, head_dim: int,
                        pool_dtype=jnp.bfloat16) -> bool:
    """The pool shapes the paged kernel takes: whole [block_size, H*D]
    blocks of the pool as it lies, in whole tiles (block_size a multiple
    of 128: it is the score rows' lane dim; H*D a multiple of 128 lanes,
    whatever the head size: the query tile keeps the heads apart, not the
    blocks), one of which, double-buffered, fits the kernel's VMEM
    budget (:func:`paged_schedule`). The TPU compiler also takes blocks
    that are not whole tiles (it pads them); they were never read on a
    chip and take the XLA path."""
    return (block_size % 128 == 0 and (heads * head_dim) % 128 == 0
            and _paged_vmem_bytes(1, heads, head_dim, block_size,
                                  pool_dtype) <= _PAGED_VMEM_BUDGET)


def paged_schedule(rows: int, heads: int, head_dim: int, block_size: int,
                   table_width: int, pool_dtype=jnp.bfloat16
                   ) -> PagedSchedule:
    """The schedule of one :func:`paged_decode_attention` kernel call: a
    rule on what the call observes, set from the chip sweep on record
    (``experiments/flash_sweep.py paged``;
    ``benchmark/records/pr34/paged_sweep.jsonl``; DESIGN section 13.1).

    A grid step has its price whatever it computes (PR 25's finding in
    the flash kernels, read again here), so the kernel takes as few as
    the table allows: the most consecutive table entries a step, up to
    ``_PAGED_ENTRIES_MAX``, that divide the table's width and whose
    blocks fit ``_PAGED_VMEM_BUDGET``. ``rows`` does not enter (the
    grid's leading dimension takes any count: decode rows, or a verify
    program's K-fold expansion), nor the head size beyond the block's
    width. A shape the sweep did not read gets the same rule and at
    worst one entry a step, the least the kernel holds. A block so wide
    that even that passes the budget is a ValueError:
    :func:`paged_tile_friendly` says so first and ``impl="auto"`` takes
    the XLA path."""
    del rows
    for entries in range(min(table_width, _PAGED_ENTRIES_MAX), 0, -1):
        need = _paged_vmem_bytes(entries, heads, head_dim, block_size,
                                 pool_dtype)
        if table_width % entries == 0 and need <= _PAGED_VMEM_BUDGET:
            return PagedSchedule(entries, need)
    raise ValueError(
        f"paged decode_attention kernel: a [{block_size}, "
        f"{heads * head_dim}] block of each pool, double-buffered, "
        f"passes {_PAGED_VMEM_BUDGET} bytes of VMEM")


_SCHEDULE_LOG: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "paged_schedule_log", default=None)


@contextlib.contextmanager
def schedule_log():
    """While a program is traced under this, collect what each
    :func:`paged_decode_attention` call in it was traced with:
    ``PagedSchedule.describe`` for the kernel, ``{"kernel": "xla"}`` for
    the gather. A compiled program carries one schedule for good, so this
    is the evidence that the rule engaged (``serving.export_generator``
    keeps it in ``export.json``, as it keeps ``ops.moe.tile_log``'s)."""
    seen: list[dict] = []
    # a ContextVar's set, not a metric's: the exporter's own list, written
    # while it traces and never under a compiled call
    token = _SCHEDULE_LOG.set(seen)  # graftlint: disable=JIT01
    try:
        yield seen
    finally:
        _SCHEDULE_LOG.reset(token)


def _log_schedule(what: dict) -> None:
    seen = _SCHEDULE_LOG.get()
    if seen is not None and what not in seen:
        seen.append(what)


def _paged_kernel(ft_ref, pos_ref, pad_ref, q_ref, *rest, entries: int,
                  block_size: int, heads: int, head_dim: int,
                  sm_scale: float, quant: bool):
    """Grid (B, NB / entries): a step holds ``entries`` consecutive table
    entries of row b, each a whole [block_size, H*D] K and V block of the
    pool as it lies, every head at once. The heads are kept apart by the
    query tile: row h of the [H, H*D] tile holds head h's query in head
    h's lanes and zeros elsewhere, so ONE matmul against the block gives
    each head's own scores (the zeros contribute exact 0), and row h of
    the [H, H*D] context accumulator is meaningful in head h's lanes,
    which the last step keeps. An entry past ``pos`` computes nothing
    (and fetched nothing: see :func:`_fetch_table`). The softmax runs
    online over the live entries (m / l / acc scratch persists across
    the revisited output block); masked slots are zeroed explicitly so
    stale slots of a live block contribute exact 0 whatever their bytes.

    MXU operands stay in the dtype the query and the pool share
    (bfloat16 x bfloat16 products are exact in the float32 accumulator);
    scores, softmax, scales and accumulation are float32.

    ``quant=True`` (int8 pools): ``entries`` [1, 1, Bs] scale rows for K
    and for V follow the blocks. The dequant is fused ALGEBRAICALLY: K's
    per-row scale multiplies the score COLUMNS (q.(k.s)^T = (q.k^T).s,
    the int8 values exact in the query's dtype) and V's scale folds into
    the probabilities before the float32 context matmul
    (p.(v.s) = (p.s).v), so no dequantized [Bs, H*D] tile is ever
    materialized and no transpose of the scale row is needed."""
    n = entries
    k_refs, v_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    if quant:
        ks_refs, vs_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos, pad = pos_ref[b], pad_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    own = _own_lanes(_tile_rows(heads), head_dim,
                     heads * head_dim)                  # [Hp, W]
    operand = (q_ref.dtype if quant
               else jnp.promote_types(q_ref.dtype, k_refs[0].dtype))
    # (the select in float32: a mask is laid out 8 rows a tile, a
    # bfloat16 operand 16, and Mosaic refuses to re-lay the mask)
    q = jnp.where(own, q_ref[0].astype(jnp.float32),
                  0.0).astype(operand)                  # [Hp, W]

    for e in range(n):              # static: the step's table entries
        first = (j * n + e) * block_size

        @pl.when(first <= pos)
        def _compute(e=e, first=first):
            s = lax.dot_general(
                q, k_refs[e][0].astype(operand), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [Hp, Bs]
            if quant:
                s = s * ks_refs[e][0]                   # [1, Bs] scales
            kpos = first + lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1)
            live = (kpos <= pos) & (kpos >= pad)
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[...]                         # [Hp, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # explicit zeroing (not exp underflow): with the finite
            # NEG_INF fill an all-masked block would otherwise see
            # exp(NEG_INF - NEG_INF) = 1
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = (l_ref[...] * alpha
                          + jnp.sum(p, axis=-1, keepdims=True))
            if quant:
                pv = p * vs_refs[e][0]                  # fold V scales
                vblk = v_refs[e][0].astype(jnp.float32)
            else:
                vblk = v_refs[e][0]
                pv = p.astype(vblk.dtype)
            acc_ref[...] = (acc_ref[...] * alpha
                            + lax.dot_general(
                                pv, vblk, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
            m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # slot `pos` is always live, so l >= exp(0) > 0; row h of the
        # accumulator keeps head h's lanes (the padding rows own none)
        ctx = jnp.where(own, acc_ref[...] / l_ref[...], 0.0)
        o_ref[0] = jnp.sum(ctx, axis=0, keepdims=True).astype(o_ref.dtype)


def _fetch_table(block_tables, pos, entries: int, block_size: int):
    """The block each grid step's K/V operands name, [B, NB]: a live
    table entry (``entry * Bs <= pos``) names its own block; a dead one
    names whatever the SAME operand held a grid step earlier (operand e
    of step (b, j) holds entry ``j * entries + e``), so the pipeline
    sees an unchanged index and fetches nothing. Entries past ``pos``
    cost no traffic at all, whatever their table holds, and a row that
    is not alive (pos 0, every entry the null block) fetches one block.
    An operand no row has used yet names row 0's entry."""
    b, nb = block_tables.shape
    steps = b * (nb // entries)
    live = (jnp.arange(nb, dtype=jnp.int32) * block_size)[None] \
        <= pos[:, None]
    step = jnp.arange(steps, dtype=jnp.int32)[:, None]
    src = lax.cummax(jnp.where(live.reshape(steps, entries), step, 0),
                     axis=0)
    # table[src[s, e], e] as one select-and-sum fusion: a gather of so few
    # integers is several operations a layer on the chip (1.3 x this one's
    # time at 64 rows, 2.3 x at a verify program's 256: PR 34's sweep)
    pick = src[:, None, :] == step[None]
    return jnp.sum(jnp.where(pick, block_tables.reshape(steps, entries)[None],
                             0), axis=1).reshape(b, nb)


def _paged_dispatch(q, k_pool, v_pool, block_tables, pos, pad,
                    k_scale=None, v_scale=None, schedule=None):
    """One ``paged_decode_attn`` call under ``schedule`` (the rule's own,
    :func:`paged_schedule`, unless the sweep hands another): grid
    (B, NB / entries); per step ``entries`` whole [Bs, H*D] K and V
    blocks of the pool, [N, Bs, H*D] as it lies (no reshape, so no
    relayout of the pool a call), each named by the fetch table through
    its own scalar-prefetch index map. int8 pools additionally stream the
    matching [1, Bs] scale row per block ([N, 1, Bs] view so the
    singleton tile dim matches its array dim)."""
    n, bs, w = k_pool.shape
    _, h, d = q.shape
    b, nb = block_tables.shape
    quant = k_scale is not None
    sch = schedule or paged_schedule(b, h, d, bs, nb, k_pool.dtype)
    hp = _tile_rows(h)

    def table_map(e):
        return lambda bb, jj, ft, pos_s, pad_s: (
            ft[bb, jj * sch.entries + e], 0, 0)

    def q_map(bb, jj, ft, pos_s, pad_s):
        return (bb, 0, 0)

    blocks = [pl.BlockSpec((1, bs, w), table_map(e))
              for e in range(sch.entries)]
    in_specs = [pl.BlockSpec((1, 1, w), q_map)] + blocks + blocks
    operands = ([q.reshape(b, 1, w)] + [k_pool] * sch.entries
                + [v_pool] * sch.entries)
    if quant:
        rows = [pl.BlockSpec((1, 1, bs), table_map(e))
                for e in range(sch.entries)]
        in_specs += rows + rows
        operands += (
            [k_scale.reshape(n, 1, bs).astype(jnp.float32)] * sch.entries
            + [v_scale.reshape(n, 1, bs).astype(jnp.float32)] * sch.entries)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # fetch table, pos, pad
        grid=(b, nb // sch.entries),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, w), q_map),
        scratch_shapes=[
            pltpu.VMEM((hp, 1), jnp.float32),           # running max
            pltpu.VMEM((hp, 1), jnp.float32),           # running sum
            pltpu.VMEM((hp, w), jnp.float32),           # context acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, entries=sch.entries,
                          block_size=bs, heads=h, head_dim=d,
                          sm_scale=1.0 / math.sqrt(d), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, 1, w), q.dtype if quant else v_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_decode_attn",
        interpret=_interpret(),
    )(_fetch_table(block_tables, pos, sch.entries, bs), pos, pad,
      *operands)
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
    friendly = paged_tile_friendly(bs, h, d, k_pool.dtype)
    if impl == "pallas" and not friendly:
        raise ValueError(
            f"paged decode_attention kernel needs block_size % 128 == 0 "
            f"and heads x head dim a multiple of 128 whose [block_size, "
            f"H*D] block fits VMEM, got block_size={bs} H={h} D={d} (use "
            "impl='auto' for the XLA fallback)")
    use_kernel = friendly and (
        impl == "pallas"
        or (impl == "auto" and jax.default_backend() == "tpu"))
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    padb = jnp.broadcast_to(jnp.asarray(pad, jnp.int32).reshape(-1), (b,))
    if not use_kernel:
        _log_schedule({"kernel": "xla"})
        return xla_paged_decode_attention(q, k_pool, v_pool,
                                          block_tables=bt, pos=posb,
                                          pad=padb, k_scale=k_scale,
                                          v_scale=v_scale)
    sch = paged_schedule(b, h, d, bs, bt.shape[1], k_pool.dtype)
    _log_schedule(sch.describe(b, bt.shape[1]))
    return _paged_dispatch(q, k_pool, v_pool, bt, posb, padb,
                           k_scale=k_scale, v_scale=v_scale, schedule=sch)


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
