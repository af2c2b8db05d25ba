"""Blocked flash attention (forward + backward) as Pallas TPU kernels.

Memory-efficient attention: never materializes the [S, S] score matrix.
VMEM use is O(block), independent of S: K/V blocks are *streamed through
the grid* (the innermost, sequential grid dimension walks K blocks while
the online-softmax state — running max ``m``, normalizer ``l``, output
accumulator — lives in VMEM scratch that persists across grid steps). The
backward recomputes probabilities blockwise from the saved per-row
logsumexp ``L``.

Backward variants (``bwd_variant``):

- ``"split"`` (the round-2 kernel): the standard flash-attention-2
  decomposition — a dq kernel streaming K blocks and a dk/dv kernel
  streaming Q/dO blocks, both operand streams O(block). Each kernel
  recomputes the score block ``s = qk^T`` and the ``dp = do v^T`` block,
  so the pair does 7 block matmuls per (q, k) block pair and streams
  every operand twice.
- ``"fused"`` (what the schedule picks wherever it fits): ONE kernel
  (grid walks k blocks outer, q blocks inner) computes dk, dv AND dq in
  a single pass — s/p/dp/ds are computed once and feed all three
  gradients (5 block matmuls per pair, ~29% fewer bwd matmul FLOPs, and
  K/V are not re-streamed by a second kernel). The dq accumulator is a
  full [S, head_dim] f32 VMEM slab (contributions for a q block arrive
  once per OUTER k step, so no O(block) scratch can hold them) and the
  dq output block is the whole [S, head_dim] too; the variant therefore
  engages only while the two fit VMEM (``_fused_dq_bytes`` against
  ``_FUSED_SLAB_LIMIT``: S <= 16384 in bf16, S <= 8192 in f32) and runs
  ``"split"`` beyond, forced or chosen.

The tile schedule (PR 25). Tiles are chosen, not constant: the defaults
come from ``flash_schedule(seq, head_dim, dtype)``, a rule set
from a chip sweep (``experiments/flash_sweep.py kernels``), and the
levers override it — ``block_q``/``block_k`` the forward tiles,
``bwd_block`` (one value for both streamed dims) the backward tiles,
``bwd_variant`` the backward kernel; ``config.TrainConfig`` exposes all
of them next to ``attention_impl``, where 0 / ``auto`` means "the
schedule's". What the sweep taught about v5e: at 128x128 a kernel is
its grid steps (0.45 us each, 12,288 of them a call), and beyond that
its per-row work — the [blk_q, 1] running statistics occupy one lane of
a vreg and the row reductions cross lanes — which is paid once per live
step, so wide K tiles win even where causal masking wastes half of
them. Three more things belong to the schedule:

- MXU operands stay in the input's dtype (bf16 in training): q, k, v, do
  are never up-cast, p and ds are cast to the operand dtype for their
  matmuls as ``ops/attention.py`` does with ``probs.astype(v.dtype)``,
  and every dot accumulates in f32. Scores after scaling, the running
  max, exp, the normaliser, ``lse``, ``Dsum`` and all accumulators are
  f32. With float32 inputs nothing is cast.
- Dead causal steps fetch nothing: a step strictly above the diagonal
  names, in its index maps, the block that is already resident (the
  row's last live K block; the first live Q block in the kernels that
  stream Q), so Pallas issues no DMA for it (``_k_stream``,
  ``_q_stream``).
- The fully-masked-row guard ``p * (s > NEG_INF / 2)`` runs only under a
  key mask: causal rows all see key 0 in the first block they visit, so
  ``exp(NEG_INF - m)`` is already an exact 0 for them.

Layout: inputs [B, S, H, D] (the framework's BSHD convention) are folded to
[B*H, S, D] so the grid is (batch·head, q/k block, k/q block) and every
program's matmuls are [block, D] x [D, block] MXU tiles.

Scope/fallbacks: the kernel path requires MXU/Mosaic-friendly tiles —
S divisible by both block sizes, a lane-aligned K block (multiple of 128),
sublane-aligned Q block (multiple of 8) and D in {64, 128·k}. Anything else
(short sequences, odd head dims) falls back to the XLA path, which is the
right tool there anyway. On non-TPU backends kernels run in Pallas
interpret mode (tests on the virtual CPU mesh exercise the same code path).

Shares mask semantics with ops/attention.py (NEG_INF, 1 = attend); fully
masked query rows yield zeros (matching ``multi_head_attention``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import NEG_INF

#: the smallest tile the kernels take (one lane width), and what a
#: sequence no larger candidate divides falls back to
DEFAULT_BLOCK = 128

#: fused-bwd dq budget: what ``_fused_dq_bytes`` may reach. The slab and
#: the dq output block share VMEM with the streamed tiles; past this the
#: fused variant silently degrades to "split" (same math, same gradients
#: — an availability boundary like ``_tile_friendly``, not an error)
_FUSED_SLAB_LIMIT = 16 * 2**20

BWD_VARIANTS = ("split", "fused")

#: block matmuls per (q, k) block pair, by phase: the forward does qk^T
#: and pv; the split backward recomputes s and dp in BOTH of its kernels
#: (dq: s, dp, dq; dkv: s, dv, dp, dk); the fused backward computes each
#: once. Basis for ``attention_train_flops``.
_FWD_MATMULS = 2
_BWD_MATMULS = {"split": 7, "fused": 5}

#: f32 [blk_q, blk_k] temporaries live at once in a kernel body, as the
#: VMEM estimate counts them: forward s, p, the causal select and p in
#: the operand dtype; backward also dp, ds and their casts
_FWD_TMPS = 4
_BWD_TMPS = 7
#: the compiler's default scoped-VMEM limit, and what a kernel may ask
#: for (v5e: 128 MiB physical)
_VMEM_DEFAULT = 16 * 2**20
_VMEM_MAX = 96 * 2**20
#: what ``flash_schedule`` lets a kernel's estimate reach
_VMEM_BUDGET = 32 * 2**20
#: the largest tile ``flash_schedule`` picks, forward and backward. From
#: the chip sweep (PR 25, v5e, S=1024..4096, D=64, bf16 and f32): a
#: kernel's time is mostly its per-ROW work ([blk_q, 1] statistics one
#: lane wide, the row reductions) paid once per live grid step, so the
#: forward wants the widest K tile there is even where causal skips
#: nothing of it (1024x1024 0.65 ms, 512x512 1.15 ms a layer at the
#: gpt2s-train shape); the backward has five matmuls a block pair and no
#: running max, and the causal skip wins there (512x512 1.34 ms,
#: 1024x1024 1.55 ms)
_FWD_TILE_MAX = 1024
_BWD_TILE_MAX = 512


def _vmem_bytes(blk_q: int, blk_k: int, d: int, tmps: int) -> int:
    """Estimate of a kernel's VMEM: ``tmps`` f32 [blk_q, blk_k]
    temporaries, plus the operand, output and scratch blocks — at most
    five Q-side and three K-side [blk, D] blocks, double-buffered,
    counted at 4 bytes and a full 128-lane width (D = 64 and the
    [blk_q, 1] statistics pad to it). The fused backward holds
    ``_fused_dq_bytes`` more."""
    lanes = max(d, 128)
    return 4 * (tmps * blk_q * blk_k + 2 * lanes * (5 * blk_q + 3 * blk_k))


def _fused_dq_bytes(seq: int, d: int, itemsize: int) -> int:
    """What the fused backward holds in VMEM for dq besides its tiles:
    the [S, D] f32 slab and the double-buffered (1, S, D) output block
    in the input's dtype, both at a full 128-lane width."""
    return seq * max(d, 128) * (4 + 2 * itemsize)


class FlashSchedule(NamedTuple):
    """Tiles and backward variant of one ``flash_attention`` call."""
    blk_q: int
    blk_k: int
    bwd_q: int
    bwd_k: int
    bwd_variant: str

    def grid_steps(self, seq: int) -> tuple[int, int]:
        """Grid steps per batch-head of (the forward call, the backward
        call or calls together)."""
        bwd = (seq // self.bwd_q) * (seq // self.bwd_k)
        return ((seq // self.blk_q) * (seq // self.blk_k),
                bwd * (1 if self.bwd_variant == "fused" else 2))

    def tileable(self, seq: int, head_dim: int) -> bool:
        """Whether the kernels take these tiles (``_tile_friendly``,
        forward and backward); the call falls back to XLA otherwise."""
        return (_tile_friendly(seq, head_dim, self.blk_q, self.blk_k)
                and _tile_friendly(seq, head_dim, self.bwd_q, self.bwd_k))


def _largest_tile(seq: int, head_dim: int, tmps: int, cap: int) -> int:
    """The largest square tile <= ``cap`` that divides ``seq`` and whose
    kernel fits ``_VMEM_BUDGET``; ``DEFAULT_BLOCK`` (clamped to ``seq``)
    where none does — ``_tile_friendly`` then decides the fallback."""
    tile = cap
    while tile > DEFAULT_BLOCK:
        if (seq % tile == 0
                and _vmem_bytes(tile, tile, head_dim, tmps) <= _VMEM_BUDGET):
            return tile
        tile //= 2
    return min(DEFAULT_BLOCK, seq)


def flash_schedule(seq: int, head_dim: int,
                   dtype=jnp.bfloat16) -> FlashSchedule:
    """The tiles and backward variant the kernels run at when no lever
    says otherwise: a rule on what the kernel observes, set from the
    chip sweep on record (``experiments/flash_sweep.py kernels``;
    PERF.md section 6, PR 25). Square tiles, the largest that divide S
    and fit VMEM up to ``_FWD_TILE_MAX`` / ``_BWD_TILE_MAX``, and the
    fused backward wherever its dq slab and output block fit: it won at
    every shape swept (S <= 4096; by 25-40 % of the split pair's time).
    ``dtype`` counts through its itemsize (the dq block is in it); the
    operands' dtype and causal masking did not move the winner in the
    sweep (float32 operands cost 5-25 % at equal tiles)."""
    fwd = _largest_tile(seq, head_dim, _FWD_TMPS, _FWD_TILE_MAX)
    bwd = _largest_tile(seq, head_dim, _BWD_TMPS, _BWD_TILE_MAX)
    return FlashSchedule(fwd, fwd, bwd, bwd, effective_bwd_variant(
        seq, head_dim, "fused", dtype))


def effective_bwd_variant(seq: int, head_dim: int,
                          bwd_variant: str | None = None,
                          dtype=jnp.bfloat16) -> str:
    """The backward variant that actually EXECUTES for these shapes:
    ``None`` is the schedule's own choice, and "fused" degrades to
    "split" when its dq slab and output block would not fit VMEM
    (``_fused_dq_bytes`` past ``_FUSED_SLAB_LIMIT``).
    Shared with the MFU accounting — counting 5 fused matmuls while the
    7-matmul split runs would understate analytic FLOPs by ~22% exactly
    where long-S comparability matters.
    """
    if bwd_variant is None:
        return flash_schedule(seq, head_dim, dtype).bwd_variant
    if bwd_variant == "fused" and _fused_dq_bytes(
            seq, head_dim, jnp.dtype(dtype).itemsize) > _FUSED_SLAB_LIMIT:
        return "split"
    return bwd_variant


def attention_train_flops(batch: int, seq: int, hidden: int, layers: int,
                          *, causal: bool = False,
                          bwd_variant: str = "split") -> float:
    """Closed-form fwd+bwd FLOPs of the flash kernels for one train step.

    XLA cost analysis cannot see inside a Pallas custom call, so gate MFU
    for flash configs must add this analytically (VERDICT r5 weak #1).
    Each block matmul contracts [S, D] x [D, S] per head per batch element
    — 2·B·S²·hidden FLOPs summed over heads — and the kernel structure
    fixes the matmul count per phase (``_FWD_MATMULS``/``_BWD_MATMULS``).
    Causal grids skip blocks strictly above the diagonal: the live
    fraction is (nk+1)/(2·nk) ≈ 0.5, counted as exactly 0.5 (the +1/2nk
    diagonal sliver is below measurement noise at the gate shapes).
    """
    if bwd_variant not in _BWD_MATMULS:
        raise ValueError(f"bwd_variant must be one of {BWD_VARIANTS}, "
                         f"got {bwd_variant!r}")
    units = _FWD_MATMULS + _BWD_MATMULS[bwd_variant]
    total = units * 2.0 * batch * float(seq) ** 2 * hidden * layers
    return total * (0.5 if causal else 1.0)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block_mask(s, mask_row, causal: bool, q_start, k_start,
                blk_q: int, blk_k: int, causal_block: int = 1):
    """Apply key-validity row mask and/or causal mask to a score block.
    ``causal_block`` B > 1 (a power of two): block-causal, a query sees
    the keys up to the end of its own block of B positions."""
    if mask_row is not None:
        s = jnp.where(mask_row != 0, s, NEG_INF)
    if causal:
        qpos = lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0) + q_start
        kpos = lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1) + k_start
        if causal_block > 1:
            qpos = qpos | (causal_block - 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return s


def _live(qi, ki, blk_q: int, blk_k: int, causal: bool):
    """causal: blocks strictly above the diagonal contribute nothing."""
    return ((qi + 1) * blk_q - 1 >= ki * blk_k) if causal else True


def _k_stream(causal: bool, blk_q: int, blk_k: int):
    """Which K-side block step ``j`` of q block ``i`` names (forward,
    dq). A dead causal step names the row's LAST LIVE block: that one is
    already resident, so Pallas issues no DMA for a step that reads
    nothing."""
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, ((i + 1) * blk_q - 1) // blk_k)


def _own(i, j):
    """The block a kernel holds across its inner walk: grid index i."""
    return i


def _q_stream(causal: bool, blk_q: int, blk_k: int):
    """The same for the kernels that stream Q-side blocks past a k block
    ``i`` (dkv, fused): dead steps come first there, and name the FIRST
    LIVE q block, fetched once and still resident when its step comes."""
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.maximum(j, (i * blk_k) // blk_q)


def _scores(q_ref, k_ref, mask_ref, *, causal: bool, q_start, k_start,
            sm_scale: float, causal_block: int = 1):
    """The scaled, masked f32 score block [blk_q, blk_k]. MXU operands
    stay in the input's dtype; the accumulator is f32."""
    s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    mrow = mask_ref[0] if mask_ref is not None else None  # [1, blk_k]
    return _block_mask(s, mrow, causal, q_start, k_start, *s.shape,
                       causal_block=causal_block)


def _maskless(kernel, n_inputs: int):
    """``kernel`` for a call without the mask operand: its refs arrive
    without one, the kernel gets ``None`` in its place."""
    def wrapped(*refs, **kw):
        return kernel(*refs[:n_inputs], None, *refs[n_inputs:], **kw)
    return wrapped


def _compiler_params(semantics: tuple, blk_q: int, blk_k: int, d: int,
                     tmps: int, more: int = 0):
    """Grid semantics, and a raised scoped-VMEM limit where the tiles'
    f32 [blk_q, blk_k] temporaries (``tmps`` of them live at once), the
    double-buffered operand blocks and ``more`` bytes (the fused
    backward's dq slab and block) outgrow the compiler's default."""
    need = _vmem_bytes(blk_q, blk_k, d, tmps) + more
    limit = min(2 * need, _VMEM_MAX) if need > _VMEM_DEFAULT // 2 else None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


# ---------------------------------------------------------------------------
# forward kernel: grid (BH, nq, nk) — nk innermost, sequential, carries the
# online-softmax state in scratch
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                blk_q: int, blk_k: int, nk: int, causal: bool,
                sm_scale: float, causal_block: int = 1):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_live(qi, ki, blk_q, blk_k, causal))
    def _compute():
        s = _scores(q_ref, k_ref, mask_ref, causal=causal,
                    q_start=qi * blk_q, k_start=ki * blk_k,
                    sm_scale=sm_scale, causal_block=causal_block)
        v = v_ref[0]
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask_ref is not None:
            # a row with no valid key yet has m_new = NEG_INF and would
            # read exp(0) = 1; only a key mask can make one (causal rows
            # all see key 0, in the block every row visits first)
            p = p * (s > NEG_INF / 2)
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
        # logsumexp per row, saved for the backward recompute; kept
        # [blk_q, 1] (a trailing singleton dim matches the array dim, which
        # Mosaic tiles without sublane constraints)
        lse_ref[0] = m + jnp.log(jnp.maximum(l, 1e-20))


def _fwd(q3, k3, v3, mask2, *, heads: int, blk_q: int, blk_k: int,
         causal: bool, causal_block: int = 1):
    """q3,k3,v3: [BH, S, D]; mask2: [B, S] or None. Returns (o, L).
    ``causal_block`` > 1 divides both tiles, so which tiles are live is
    what plain causal has: only the mask inside a tile differs."""
    bh, s, d = q3.shape
    sm_scale = 1.0 / math.sqrt(d)
    nq, nk = s // blk_q, s // blk_k
    grid = (bh, nq, nk)
    kblk = _k_stream(causal, blk_q, blk_k)

    in_specs = [pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, blk_k, d),
                             lambda b, i, j: (b, kblk(i, j), 0)),
                pl.BlockSpec((1, blk_k, d),
                             lambda b, i, j: (b, kblk(i, j), 0))]
    args = [q3, k3, v3]
    kw = dict(blk_q=blk_q, blk_k=blk_k, nk=nk, causal=causal,
              sm_scale=sm_scale)
    if causal_block > 1:        # the default adds no keyword: same trace
        kw["causal_block"] = causal_block
    if mask2 is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, blk_k), lambda b, i, j: (b // heads, 0, kblk(i, j))))
        args.append(mask2[:, None, :])
        kernel = functools.partial(_fwd_kernel, **kw)
    else:
        kernel = functools.partial(_maskless(_fwd_kernel, 3), **kw)

    o, L = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), blk_q, blk_k, d,
            _FWD_TMPS),
        name="flash_fwd",
        interpret=_interpret(),
    )(*args)
    return o, L


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_block(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, mask_ref, *,
               causal: bool, q_start, k_start, sm_scale: float):
    """What every backward kernel recomputes for one (q, k) block pair:
    the probabilities ``p`` and the score gradient ``ds`` (f32,
    [blk_q, blk_k])."""
    s = _scores(q_ref, k_ref, mask_ref, causal=causal, q_start=q_start,
                k_start=k_start, sm_scale=sm_scale)
    p = jnp.exp(s - L_ref[0])                             # L: [blk_q, 1]
    if mask_ref is not None:
        p = p * (s > NEG_INF / 2)       # key-masked rows: L is the floor
    dp = lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return p, p * (dp - D_ref[0]) * sm_scale


def _tdot(a, b):
    """a.T @ b with ``a`` cast to ``b``'s dtype, f32 accumulation."""
    return lax.dot_general(a.astype(b.dtype), b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, mask_ref,
                   dq_ref, dq_scr, *, blk_q: int, blk_k: int, nk: int,
                   causal: bool, sm_scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_live(qi, ki, blk_q, blk_k, causal))
    def _compute():
        _, ds = _bwd_block(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref,
                           mask_ref, causal=causal, q_start=qi * blk_q,
                           k_start=ki * blk_k, sm_scale=sm_scale)
        k = k_ref[0]
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, mask_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, blk_q: int,
                    blk_k: int, nq: int, causal: bool, sm_scale: float):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_live(qi, ki, blk_q, blk_k, causal))
    def _compute():
        p, ds = _bwd_block(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref,
                           mask_ref, causal=causal, q_start=qi * blk_q,
                           k_start=ki * blk_k, sm_scale=sm_scale)
        dv_scr[...] += _tdot(p, do_ref[0])                # p.T @ do
        dk_scr[...] += _tdot(ds, q_ref[0])                # ds.T @ q

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, mask_ref,
                      dq_ref, dk_ref, dv_ref, dq_slab, dk_scr, dv_scr, *,
                      blk_q: int, blk_k: int, nq: int, nk: int,
                      causal: bool, sm_scale: float):
    """One-pass backward: grid (BH, nk, nq), BOTH block dims sequential.

    For each (k block, q block) pair the score/probability/ds blocks are
    computed ONCE and feed dk, dv (O(block) scratch over the inner q
    walk, as in the split dkv kernel) and dq (accumulated into the full
    [S, D] f32 ``dq_slab`` — a q block's contributions arrive once per
    OUTER k step, ascending, which matches the split dq kernel's
    accumulation order exactly, so the two variants agree bit-for-bit).
    The dq output block is the whole [S, D] slab with a constant index
    map: Pallas copies it out once per batch-head, not per grid step.
    """
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    q_start = qi * blk_q

    @pl.when(ki == 0)
    def _init_dq():
        dq_slab[pl.dslice(q_start, blk_q), :] = jnp.zeros(
            (blk_q, dq_slab.shape[1]), jnp.float32)

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_live(qi, ki, blk_q, blk_k, causal))
    def _compute():
        p, ds = _bwd_block(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref,
                           mask_ref, causal=causal, q_start=q_start,
                           k_start=ki * blk_k, sm_scale=sm_scale)
        k = k_ref[0]
        dv_scr[...] += _tdot(p, do_ref[0])                # p.T @ do
        dk_scr[...] += _tdot(ds, q_ref[0])                # ds.T @ q
        dq_slab[pl.dslice(q_start, blk_q), :] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize_kv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finalize_dq():
        dq_ref[0, pl.dslice(q_start, blk_q), :] = dq_slab[
            pl.dslice(q_start, blk_q), :].astype(dq_ref.dtype)


def _bwd_specs(q3, k3, v3, do3, L, Dsum, mask2, *, heads: int, blk_q: int,
               blk_k: int, q_index, k_index):
    """in_specs and operands shared by the three backward kernels;
    ``q_index(i, j)`` / ``k_index(i, j)`` give the q-side and k-side
    block a grid step (b, i, j) names."""
    d = q3.shape[-1]
    qspec = pl.BlockSpec((1, blk_q, d),
                         lambda b, i, j: (b, q_index(i, j), 0))
    kspec = pl.BlockSpec((1, blk_k, d),
                         lambda b, i, j: (b, k_index(i, j), 0))
    rowspec = pl.BlockSpec((1, blk_q, 1),
                           lambda b, i, j: (b, q_index(i, j), 0))
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    args = [q3, k3, v3, do3, L, Dsum]
    if mask2 is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, blk_k), lambda b, i, j: (b // heads, 0, k_index(i, j))))
        args.append(mask2[:, None, :])
    return in_specs, args


def _bwd(q3, k3, v3, o3, do3, L, mask2, *, heads: int, blk_q: int,
         blk_k: int, causal: bool, variant: str = "split"):
    bh, s, d = q3.shape
    sm_scale = 1.0 / math.sqrt(d)
    nq, nk = s // blk_q, s // blk_k
    Dsum = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                   axis=-1, keepdims=True)                # [BH, S, 1]
    operands = (q3, k3, v3, do3, L, Dsum, mask2)
    kw = dict(blk_q=blk_q, blk_k=blk_k, causal=causal, sm_scale=sm_scale)
    qstream = _q_stream(causal, blk_q, blk_k)
    kv_out = pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, i, 0))
    kv_shape = [jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                jax.ShapeDtypeStruct(v3.shape, v3.dtype)]
    kv_scratch = [pltpu.VMEM((blk_k, d), jnp.float32),
                  pltpu.VMEM((blk_k, d), jnp.float32)]

    def bind(kernel, **more):
        kernel = kernel if mask2 is not None else _maskless(kernel, 6)
        return functools.partial(kernel, **kw, **more)

    if variant == "fused":
        # grid (BH, nk, nq): Q/dO/L/D streamed innermost, both block
        # dims sequential (the dq slab lives across the outer k walk)
        in_specs, args = _bwd_specs(*operands, heads=heads, blk_q=blk_q,
                                    blk_k=blk_k, q_index=qstream,
                                    k_index=_own)
        return pl.pallas_call(
            bind(_bwd_fused_kernel, nq=nq, nk=nk), grid=(bh, nk, nq),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, s, d), lambda b, i, j: (b, 0, 0)),
                       kv_out, kv_out],
            out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype)] + kv_shape,
            scratch_shapes=[pltpu.VMEM((s, d), jnp.float32)] + kv_scratch,
            compiler_params=_compiler_params(
                ("parallel", "arbitrary", "arbitrary"), blk_q, blk_k, d,
                _BWD_TMPS, _fused_dq_bytes(s, d, q3.dtype.itemsize)),
            name="flash_bwd_fused",
            interpret=_interpret(),
        )(*args)

    # dq: grid (BH, nq, nk) — K/V streamed innermost
    in_specs, args = _bwd_specs(*operands, heads=heads, blk_q=blk_q,
                                blk_k=blk_k, q_index=_own,
                                k_index=_k_stream(causal, blk_q, blk_k))
    dq = pl.pallas_call(
        bind(_bwd_dq_kernel, nk=nk), grid=(bh, nq, nk), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), blk_q, blk_k, d,
            _BWD_TMPS),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(*args)

    # dk/dv: grid (BH, nk, nq) — Q/dO/L/D streamed innermost
    in_specs, args = _bwd_specs(*operands, heads=heads, blk_q=blk_q,
                                blk_k=blk_k, q_index=qstream, k_index=_own)
    dk, dv = pl.pallas_call(
        bind(_bwd_dkv_kernel, nq=nq), grid=(bh, nk, nq), in_specs=in_specs,
        out_specs=[kv_out, kv_out], out_shape=kv_shape,
        scratch_shapes=kv_scratch,
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), blk_q, blk_k, d,
            _BWD_TMPS),
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrappers
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_flash(heads: int, blk_q: int, blk_k: int, bwd_q: int, bwd_k: int,
                bwd_variant: str, causal: bool, has_mask: bool):
    fwd_kw = dict(heads=heads, blk_q=blk_q, blk_k=blk_k, causal=causal)
    bwd_kw = dict(heads=heads, blk_q=bwd_q, blk_k=bwd_k, causal=causal,
                  variant=bwd_variant)

    @jax.custom_vjp
    def fn(q3, k3, v3, mask2):
        o, _ = _fwd(q3, k3, v3, mask2 if has_mask else None, **fwd_kw)
        return o

    def fwd(q3, k3, v3, mask2):
        o, L = _fwd(q3, k3, v3, mask2 if has_mask else None, **fwd_kw)
        return o, (q3, k3, v3, o, L, mask2)

    def bwd(res, do3):
        q3, k3, v3, o3, L, mask2 = res
        dq, dk, dv = _bwd(q3, k3, v3, o3, do3, L,
                          mask2 if has_mask else None, **bwd_kw)
        dmask = jnp.zeros_like(mask2) if mask2 is not None else None
        return dq, dk, dv, dmask

    fn.defvjp(fwd, bwd)
    return fn


def _tile_friendly(s: int, d: int, blk_q: int, blk_k: int) -> bool:
    """Mosaic tiling constraints for the kernel path: lane-dim K blocks
    must be 128-multiples, sublane-dim Q blocks 8-multiples, and the head
    dim MXU-aligned. Short/odd shapes fall back to XLA (which also dodges
    interpret-mode-passes-but-Mosaic-fails drift on real TPU)."""
    return (s % blk_q == 0 and s % blk_k == 0
            and blk_q % 8 == 0 and blk_k % 128 == 0
            and (d == 64 or d % 128 == 0))


def resolve_schedule(seq: int, head_dim: int, dtype=jnp.bfloat16, *,
                     block_q: int | None = None,
                     block_k: int | None = None, bwd_block: int = 0,
                     bwd_variant: str | None = None) -> FlashSchedule:
    """``flash_schedule`` with the levers laid over it: a lever that is
    set wins (clamped to the sequence length), one left unset (``None``;
    0 for ``bwd_block``) takes the schedule's. A set forward tile also
    tiles the backward unless ``bwd_block`` says otherwise, as it always
    has. The variant is the one that executes
    (``effective_bwd_variant``)."""
    auto = flash_schedule(seq, head_dim, dtype)
    blk_q = min(block_q, seq) if block_q else auto.blk_q
    blk_k = min(block_k, seq) if block_k else auto.blk_k
    if bwd_block:
        bwd_q = bwd_k = min(bwd_block, seq)
    elif block_q or block_k:
        bwd_q, bwd_k = blk_q, blk_k
    else:
        bwd_q, bwd_k = auto.bwd_q, auto.bwd_k
    return FlashSchedule(blk_q, blk_k, bwd_q, bwd_k, effective_bwd_variant(
        seq, head_dim, bwd_variant or auto.bwd_variant, dtype))


def kernel_engages(seq: int, head_dim: int, *,
                   block_q: int | None = None,
                   block_k: int | None = None,
                   bwd_block: int = 0) -> bool:
    """True iff these shapes/blocks take the Pallas kernel path (vs the
    XLA fallback). Shared with bench.py's MFU accounting: analytic
    attention FLOPs must be added exactly when the custom call (which
    XLA cost analysis cannot see into) actually runs. The dtype moves
    the backward variant, never a tile, so it does not count here."""
    return resolve_schedule(seq, head_dim, block_q=block_q, block_k=block_k,
                            bwd_block=bwd_block).tileable(seq, head_dim)


def describe_attention(seq: int, head_dim: int, dtype=jnp.bfloat16,
                       **levers) -> str:
    """What a flash call of this shape runs, for a start-up log line and
    the sweep's rows: the tiles and variant chosen (or set by
    ``levers``) and the grid steps they make, or the XLA fallback."""
    sch = resolve_schedule(seq, head_dim, dtype, **levers)
    if not sch.tileable(seq, head_dim):
        return (f"flash attention falls back to XLA at S={seq} "
                f"D={head_dim} (no tile the kernels take)")
    fwd, bwd = sch.grid_steps(seq)
    return (f"flash kernels at S={seq} D={head_dim} "
            f"{jnp.dtype(dtype).name}: "
            f"fwd {sch.blk_q}x{sch.blk_k}, bwd {sch.bwd_variant} "
            f"{sch.bwd_q}x{sch.bwd_k}; grid steps per batch-head "
            f"fwd {fwd}, bwd {bwd}")


def _partitioned(amesh, q, k, v, mask, **kw):
    """The kernel under a mesh that jit partitions automatically.

    The TPU compiler refuses to partition a Mosaic kernel by itself
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"). Attention is independent per (batch row,
    head), so the call is wrapped in ``shard_map`` over the ambient
    mesh — batch dim over the framework's batch axes, head dim over the
    tensor-parallel axis where it divides the head count — and each
    shard runs the whole kernel on its slice; no collective. The mesh is
    the trace context's (``jax.sharding.use_abstract_mesh``, entered by
    whoever jits a model over a multi-device mesh: SyncReplicas' step,
    the Trainer's eval pass). No ambient mesh — a single-device trace
    such as an export — or one already manual (``sync_mode="shard_map"``,
    pipeline stages) calls the kernel bare."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import AxisNames
    heads = (AxisNames.MODEL
             if q.shape[2] % amesh.shape[AxisNames.MODEL] == 0 else None)
    qkv = P(AxisNames.BATCH, None, heads, None)
    masks = () if mask is None else (mask,)
    return jax.shard_map(
        lambda q_, k_, v_, *m: flash_attention(
            q_, k_, v_, mask=m[0] if m else None, **kw),
        in_specs=(qkv, qkv, qkv) + (P(AxisNames.BATCH, None),) * len(masks),
        out_specs=qkv, check_vma=False)(q, k, v, *masks)


def _block_causal(q, k, v, mask, sch: FlashSchedule, causal_block: int):
    """The forward under the block-causal mask: ``flash_fwd`` where the
    shape tiles (and the tiles hold whole blocks), plain XLA attention
    under the same mask elsewhere. No gradient: a serving prefill."""
    b, s, h, d = q.shape
    if mask is not None and mask.ndim == 4:
        mask = mask[:, 0, 0, :]
    if (sch.tileable(s, d) and sch.blk_q % causal_block == 0
            and sch.blk_k % causal_block == 0):
        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

        o3, _ = _fwd(fold(q), fold(k), fold(v),
                     None if mask is None else mask.astype(jnp.int32),
                     heads=h, blk_q=sch.blk_q, blk_k=sch.blk_k,
                     causal=True, causal_block=causal_block)
        return o3.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return xla_block_causal_attention(q, k, v, causal_block, mask=mask)


def xla_block_causal_attention(q, k, v, causal_block: int, *, mask=None):
    """Plain attention under the block-causal mask ([B,S,H,D] in/out,
    ``mask`` [B,S] key validity): the kernel path's fallback, and what a
    portable export pins."""
    s, d = q.shape[1], q.shape[3]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    pos = jnp.arange(s)
    allowed = ((pos[:, None] | (causal_block - 1)) >= pos[None, :])[None,
                                                                    None]
    if mask is not None:
        allowed = allowed & (mask != 0)[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(allowed, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    mask: jax.Array | None = None, causal: bool = False,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    bwd_block: int = 0,
                    bwd_variant: str | None = None,
                    causal_block: int = 1) -> jax.Array:
    """Drop-in for ``multi_head_attention(impl="xla")``: [B,S,H,D] in/out.

    ``causal_block`` B > 1 (a power of two, with ``causal``) is the
    block-causal mask of a block-diffusion decoder's prefill: query i
    sees key j iff ``j // B <= i // B``. Forward only (it serves); 1, the
    default, is the causal mask and traces what it always has.

    ``mask``: [B,S] key-validity (1 = attend) or broadcastable [B,1,1,S].
    The levers override ``flash_schedule``'s choice for this shape and
    dtype: ``block_q``/``block_k`` tile the forward grid (and the
    backward's, unless ``bwd_block`` is set); ``bwd_block`` tiles BOTH
    streamed dims of the backward; ``bwd_variant`` picks the split
    (two-kernel) or fused (one-kernel) backward — see the module
    docstring. Left at ``None`` (0 for ``bwd_block``) each is the
    schedule's. Falls back to the XLA path for tile-unfriendly shapes
    (see ``_tile_friendly``); nonsensical lever values (non-positive
    blocks, unknown variant) raise instead of silently falling back.
    """
    if ((block_q is not None and block_q <= 0)
            or (block_k is not None and block_k <= 0) or bwd_block < 0):
        raise ValueError(
            f"block_q/block_k must be positive and bwd_block >= 0, got "
            f"block_q={block_q} block_k={block_k} bwd_block={bwd_block}")
    if bwd_variant is not None and bwd_variant not in BWD_VARIANTS:
        raise ValueError(f"bwd_variant must be one of {BWD_VARIANTS}, "
                         f"got {bwd_variant!r}")
    if causal_block < 1 or causal_block & (causal_block - 1) or (
            causal_block > 1 and not causal):
        raise ValueError(f"causal_block must be a power of two and needs "
                         f"causal=True, got {causal_block} (causal="
                         f"{causal})")
    b, s, h, d = q.shape
    sch = resolve_schedule(s, d, q.dtype, block_q=block_q,
                           block_k=block_k, bwd_block=bwd_block,
                           bwd_variant=bwd_variant)
    if causal_block > 1:
        return _block_causal(q, k, v, mask, sch, causal_block)
    if not sch.tileable(s, d):
        from ..attention import multi_head_attention
        m4 = None
        if mask is not None:
            m4 = mask if mask.ndim == 4 else mask[:, None, None, :]
        return multi_head_attention(q, k, v, mask=m4, causal=causal,
                                    impl="xla")
    if mask is not None and mask.ndim == 4:
        mask = mask[:, 0, 0, :]
    amesh = jax.sharding.get_abstract_mesh()
    if any(amesh.shape[a] > 1 for a in amesh.axis_names
           if a not in amesh.manual_axes):
        return _partitioned(amesh, q, k, v, mask, causal=causal,
                            block_q=block_q, block_k=block_k,
                            bwd_block=bwd_block, bwd_variant=bwd_variant)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    fn = _make_flash(h, *sch, causal, mask is not None)
    mask2 = (mask.astype(jnp.int32) if mask is not None
             else jnp.ones((b, s), jnp.int32))
    o3 = fn(fold(q), fold(k), fold(v), mask2)
    return o3.reshape(b, h, s, d).transpose(0, 2, 1, 3)
