"""Mixture-of-Experts FFN with expert parallelism over the ``expert`` axis.

No MoE exists in the reference (SURVEY.md §2.5 marks EP absent); it is
built here because the framework reserves the ``expert`` mesh axis as a
first-class parallelism dimension and a reserved axis name is not a
capability (VERDICT r1 missing #6).

Two interchangeable implementations of the same math:

- :func:`moe_ffn` — dense dispatch/combine (Switch-Transformer layout):
  routing builds one-hot dispatch tensors and the whole layer is einsums,
  so under ``jit`` with expert-sharded weights (``P('expert', ...)``)
  GSPMD inserts the token exchange automatically. This is the production
  path: static shapes, MXU-friendly, composes with dp/fsdp/tp.
- :func:`moe_ffn_shard_map` — explicit expert parallelism: tokens sharded
  over ``expert``, a hand-written ``lax.all_to_all`` sends each token
  group to its expert's rank, local FFN, ``all_to_all`` back, combine.
  The literal EP dataflow (the analogue of what the reference's PS would
  have done with per-expert placement), used to assert the dense path's
  semantics in tests — the same auto/explicit pairing as
  ``parallel/sync_replicas.py``.

Routing: top-1 (Switch) or top-k via repeated argmax with masking;
capacity ``C = ceil(T/E · capacity_factor)`` per expert, overflow tokens
dropped (their residual path passes through untouched — standard Switch
semantics). Aux load-balancing loss per Switch Transformer §2.2:
``E · Σ_e fraction_tokens_e · mean_router_prob_e``.

Training-quality mechanisms (ST-MoE / Switch appendix; VERDICT r3 weak
#1): optional router JITTER noise (multiplicative uniform on the router
input, training only) decorrelates routing early in training; the router
Z-LOSS ``mean(logsumexp(logits)²)`` keeps router logits small and
training stable. Both paths also report routing VISIBILITY statistics —
``dropped_fraction`` (assignments lost to capacity overflow; the first
thing that silently goes wrong at scale) and per-expert ``expert_load``
(capacity-slot utilization in [0, 1]) — which MoeBert surfaces into the
per-step metrics stream.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.xla_metadata import set_xla_metadata

from . import nn

Params = Any


def moe_ffn_init(rng: jax.Array, n_experts: int, hidden: int,
                 intermediate: int, *, param_dtype=jnp.float32) -> Params:
    """Router + per-expert FFN weights (stacked on a leading E dim, which
    sharding rules place on the ``expert`` axis)."""
    kr, ki, ko = jax.random.split(rng, 3)
    lim = math.sqrt(6.0 / (hidden + intermediate))
    return {
        "router": {"kernel": (jax.random.normal(kr, (hidden, n_experts),
                                                jnp.float32) * 0.02
                              ).astype(param_dtype)},
        "w_in": (jax.random.uniform(ki, (n_experts, hidden, intermediate),
                                    jnp.float32, -lim, lim)
                 ).astype(param_dtype),
        "b_in": jnp.zeros((n_experts, intermediate), param_dtype),
        "w_out": (jax.random.uniform(ko, (n_experts, intermediate, hidden),
                                     jnp.float32, -lim, lim)
                  ).astype(param_dtype),
        "b_out": jnp.zeros((n_experts, hidden), param_dtype),
    }


def aux_loss(frac_tokens: jax.Array, mean_probs: jax.Array,
             n_experts: int, k: int) -> jax.Array:
    """Switch load-balancing loss from routing statistics.

    Separated from :func:`_route` so the shard_map EP path can pmean the
    statistics over the expert axis FIRST and apply the formula to the
    global values — making dense and explicit-EP aux agree exactly (the
    formula is nonlinear in its inputs, so pmean(aux(local)) !=
    aux(pmean(local)))."""
    return n_experts * jnp.sum(frac_tokens / k * mean_probs)


def _route(router_params: Params, x2: jax.Array, n_experts: int, k: int,
           capacity: int, *, rng: jax.Array | None = None,
           jitter: float = 0.0):
    """x2: [T, D] -> (dispatch [T,E,C], combine [T,E,C], stats) where
    ``stats`` = {frac [E], mp [E], z scalar, kept [E]} — callers turn
    frac/mp into the load-balancing loss via :func:`aux_loss`, ``z`` is
    the ST-MoE router z-loss term, ``kept`` the per-expert count of
    assignments that fit under capacity.

    ``jitter`` (with ``rng``) multiplies the ROUTER's input by
    ``U[1-jitter, 1+jitter]`` — routing noise only; the expert compute
    sees the clean activations.

    Top-k by repeated masked argmax; per-expert slot positions via cumsum
    (all static shapes — no sort, no gather, TPU-friendly).
    """
    xr = x2.astype(jnp.float32)
    if jitter > 0.0 and rng is not None:
        xr = xr * jax.random.uniform(rng, x2.shape, jnp.float32,
                                     1.0 - jitter, 1.0 + jitter)
    logits = jnp.einsum("td,de->te", xr,
                        router_params["kernel"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                 # [T, E]
    # ST-MoE router z-loss: mean squared logsumexp keeps logits from
    # drifting large (f32 softmax headroom)
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))

    remaining = probs
    counts = jnp.zeros((n_experts,), jnp.int32)             # slots used
    dispatch = jnp.zeros((x2.shape[0], n_experts, capacity), jnp.float32)
    combine = jnp.zeros_like(dispatch)
    total_assigned = jnp.zeros((x2.shape[0], n_experts), jnp.float32)

    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)             # [T]
        onehot = jax.nn.one_hot(choice, n_experts)          # [T, E]
        # slot index for each token within its chosen expert, in token order
        pos = (jnp.cumsum(onehot, axis=0) - 1 + counts) * onehot   # [T, E]
        keep = (pos < capacity) * onehot
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity)     # [T,E,C]
        d = keep[..., None] * slot
        gate = (probs * onehot).sum(-1, keepdims=True)      # chosen prob
        dispatch = dispatch + d
        combine = combine + d * gate[..., None]
        counts = counts + keep.sum(0).astype(jnp.int32)
        total_assigned = total_assigned + onehot
        remaining = remaining * (1.0 - onehot)              # mask the chosen

    # routing statistics for the Switch load-balance loss + visibility
    frac_tokens = total_assigned.mean(0)                    # [E]
    mean_probs = probs.mean(0)
    stats = {"frac": frac_tokens, "mp": mean_probs, "z": z,
             "kept": counts.astype(jnp.float32)}
    return dispatch, combine, stats


def _expert_compute(params: Params, inp: jax.Array, dtype, *,
                    psum_axis: str | None = None) -> jax.Array:
    """[E, C, D] -> [E, C, D]: the per-expert FFN (batched einsum over E —
    one MXU matmul per expert, stacked).

    ``psum_axis``: Megatron TP inside each expert — the caller holds
    w_in [E, H, I/tp] / w_out [E, I/tp, H] slices, the intermediate dim
    is partial, and the output contraction is closed by a psum over the
    named axis BEFORE the (full, unsharded-along-I) output bias."""
    h = jnp.einsum("ecd,edh->ech", inp.astype(dtype),
                   params["w_in"].astype(dtype),
                   preferred_element_type=jnp.float32)
    h = h + params["b_in"][:, None, :]
    h = jax.nn.gelu(h).astype(dtype)
    out = jnp.einsum("ech,ehd->ecd", h, params["w_out"].astype(dtype),
                     preferred_element_type=jnp.float32)
    if psum_axis is not None:
        out = lax.psum(out, psum_axis)
    return out + params["b_out"][:, None, :]


def capacity_for(tokens: int, n_experts: int,
                 capacity_factor: float) -> int:
    return max(1, math.ceil(tokens / n_experts * capacity_factor))


def _aux_pack(stats: dict, n_experts: int, k: int, tokens: int,
              capacity: int) -> dict:
    """Routing stats -> the aux dict both MoE paths return:

    - ``lb_loss``: Switch load-balancing loss (weight it into training)
    - ``z_loss``: ST-MoE router z-loss (weight it into training)
    - ``dropped_fraction``: share of the T·k routing assignments lost to
      capacity overflow — 0.0 means no token dropped
    - ``expert_load`` [E]: capacity-slot utilization per expert in [0,1]
    """
    kept = stats["kept"]
    return {
        "lb_loss": aux_loss(stats["frac"], stats["mp"], n_experts, k),
        "z_loss": stats["z"],
        "dropped_fraction": 1.0 - jnp.sum(kept) / float(tokens * k),
        "expert_load": kept / float(capacity),
    }


def moe_ffn(params: Params, x: jax.Array, *, n_experts: int, top_k: int = 1,
            capacity_factor: float = 1.25, dtype=jnp.float32,
            rng: jax.Array | None = None, jitter: float = 0.0
            ) -> tuple[jax.Array, dict]:
    """[B, S, D] -> ([B, S, D], aux dict — see :func:`_aux_pack`).
    Dense dispatch/combine MoE. ``rng``+``jitter`` enable router noise
    (training only — pass no rng at eval)."""
    b, s, d = x.shape
    t = b * s
    cap = capacity_for(t, n_experts, capacity_factor)
    x2 = x.reshape(t, d)
    dispatch, combine, stats = _route(params["router"], x2, n_experts,
                                      top_k, cap, rng=rng, jitter=jitter)
    aux = _aux_pack(stats, n_experts, top_k, t, cap)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dtype),
                           x2.astype(dtype),
                           preferred_element_type=jnp.float32)
    expert_out = _expert_compute(params, expert_in, dtype)
    out = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                     expert_out.astype(jnp.float32))
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn_ep_body(p_local: Params, x_local: jax.Array, *,
                    n_experts: int, n_ranks: int, top_k: int,
                    capacity_factor: float, dtype,
                    axis_name: str, stat_axes,
                    model_axis: str | None = None,
                    rng: jax.Array | None = None,
                    jitter: float = 0.0) -> tuple[jax.Array, dict]:
    """The per-member EP dataflow — call INSIDE an active ``shard_map``
    whose ``axis_name`` axis shards tokens and expert weights (and whose
    ``stat_axes`` shard tokens). :func:`moe_ffn_shard_map` wraps it; the
    pipelined MoE model (EP×PP) calls it per stage tick. One
    implementation, every composition.

    ``x_local``: [B, S, D] — this member's token shard. ``p_local``'s
    expert arrays are the local [e_local, ...] slices. Returns
    (y_local, aux) with aux computed from stats pmean'd over
    ``stat_axes`` (global-batch values; the lb formula is nonlinear, so
    it must see the pmean'd stats)."""
    e_local = n_experts // n_ranks
    bl, sl, dl = x_local.shape
    tl = bl * sl
    x2 = x_local.reshape(tl, dl)
    cap = capacity_for(tl, n_experts, capacity_factor)
    lrng = rng
    if lrng is not None:
        # independent noise per token shard: fold in EVERY axis the
        # tokens are sharded over, not just the expert rank
        for ax in stat_axes:
            lrng = jax.random.fold_in(lrng, lax.axis_index(ax))
    dispatch, combine, stats = _route(p_local["router"], x2,
                                      n_experts, top_k, cap,
                                      rng=lrng, jitter=jitter)
    send = jnp.einsum("tec,td->ecd", dispatch.astype(dtype),
                      x2.astype(dtype),
                      preferred_element_type=jnp.float32)   # [E, C, D]
    # exchange: chunk j of the expert dim goes to rank j; rank r then
    # holds, source-rank-major, every rank's buffers for ITS experts
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)
    # regroup [n_ranks · e_local, C, D] -> [e_local, n_ranks · C, D]
    recv = recv.reshape(n_ranks, e_local, cap, dl).transpose(1, 0, 2, 3)
    recv = recv.reshape(e_local, n_ranks * cap, dl)
    out = _expert_compute(
        {k: v for k, v in p_local.items() if k != "router"},
        recv, dtype, psum_axis=model_axis)                  # [e_l, nC, D]
    # send results back: invert the regrouping then all_to_all again
    back = out.reshape(e_local, n_ranks, cap, dl).transpose(1, 0, 2, 3)
    back = back.reshape(n_ranks * e_local, cap, dl)
    got = lax.all_to_all(back.astype(jnp.float32), axis_name,
                          split_axis=0, concat_axis=0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32), got)
    gstats = jax.tree_util.tree_map(
        lambda v: lax.pmean(v, stat_axes), stats)
    aux = _aux_pack(gstats, n_experts, top_k, tl, cap)
    return y.reshape(bl, sl, dl).astype(x_local.dtype), aux


def moe_ffn_shard_map(params: Params, x: jax.Array, mesh, *,
                      n_experts: int, top_k: int = 1,
                      capacity_factor: float = 1.25, dtype=jnp.float32,
                      axis_name: str = "expert",
                      batch_axes=("data", "fsdp"),
                      model_axis: str | None = None,
                      rng: jax.Array | None = None,
                      jitter: float = 0.0) -> tuple[jax.Array, dict]:
    """Explicit expert-parallel MoE: tokens sharded over the ``expert``
    axis, weights sharded one-expert-group-per-rank, exchange via
    ``lax.all_to_all`` (the EP collective; parallel/collectives.py).

    ``model_axis``: EP × TP — each local expert's FFN kernels are
    additionally Megatron-split over this axis (w_in [e, H, I/tp],
    w_out [e, I/tp, H]); every model rank routes the SAME tokens with
    the same rng (the model axis is deliberately NOT folded into the
    jitter key), runs its kernel slice, and a psum over ``model_axis``
    closes each expert FFN before the output bias.

    Output semantics match :func:`moe_ffn` exactly when no token is
    dropped (capacity is per-(source rank, expert) here, so use a
    generous capacity_factor when asserting parity). The aux statistics
    are pmean'd over every token-sharding axis FIRST — global-batch
    values — so lb/z/dropped match the dense path too; ``expert_load``
    matches when the per-rank capacity divides evenly (see
    tests/test_moe.py). Router jitter folds the rank index into ``rng``
    (each rank draws its own noise), so jittered routing is NOT
    bit-matched to the dense path — parity asserts use jitter=0.
    """
    from jax.sharding import PartitionSpec as P

    n_ranks = mesh.shape[axis_name]
    if n_experts % n_ranks:
        raise ValueError(f"{n_experts} experts not divisible over "
                         f"{n_ranks} '{axis_name}' ranks")
    if model_axis is not None:
        inter = params["w_in"].shape[2]
        if inter % mesh.shape[model_axis]:
            raise ValueError(
                f"intermediate dim {inter} not divisible over "
                f"{mesh.shape[model_axis]} '{model_axis}' ranks")
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    stat_axes = batch_axes + (axis_name,)

    e_local = n_experts // n_ranks

    def body(p_local, x_local):
        return moe_ffn_ep_body(
            p_local, x_local, n_experts=n_experts, n_ranks=n_ranks,
            top_k=top_k, capacity_factor=capacity_factor, dtype=dtype,
            axis_name=axis_name, stat_axes=stat_axes,
            model_axis=model_axis, rng=rng, jitter=jitter)

    xspec = P(batch_axes, axis_name, None)
    tp = model_axis
    pspec = {
        "router": jax.tree_util.tree_map(lambda _: P(), params["router"]),
        "w_in": P(axis_name, None, tp),
        "b_in": P(axis_name, tp),
        "w_out": P(axis_name, tp, None),
        "b_out": P(axis_name, None),
    }
    aux_spec = {"lb_loss": P(), "z_loss": P(), "dropped_fraction": P(),
                "expert_load": P()}
    fn = jax.shard_map(body, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=(xspec, aux_spec), check_vma=False)
    return fn(params, x)


# ---------------------------------------------------------------------------
# Dropless expert layer that holds a SHARE of the experts (serving decoders)
# ---------------------------------------------------------------------------

#: rows of a grouped-matmul tile (see :func:`ragged_tiling`)
_RAGGED_ROWS = 128
#: what a tile may hold of the chip's VMEM, counted as both operand tiles
#: twice (they are double-buffered) and the float32 result tile three
#: times. Compiled for a described v5e, every tile up to 15.25 MiB by
#: this count passed and every one from 16 MiB on was refused (PR 38:
#: again at dots3's widths, 13.25 passed and 16.5 was refused); the
#: largest the sweeps ran, 128 x 5120 x 512, counts 13.25 MiB.
_RAGGED_VMEM_BUDGET = 14 * 2**20


def ragged_tiling(pairs: int, k: int, n: int, dtype) -> str | None:
    """The tile ``"m,k,n"`` of one ``lax.ragged_dot`` of ``pairs`` rows
    over groups of ``[k, n]`` weights, or ``None`` for XLA's
    own choice: a rule on the shapes the call observes, set from the
    chip sweeps on record (``experiments/flash_sweep.py ragged``;
    ``benchmark/records/pr28/ragged_sweep.jsonl``, ``pr32/``,
    ``pr38/ragged_dots3_sweep.jsonl``; DESIGN §24, §26).

    XLA's grouped matmul visits every (row tile, group) pair in which
    the group owns a row of the tile and multiplies the tile WHOLE; its
    own tile is 512 rows, which at 16 rows a group is multiplied 32
    times over. The sweep's answer is one tile for every row count it
    read (8 to 256 rows a group, 128 groups, 2048 x 768 and 768 x 2048,
    uniform and skewed groups): 128 rows by the whole ``k`` and the
    whole ``n``. Fewer rows waste less at each group's edge; the whole
    weight matrix in the tile is fetched once a group (consecutive
    visits of a group name the same block) and the rows are read once,
    not once a column tile; 64 rows gain nothing more. It was within
    2.5 % of the best tile in all twelve shapes and 2.0 to 3.3 times
    faster than XLA's own. The number of groups does not enter: the tile
    that is best at 8 rows a group is best at 256.

    Where the whole matrix is over the budget (dots3-note-prev's
    [5120, 1536] is 36 MB double-buffered, Laguna-S-2.1's [3072, 1024]
    15.7) ``n`` is cut: 128 rows by the whole ``k`` by the widest
    ``n / d`` that is a multiple of 128 and fits (``128,3072,512`` and
    ``128,1024,1536`` for Laguna's: ``benchmark/records/pr41/
    ragged_laguna_sweep.jsonl``). Read at 32 groups of [5120, 1536] and
    [1536, 5120], 1,024 grouped rows in 8,192, 2,048 and 1,024:
    ``128,5120,512`` 0.77-0.78 ms and ``128,1536,1280`` 0.80 against
    1.74-1.77 under XLA's own tile and 0.61 of weight read; a cut of
    ``k`` (a float32 tile read and written again a step) 0.87-0.98;
    rows past the last group cost nothing. Up to 8,192 the rule is the
    budget's count, compiled for a described v5e and not timed.

    ``None`` wherever no sweep was read or XLA refuses the tile:
    operands that are not bfloat16, ``k`` or ``n`` not a multiple of
    128 or over 8,192, ``pairs`` not a multiple of the tile's rows, no
    tile under ``_RAGGED_VMEM_BUDGET``."""
    tm = _RAGGED_ROWS
    if (jnp.dtype(dtype) != jnp.bfloat16 or k % 128 or n % 128
            or max(k, n) > 8192 or pairs % tm):
        return None
    # the whole n where it fits the budget, else the widest cut of it
    for d in range(1, n // 128 + 1):
        tn = n // d
        vmem = 2 * 2 * (tm * k + k * tn) + 3 * 4 * tm * tn
        if n % d == 0 and tn % 128 == 0 and vmem <= _RAGGED_VMEM_BUDGET:
            return f"{tm},{k},{tn}"
    return None


def ragged_dot_tiled(a: jax.Array, w: jax.Array, rows: jax.Array,
                     tile: str | None) -> jax.Array:
    """``lax.ragged_dot`` with float32 accumulation at the tile
    ``"m,k,n"``, handed to XLA as the ``ragged_dot_tiling`` frontend
    attribute (inert off the TPU); ``None`` = XLA's own tile."""
    with (set_xla_metadata(ragged_dot_tiling=tile) if tile
          else contextlib.nullcontext()):
        return lax.ragged_dot(a, w, rows,
                              preferred_element_type=jnp.float32)


def pair_bound(pairs: int, held: int, experts: int) -> int:
    """How many rows of the sorted (row, expert) pairs
    :func:`moe_dropless` runs its grouped matmuls over: twice what the
    ``held`` of ``experts`` published experts expect of ``pairs`` under
    even routing, in whole tiles, where that is at most half of
    ``pairs``; else ``pairs`` (a chip that holds half the experts or
    more, a one-token step of a few hundred pairs: the parent's
    program). Arithmetic on shapes alone; a layer whose held pairs
    outnumber the bound runs at the whole width (DESIGN §26)."""
    bound = _RAGGED_ROWS * math.ceil(
        2 * pairs * held / experts / _RAGGED_ROWS)
    return bound if 2 * bound <= pairs else pairs


def _combine_bounded(out: jax.Array, at: jax.Array, w: jax.Array,
                     top_k: int) -> jax.Array:
    """[T, H]: the rows ``out`` of the pairs ``at`` (``pair = row *
    top_k + pick``), each times its pick's weight, added up by row: every
    pair gathers its row of ``out``, or one zero row where it is not
    among ``at``. Of the four forms timed at dots3's chunk
    (``benchmark/records/pr38/layer_bench.py``) the fastest that is
    exact: a scatter-add of the rows walks them one at a time (+1.5 ms
    a layer), a one-hot matmul at the highest precision is +0.2."""
    where = jnp.full((w.size,), out.shape[0], at.dtype).at[at].set(
        jnp.arange(out.shape[0], dtype=at.dtype))
    padded = jnp.concatenate([out, jnp.zeros_like(out[:1])])
    return jnp.sum(padded[where].reshape(*w.shape, -1) * w[..., None],
                   axis=1)


_TILE_LOG: contextvars.ContextVar[tuple | None] = contextvars.ContextVar(
    "moe_tile_log", default=None)


@contextlib.contextmanager
def tile_log(rows: dict | None = None):
    """While a program is traced under this, collect the tile each of
    :func:`moe_dropless`'s grouped matmuls was given: ``{"gate": "m,k,n"
    | "xla", "up": ..., "down": ...}`` and, into ``rows`` where one is
    passed, the rows they run over: ``{"pairs": T x k, "bound":``
    :func:`pair_bound```}``. A compiled program carries a tile and a
    bound always or never, so this is the evidence that the rules
    engaged (``serving.export_generator`` keeps it in ``export.json``)."""
    tiles: dict[str, str] = {}
    # a ContextVar's set, not a metric's: the exporter's own dicts, written
    # while it traces and never under a compiled call
    token = _TILE_LOG.set(  # graftlint: disable=JIT01
        (tiles, {} if rows is None else rows))
    try:
        yield tiles
    finally:
        _TILE_LOG.reset(token)


def sigmoid_top_k(logits, select_bias, top_k: int, scale: float,
                  groups: int = 1, top_groups: int = 1):
    """The sigmoid router's choice: per row the ``top_k`` largest of
    ``sigmoid(logits) + select_bias`` and their weights, the picked
    scores (the bias left out) renormalised to sum to ``scale``.

    ``groups`` > 1 limits the choice by groups (DeepSeek-V3's router):
    the experts lie in ``groups`` runs of ``E / groups``, a group's score
    is the sum of its two largest biased scores, and only experts of the
    ``top_groups`` best groups can be picked. One group is the plain
    top-k, and traces nothing more than it."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = s if select_bias is None else s + select_bias.astype(jnp.float32)
    if groups > 1:
        pick = _keep_groups(pick, groups, top_groups)
    _, idx = lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return w * (scale / jnp.sum(w, axis=-1, keepdims=True)), idx


def _keep_groups(pick, groups: int, top_groups: int):
    """``pick`` [T, E] with the experts outside each row's ``top_groups``
    best groups at ``-inf`` (a group's score: its two largest)."""
    t, e = pick.shape
    if e % groups or not 0 < top_groups <= groups or e // groups < 2:
        raise ValueError(f"{e} experts do not lie in {groups} groups of "
                         f"two or more, {top_groups} of them kept")
    by_group = pick.reshape(t, groups, e // groups)
    score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)     # [T, G]
    _, kept = lax.top_k(score, top_groups)
    keep = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    return jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, e)


def moe_dropless(x: jax.Array, router: jax.Array, experts: Params, *,
                 top_k: int, first_expert: int = 0,
                 dtype=jnp.bfloat16, router_dtype=jnp.float32,
                 scores: str = "softmax", select_bias=None,
                 scale: float = 1.0, groups: int = 1, top_groups: int = 1,
                 count_routed=None) -> tuple:
    """A gated (SiLU) expert FFN that never drops a token, told which
    experts it holds.

    ``x``: [T, H]; ``router``: [H, E], always the PUBLISHED expert count;
    ``experts``: ``{"gate": [Eh, H, F], "up": [Eh, H, F], "down":
    [Eh, F, H]}``, the held experts, whose global ids are
    ``first_expert .. first_expert + Eh - 1``. Routing is over all E:
    float32 softmax of the float32 router product, the ``top_k``
    largest, renormalised to sum to ``scale``
    (``routed_scaling_factor``; 1 unless a description says otherwise).
    ``scores="sigmoid"`` is the other published router: float32 sigmoid
    scores, the ``top_k`` largest of ``score + select_bias`` (a bias [E]
    that picks and does not weigh), the picked scores renormalised to
    sum to ``scale`` too, the choice limited to the ``top_groups`` best
    of ``groups`` groups of experts where ``groups`` > 1
    (:func:`sigmoid_top_k`). The (row, expert) pairs whose
    expert is held are sorted by expert and run through one grouped
    matmul per projection (``lax.ragged_dot``, ``ragged-dot-*`` in a
    capture, at the tile :func:`ragged_tiling` gives for its shape);
    the rest contribute
    nothing, here as on the chip that would hold them, so the shares of
    a layer add up to the whole layer. Matmul operands are ``dtype``,
    accumulation float32; the router's operands are ``router_dtype``
    (float32: which 8 of 128 near-equal probabilities are largest is
    decided by the last bits, and a coarser product picks other experts).

    Returns ``(y [T, H] float32, rows [Eh] int32)``: the held experts'
    part of the layer's output, and how many rows each held expert
    received; with ``count_routed`` ([T] bool: the rows that count, a
    chunk's padding and a step's dead slots left out) a third, how many
    of those rows picked at least one held expert (int32: under a group
    limit a row whose kept groups lie on other chips brings this chip
    nothing). (The encoder's
    capacity dispatch, :func:`moe_ffn`, is another layer: it drops past
    a capacity and trains.)
    """
    t, _ = x.shape
    pairs = t * top_k
    e_held = experts["gate"].shape[0]
    with jax.named_scope("moe_route"):
        logits = jnp.dot(
            x.astype(router_dtype).astype(jnp.float32),
            router.astype(router_dtype).astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        if scores == "softmax":
            w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
            w = w / jnp.sum(w, axis=-1, keepdims=True)
            if scale != 1.0:
                w = w * scale
        else:
            w, idx = sigmoid_top_k(logits, select_bias, top_k, scale,
                                   groups, top_groups)
        local = (idx - first_expert).reshape(-1)            # [T * k]
        held = (local >= 0) & (local < e_held)
        # pairs of absent experts sort past the last group
        key = jnp.where(held, local, e_held)
        order = jnp.argsort(key, stable=True)
        rows = jnp.bincount(key, length=e_held + 1)[:e_held].astype(
            jnp.int32)
        live = (jnp.arange(pairs) < jnp.sum(rows))[:, None]
    bound = pair_bound(pairs, e_held, router.shape[1])
    tiles, rows_log = _TILE_LOG.get() or (None, None)
    if rows_log is not None:
        rows_log.update(pairs=pairs, bound=bound)

    def grouped(a, name, live):
        w_e = experts[name].astype(dtype)
        tile = ragged_tiling(a.shape[0], *w_e.shape[1:], dtype)
        # the tile the program runs: its whole-width fallback logs none
        if tiles is not None and a.shape[0] == bound:
            tiles[name] = tile or "xla"
        # rows past the last group belong to no held expert: what
        # the grouped matmul leaves there is not a result
        return jnp.where(live, ragged_dot_tiled(a, w_e, rows, tile), 0.0)

    def ffn(at, live):
        """The held experts' outputs for the pairs ``at`` of the sorted
        order: [len(at), H] float32, zero where ``live`` is not."""
        xs = x.astype(dtype)[at // top_k]
        act = (jax.nn.silu(grouped(xs, "gate", live))
               * grouped(xs, "up", live))
        return grouped(act.astype(dtype), "down", live)

    def whole():
        with jax.named_scope("moe_experts"):
            out = ffn(order, live)
        with jax.named_scope("moe_combine"):
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(pairs, dtype=order.dtype))
            return jnp.sum(out[inverse].reshape(t, top_k, -1)
                           * w[..., None], axis=1)

    def bounded():
        at = order[:bound]
        with jax.named_scope("moe_experts"):
            out = ffn(at, live[:bound])
        with jax.named_scope("moe_combine"):
            return _combine_bounded(out, at, w, top_k)

    # dropless for any routing: a layer whose held pairs outnumber the
    # bound runs at the whole width
    y = whole() if bound == pairs else lax.cond(
        jnp.sum(rows) <= bound, bounded, whole)
    if count_routed is None:
        return y, rows
    return y, rows, jnp.sum(jnp.any(held.reshape(t, top_k), axis=-1)
                            & count_routed).astype(jnp.int32)
