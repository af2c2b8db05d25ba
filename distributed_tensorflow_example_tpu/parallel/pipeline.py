"""Pipeline parallelism: GPipe-style microbatch pipelining over ``pipe``.

The reference has no pipeline parallelism (SURVEY.md §2.5 marks PP "not
required" — its parallelism surface is exactly {sync DP, async DP,
round-robin PS variable placement}), and through round 2 the ``pipe`` mesh
axis existed only as a reserved name. This module delivers the minimal real
thing so the axis vocabulary is fully load-bearing.

Design — "pipelining as collective permute", the SPMD formulation that fits
XLA's compilation model (one program, no per-stage executables):

- A stack of **identical** layer blocks ``[L, ...]`` is sharded over the
  ``pipe`` axis: each of the P devices holds ``L/P`` consecutive blocks —
  one *stage*. Homogeneous stages are what make pipelining SPMD-able; input
  and output projections stay outside the pipeline, replicated.
- The (per-data-shard) batch is split into M microbatches. All stages run
  in lockstep for ``M + P - 1`` ticks; each tick every stage applies its
  blocks to its current activation and hands the result to the next stage
  with a single :func:`jax.lax.ppermute` hop (ICI neighbor DMA on TPU).
  During fill/drain a stage computes on zeros — the textbook GPipe bubble,
  amortized by M >> P.
- ``ppermute`` (and the tick ``lax.scan``) are differentiable, so the GPipe
  backward schedule — activations flowing backward through the ring — falls
  out of ``jax.grad`` with no hand-written reverse pass: the transpose of a
  shift-right permute is a shift-left permute.
- The final stage's outputs are broadcast to all pipe members with a
  masked ``psum`` so downstream (replicated-over-pipe) loss code sees a
  full activation tensor on every device.

Composes with data parallelism: the batch stays sharded over the
``(data, fsdp)`` axes in the same ``shard_map``, so a ``{data, pipe}`` mesh
runs P-stage pipelines in parallel, one per data shard, and the gradient
all-reduce over ``data`` is inserted by XLA exactly as in the pure-DP path
(:mod:`.sync_replicas`).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import AxisNames

# stage_fn(stage_params, x, mb_idx) -> y with the same pytree
# structure/shapes as x (homogeneous blocks; the leading dim of every
# stage_params leaf is the per-stage block count L/P). ``x`` may be a
# bare array or a pytree — transformer stages thread (activations,
# attention mask) together; passthrough leaves must come back unchanged.
# ``mb_idx`` is the microbatch index this tick processes (clamped during
# fill/drain, when the compute is bubble anyway) — stages use it to fold
# per-microbatch randomness (dropout) deterministically.
StageFn = Callable[[Any, Any, jax.Array], Any]

_tmap = jax.tree_util.tree_map


def pipeline_spmd(stage_fn: StageFn, stage_params, microbatches,
                  *, axis_name: str = AxisNames.PIPE):
    """Per-shard GPipe body — call inside ``shard_map``.

    Args:
      stage_fn: applies this stage's blocks to one microbatch.
      stage_params: this stage's parameter shard (leading dim ``L/P``).
      microbatches: pytree with ``[M, mb, ...]`` leaves — the local batch
        pre-split into M microbatches, replicated over the pipe axis.

    Returns the same pytree with the final stage's outputs, identical on
    every pipe member.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = jax.tree_util.tree_leaves(microbatches)[0].shape[0]

    # non-circular shift: stage i -> i+1; stage 0 receives zeros (unused —
    # it always reads from the microbatch queue)
    perm = [(r, r + 1) for r in range(n - 1)]

    def tick(carry, t):
        recv, outputs = carry
        # stage 0 dequeues microbatch t (clamped during drain, when its
        # compute is bubble anyway); later stages take the ppermute'd
        # activation from their predecessor
        x = _tmap(lambda q, r: jnp.where(me == 0,
                                         q[jnp.minimum(t, m - 1)], r),
                  microbatches, recv)
        # stage ``me`` works on microbatch t - me at tick t
        mb_idx = jnp.clip(t - me, 0, m - 1)
        y = stage_fn(stage_params, x, mb_idx)
        # the last stage completes microbatch t-(n-1) at tick t
        out_idx = t - (n - 1)
        safe = jnp.maximum(out_idx, 0)
        outputs = _tmap(
            lambda o, yy: jnp.where(
                out_idx >= 0,
                lax.dynamic_update_index_in_dim(o, yy, safe, 0), o),
            outputs, y)
        recv = _tmap(lambda yy: lax.ppermute(yy, axis_name, perm), y)
        return (recv, outputs), None

    zero = _tmap(lambda q: jnp.zeros_like(q[0]), microbatches)
    (_, outputs), _ = lax.scan(
        tick, (zero, _tmap(jnp.zeros_like, microbatches)),
        jnp.arange(m + n - 1))

    # broadcast the final stage's buffer to every pipe member (all other
    # stages contribute zeros); psum's transpose is the identity per shard,
    # so gradients re-enter the drain ticks correctly
    outputs = _tmap(lambda o: jnp.where(me == n - 1, o,
                                        jnp.zeros_like(o)), outputs)
    return _tmap(lambda o: lax.psum(o, axis_name), outputs)


def make_pipeline(mesh: Mesh, stage_fn: StageFn, *,
                  num_microbatches: int,
                  pipe_axis: str = AxisNames.PIPE,
                  batch_axes=AxisNames.BATCH,
                  param_specs=None, x_specs=None):
    """Bind a mesh → ``apply(stacked_params, x) -> y`` pipelined over pipe.

    ``stacked_params`` leaves have leading dim L (total blocks), sharded
    over ``pipe``; ``x`` is ``[B, ...]`` batch-sharded over the batch axes
    and replicated over pipe. Usable inside jit (shard_map composes).

    ``param_specs`` / ``x_specs`` optionally override the per-leaf
    ``PartitionSpec``s (pytrees matching ``stacked_params`` / ``x``) so the
    pipeline composes with tensor parallelism: PipeBert passes param specs
    whose kernel dims also carry the ``model`` axis and activation specs
    seq-sharded over ``model`` (Megatron sequence-parallel layout). Every
    param spec must keep ``pipe`` on the leading (stage) dim.
    """
    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got "
                         f"{num_microbatches}")
    n_pipe = mesh.shape[pipe_axis]

    def apply(stacked_params, x):
        L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if L % n_pipe:
            raise ValueError(
                f"block count {L} not divisible by pipe axis size {n_pipe}")

        def body(params_local, x_local):
            b = jax.tree_util.tree_leaves(x_local)[0].shape[0]
            if b % num_microbatches:
                raise ValueError(
                    f"per-shard batch {b} not divisible by "
                    f"num_microbatches={num_microbatches}")
            mb = _tmap(
                lambda a: a.reshape((num_microbatches,
                                     b // num_microbatches) + a.shape[1:]),
                x_local)
            out = pipeline_spmd(stage_fn, params_local, mb,
                                axis_name=pipe_axis)
            return _tmap(lambda a: a.reshape((b,) + a.shape[2:]), out)

        p_specs = (param_specs if param_specs is not None
                   else _tmap(lambda _: P(pipe_axis), stacked_params))
        a_specs = (x_specs if x_specs is not None
                   else _tmap(lambda _: P(batch_axes), x))
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, a_specs),
            out_specs=a_specs, check_vma=False)(stacked_params, x)

    return apply


def sequential_blocks(stage_fn: StageFn, stacked_params, x,
                      *, num_microbatches: int = 1):
    """Unpartitioned oracle: apply ALL stacked blocks in order on one
    device (what the pipeline computes, minus the pipelining), with the
    same per-microbatch split so mb-indexed randomness matches. Used as
    the pipe-axis-absent fallback and as the parity target in tests."""
    b = jax.tree_util.tree_leaves(x)[0].shape[0]
    if not isinstance(b, int):
        # batch-polymorphic trace (jax.export symbolic dim): the
        # microbatch split depends concretely on the batch size —
        # raise the same family MoE capacity math does so the
        # exporter's static-batch fallback engages (serving.py)
        raise TypeError(
            f"microbatch split needs a concrete batch size, got "
            f"symbolic {b!r}")
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by "
                         f"num_microbatches={num_microbatches}")
    mb = _tmap(lambda a: a.reshape(
        (num_microbatches, b // num_microbatches) + a.shape[1:]), x)
    out = jax.lax.map(
        lambda args: stage_fn(stacked_params, args[0], args[1]),
        (mb, jnp.arange(num_microbatches)))
    return _tmap(lambda a: a.reshape((b,) + a.shape[2:]), out)
