"""Named collectives: the ICI/DCN replacement for the rendezvous layer.

In the reference, every cross-device byte moved as a receiver-initiated gRPC
``RecvTensor`` through the Rendezvous abstraction (SURVEY.md §2.4, §5.8):
workers pulled parameters from the PS and the PS pulled gradients — two full
param-size Ethernet transfers per step per worker (SURVEY.md §3.3). Here the
same dataflow is expressed as XLA collective ops that the TPU compiler lowers
to ICI DMA and fuses into the step program; this module is a thin,
consistently-named veneer over ``jax.lax`` usable inside ``shard_map``.

All functions take ``axis_name`` (one of
:class:`~distributed_tensorflow_example_tpu.parallel.mesh.AxisNames`) or a
tuple of axis names.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax.numpy as jnp
from jax import lax

AxisName = Any  # str | tuple[str, ...]


def all_reduce_sum(x, axis_name: AxisName):
    """Sum over the axis — the gradient-aggregation primitive (replaces the
    PS-side ConditionalAccumulator take_grad, SURVEY.md §3.3 step 3)."""
    return lax.psum(x, axis_name)


def all_reduce_mean(x, axis_name: AxisName):
    """Mean over the axis. The reference *averages* aggregated gradients
    (sync_replicas_optimizer.py:36-40 note, SURVEY.md §7 hard-parts item 2),
    so this is the collective used for sync-DP gradient exchange."""
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name: AxisName, *, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` from every member of the mesh axis
    (replaces the worker param-pull, SURVEY.md §3.3 step 1, when params are
    sharded fsdp-style)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter_mean(x, axis_name: AxisName, *, scatter_axis: int = 0):
    """Reduce-then-shard: each member keeps 1/N of the mean. The fsdp
    gradient exchange (ZeRO): cheaper than all-reduce when params are
    sharded, since each host only materializes its own shard."""
    summed = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                              tiled=True)
    return summed / lax.axis_size(axis_name)


def ppermute_ring_shift(x, axis_name: AxisName, *, shift: int = 1):
    """Rotate values around the mesh axis ring (source i → dest i+shift).

    The building block for ring attention / context parallelism
    (SURVEY.md §5.7): each step passes KV blocks to the ring neighbor over
    ICI while the MXU overlaps compute on the resident block.
    """
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: AxisName, *, split_axis: int, concat_axis: int,
               tiled: bool = True):
    """All-to-all reshard — the DeepSpeed-Ulysses-style sequence↔head
    exchange and the MoE token-routing primitive (expert axis)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def broadcast_one_to_all(x, axis_name: AxisName, *, src: int = 0):
    """Broadcast member ``src``'s value to all members of the axis (chief →
    workers, e.g. init parity with the chief-initializes protocol of
    SURVEY.md §3.2)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)
