"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

Nothing like this exists in the reference (SURVEY.md §5.7 records the gap);
it is first-class here because long-context is a headline capability of the
new framework. Design: the sequence dimension is sharded over the ``seq``
axis; each device keeps its Q shard resident and the K/V shards rotate
around the ring with ``lax.ppermute`` (lowered to ICI neighbor DMA on TPU),
one hop per step, while the MXU computes the local block — compute hides
the communication. Softmax is computed *online* (running max / normalizer,
the flash-attention recurrence) so no device ever materializes the full
[S, S] score matrix: memory is O(S·S/n) per device and the sequence length
scales linearly with the ring size.

Use :func:`make_ring_attention` to bind a mesh and get a drop-in
replacement for
:func:`~distributed_tensorflow_example_tpu.ops.attention.multi_head_attention`.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF as _NEG, apply_mask, attention_scores
from .mesh import AxisNames


def _block_update(q, k, v, o, m, l, *, q_off, k_off, causal, kv_mask):
    """One online-softmax accumulation step against a K/V block.

    q: [B,Sq,H,D]; k,v: [B,Sk,H,D]; o: [B,H,Sq,D] f32; m,l: [B,H,Sq,1] f32.
    kv_mask: [B,Sk] (1 = valid key) or None. Score/mask math is shared with
    ops/attention.py (attention_scores / apply_mask).
    """
    s = attention_scores(q, k)
    s = apply_mask(
        s, kv_mask[:, None, None, :] if kv_mask is not None else None,
        causal=causal, q_offset=q_off, k_offset=k_off)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # explicitly zero masked probabilities: a fully-masked block would
    # otherwise yield exp(_NEG - _NEG) = 1 and corrupt the normalizer
    p = jnp.exp(s - m_new) * (s > _NEG / 2)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * corr + pv
    return o_new, m_new, l_new


def ring_attention_local(q, k, v, *, axis_name: str = AxisNames.SEQ,
                         causal: bool = False, kv_mask=None) -> jax.Array:
    """Per-shard ring attention body — call inside ``shard_map``.

    Args are the LOCAL shards [B, S/n, H, D] (+ optional kv_mask [B, S/n]).
    Returns the local context shard [B, S/n, H, D].
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]

    o = jnp.zeros((b, h, sq, d), jnp.float32)
    m = jnp.full((b, h, sq, 1), _NEG, jnp.float32)
    l = jnp.zeros((b, h, sq, 1), jnp.float32)

    def step(carry, i):
        o, m, l, k_cur, v_cur, mask_cur = carry
        src = (me - i) % n                 # origin rank of the block we hold
        o, m, l = _block_update(
            q, k_cur, v_cur, o, m, l,
            q_off=me * sq, k_off=src * sk, causal=causal, kv_mask=mask_cur)
        # rotate K/V (and mask) one hop around the ring: ICI neighbor DMA
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = (lax.ppermute(mask_cur, axis_name, perm)
                    if mask_cur is not None else None)
        return (o, m, l, k_nxt, v_nxt, mask_nxt), None

    (o, m, l, *_), _ = lax.scan(
        step, (o, m, l, k, v, kv_mask), jnp.arange(n))

    out = o / jnp.maximum(l, 1e-20)        # guard fully-masked rows
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def make_ring_attention(mesh: Mesh, *, causal: bool = False,
                        batch_axes=AxisNames.BATCH,
                        seq_axis: str = AxisNames.SEQ):
    """Bind a mesh → a [B,S,H,D] attention fn sharded over the seq axis.

    Drop-in for ``multi_head_attention`` (mask argument = key validity
    [B,S]); usable inside jit (shard_map composes with jit).
    """
    qkv_spec = P(batch_axes, seq_axis, None, None)
    mask_spec = P(batch_axes, seq_axis)
    bound_causal = causal

    def attn(q, k, v, *, mask=None, causal=None, **unexpected):
        if unexpected:
            raise TypeError(f"unexpected kwargs {sorted(unexpected)}; "
                            "bind options at make_ring_attention() time")
        if causal is not None and causal != bound_causal:
            # silently ignoring a call-site causal flag would run
            # bidirectional attention in a decoder — fail loudly instead
            raise ValueError(
                f"causal={causal} at call time conflicts with "
                f"make_ring_attention(causal={bound_causal}); causality is "
                "baked into the ring schedule and must be bound at "
                "construction")
        if mask is not None:
            fn = partial(ring_attention_local, axis_name=seq_axis,
                         causal=bound_causal)
            sharded = jax.shard_map(
                lambda q_, k_, v_, m_: fn(q_, k_, v_, kv_mask=m_),
                mesh=mesh,
                in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
                out_specs=qkv_spec, check_vma=False)
            return sharded(q, k, v, mask)
        sharded = jax.shard_map(
            lambda q_, k_, v_: ring_attention_local(
                q_, k_, v_, axis_name=seq_axis, causal=bound_causal,
                kv_mask=None),
            mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec, check_vma=False)
        return sharded(q, k, v)

    return attn
