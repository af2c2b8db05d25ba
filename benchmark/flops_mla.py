"""Operations and bytes multi-head latent attention REQUIRES, from shapes
alone (the same for a kernel and for XLA operations)."""

from __future__ import annotations


def mla_decode_bytes(context_tokens: float, row: int,
                     itemsize: int = 2) -> float:
    """Least HBM bytes of one decode step's absorbed attention in one
    layer: every latent row of the live contexts read once, for all
    heads (``row`` values a token as stored)."""
    return context_tokens * row * itemsize


def mla_decode_flops(context_tokens: float, heads: int, latent: int,
                     rank: int) -> float:
    """FLOPs of the same: a score over ``latent`` values and a weighted
    sum over ``rank`` values, a head and a context token."""
    return 2.0 * context_tokens * heads * (latent + rank)


def mla_prefill_flops(queries: float, keys: float, heads: int, qk: int,
                      v: int, rank: int) -> float:
    """FLOPs of a chunk's unabsorbed attention in one layer: K and V made
    from ``keys`` latent rows (rank x heads x (nope + v), counted at
    ``qk + v`` wide less the shared part), scores and weighted sums
    under the causal mask of the diagonal tile left out of the count
    (``keys`` is what the queries see on average)."""
    return 2.0 * heads * (keys * rank * (qk + v) + queries * keys * (qk + v))
