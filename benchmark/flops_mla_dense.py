"""Operations and bytes DENSE latent attention requires (every layer
attends to the whole of a paged latent cache), from shapes and the spans'
counts alone: the same whatever implements it, and the CHEAPER of the two
forms the mathematics allows, so that no share of a roofline read against
them can pass 100 %.

Two forms of one mathematics. *Expanded*: K and V of a head are made from
the latent rows (``2 x rank x (nope + v)`` FLOPs a context row a head,
once for all the queries that share the context) and a (query, key) pair
costs a score over ``nope + pe`` values and a weighted sum over ``v``.
*Absorbed*: ``W_kvb`` is folded into the query and the output (``2 x
rank x (nope + v)`` FLOPs a QUERY a head) and a pair costs a score over
``rank + pe`` values and a weighted sum over ``rank``. At A.X-K1's sizes
(rank 512, nope 128, pe 64, v 128) a pair is 640 against 2,176 FLOPs a
head, an expansion or an absorption 262,144: a chunk of 1,024 queries
over one context expands, one query a slot absorbs."""

from __future__ import annotations


def expanded_flops(pairs: float, keys: float, heads: int, rank: int,
                   nope: int, pe: int, v: int) -> float:
    """``pairs`` (query, key) pairs and ``keys`` context rows expanded
    (both summed over layers)."""
    return 2.0 * heads * (pairs * (nope + pe + v) + keys * rank * (nope + v))


def absorbed_flops(pairs: float, queries: float, heads: int, rank: int,
                   nope: int, pe: int, v: int) -> float:
    """``pairs`` pairs and ``queries`` query rows absorbed (both summed
    over layers)."""
    return 2.0 * heads * (pairs * (2 * rank + pe)
                          + queries * rank * (nope + v))


def required_flops(pairs: float, keys: float, queries: float, heads: int,
                   rank: int, nope: int, pe: int, v: int) -> float:
    """The cheaper form's count."""
    return min(expanded_flops(pairs, keys, heads, rank, nope, pe, v),
               absorbed_flops(pairs, queries, heads, rank, nope, pe, v))


def required_bytes(kv_bytes: float, queries: float, heads: int, nope: int,
                   pe: int, v: int, itemsize: int = 2) -> float:
    """Least HBM bytes: every latent row of the contexts read once for
    all heads and all queries that share it (a span's ``kv_bytes``: rows
    as stored, all layers), each query read (``nope + pe`` values a
    head, the narrower of the two forms) and its context written
    (``v`` values a head, float32); ``queries`` summed over layers."""
    return kv_bytes + queries * heads * ((nope + pe) * itemsize + v * 4)
