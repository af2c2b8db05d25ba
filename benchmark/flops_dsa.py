"""Operations and bytes a learned sparse selection REQUIRES, from shapes
and the spans' counts alone: the same whatever implements them (a tile
loop, a gather, a kernel), and a floor no implementation can beat, so
that no share of a roofline read against them can pass 100 %."""

from __future__ import annotations


def index_flops(pairs: float, heads: int, dim: int) -> float:
    """FLOPs of index scoring: a ``dim``-long product a head and a
    (query row, earlier token) pair, and the weighted sum over heads.
    ``pairs``: rows the contexts hold, summed over query rows (and
    layers): a span's ``context_rows``."""
    return 2.0 * pairs * heads * (dim + 1)


def index_bytes(key_bytes: float, rows: float, layers: int, heads: int,
                dim: int, itemsize: int = 2) -> float:
    """Least HBM bytes of index scoring and selection: every index key of
    the contexts read once (a span's ``index_bytes``) and each row's
    queries and weights."""
    return key_bytes + rows * layers * heads * (dim * itemsize + 4)


def selected_attn_flops(pairs: float, heads: int, qk: int, v: int) -> float:
    """FLOPs of attention over the selected rows: a score over ``qk``
    values and a weighted sum over ``v`` values a head and a (query row,
    selected row) pair (a span's ``selected_rows``). The unabsorbed
    count without the K/V expansion: the absorbed form pays more a pair,
    the unabsorbed form pays the expansion besides."""
    return 2.0 * pairs * heads * (qk + v)


def selected_attn_bytes(selected_bytes: float, context_bytes: float
                        ) -> float:
    """Least HBM bytes of the same: the selected latent rows as stored (a
    span's ``kv_bytes``), or every row of the context once where the rows
    of a dispatch share one context and select more than it holds."""
    return min(selected_bytes, context_bytes)


def window_attn_flops(pairs: float, heads: int, qk: int, v: int) -> float:
    """FLOPs of a window layer's attention: as the selected attention's,
    a (query row, row in its window) pair."""
    return 2.0 * pairs * heads * (qk + v)
