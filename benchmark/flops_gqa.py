"""Operations and bytes grouped-query attention REQUIRES over a paged K/V
pool and over rings, from shapes and the spans' counts alone: the same
whatever implements them (a gather, a tile loop, a kernel under a masked
query tile), and a floor no implementation can beat, so that no share of
a roofline read against them can pass 100 %."""

from __future__ import annotations


def attn_flops(pairs: float, heads: int, head_dim: int) -> float:
    """FLOPs of attention over ``pairs`` (query row, key row) pairs a
    layer (summed over layers): a score over ``head_dim`` values and a
    weighted sum over ``head_dim`` values a query head and a pair. The
    masked query tile pays ``KVH`` times this on the MXU; it is not
    required."""
    return 2.0 * pairs * heads * 2 * head_dim


def decode_attn_bytes(kv_bytes: float, rows: float, layers: int, heads: int,
                      head_dim: int, itemsize: int = 2) -> float:
    """Least HBM bytes of one token a slot against the pool: every K and V
    row of the live contexts read once (a ``decode_step`` span's
    ``kv_bytes``: all full layers), each row's queries read and its
    context written (float32)."""
    return kv_bytes + rows * layers * heads * head_dim * (itemsize + 4)


def window_attn_bytes(window_bytes: float, rows: float, layers: int,
                      heads: int, head_dim: int, itemsize: int = 2) -> float:
    """The same over the rings: the ring rows inside each live slot's
    window read once (the span's ``window_bytes``: K and V, all window
    layers)."""
    return window_bytes + rows * layers * heads * head_dim * (itemsize + 4)


def chunk_attn_bytes(context_bytes: float, tokens: float, layers: int,
                     heads: int, head_dim: int, itemsize: int = 2) -> float:
    """Least HBM bytes of a chunk's attention: the context's K and V rows
    read once for all heads and query rows (``context_bytes``), the
    chunk's queries read and its context written (float32)."""
    return context_bytes + tokens * layers * heads * head_dim * (itemsize + 4)
