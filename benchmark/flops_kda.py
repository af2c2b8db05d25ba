"""Operations and bytes Kimi Delta Attention REQUIRES, from shapes alone:
the same whatever computes it (a token recurrence, a chunked scan, a
kernel or XLA operations)."""

from __future__ import annotations


def kda_flops(tokens: float, heads: int, dk: int, dv: int) -> float:
    """FLOPs of the recurrence over ``tokens`` tokens of ``heads`` heads:
    a token decays the state (dk x dv), reads it twice (``S'^T k`` and
    ``S^T q``: 2 dk dv each) and adds a rank-one update (2 dk dv)."""
    return 7.0 * tokens * heads * dk * dv


def kda_step_bytes(rows: float, layers: int, heads: int, dk: int, dv: int,
                   itemsize: int = 4) -> float:
    """Least HBM bytes of one decode step's state updates: every live
    row's state read once and written once, in every KDA layer."""
    return 2.0 * rows * layers * heads * dk * dv * itemsize


def kda_chunk_bytes(tokens: float, heads: int, dk: int, dv: int,
                    itemsize: int = 4) -> float:
    """Least HBM bytes of one layer's scan over a chunk: q, k, the decay
    (dk a head) and v read, o written (dv), the rate, and the state in
    and out."""
    per_token = heads * (3.0 * dk + 2.0 * dv + 1.0) * itemsize
    return tokens * per_token + 2.0 * heads * dk * dv * itemsize
