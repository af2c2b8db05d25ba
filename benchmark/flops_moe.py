"""Operations and bytes a dropless expert layer's grouped matmuls REQUIRE,
from shapes alone (the count functions of ``ops/moe.moe_dropless``'s three
``ragged-dot`` calls: gate, up, down)."""

from __future__ import annotations


def moe_flops(pairs: float, hidden: int, width: int) -> float:
    """FLOPs of ``pairs`` (row, expert) pairs through gate, up and down:
    three [hidden x width] products a pair."""
    return 3.0 * 2.0 * pairs * hidden * width


def moe_bytes(expert_rows: float, pairs: float, hidden: int, width: int,
              itemsize: int = 2) -> float:
    """Least HBM bytes of the same: the weights of every held expert that
    received a row, read once (``expert_rows`` counts them, summed over
    layers: 3 matrices of hidden x width each), plus the pairs'
    activations: the sorted rows read by gate and by up, the gated
    product written and read, the down product written in float32."""
    weights = expert_rows * 3.0 * hidden * width * itemsize
    acts = pairs * (2.0 * hidden * itemsize + 2.0 * width * itemsize
                    + hidden * 4.0)
    return weights + acts
