"""Operations and bytes the algorithms REQUIRE, from shapes alone.

Model FLOPs per token live with each configuration's plain reference
(``train_flops_per_token``), which knows the architecture; this module
holds what is shared: utilisation arithmetic and kernels' counts.
"""

from __future__ import annotations


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            peak_flops: float) -> float:
    """Model FLOP/s utilisation: required FLOPs per second over the
    chips' peak. Recomputed operations never count."""
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops)


def flash_train_flops(batch: int, heads: int, seq_len: int, head_dim: int,
                      layers: int, causal: bool = True) -> float:
    """Required FLOPs of attention's two matmuls in one training step,
    forward and backward: per head and sequence 4*S^2*D forward (QK^T and
    PV) and 8*S^2*D backward, halved under a causal mask: 6*S^2*D causal.
    The backward kernel's recomputed QK^T does not count."""
    per = 12.0 * seq_len * seq_len * head_dim
    if causal:
        per /= 2.0
    return per * batch * heads * layers


def flash_train_bytes(batch: int, heads: int, seq_len: int, head_dim: int,
                      layers: int, itemsize: int = 2) -> float:
    """Least HBM bytes of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv (12 tensors of
    [S, D] per head and sequence; the f32 softmax statistics are small
    beside them and left out)."""
    return 12.0 * seq_len * head_dim * itemsize * batch * heads * layers


def roofline_pct(flops: float, bytes_: float, seconds: float,
                 peak_flops: float, peak_bytes_per_s: float
                 ) -> tuple[float, str]:
    """Share of the roofline a kernel reached, and which bound applies:
    the least time the chip could take over the time it took."""
    t_compute = flops / peak_flops
    t_memory = bytes_ / peak_bytes_per_s
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
