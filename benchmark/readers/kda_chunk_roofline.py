"""Share of its roofline KDA's chunked scan reached in the traced chunk
programs: the larger of the recurrence's FLOPs over the peak and its
bytes over the bandwidth (``benchmark/flops_kda.py``, a chunk's
``tokens`` in every KDA layer), against the device time of the
operations that touch the scan's tensors (``readers/state_steps.py``).
``None`` without such programs."""

from benchmark import flops, flops_kda
from benchmark.readers import state_steps


def read(ctx: dict):
    found = state_steps.steps(ctx)
    if not found or not found["prefill_chunk"]:
        return None
    layers, _, heads, d, dv = ctx["state"]["specs"]["cache_state"]["shape"]
    pattern = state_steps.patterns(ctx)["kda_chunk"]
    least = seconds = 0.0
    for p in found["prefill_chunk"]:
        t = state_steps.seconds(p["ops"], pattern)
        tokens = float(p["args"].get("tokens", 0))
        if not t or not tokens:
            continue
        pct, _ = flops.roofline_pct(
            layers * flops_kda.kda_flops(tokens, heads, d, dv),
            layers * flops_kda.kda_chunk_bytes(tokens, heads, d, dv), 1.0,
            ctx["peak"]["bf16_flops"], ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += t
    return 100.0 * least / seconds if seconds else None
