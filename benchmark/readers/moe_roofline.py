"""Share of its roofline the expert layers' grouped matmuls reached in the
traced block steps: the least time the chip could take (the larger of
bytes over its bandwidth and FLOPs over its peak; ``benchmark/
flops_moe.py``) over the device time of the ``ragged-dot`` operations
inside those steps. Bytes: the held experts that received a row (the
``block_step`` span's ``expert_rows`` argument, summed over layers) times
an expert's three matrices, plus the rows' activations. At decode sizes
the weights bound it. ``None`` without such steps, the argument or the
operations."""

import re

from benchmark import flops, flops_moe
from benchmark.readers import block_steps


def read(ctx: dict, pattern: str = "ragged-dot-none"):
    found = block_steps.steps(ctx)
    cfg, eng = ctx.get("ref_cfg"), ctx.get("engine")
    if not found or not cfg or not eng:
        return None
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = (eng["slots"] * cfg["assumed"]["block_length"]
             * cfg["num_experts_per_tok"] * cfg["num_hidden_layers"])
    least = seconds = 0.0
    for step in found:
        t = sum(b - a for a, b, n in step["ops"] if re.search(pattern, n))
        if not t or "expert_rows" not in step["args"]:
            continue
        rows = float(step["args"]["expert_rows"])
        if not rows:
            continue        # the first step's span knows no routing yet
        pct, _ = flops.roofline_pct(
            flops_moe.moe_flops(pairs, h, f),
            flops_moe.moe_bytes(rows, pairs, h, f), 1.0,
            ctx["peak"]["bf16_flops"], ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += t
    return 100.0 * least / seconds if seconds else None
