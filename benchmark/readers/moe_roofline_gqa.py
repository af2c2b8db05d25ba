"""``readers/moe_roofline_state.py`` for a grouped-query state artifact
(another configuration's keys, another module's patterns): the share of
their roofline the expert layers' grouped matmuls reached in the traced
one-token decode steps. ``None`` without them."""

from benchmark import flops, flops_moe
from benchmark.readers import gqa_steps, state_steps


def read(ctx: dict):
    found = gqa_steps.steps(ctx)
    if not found or not found["decode"]:
        return None
    z = gqa_steps.sizes(ctx)
    least = seconds = 0.0
    for p in found["decode"]:
        t = state_steps.seconds(p["ops"], "ragged-dot")
        rows = float(p["args"].get("expert_rows", 0))
        live = float(p["args"].get("slots", 0))
        if not t or not rows or not live:
            continue        # the first step's span knows no routing yet
        # of a live row's picks, the share that falls on held experts
        pairs = live * z["sparse"] * z["picks"] * z["held"] / z["experts"]
        pct, _ = flops.roofline_pct(
            flops_moe.moe_flops(pairs, z["hidden"], z["f"]),
            flops_moe.moe_bytes(rows, pairs, z["hidden"], z["f"]), 1.0,
            ctx["peak"]["bf16_flops"], ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += t
    return 100.0 * least / seconds if seconds else None
