"""``readers/moe_roofline_state.py`` for a selecting artifact (another
configuration's keys, another module's patterns): the share of their
roofline the expert layers' grouped matmuls reached in the traced
one-token decode steps. ``None`` without them."""

from benchmark import flops, flops_moe
from benchmark.readers import dsa_steps, state_steps


def read(ctx: dict):
    found = dsa_steps.steps(ctx)
    cfg, st = ctx.get("ref_cfg"), ctx.get("state")
    if not found or not found["decode"] or not cfg or not st:
        return None
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sparse = st["ffns"].count("moe")
    least = seconds = 0.0
    for p in found["decode"]:
        t = state_steps.seconds(p["ops"], "ragged-dot")
        rows = float(p["args"].get("expert_rows", 0))
        live = float(p["args"].get("slots", 0))
        if not t or not rows or not live:
            continue        # the first step's span knows no routing yet
        # of a live row's picks, the share that falls on held experts
        pairs = (live * sparse * cfg["num_experts_per_tok"]
                 * st["experts_held"] / st["experts"])
        pct, _ = flops.roofline_pct(
            flops_moe.moe_flops(pairs, h, f),
            flops_moe.moe_bytes(rows, pairs, h, f), 1.0,
            ctx["peak"]["bf16_flops"], ctx["peak"]["hbm_bytes_per_s"])
        least += pct / 100.0
        seconds += t
    return 100.0 * least / seconds if seconds else None
