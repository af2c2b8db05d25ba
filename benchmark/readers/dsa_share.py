"""Index scoring, selection and the attention over the selected rows as
a share of every executed program's device time in the traced window
(chunk programs and decode steps together): whether the mechanism does
most of the work. ``None`` without a selecting artifact's programs."""

from benchmark.readers import dsa_steps


def read(ctx: dict):
    found = dsa_steps.steps(ctx)
    if not found or not found.get("modules_s"):
        return None
    mine = 0.0
    for program in ("prefill_chunk", "decode"):
        for _, by in dsa_steps.totals(ctx, program) or ():
            mine += by["index"] + by["attn"]
    return 100.0 * mine / found["modules_s"]
