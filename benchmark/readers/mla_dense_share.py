"""The two attention kernels of a dense-latent artifact
(``mla_chunk_attn`` in the chunk programs, ``paged_latent_attn`` in the
decode steps, both by name) as a share of every executed program's
device time in the traced window: whether the mechanism does most of the
work. The projections around them (the query's low-rank pair, the
absorption, ``W_o``) are not counted. ``None`` without such programs."""

from benchmark.readers import mla_dense_steps


def read(ctx: dict):
    found = mla_dense_steps.steps(ctx)
    if not found or not found.get("modules_s"):
        return None
    mine = 0.0
    for program in ("prefill_chunk", "decode"):
        for _, by in mla_dense_steps.totals(ctx, program) or ():
            mine += by["attn"]
    return 100.0 * mine / found["modules_s"] if mine else None
