"""Device milliseconds an executed program (``decode`` or
``prefill_chunk`` of a grouped-query state artifact) of one computation
inside it (``what``: ``attn`` = the full layers' attention, ``window`` =
the window layers' attention, ``moe`` = the expert layers;
``readers/gqa_steps.py``). ``None`` without such programs or a matching
operation."""

from benchmark.readers import gqa_steps


def read(ctx: dict, program: str, what: str):
    progs = gqa_steps.totals(ctx, program)
    if not progs:
        return None
    total = sum(by[what] for _, by in progs)
    return 1e3 * total / len(progs) if total else None
