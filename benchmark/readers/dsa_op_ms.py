"""Device milliseconds an executed program (``decode`` or
``prefill_chunk`` of a selecting artifact) of one computation inside it
(``what``: ``index`` = index scoring and selection, ``attn`` = attention
over the selected rows, ``window`` = the window layers' attention,
``moe`` = the expert layers; ``readers/dsa_steps.py``). ``None`` without
such programs or a matching operation."""

from benchmark.readers import dsa_steps


def read(ctx: dict, program: str, what: str):
    progs = dsa_steps.totals(ctx, program)
    if not progs:
        return None
    total = sum(by[what] for _, by in progs)
    return 1e3 * total / len(progs) if total else None
