"""Device milliseconds an executed program (``decode`` or
``prefill_chunk`` of a per-request-state artifact) of one computation
inside it (``what``: a key of ``readers/state_steps.patterns``), or of
the whole program (``what = "program"``). ``None`` without such programs
or a matching operation."""

from benchmark.readers import state_steps


def read(ctx: dict, program: str, what: str):
    found = state_steps.steps(ctx)
    if not found or not found.get(program):
        return None
    progs = found[program]
    if what == "program":
        total = sum(m1 - m0 for m0, m1 in (p["module"] for p in progs))
    else:
        pattern = state_steps.patterns(ctx, program)[what]
        total = sum(state_steps.seconds(p["ops"], pattern) for p in progs)
    return 1e3 * total / len(progs) if total else None
