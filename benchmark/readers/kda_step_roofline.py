"""Share of its roofline KDA's one-token state update reached in the
traced decode steps: the state arrays' rows the live slots read and wrote
(``state_bytes`` of each ``decode_step`` span less the convolutions'
tails' share of it, which other operations move; ``benchmark/flops_kda.
kda_step_bytes`` reckons the same from shapes) over the peak bandwidth,
against the device time of the operations that touch the state
(``readers/state_steps.py``). ``None`` without such steps."""

from benchmark.readers import state_steps


def read(ctx: dict):
    found = state_steps.steps(ctx)
    if not found or not found["decode"]:
        return None
    pattern = state_steps.patterns(ctx)["kda_step"]
    moved = seconds = 0.0
    for p in found["decode"]:
        t = state_steps.seconds(p["ops"], pattern)
        if t and "state_bytes" in p["args"]:
            moved += float(p["args"]["state_bytes"])
            seconds += t
    if not seconds or not moved:
        return None
    moved *= state_steps.state_share(ctx)
    return 100.0 * (moved / ctx["peak"]["hbm_bytes_per_s"]) / seconds
