"""Device-idle milliseconds per block step, charged to the phase of the
scheduler loop the host was in: ``readers/sched_idle_ms.py``'s split
(``wait_logits``, ``sample_emit``, ``admit``, ``launch``,
``unattributed``; the five add up to the window's idle time) for an
engine whose step is a block step. That reader counts a step as one
device ``while`` (the one-token decode program's scan over layers); a
block step holds none, so the steps here are the executed programs whose
name holds ``module`` (``jit_block_step``), as ``serve_moe_ms`` counts
them. A prefill's idle time is inside, as it is there: the five add up to
``serve_device_idle_pct`` x window / block steps. ``None`` without such
a program or without the ``sched_*`` spans."""

from benchmark import trace_reduce
from benchmark.readers import sched_idle_ms, xplane_join


def read(ctx: dict, phase: str, module: str = "block_step"):
    trace = ctx["trace"]
    steps = trace_reduce.call_count(trace, per_module=module)
    if not steps or not trace.get("chips"):
        return None
    found = xplane_join.join(ctx)
    if found is None:
        return None
    if "_sched_idle" not in ctx:
        ctx["_sched_idle"] = sched_idle_ms.idle_by_phase(found)
    idle = ctx["_sched_idle"]
    if idle is None:
        return None
    return 1e3 * idle["seconds"][phase] / (steps / trace["chips"])
