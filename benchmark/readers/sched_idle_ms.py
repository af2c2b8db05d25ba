"""Device-idle milliseconds per decode step, charged to the phase of the
scheduler loop the host was in (``readers/xplane_join.py``).

The device's idle gaps (between its outermost operations, inside the
traced window) are intersected with the host's ``sched_*`` spans, which
tile every working iteration of the scheduler thread:

- ``wait_logits``: inside ``sched_wait_logits`` (the logits leaving the
  chip, completion latency);
- ``sample_emit``: inside ``sched_sample_emit`` (sampling, emit, retire);
- ``admit``: inside ``sched_housekeeping`` and ``sched_admit``;
- ``launch``: inside ``sched_secure_blocks``, ``sched_build_feats`` and
  ``sched_dispatch`` (from the step's operands to the program enqueued);
- ``unattributed``: under none of them (small, or the loop does not
  tile).

The five add up to the window's idle time. A step is one device
``while`` (the decode program's scan over layers), as
``serve_custom_call_ms`` counts them, so the five add up to
``serve_device_idle_pct`` x window / steps. ``None`` without the spans."""

from benchmark.readers import xplane_join

PHASES = {
    "wait_logits": ("sched_wait_logits",),
    "sample_emit": ("sched_sample_emit",),
    "admit": ("sched_housekeeping", "sched_admit"),
    "launch": ("sched_secure_blocks", "sched_build_feats", "sched_dispatch"),
}


def idle_by_phase(found: dict) -> dict | None:
    """Idle seconds by phase, averaged over the chips, and the steps."""
    spans = found["spans"]
    if "sched_wait_logits" not in spans:
        return None
    out = dict.fromkeys([*PHASES, "unattributed"], 0.0)
    steps = 0.0
    n = len(found["chips"])
    for chip in found["chips"]:
        gaps = xplane_join.idle_gaps(chip)
        total = sum(b - a for a, b in gaps)
        for phase, names in PHASES.items():
            inside = xplane_join.merged(
                [(s[0], s[1]) for name in names
                 for s in spans.get(name, ())])
            seconds = xplane_join.overlap(gaps, inside)
            out[phase] += seconds / n
            total -= seconds
        out["unattributed"] += total / n
        steps += sum(1 for op in chip["ops"] if op[2] == "while") / n
    return {"seconds": out, "steps": steps}


def read(ctx: dict, phase: str):
    found = xplane_join.join(ctx)
    if found is None:
        return None
    if "_sched_idle" not in ctx:
        ctx["_sched_idle"] = idle_by_phase(found)
    idle = ctx["_sched_idle"]
    if idle is None or not idle["steps"]:
        return None
    return 1e3 * idle["seconds"][phase] / idle["steps"]
