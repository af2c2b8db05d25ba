"""The executed block-step programs of a traced window, each beside the
``block_step`` span that dispatched it and the device operations inside
it BY NAME (``readers/xplane_join.py`` keeps opcodes only, and a grouped
matmul is a ``custom-call`` as the attention kernel is).

A program's operations are those of the chip's ``XLA Ops`` line that lie
inside its event on the ``XLA Modules`` line: one clock, no join. The
span whose arguments a program gets is the last ``block_step`` span that
began before the program did (the clocks of a capture agree to about a
millisecond; a step is tens). ``None`` where the capture holds no such
program or span: the parent of PR 27, and every cell that decodes one
token a step."""

from __future__ import annotations

import bisect

from benchmark import trace_reduce
from benchmark.readers import xplane_join

_KEY = "_block_steps"
_SLACK_S = 2e-3


def parse(path: str, module: str = "block_step", span: str = "block_step"):
    from jax.profiler import ProfileData
    spans, steps = [], []
    planes = list(ProfileData.from_file(path).planes)
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == span:
                        spans.append((e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      dict(e.stats)))
    spans.sort(key=lambda s: s[0])
    starts = [s[0] for s in spans]
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        ops = sorted((e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9,
                      trace_reduce.op_name(e.name))
                     for e in lines["XLA Ops"].events)
        op_starts = [o[0] for o in ops]
        for m in lines["XLA Modules"].events:
            if module not in m.name:
                continue
            m0 = m.start_ns * 1e-9
            m1 = m0 + m.duration_ns * 1e-9
            i = bisect.bisect_right(starts, m0 + _SLACK_S) - 1
            if i < 0 or m0 >= spans[i][1] + _SLACK_S:
                continue
            inside = ops[bisect.bisect_left(op_starts, m0):
                         bisect.bisect_left(op_starts, m1)]
            steps.append({"args": spans[i][2], "module": (m0, m1),
                          "ops": inside})
        break                               # the first chip that ran any
    return steps or None


def steps(ctx: dict):
    """Parsed once a run and cached on ``ctx``."""
    if _KEY not in ctx:
        path = ctx.get("xplane_path") or xplane_join.find_capture(
            ctx["trace"])
        ctx[_KEY] = parse(path) if path is not None else None
    return ctx[_KEY]
