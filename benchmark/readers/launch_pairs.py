"""Launch spans paired with the programs they launched BY ORDER, the
capture's clocks aligned from the pairs, and the host turn read on that
alignment: ``sched_idle_admit_{launch,read,emit,self}_ms``,
``sched_pair_shift_ms``, ``sched_pair_slack_ms`` and their ``.chat``
twins (PR 37).

Since PR 37 every span around a launch of a device program says which
launch it was: ``program`` (the capture's module name without ``jit_``)
and ``seq`` (how many times the engine launched that program before).
An engine launches from one thread and the device runs its programs in
the order of their launches, so the launch spans of a capture, by
start, and the executed ``jit_<program>`` events of the first chip's
``XLA Modules`` line, by start, are ONE sequence cut at the capture's
two edges. :func:`pair` lays one on the other: the offset (at most
``EDGE`` launches either way) at which every pair's names agree and
the causal constraints below can all hold; where several do (a strictly
periodic capture), the one that moves the device's clock least: the
nearest start, asked once for the whole capture and not per event. No
pair depends on the two clocks' difference, which
``xplane_join.causal_shift``'s nearest-start pairing does. Per program
the ``seq`` of the paired spans must rise by one: a span the profiler
dropped, or counts that disagree by more than the edges, give ``None``.

A program cannot start before its launch span does, and cannot end
after the span that read its result: ``admit_read`` with the same
``program`` and ``seq``, or the step span itself (``decode_step``,
``verify_step``, ``block_step`` hold launch and read). So the shifts of
the device's clock that keep every pair causal are an interval
``[lo, hi]``; the least of them in size is applied (0 where the
capture's own clocks are causal, as ``causal_shift`` does), and both
ends are printed and published: ``sched_pair_shift_ms`` is what was
applied, ``sched_pair_slack_ms`` the interval's width, i.e. the least
launch latency plus the least return latency of the capture: how far
any split of idle time between a launch and the read after it can be
trusted. Cells whose programs hold no ``while`` (Kimi's, dots3's,
SDAR's) get their anchor here.

On that alignment (``what``):

- ``admit_launch``, ``admit_read``, ``admit_emit``: device-idle ms a
  step inside the spans of that name; ``admit_self``: inside
  ``sched_housekeeping`` and ``sched_admit`` outside the three, so the
  four add up to ``sched_idle_ms``'s ``admit`` on the same alignment;
- ``shift``, ``slack``: as above, ms.

A child counts with its parent: one whose ``sched_admit`` the capture's
edge cut (a single one can hold eight prefills, half a second) is left
out of the parts. A step is one ``decode_step``, ``verify_step`` or
``block_step`` span inside the first chip's window. ``None`` wherever
the spans carry no ``program`` (the parent of PR 37) or the capture
cannot be paired."""

from __future__ import annotations

import bisect

from benchmark.readers import sched_idle_ms, xplane_join

_KEY = "_launch_pairs"
#: spans that hold a program's launch AND the read of its result
STEPS = ("decode_step", "verify_step", "block_step")
#: spans that hold a launch alone
LAUNCHES = ("admit_launch", "cow_copy")
READ = "admit_read"
CHILDREN = ("admit_launch", "admit_read", "admit_emit")
#: launches a capture may cut at either edge: its host and device
#: recorders start and stop some milliseconds apart
EDGE = 16


def modules(path: str) -> list[tuple[float, float, str]]:
    """``(start_s, end_s, program)`` of every ``jit_<program>`` the
    first chip that ran any executed, by start."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        line = next((ln for ln in plane.lines
                     if ln.name == "XLA Modules"), None)
        out = sorted(
            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             e.name.split("(")[0][len("jit_"):])
            for e in (line.events if line is not None else ())
            if e.name.startswith("jit_"))
        if out:
            return out
    return []


def launches(spans: dict) -> list[tuple[float, float | None, str, int]]:
    """``(start_s, read_end_s | None, program, seq)`` of every launch
    span, by start: ``read_end_s`` is where the host held the program's
    result (None for a launch nobody reads: a zeroed slot, a copied
    block)."""
    read = {(s[2]["program"], int(s[2]["seq"])): s[1]
            for s in spans.get(READ, ()) if "program" in s[2]}
    out = []
    for name in STEPS + LAUNCHES:
        for s in spans.get(name, ()):
            if "program" not in s[2]:
                continue
            key = (s[2]["program"], int(s[2]["seq"]))
            out.append((s[0], s[1] if name in STEPS else read.get(key),
                        *key))
    return sorted(out)


def feasible(pairs: list) -> tuple[float, float]:
    """The shifts of the device's clock, ``(lo, hi)`` seconds, under
    which no program of ``pairs`` starts before its launch span or ends
    after its read (``lo > hi``: none)."""
    lo = max(launch[0] - mod[0] for launch, mod in pairs)
    hi = min((launch[1] - mod[1] for launch, mod in pairs
              if launch[1] is not None), default=float("inf"))
    return lo, hi


def least(lo: float, hi: float) -> float:
    """The member of ``[lo, hi]`` nearest 0."""
    return min(max(0.0, lo), hi)


def pair(spans: dict, mods: list) -> dict | None:
    """The launches of ``spans`` laid on ``mods`` by order (module
    docstring). ``pairs``: ``[(launch, module)]``; ``lo``, ``hi``,
    ``shift``: seconds to add to the device's clock; ``launches``,
    ``modules``: what there was to pair, by program. ``None`` where
    nothing fits."""
    found = launches(spans)
    programs = {launch[2] for launch in found}
    mods = [m for m in mods if m[2] in programs]
    if not found or not mods:
        return None
    best = None
    for o in range(-EDGE, EDGE + 1):            # mods[j] <-> found[j + o]
        j0, j1 = max(0, -o), min(len(mods), len(found) - o)
        # what the offset leaves unpaired at either edge, of either
        cut = max(j0, len(mods) - j1, j0 + o, len(found) - o - j1)
        if j1 <= j0 or cut > EDGE:
            continue
        pairs = list(zip(found[j0 + o:j1 + o], mods[j0:j1]))
        if any(launch[2] != mod[2] for launch, mod in pairs):
            continue
        lo, hi = feasible(pairs)
        if lo <= hi < float("inf") and (
                best is None or abs(least(lo, hi)) < abs(best["shift"])):
            best = {"pairs": pairs, "lo": lo, "hi": hi,
                    "shift": least(lo, hi)}
    if best is None:
        return None
    seqs: dict = {}
    for launch, _ in best["pairs"]:
        seqs.setdefault(launch[2], []).append(launch[3])
    if any(q != list(range(q[0], q[0] + len(q))) for q in seqs.values()):
        return None                     # a launch span is missing

    def count(rows):
        return {p: sum(1 for r in rows if r[2] == p)
                for p in sorted(programs)}
    best.update(launches=count(found), modules=count(mods),
                paired=count([launch for launch, _ in best["pairs"]]))
    return best


def describe(paired: dict | None) -> str:
    """One line for the run's log: how many of the capture's launch
    spans were paired with an executed program, and the interval."""
    if paired is None:
        return ("launch_pairs: the capture's launch spans and executed "
                "programs do not pair by order")
    by = ", ".join(
        f"{p} {paired['paired'][p]} of {n} spans / "
        f"{paired['modules'][p]} programs"
        for p, n in paired["launches"].items())
    return (f"launch_pairs: {len(paired['pairs'])} of "
            f"{sum(paired['launches'].values())} launch spans inside the "
            f"capture paired with one executed program each ({by}); the "
            f"device clock may move by [{1e3 * paired['lo']:+.3f}, "
            f"{1e3 * paired['hi']:+.3f}] ms, moved "
            f"{1e3 * paired['shift']:+.3f} ms")


def moved(chips: list, d: float) -> list:
    """``chips`` with every device time ``d`` seconds later."""
    return [{"ops": [(a + d, b + d, c) for a, b, c in chip["ops"]],
             "busy": [(a + d, b + d) for a, b in chip["busy"]],
             "window": tuple(x + d for x in chip["window"])}
            for chip in chips]


def aligned(found: dict, shift: float) -> dict:
    """``found`` (``xplane_join.join``'s, whatever it shifted) with the
    device's clock ``shift`` seconds from the capture's own."""
    d = shift - found.get("device_shift_s", 0.0)
    return dict(found, chips=moved(found["chips"], d),
                device_shift_s=shift)


def inside(spans: dict, names, within: list[tuple]) -> list[tuple]:
    """The spans of ``names`` that lie inside one of the disjoint,
    sorted intervals ``within``."""
    starts = [w[0] for w in within]
    out = []
    for name in names:
        for s in spans.get(name, ()):
            i = bisect.bisect_right(starts, s[0]) - 1
            if i >= 0 and s[1] <= within[i][1]:
                out.append(s)
    return out


def admit_idle(found: dict) -> dict:
    """Idle seconds, averaged over the chips, inside each child of
    ``sched_admit`` and inside the rest of ``sched_idle_ms``'s ``admit``
    phase."""
    spans, n = found["spans"], len(found["chips"])
    out = dict.fromkeys([*CHILDREN, "admit_self"], 0.0)
    for chip in found["chips"]:
        gaps = xplane_join.idle_gaps(chip)

        def idle(rows):
            return xplane_join.overlap(gaps, xplane_join.merged(
                [(s[0], s[1]) for s in rows])) / n
        # children of a recorded parent: the others' idle time stays
        # where sched_idle_ms has it, outside the admit phase
        held = [(s[0], s[1]) for s in spans.get("sched_admit", ())]
        parts = {name: idle(inside(spans, (name,), held))
                 for name in CHILDREN}
        for name, seconds in parts.items():
            out[name] += seconds
        out["admit_self"] += idle(
            [s for name in sched_idle_ms.PHASES["admit"]
             for s in spans.get(name, ())]) - sum(parts.values())
    return out


def reading(ctx: dict) -> dict | None:
    """Every number of this module for the run, read once."""
    if _KEY not in ctx:
        ctx[_KEY] = None
        found = xplane_join.join(ctx)
        if found is not None and launches(found["spans"]):
            path = ctx.get("xplane_path") or xplane_join.find_capture(
                ctx["trace"])
            paired = pair(found["spans"], modules(path)) if path else None
            print(describe(paired), flush=True)
            if paired is not None:
                at = aligned(found, paired["shift"])
                steps = len(inside(at["spans"], STEPS,
                                   [at["chips"][0]["window"]]))
                ctx[_KEY] = {"paired": paired, "aligned": at,
                             "steps": steps, "idle": admit_idle(at)}
    return ctx[_KEY]


def read(ctx: dict, what: str):
    got = reading(ctx)
    if got is None or not got["steps"]:
        return None
    if what == "shift":
        return 1e3 * abs(got["paired"]["shift"])
    if what == "slack":
        return 1e3 * (got["paired"]["hi"] - got["paired"]["lo"])
    return 1e3 * got["idle"][what] / got["steps"]
