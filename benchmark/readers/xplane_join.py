"""The profiler capture's host spans beside its device operations: what
the scheduler did while the chip was idle.

The program's live spans (``obs/trace.py``) enter a
``jax.profiler.TraceAnnotation``, so inside the traced window they land
on the capture's ``/host:CPU`` plane on the timebase of the
``/device:TPU:N`` planes, with their arguments as event stats. The
harness hands a reader the REDUCED trace, not the file; until it puts
the path into ``ctx`` this helper finds the capture itself: the newest
``<tempdir>/benchmark_run_*/trace/plugins/profile/*/*.xplane.pb`` (this
process wrote it a moment ago), accepted only if ``trace_reduce.reduce``
of it gives exactly the ``busy_s`` and ``window_s`` of ``ctx["trace"]``.
The file is parsed once and the result cached on ``ctx``. A capture that cannot be found, or
holds no span of the program (the parent of PR 24 records none), gives
``None``, and every metric built on it is left out.

``join(ctx)`` yields:

- ``spans``: ``{name: [(start_s, end_s, stats), ...]}``, the host events
  whose name is a plain identifier (the program's span names are; the
  runtime's own and the Python tracer's are not), sorted by start;
- ``chips``: per chip ``busy`` (the merged intervals in which an
  outermost operation ran), ``window`` (first start, last end) and
  ``ops`` (``(start_s, end_s, opcode)`` of every operation at any depth);
- ``device_shift_s``: what was added to the device's clock so that no
  decode program starts before the span that dispatched it
  (:func:`causal_shift`; 0.0 in most captures);
- ``raw_one_while``: ``(one, of)``, how many ``decode_step`` spans held
  exactly one device ``while`` BEFORE that shift: the agreement of the
  two clocks as the capture has them. After a shift the same count is
  true by construction and proves nothing; ``readers/join_check.py``
  publishes both, so that a forced join can be told from a found one.
  A shift moves idle time between ``launch`` and ``wait_logits`` and
  nowhere else: their sum does not depend on it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import tempfile

from benchmark import trace_reduce

_KEY = "_xplane_join"
_SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


def find_capture(reduced: dict) -> str | None:
    """The capture this run's reduced trace was made from, or None:
    the newest under the temp directory, if it reduces to ``reduced``."""
    found = glob.glob(os.path.join(
        tempfile.gettempdir(), "benchmark_run_*", "trace", "plugins",
        "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    try:
        r = trace_reduce.reduce(path)
    except Exception:           # a torn file of another run: not ours
        return None
    same = (r["busy_s"] == reduced["busy_s"]
            and r["window_s"] == reduced["window_s"])
    return path if same else None


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def parse(path: str) -> dict | None:
    from jax.profiler import ProfileData
    spans: dict = {}
    chips = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if _SPAN_NAME.match(name):
                        start = e.start_ns * 1e-9
                        spans.setdefault(name, []).append(
                            (start, start + e.duration_ns * 1e-9,
                             dict(e.stats)))
        elif plane.name.startswith("/device:TPU:"):
            line = next((ln for ln in plane.lines
                         if ln.name == "XLA Ops"), None)
            ops = [(e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9,
                    trace_reduce.opcode(e.name))
                   for e in (line.events if line is not None else ())]
            if ops:
                ops.sort()
                busy = merged([(a, b) for a, b, _ in ops])
                chips.append({"busy": busy, "ops": ops,
                              "window": (busy[0][0], busy[-1][1])})
    if not chips:
        return None
    for events in spans.values():
        events.sort(key=lambda s: s[0])
    return align({"spans": spans, "chips": chips})


def causal_shift(found: dict, name: str = "decode_step") -> float:
    """Seconds to add to the device's clock so that cause precedes effect.

    A capture's host and device timelines agree to about a millisecond,
    not better, and differently in each capture (PERF.md section 7): a
    run can show every decode program starting some tenths of a
    millisecond BEFORE the ``decode_step`` span that dispatched it. Each
    device ``while`` is matched to the span whose start is nearest its
    own; if some ``while`` starts before its span (or ends after it), the
    smallest shift that puts every one inside is returned, and 0.0 where
    none is needed or none would do."""
    chip = found["chips"][0]
    spans = found["spans"].get(name, ())
    starts = [s[0] for s in spans]
    lead, lag = [], []
    for a, b, code in chip["ops"] if spans else ():
        if code != "while":
            continue
        i = bisect.bisect_left(starts, a)
        span = min(spans[max(0, i - 1):i + 1], key=lambda s: abs(a - s[0]))
        # a while whose span began before the capture did has no span
        if -0.25 * (span[1] - span[0]) <= a - span[0] <= span[1] - span[0]:
            lead.append(a - span[0])
            lag.append(span[1] - b)
    if not lead:
        return 0.0
    early, late = -min(lead), -min(lag)
    if early > 0 and early <= min(lag):
        return early
    if late > 0 and late <= min(lead):
        return -late
    return 0.0


def align(found: dict) -> dict:
    """``found`` with the device's clock shifted by :func:`causal_shift`
    (kept as ``device_shift_s``, beside ``raw_one_while``: the spans with
    exactly one ``while`` as the capture's own clocks had them)."""
    found["raw_one_while"] = steps_with_one_while(found)
    shift = causal_shift(found)
    found["device_shift_s"] = shift
    if shift:
        for chip in found["chips"]:
            chip["ops"] = [(a + shift, b + shift, c)
                           for a, b, c in chip["ops"]]
            chip["busy"] = [(a + shift, b + shift)
                            for a, b in chip["busy"]]
            chip["window"] = tuple(x + shift for x in chip["window"])
    return found


def join(ctx: dict) -> dict | None:
    """Parsed once per run; ``None`` where there is nothing to join."""
    if _KEY not in ctx:
        found = None
        path = ctx.get("xplane_path") or find_capture(ctx["trace"])
        if path is not None:
            found = parse(path)
            if found is not None:
                print(describe(found), flush=True)
        ctx[_KEY] = found
    return ctx[_KEY]


def idle_gaps(chip: dict) -> list[tuple[float, float]]:
    busy = chip["busy"]
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def overlap(gaps: list[tuple[float, float]],
            spans: list[tuple[float, float]]) -> float:
    """Seconds of ``gaps`` that lie inside ``spans`` (both disjoint and
    sorted)."""
    total, starts = 0.0, [s[0] for s in spans]
    for lo, hi in gaps:
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(spans) and spans[i][0] < hi:
            total += max(0.0, min(hi, spans[i][1]) - max(lo, spans[i][0]))
            i += 1
    return total


def ops_inside(chip: dict, spans: list[tuple], opcode: str
               ) -> list[list[tuple[float, float]]]:
    """For each span, the chip's operations of ``opcode`` that start
    inside it."""
    ops = [(a, b) for a, b, code in chip["ops"] if code == opcode]
    starts = [a for a, _ in ops]
    return [ops[bisect.bisect_left(starts, s[0]):
                bisect.bisect_left(starts, s[1])] for s in spans]


def steps_and_whiles(found: dict, name: str = "decode_step"):
    """The ``name`` spans inside the first chip's window, each beside
    the device ``while`` operations that start inside it."""
    chip = found["chips"][0]
    lo, hi = chip["window"]
    spans = [s for s in found["spans"].get(name, ())
             if s[0] >= lo and s[1] <= hi]
    return list(zip(spans, ops_inside(chip, spans, "while")))


def steps_with_one_while(found: dict, name: str = "decode_step"
                         ) -> tuple[int, int]:
    """How many ``name`` spans inside the device's window hold exactly
    one device ``while`` (the decode program has one: the scan over
    layers), and how many spans there are: the proof that the host's
    and the device's clocks agree."""
    pairs = steps_and_whiles(found, name)
    return sum(1 for _, ops in pairs if len(ops) == 1), len(pairs)


def describe(found: dict) -> str:
    """One line for the run's log: the agreement of the clocks (a device
    ``while`` cannot start before its step's span does), and the spans
    found."""
    one, of = steps_with_one_while(found)
    lead, lag = [], []
    for span, ops in steps_and_whiles(found):
        if len(ops) == 1:
            lead.append(1e3 * (ops[0][0] - span[0]))
            lag.append(1e3 * (span[1] - ops[0][1]))
    mid = len(lead) // 2
    edges = (f"median {sorted(lead)[mid]:.3f} ms from a step's start to "
             f"its while, {sorted(lag)[mid]:.3f} ms from the while's end "
             f"to the step's; " if lead else "")
    names = {k: len(v) for k, v in sorted(found["spans"].items())}
    raw = found.get("raw_one_while", (one, of))
    return (f"xplane_join: {of} decode_step spans inside the device "
            f"window, {one} hold exactly one device while ({raw[0]} of "
            f"{raw[1]} before the device clock was shifted "
            f"{1e3 * found.get('device_shift_s', 0.0):+.3f} ms); "
            f"{edges}host spans {names}")
