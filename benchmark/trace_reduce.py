"""From a profiler trace (``.xplane.pb``) to the numbers metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU
trace holds one plane per chip (``/device:TPU:N``) whose ``XLA Ops`` line
carries every operation as an event (nested: a ``while`` holds its body's
operations) and whose ``XLA Modules`` line carries one event per executed
program. The reduction keeps, per chip and averaged over the chips:

- ``busy_s``: the union of the intervals in which an operation ran;
- ``window_s``: first operation's start to last operation's end;
- ``top_ops``: seconds by operation, outermost operations only, so the
  rows add up to the busy time;
- ``all_ops``: seconds and calls by operation at any depth (a kernel
  inside a scan is found here); ``opcodes``: the same by HLO opcode
  (``custom-call`` is every Pallas kernel);
- ``modules``: durations of each executed program;
- ``idle_gaps``: idle seconds by the operation they followed.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

_SUFFIX = re.compile(r"([._]\d+)+$")


def op_name(raw: str) -> str:
    """``%fusion.123`` -> ``fusion``; ``%copy-done.4`` -> ``copy-done``."""
    name = raw.strip().lstrip("%")
    name = name.split(" = ")[0].split("(")[0]
    return _SUFFIX.sub("", name) or raw


_OPCODE = re.compile(r"^%?[\w.\-]+ = .*?\)?\}? ([a-z][\w\-]*)\(")


def opcode(raw: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event, whose name is the
    instruction's text: ``%x.1 = f32[8]{0} custom-call(...)`` ->
    ``custom-call``. A bare name is returned through :func:`op_name`."""
    m = _OPCODE.match(raw.strip())
    return m.group(1) if m else op_name(raw)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _line(plane, *names):
    for line in plane.lines:
        if line.name in names:
            return line
    return None


def reduce_plane(events: list[tuple[float, float, str]]) -> dict:
    """``events``: (start_s, duration_s, name[, opcode]) of one chip's
    ``XLA Ops`` line."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    codes: dict = defaultdict(lambda: {"seconds": 0.0, "calls": 0})
    for e in events:
        code = codes[e[3] if len(e) > 3 else e[2]]
        code["seconds"] += e[1]
        code["calls"] += 1
    top: dict = defaultdict(float)
    all_s: dict = defaultdict(float)
    all_n: dict = defaultdict(int)
    gaps: dict = defaultdict(float)
    busy = 0.0
    cur_end = None
    cur_name = "window_start"
    first = events[0][0] if events else 0.0
    last = first
    for start, dur, name, *_ in events:
        end = start + dur
        all_s[name] += dur
        all_n[name] += 1
        if cur_end is None or start >= cur_end:      # an outermost event
            if cur_end is not None and start > cur_end:
                gaps[cur_name] += start - cur_end
            top[name] += dur
            busy += dur
            cur_end, cur_name = end, name
        elif end > cur_end:        # overlaps past its parent: count the rest
            busy += end - cur_end
            top[name] += end - cur_end
            cur_end, cur_name = end, name
        last = max(last, end)
    return {"busy_s": busy, "window_s": last - first, "top_ops": dict(top),
            "all_ops": {k: {"seconds": all_s[k], "calls": all_n[k]}
                        for k in all_s},
            "opcodes": dict(codes), "idle_gaps": dict(gaps)}


def reduce(path: str) -> dict:
    """The whole trace file: per-chip reductions averaged over the chips
    that ran anything. No device plane with operations -> ``chips`` 0."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_chip, modules = [], defaultdict(list)
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = _line(plane, "XLA Ops")
        if ops is None:
            continue
        events = [(e.start_ns * 1e-9, e.duration_ns * 1e-9, op_name(e.name),
                   opcode(e.name)) for e in ops.events]
        if not events:
            continue
        per_chip.append(reduce_plane(events))
        mods = _line(plane, "XLA Modules")
        for e in (mods.events if mods is not None else ()):
            modules[op_name(e.name)].append(e.duration_ns * 1e-9)
    if not per_chip:
        return {"chips": 0, "busy_s": 0.0, "window_s": 0.0, "top_ops": {},
                "all_ops": {}, "opcodes": {}, "modules": {},
                "idle_gaps": {}}
    n = len(per_chip)

    def mean_dict(key):
        out: dict = defaultdict(float)
        for c in per_chip:
            for k, v in c[key].items():
                out[k] += v / n
        return dict(out)

    def mean_calls(key):
        out: dict = {}
        for c in per_chip:
            for k, v in c[key].items():
                o = out.setdefault(k, {"seconds": 0.0, "calls": 0.0})
                o["seconds"] += v["seconds"] / n
                o["calls"] += v["calls"] / n
        return out

    return {"chips": n,
            "busy_s": sum(c["busy_s"] for c in per_chip) / n,
            "window_s": sum(c["window_s"] for c in per_chip) / n,
            "top_ops": mean_dict("top_ops"),
            "all_ops": mean_calls("all_ops"),
            "opcodes": mean_calls("opcodes"),
            "modules": dict(modules), "idle_gaps": mean_dict("idle_gaps")}


def op_seconds(reduced: dict, opcode: str = "", pattern: str = "") -> float:
    """Device seconds of the operations with HLO ``opcode``, or whose name
    matches ``pattern``, at any depth."""
    if opcode:
        return reduced["opcodes"].get(opcode, {}).get("seconds", 0.0)
    return sum(v["seconds"] for k, v in reduced["all_ops"].items()
               if re.search(pattern, k))


def call_count(reduced: dict, per_module: str = "", per_opcode: str = ""
               ) -> float:
    """How many executed programs match ``per_module``; or, where programs
    cannot be told apart by name, how many operations of ``per_opcode``
    ran."""
    if per_opcode:
        return reduced["opcodes"].get(per_opcode, {}).get("calls", 0)
    return sum(len(v) for k, v in reduced["modules"].items()
               if re.search(per_module, k))


def breakdown(reduced: dict, label: str, limit: int = 10) -> dict:
    """The result line's ``breakdown``: the operations that took most
    device time and the longest idle gaps, by what they followed."""
    def top(d):
        return sorted(d.items(), key=lambda kv: -kv[1])[:limit]
    return {"device_ops": [["_" + k, v] for k, v in top(reduced["top_ops"])],
            "idle_gaps": [[f"{label}_after__{k}", v]
                          for k, v in top(reduced["idle_gaps"])]}


def describe(path: str) -> list[str]:
    """Planes, lines and a few event names: look at a trace by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            names = sorted({e.name for e in ev[:2000]})[:12]
            out.append(f"  line {line.name!r}: {len(ev)} events, e.g. {names}")
    return out
