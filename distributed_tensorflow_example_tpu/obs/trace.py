"""Request-scoped tracing: span API + bounded ring-buffer recorder.

The only tracing the stack had was the *offline* xplane reducer
(utils/trace_summary.py) — good for "where did the compiled step spend
device time", useless for "where did THIS slow request spend its
800 ms" or "is the scheduler starving on prefill vs decode" on a live
server. This module is the live half:

- :func:`span` — ``with span("prefill", lane="slot0",
  request_id=rid):`` records one complete event into the process
  recorder. When tracing is off it returns a shared no-op context
  manager after a single attribute check: zero allocations, zero
  recorder calls (the overhead guard in tests/test_obs.py pins this).
- :class:`TraceRecorder` — a bounded ring buffer (oldest events drop
  first; ``events_dropped`` counts them) holding (process, lane, name,
  t0, t1, args) tuples stamped with ``time.perf_counter()``.
  ``add()`` takes explicit timestamps so retroactive spans work — the
  scheduler records a request's queue-wait AT admission, from its
  submit stamp.
- :class:`ChromeTraceWriter` — the ONE chrome/Perfetto trace-event
  emitter: process/thread name metadata ("M") events plus complete
  ("X") events in microseconds. Both this recorder's dump and
  ``trace_summary.py --chrome`` (the xplane producer) write through
  it, so the two producers can never disagree on the format.

Lanes are (process, thread) string pairs — e.g. ``("serving",
"slot3")`` or ``("training", "data")`` — mapped to stable pid/tid
integers at dump time. The scheduler gives every cache slot its own
lane so per-slot spans tile without overlapping; Perfetto renders each
as one row.

Round 17 — distributed tracing + the always-on ring:

- :class:`TraceContext` carries a W3C-``traceparent``-shaped context
  (``00-<trace_id:32hex>-<span_id:16hex>-<01|00>``) across process
  boundaries: the fleet router opens one root context per client
  request and forwards a child context per attempt; the replica
  parents its engine spans under it (``trace_id``/``parent_id`` span
  args), so the fleet stitcher (obs/stitch.py) can reassemble one
  timeline per request.
- The recorder gains **per-process drain** (:meth:`TraceRecorder.
  drain` — ``GET /trace/export`` empties only the exporting server's
  process label, so N in-process replicas sharing the ring never steal
  each other's spans) and a non-destructive :meth:`TraceRecorder.tail`
  (the flight recorder's last-N-spans bundle source).
- The flight recorder (obs/flightrec.py) runs the ring ALWAYS-ON:
  servers arm it at construction (without clearing a capture someone
  else armed) instead of waiting for ``POST /trace/start``, so an
  incident bundle always has history. The armed per-call cost is one
  lock + deque append — bounded by the same <2 µs/call guard as the
  disabled path (tests/test_obs.py).
- :func:`process_span_stats` accumulates recorded/dropped counts
  across every recorder this process ever armed — the tier-1 TRACE
  banner's data source (tests/conftest.py).
"""

from __future__ import annotations

import dataclasses
import secrets
import sys
import threading
import time
from collections import deque
from typing import Any

# process-wide span accounting for the tier-1 TRACE banner: survives
# recorder swaps (set_recorder) the way the registry's name accumulator
# survives engine teardown. Updated inside the recorder lock.
_SPAN_TOTALS = {"recorded": 0, "dropped": 0}


def process_span_stats() -> dict[str, int]:
    """{"recorded": N, "dropped": M} across every recorder this process
    armed — the TRACE line in the tier-1 telemetry banner."""
    return dict(_SPAN_TOTALS)


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """One hop of a distributed trace: the ``traceparent`` triple.

    ``trace_id`` names the whole client request fleet-wide;
    ``span_id`` names the SENDER's span (the receiver's parent);
    ``sampled`` is the propagated record/don't-record decision (the
    router's ``--trace_sample`` draw — an unsampled context still
    carries the ids so logs correlate, but receivers attach no span
    args for it)."""

    trace_id: str
    span_id: str
    sampled: bool = True

    def child(self) -> "TraceContext":
        """Same trace, fresh span id — one per forward attempt."""
        return TraceContext(self.trace_id, new_span_id(), self.sampled)

    def to_traceparent(self) -> str:
        return (f"00-{self.trace_id}-{self.span_id}-"
                f"{'01' if self.sampled else '00'}")

    def span_args(self) -> dict[str, str]:
        """The args a receiver merges into spans recorded under this
        context ({} when unsampled) — trace_id groups, parent_id
        parents."""
        if not self.sampled:
            return {}
        return {"trace_id": self.trace_id, "parent_id": self.span_id}


def new_trace_id() -> str:
    return secrets.token_hex(16)


def new_span_id() -> str:
    return secrets.token_hex(8)


def parse_traceparent(header: str | None) -> TraceContext | None:
    """``traceparent`` header -> :class:`TraceContext`, or None for a
    missing/malformed value (propagation is best-effort: a garbled
    header must degrade to local-only tracing, never to a 4xx)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16), int(flags, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id, span_id,
                        sampled=bool(int(flags, 16) & 1))


class ChromeTraceWriter:
    """Builds a chrome://tracing / Perfetto trace-event JSON dict.

    Shared by the live recorder and the offline xplane converter: call
    :meth:`pid` / :meth:`tid` to name processes/threads (metadata
    events are emitted once per name) and :meth:`complete` per "X"
    event; :meth:`to_dict` yields the loadable object.
    """

    def __init__(self):
        self.events: list[dict[str, Any]] = []
        self._pids: dict[str, int] = {}
        self._tids: dict[tuple[int, str], int] = {}

    def pid(self, process_name: str) -> int:
        p = self._pids.get(process_name)
        if p is None:
            p = len(self._pids) + 1
            self._pids[process_name] = p
            self.events.append({"ph": "M", "pid": p,
                                "name": "process_name",
                                "args": {"name": process_name}})
        return p

    def tid(self, pid: int, thread_name: str) -> int:
        key = (pid, thread_name)
        t = self._tids.get(key)
        if t is None:
            t = sum(1 for (p, _) in self._tids if p == pid) + 1
            self._tids[key] = t
            self.events.append({"ph": "M", "pid": pid, "tid": t,
                                "name": "thread_name",
                                "args": {"name": thread_name}})
        return t

    def complete(self, *, pid: int, tid: int, name: str, ts_us: float,
                 dur_us: float, args: dict | None = None) -> None:
        ev: dict[str, Any] = {"ph": "X", "pid": pid, "tid": tid,
                              "name": name, "ts": ts_us,
                              # Perfetto drops true-zero durations
                              "dur": max(dur_us, 0.001)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def to_dict(self) -> dict[str, Any]:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}


class TraceRecorder:
    """Bounded in-memory span store. ``start()`` arms it and anchors
    the timebase; ``stop()`` disarms; ``to_chrome()`` dumps whatever
    the ring currently holds (callable while armed — a live snapshot).
    Thread-safe: spans arrive from scheduler/HTTP/trainer threads."""

    def __init__(self, max_events: int = 65536):
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.max_events = max_events
        self._buf: deque[tuple] = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self.enabled = False
        self._t0 = 0.0
        self.spans_recorded = 0
        self.events_dropped = 0

    def start(self) -> None:
        with self._lock:
            self._buf.clear()
            self._t0 = time.perf_counter()
            self.spans_recorded = 0
            self.events_dropped = 0
            self.enabled = True

    def stop(self) -> None:
        self.enabled = False

    def add(self, process: str, lane: str, name: str, t0: float,
            t1: float, args: dict | None = None) -> None:
        """One complete span, ``t0``/``t1`` in ``time.perf_counter()``
        seconds. Spans that began before ``start()`` are clamped to the
        capture window (a queue-wait recorded retroactively must not
        render at negative timestamps)."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.events_dropped += 1
                _SPAN_TOTALS["dropped"] += 1
            self._buf.append((process, lane, name, max(t0, self._t0),
                              max(t1, self._t0), args))
            self.spans_recorded += 1
            _SPAN_TOTALS["recorded"] += 1

    def drain(self, process: str | None = None) -> list[tuple]:
        """Remove and return spans (sorted by start time) — ALL of
        them, or only one ``process`` label's. Per-process drain is the
        ``GET /trace/export`` contract: N in-process replicas share ONE
        ring (distinct labels), and each export must empty only its own
        lane group. Draining does not disarm."""
        with self._lock:
            if process is None:
                items = list(self._buf)
                self._buf.clear()
            else:
                items = [it for it in self._buf if it[0] == process]
                if items:
                    keep = [it for it in self._buf if it[0] != process]
                    self._buf.clear()
                    self._buf.extend(keep)
        return sorted(items, key=lambda it: it[3])

    def tail(self, n: int, process: str | None = None) -> list[tuple]:
        """The newest ``n`` spans (optionally one process label's),
        WITHOUT removing them — the flight recorder's bundle source
        (an incident dump must not eat the capture an operator might
        still export)."""
        with self._lock:
            items = [it for it in self._buf
                     if process is None or it[0] == process]
        items.sort(key=lambda it: it[3])
        return items[-n:] if n > 0 else []

    def to_chrome(self) -> dict[str, Any]:
        """Ring contents as chrome trace-event JSON (via the shared
        :class:`ChromeTraceWriter`). Lanes become threads; events sort
        by timestamp inside the dump so truncated rings still render
        coherently."""
        with self._lock:
            items = sorted(self._buf, key=lambda it: it[3])
            t0 = self._t0
            dropped = self.events_dropped
        w = ChromeTraceWriter()
        for process, lane, name, s, e, args in items:
            pid = w.pid(process)
            tid = w.tid(pid, lane)
            w.complete(pid=pid, tid=tid, name=name,
                       ts_us=(s - t0) * 1e6, dur_us=(e - s) * 1e6,
                       args=args)
        out = w.to_dict()
        out["metadata"] = {"events_dropped": dropped,
                           "max_events": self.max_events}
        return out


class _NoopSpan:
    """The disabled fast path: one shared instance, enter/exit do
    nothing. ``span()`` hands this back after a single enabled check —
    no allocation, no recorder traffic."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args):
        pass


_NOOP = _NoopSpan()


_annotation_cls = None


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` once this process has imported
    JAX's profiler, else None: a process that never did (the fleet
    router) has no profiler session a span could land in, and must not
    pay JAX's import for one."""
    global _annotation_cls
    if _annotation_cls is None:
        mod = sys.modules.get("jax.profiler")
        _annotation_cls = getattr(mod, "TraceAnnotation", None)
    return _annotation_cls


class _LiveSpan:
    """One live span: ring stamps on ``perf_counter`` (flight recorder,
    ``/trace/export``, the stitcher), and for its duration a
    ``jax.profiler.TraceAnnotation`` of the same name and args, so
    inside a profiler session the span lands on the capture's
    ``/host:CPU`` plane on the device planes' timebase. Outside a
    session the annotation is one atomic check."""

    __slots__ = ("_rec", "_process", "_lane", "_name", "_args", "_t0",
                 "_ann")

    def __init__(self, rec, process, lane, name, args):
        self._rec = rec
        self._process = process
        self._lane = lane
        self._name = name
        self._args = args
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        cls = _annotation_cls or _profiler_annotation()
        if cls is not None:
            self._ann = cls(self._name, **self._args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def note(self, **args):
        """Arguments known only once the span is under way (which
        launch of its program a scheduler span held): into the ring's
        args and onto the live annotation, so a capture holds them
        too."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec.add(self._process, self._lane, self._name, self._t0,
                      t1, self._args or None)
        return False


# the process recorder: one per process, disabled until someone calls
# recorder().start() (the POST /trace/start route, a trainer with
# obs.trace_path, or a test)
_recorder = TraceRecorder()


def recorder() -> TraceRecorder:
    return _recorder


def set_recorder(rec: TraceRecorder) -> TraceRecorder:
    """Swap the process recorder (the server does this to honor
    ``--trace_buffer_events``); returns the new one."""
    global _recorder
    _recorder = rec
    return rec


def ensure_capacity(max_events: int) -> TraceRecorder:
    """Resize the process recorder to ``max_events`` — UNLESS a capture
    is currently armed (another server/trainer in this process owns it;
    swapping would silently discard its spans). The one owner of this
    check-then-swap invariant; both ``--trace_buffer_events`` call
    sites go through it. Returns the (possibly unchanged) recorder."""
    rec = _recorder
    if rec.max_events != max_events and not rec.enabled:
        return set_recorder(TraceRecorder(max_events))
    return rec


def arm_always_on(max_events: int = 65536) -> TraceRecorder:
    """The flight-recorder arming path: size the process recorder (the
    usual armed-capture guard applies) and START it — unless a capture
    is already armed, which must not be cleared out from under its
    owner (a second in-process server, or an operator's live
    ``POST /trace/start`` capture). Idempotent."""
    rec = ensure_capacity(max_events)
    if not rec.enabled:
        rec.start()
    return rec


def span(name: str, *, process: str = "serving", lane: str = "main",
         **args):
    """Context manager recording one complete event on ``(process,
    lane)``. Extra keyword args (``request_id=...``) land in the
    event's ``args`` — the request-correlation hook."""
    rec = _recorder
    if not rec.enabled:
        return _NOOP
    return _LiveSpan(rec, process, lane, name, args)


def add_span(name: str, t0: float, t1: float, *, process: str = "serving",
             lane: str = "main", **args) -> None:
    """Retroactive span with explicit perf_counter stamps (queue-wait
    is only known at admission). Same disabled fast path as
    :func:`span`."""
    rec = _recorder
    if not rec.enabled:
        return
    rec.add(process, lane, name, t0, t1, args or None)
