"""XLA compilations, counted for the thread that asked for them:
``jit_compiles_total`` and ``jit_compile_seconds_total`` in the asking
component's own registry, and a ring span ``xla_compile`` (lane
``compile``) per compilation under that component's process name.

JAX reports every backend compile (a persistent-cache load included)
through ``jax.monitoring``, on the thread whose call compiled, with the
compiled function's name. The listener is one per process and cannot be
scoped by JAX, so it is scoped here: it does nothing on a thread that
has not called :func:`count_on_this_thread`. The serving engine's
scheduler thread does, so the engine counts the compilations of its own
programs and of nothing else: a trainer, or a second engine, in the same
process is not touched (no ``serving`` span in a trainer's dump, and N
in-process replicas add up on the router as N subprocesses would).

The operator's reading: both counters go flat after warm-up, and a step
in them under traffic is a request that recompiled (PR 21 found the
second request ever served recompiling prefill, PR 23 the copy-on-write
program compiling inside the ramp, both by accident).
"""

from __future__ import annotations

import contextlib
import threading
import time

from .registry import Registry
from .trace import add_span

#: the jax.monitoring event a backend compile (or cache load) ends with
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_listening = False
_thread = threading.local()


def counters(registry: Registry):
    """The two counters, registered in ``registry`` (get-or-create)."""
    return (
        registry.counter(
            "jit_compiles_total",
            "XLA backend compilations (persistent-cache loads included) "
            "asked for by this component's thread: flat after warm-up, or "
            "a request recompiled"),
        registry.counter(
            "jit_compile_seconds_total",
            "seconds this component's thread spent in XLA backend "
            "compilation"))


def _on_duration(event: str, duration: float, **kw) -> None:
    sink = getattr(_thread, "sink", None)
    if sink is None or event != BACKEND_COMPILE_EVENT:
        return
    registry, process = sink
    c_compiles, c_seconds = counters(registry)
    with registry.atomic():
        c_compiles.inc()
        c_seconds.inc(duration)
    t1 = time.perf_counter()
    add_span("xla_compile", t1 - duration, t1, process=process,
             lane="compile", fun_name=str(kw.get("fun_name", "")))


def count_on_this_thread(registry: Registry, process: str) -> None:
    """From now on, and for as long as the calling thread lives, count
    its compilations into ``registry`` and record them as ``process``'s
    spans. Registers the process's one listener on first use."""
    global _listening
    counters(registry)
    _thread.sink = (registry, process)
    with _lock:
        if not _listening:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


@contextlib.contextmanager
def counting(registry: Registry, process: str):
    """:func:`count_on_this_thread` for the length of a ``with`` block:
    the calling thread's compilations inside it are ``registry``'s, and
    afterwards whoever's they were before (a caller that compiles a
    component's programs on its own thread before the component's thread
    exists)."""
    before = getattr(_thread, "sink", None)
    count_on_this_thread(registry, process)
    try:
        yield
    finally:
        _thread.sink = before
