"""What a scheduler span costs this host inside and outside a profiler
session (PR 37, second round): ``with span(...)`` bare and with
arguments, ``note`` (``TraceAnnotation.set_metadata``), the bare
annotation, a plain Python call; us a piece over N pieces, the session's
Python tracer on (``jax.profiler.start_trace``'s default, what
``benchmark.run --trace 1`` uses).

    python3 benchmark/records/pr37/span_cost.py [N]
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax

from distributed_tensorflow_example_tpu.obs.trace import arm_always_on, span

N = int(sys.argv[1]) if len(sys.argv) > 1 else 20000
arm_always_on()
Ann = jax.profiler.TraceAnnotation


def plain():
    pass


def bare_span():
    with span("sched_admit", lane="scheduler"):
        pass


def span_with_args():
    with span("admit_read", lane="scheduler", program="prefill", seq=7):
        pass


def span_then_note():
    with span("admit_launch", lane="scheduler") as s:
        s.note(program="prefill", seq=7)


def annotation():
    with Ann("admit_emit"):
        pass


def annotation_with_args():
    with Ann("admit_read", program="prefill", seq=7):
        pass


def annotation_then_metadata():
    with Ann("admit_launch") as a:
        a.set_metadata(program="prefill", seq=7)


PIECES = (plain, bare_span, span_with_args, span_then_note, annotation,
          annotation_with_args, annotation_then_metadata)


def price():
    out = {}
    for piece in PIECES:
        t = time.perf_counter()
        for _ in range(N):
            piece()
        out[piece.__name__] = round(1e6 * (time.perf_counter() - t) / N, 3)
    return out


print(json.dumps({"session": False, "us": price()}), flush=True)
jax.profiler.start_trace(tempfile.mkdtemp())
try:
    print(json.dumps({"session": True, "us": price()}), flush=True)
finally:
    jax.profiler.stop_trace()
print(json.dumps({"session": False, "us": price()}), flush=True)
