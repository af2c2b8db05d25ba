"""Record the small serving capture the xplane_join tests read (run once
on the chip: ``chiprun -- python3 benchmark/tests/record_sched_fixture.py``).

A two-layer decoder at rehearsal width (hidden 128, 2 heads x 64 so the
paged Pallas decode kernel compiles, vocabulary 1000) is exported paged
and served by the program's engine under a closed loop of clients for a
second of tracing; the Python tracer is off so the file stays small. The
capture holds the scheduler's spans on ``/host:CPU`` and the decode
program's operations on ``/device:TPU:0``. Prints what the capture shows
of the names the program gives (kernels, programs, scopes)."""

import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import trace_reduce
from benchmark.readers import xplane_join
from distributed_tensorflow_example_tpu.models.gpt import GPT, GPTConfig
from distributed_tensorflow_example_tpu.obs.trace import arm_always_on
from distributed_tensorflow_example_tpu.serving import (export_generator,
                                                        load_stepwise)
from distributed_tensorflow_example_tpu.serving_batch import GenerationEngine

SLOTS, BLOCK, PROMPT, NEW, VOCAB = 8, 128, 128, 32, 1000


def trim(src: str, dst: str) -> None:
    """Keep what the readers read, so the fixture stays under 1 MB: the
    chip's ``XLA Ops`` and ``XLA Modules`` lines, and of the host plane
    the events named like the program's spans."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = xplane_pb2.XSpace()
    for plane in space.planes:
        host = plane.name == "/host:CPU"
        if not host and not plane.name.startswith("/device:TPU:"):
            continue
        new = keep.planes.add()
        new.CopyFrom(plane)
        del new.lines[:]
        used = set()
        for line in plane.lines:
            if host:
                events = [e for e in line.events if xplane_join._SPAN_NAME
                          .match(plane.event_metadata[e.metadata_id].name)]
            elif line.name in ("XLA Ops", "XLA Modules"):
                events = list(line.events)
            else:
                continue
            if events:
                kept = new.lines.add()
                kept.CopyFrom(line)
                del kept.events[:]
                kept.events.extend(events)
                used.update(e.metadata_id for e in events)
        for key in [k for k in new.event_metadata if k not in used]:
            del new.event_metadata[key]
    with open(dst, "wb") as f:
        f.write(keep.SerializeToString())

model = GPT(GPTConfig(vocab_size=VOCAB, hidden=128, layers=2, heads=2,
                      intermediate=256, max_len=256, dropout=0.0),
            dtype=jnp.bfloat16)
work = tempfile.mkdtemp()
export_generator(model, model.init(jax.random.key(0)), work, ragged=True,
                 stepwise=True, paged=True, slots=SLOTS, block_size=BLOCK,
                 prompt_len=PROMPT, max_new_tokens=NEW,
                 platforms=(jax.default_backend(),))
arm_always_on()
eng = GenerationEngine(load_stepwise(work), prefix_cache=False).start()
rs = np.random.RandomState(0)
eng.generate(rs.randint(1, VOCAB, 100).astype(np.int32), max_new=4)
stop = threading.Event()


def client(i):
    r = np.random.RandomState(i)
    while not stop.is_set():
        eng.generate(r.randint(1, VOCAB, int(r.randint(8, PROMPT)))
                     .astype(np.int32), max_new=int(r.randint(4, NEW)))


threads = [threading.Thread(target=client, args=(i,)) for i in range(16)]
for t in threads:
    t.start()
time.sleep(1.0)


def capture(name, seconds, host_level):
    trace_dir = os.path.join(work, name)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = host_level
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    time.sleep(seconds)
    jax.profiler.stop_trace()
    return trace_reduce.find_xplane(trace_dir)


# the first capture of a process reads the device ~0.5 ms late against
# the host (PERF.md section 7); the second, trimmed, is the fixture
# (under 1 MB); the third says what a default capture holds besides
paths = {name: capture(name, seconds, level) for name, seconds, level in
         [("sched_tpu_first_100ms", 0.1, 1), ("sched_tpu_250ms", 0.25, 1),
          ("sched_tpu_250ms_level2", 0.25, 2)]}
stop.set()
for t in threads:
    t.join()
print("stats", eng.stats())
eng.close()

out = os.path.join("chiprun_out", "fixture")
os.makedirs(out, exist_ok=True)
trim(paths["sched_tpu_250ms"], os.path.join(out, "sched_tpu.xplane.pb"))
paths["sched_tpu"] = os.path.join(out, "sched_tpu.xplane.pb")
from jax.profiler import ProfileData
for name, path in paths.items():
    if name != "sched_tpu":
        shutil.copy(path, os.path.join(out, name + ".xplane.pb"))
    print("==", name, os.path.getsize(path), "bytes")
    print("\n".join(trace_reduce.describe(path))[:5000])
    reduced = trace_reduce.reduce(path)
    print("modules", {k: len(v) for k, v in reduced["modules"].items()})
    print("all_ops", sorted(reduced["all_ops"]))
    found = xplane_join.parse(path)
    print(xplane_join.describe(found) if found else "no device plane")
    # what an XLA Ops event carries besides its text
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    seen = set()
                    for e in line.events:
                        code = trace_reduce.opcode(e.name)
                        if code in ("custom-call", "while") \
                                and code not in seen:
                            seen.add(code)
                            print(e.name[:400], dict(e.stats))
