"""Record the two small serving captures ``test_launch_pairs.py`` reads
(run once on the chip: ``chiprun -- python3
benchmark/tests/record_pair_fixture.py``).

``pair_tpu.xplane.pb``: the run of ``record_sched_fixture.py`` shaped as
``gpt2s-serve-backlog`` is (a two-layer decoder at rehearsal width, 8
slots under a closed loop of 16 clients, the prefix cache on, so every
cold prefill is followed by a copy-on-write copy): programs ``prefill``,
``decode`` and ``copy``. ``pair_state_tpu.xplane.pb``: the Kimi cell's
rehearsal artifact (``kimi_linear_tiny``, chunks of 32) under the same
loop: programs ``zero_slot``, ``prefill_chunk`` and ``decode``, none
with a ``while`` the older join could anchor on. Each is captured at
several lengths with the Python tracer off, trimmed to what the readers
read, and the longest under 1 MB is kept. Prints what
``readers/launch_pairs.py`` makes of each."""

import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import trace_reduce, weights_by_range
from benchmark.manifest import load_module
from benchmark.readers import launch_pairs, xplane_join
from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.models.gpt import GPT, GPTConfig
from distributed_tensorflow_example_tpu.obs.trace import arm_always_on
from distributed_tensorflow_example_tpu.serving import (export_generator,
                                                        load_stepwise)
from distributed_tensorflow_example_tpu.serving_batch import GenerationEngine

LIMIT = 1_000_000
OUT = os.path.join("chiprun_out", "fixture")


def trim(src: str, dst: str) -> None:
    """``record_sched_fixture.trim``: the chip's ``XLA Ops`` and ``XLA
    Modules`` lines, and of the host plane the events named like the
    program's spans (their stats, ``program`` and ``seq`` among them,
    stay). Cut further, because a state program's ~1,400
    operations are 1.1 MB of text before any event: an operation's text
    is cut to what the readers take from it (``%name = _ opcode(``:
    ``trace_reduce.op_name`` and ``opcode`` read the same from it), and
    the operations' own stats (the device's offsets, FLOP counts) go."""
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
        if not host:
            ops = {e.metadata_id for line in new.lines
                   if line.name == "XLA Ops" for e in line.events}
            for key in ops:
                meta = new.event_metadata[key]
                text = meta.name
                meta.name = (f"%{trace_reduce.op_name(text)}.{key} = _ "
                             f"{trace_reduce.opcode(text)}(")
                assert trace_reduce.op_name(meta.name) == \
                    trace_reduce.op_name(text), text
                assert trace_reduce.opcode(meta.name) == \
                    trace_reduce.opcode(text), text
                meta.ClearField("stats")
                meta.ClearField("metadata")
                meta.ClearField("display_name")
            for line in new.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        e.ClearField("stats")
    with open(dst, "wb") as f:
        f.write(keep.SerializeToString())


def capture(work: str, name: str, seconds: float) -> str:
    trace_dir = os.path.join(work, name)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    time.sleep(seconds)
    jax.profiler.stop_trace()
    return trace_reduce.find_xplane(trace_dir)


def record(name: str, eng: GenerationEngine, request, lengths) -> None:
    """``eng`` under 16 clients posting ``request(rng)``; one trimmed
    capture a length, the longest under the limit kept as ``name``."""
    work = tempfile.mkdtemp()
    prompt, max_new = request(np.random.RandomState(0))
    eng.generate(prompt, max_new=max_new)                   # warm
    stop = threading.Event()

    def client(i):
        r = np.random.RandomState(i)
        while not stop.is_set():
            prompt, max_new = request(r)
            eng.generate(prompt, max_new=max_new)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(1, 17)]
    for t in threads:
        t.start()
    time.sleep(1.5)
    capture(work, "first", 0.05)     # a process's first capture is odd
    kept = None
    for seconds in lengths:
        path = capture(work, f"{name}_{seconds}", seconds)
        dst = os.path.join(OUT, f"{name}_{seconds}.xplane.pb")
        trim(path, dst)
        size = os.path.getsize(dst)
        print(f"== {name} {seconds} s: {os.path.getsize(path)} bytes, "
              f"trimmed {size}", flush=True)
        if size < LIMIT:
            kept = dst
    stop.set()
    for t in threads:
        t.join()
    stats = eng.stats()
    print("stats", json.dumps({k: stats[k] for k in (
        "prefills", "prefill_chunks", "decode_steps", "cow_copies",
        "sched_phase_seconds")}))
    eng.close()
    if kept is None:
        raise SystemExit(f"{name}: no capture under {LIMIT} bytes")
    final = os.path.join(OUT, name + ".xplane.pb")
    os.replace(kept, final)
    found = xplane_join.parse(final)
    if found is None:
        print(f"{name}: no device plane (recorded off the chip)")
        return
    print(xplane_join.describe(found))
    paired = launch_pairs.pair(found["spans"], launch_pairs.modules(final))
    print(launch_pairs.describe(paired))
    ctx = {"trace": trace_reduce.reduce(final), "xplane_path": final}
    print({what: launch_pairs.read(ctx, what) for what in (
        "admit_launch", "admit_read", "admit_emit", "admit_self",
        "shift", "slack")}, flush=True)


def gpt_engine() -> tuple:
    slots, block, prompt, new, vocab = 8, 128, 128, 32, 1000
    model = GPT(GPTConfig(vocab_size=vocab, hidden=128, layers=2, heads=2,
                          intermediate=256, max_len=256, dropout=0.0),
                dtype=jnp.bfloat16)
    work = tempfile.mkdtemp()
    export_generator(model, model.init(jax.random.key(0)), work,
                     ragged=True, stepwise=True, paged=True, slots=slots,
                     block_size=block, prompt_len=prompt,
                     max_new_tokens=new,
                     platforms=(jax.default_backend(),))

    def request(r):
        return (r.randint(1, vocab, int(r.randint(8, prompt)))
                .astype(np.int32), int(r.randint(4, new)))
    return GenerationEngine(load_stepwise(work)).start(), request


def state_engine() -> tuple:
    slots, block, chunk, prompt, new = 3, 16, 32, 96, 24
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json")))
    ref = load_module(os.path.join(ROOT, "benchmark", "reference",
                                   "kimi-linear-48b-a3b.py"))
    model = get_model("kimi_linear_tiny", TrainConfig(
        model="kimi_linear_tiny", dtype="float32", param_dtype="float32"))
    for k, v in config["rehearsal"]["model_cfg"].items():
        setattr(model.cfg, k, v)
    params = weights_by_range.make_params(
        ref.param_spec(config["rehearsal"]["sizes"]), 7, "float32")
    work = tempfile.mkdtemp()
    export_generator(model, params, work, ragged=True, stepwise=True,
                     paged=True, slots=slots, block_size=block,
                     prompt_len=prompt, max_new_tokens=new,
                     prefill_chunk=chunk,
                     platforms=(jax.default_backend(),))

    def request(r):
        return (r.randint(0, 384, int(r.randint(8, prompt))).tolist(),
                int(r.randint(4, new)))
    return GenerationEngine(load_stepwise(work)).start(), request


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    arm_always_on()
    record("pair_tpu", *gpt_engine(), lengths=(0.15, 0.25, 0.4))
    record("pair_state_tpu", *state_engine(), lengths=(0.1, 0.2, 0.3))
