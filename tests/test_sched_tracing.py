"""The scheduler's spans on the profiler's clock (PR 24):

- a live span entered under ``jax.profiler.start_trace`` lands on the
  capture's ``/host:CPU`` plane with its arguments (read back through
  ``jax.profiler.ProfileData``); a retroactive ``add_span`` stays
  ring-only,
- an engine run at test size emits the ``sched_*`` phases, they tile
  each working iteration of the scheduler thread, ``sched_dispatch`` and
  ``sched_wait_logits`` sit inside ``decode_step``, the number of spans
  per step is the same at one live slot and at all of them, and
  ``kv_bytes`` is the sum over the live slots' positions,
- the phase counters in ``stats()`` add up to the spans' durations,
- PR 37: an admission that runs a program puts ``admit_launch``,
  ``admit_read`` and ``admit_emit`` inside ``sched_admit`` in that order;
  every launch of a device program carries ``program`` and ``seq`` (one
  pair an executed program, whatever the artifact) onto the profiler's
  host plane; a phase reads the wall clock and no other,
- ``jit_compiles_total`` is flat over a second identical request, and
  the served programs compile under their own names.
"""

import glob
import inspect
import os
import re
import time

import jax
import numpy as np
import pytest

from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.obs.trace import (
    TraceRecorder, add_span, recorder, set_recorder, span)
from distributed_tensorflow_example_tpu.serving import (export_generator,
                                                        load_stepwise)
from distributed_tensorflow_example_tpu import serving_batch
from distributed_tensorflow_example_tpu.serving_batch import (
    ADMIT_CHILDREN, SCHED_PHASES, GenerationEngine)

PROMPT_LEN = 8
MAX_NEW = 6
SLOTS = 4
BLOCK = 4

#: the spans one paged, non-speculative working iteration with a decode
#: step records on the scheduler lane, in order (cow_copy comes and goes
#: with copy-on-write events, not with steps)
STEP_SPANS = ["sched_housekeeping", "sched_admit", "sched_secure_blocks",
              "sched_build_feats", "decode_step", "sched_dispatch",
              "sched_wait_logits", "sched_sample_emit"]
TOP_LEVEL = [n for n in STEP_SPANS
             if n not in ("sched_dispatch", "sched_wait_logits")]
#: what an admission that runs a program adds inside sched_admit
CHILDREN = list(ADMIT_CHILDREN)


@pytest.fixture
def ring():
    old = recorder()
    rec = set_recorder(TraceRecorder())
    rec.start()
    yield rec
    set_recorder(old)


@pytest.fixture(scope="module")
def paged_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("paged"))
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    export_generator(m, m.init(jax.random.key(0)), d,
                     prompt_len=PROMPT_LEN, max_new_tokens=MAX_NEW,
                     batch_size=1, ragged=True, stepwise=True, slots=SLOTS,
                     paged=True, block_size=BLOCK, platforms=("cpu",))
    return d


def _prompts(n, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 1000, (int(rs.randint(2, PROMPT_LEN + 1)),))
            .astype(np.int32) for _ in range(n)]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.duration_ns, dict(e.stats)))
    return out


def test_live_span_lands_on_the_profilers_host_plane(ring, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("decode_step", lane="scheduler", slots=3,
                  kv_bytes=12345):
            with span("sched_dispatch", lane="scheduler"):
                time.sleep(0.002)
        t = time.perf_counter()
        add_span("queue_wait", t - 0.5, t, lane="slot0", request_id="r")
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == "decode_step"]
    assert len(steps) == 1
    assert steps[0][2] == {"slots": 3, "kv_bytes": 12345}
    inner = [e for e in events if e[0] == "sched_dispatch"]
    assert len(inner) == 1 and 2e6 <= inner[0][1] <= steps[0][1]
    # explicit stamps cannot be put on the profiler's clock: ring only
    assert not [e for e in events if e[0] == "queue_wait"]
    assert sorted(s[2] for s in ring.drain()) == [
        "decode_step", "queue_wait", "sched_dispatch"]


def test_span_outside_a_session_and_with_the_ring_off_is_inert(tmp_path):
    old = recorder()
    try:
        rec = set_recorder(TraceRecorder())      # never started
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("decode_step", lane="scheduler", slots=1):
                pass
        finally:
            jax.profiler.stop_trace()
        assert rec.spans_recorded == 0
        assert not [e for e in _host_events(str(tmp_path))
                    if e[0] == "decode_step"]
    finally:
        set_recorder(old)


def _scheduler_lane(rec):
    spans = [s for s in rec.drain() if s[1] == "scheduler"
             and s[2] != "cow_copy"]
    # by start; a parent before the children that share its start
    return sorted(spans, key=lambda s: (s[3], -s[4]))


def _iterations(spans):
    """Scheduler-lane spans grouped into working iterations: each
    starts at a sched_housekeeping."""
    runs = []
    for s in spans:
        if s[2] == "sched_housekeeping":
            runs.append([])
        if runs:
            runs[-1].append(s)
    return runs


def _children_lie_inside_admit(run):
    """One iteration's spans: the admit children lie inside its
    ``sched_admit``, whole admissions of launch, read, emit in that
    order, none overlapping the next. How many admissions."""
    admit = next(s for s in run if s[2] == "sched_admit")
    kids = [s for s in run if s[2] in CHILDREN]
    assert [s[2] for s in kids] == CHILDREN * (len(kids) // 3), kids
    for a, b in zip([admit[:3] + (admit[3], admit[3])] + kids, kids):
        assert a[4] <= b[3] <= b[4] <= admit[4], (a, b, admit)
    return len(kids) // 3


def _run_engine(paged_dir, prompts):
    eng = GenerationEngine(load_stepwise(paged_dir), prefix_cache=False)
    handles = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    eng.start()                 # queue pre-loaded: one wave of admissions
    try:
        for h in handles:
            h.result(timeout=120)
        return eng.stats()
    finally:
        eng.close()


def test_scheduler_spans_tile_each_working_iteration(ring, paged_dir):
    stats = _run_engine(paged_dir, _prompts(SLOTS))
    spans = _scheduler_lane(ring)
    assert {s[2] for s in spans} == set(STEP_SPANS) | set(CHILDREN)
    gaps, steps, admitted = [], 0, 0
    for run in _iterations(spans):
        admitted += _children_lie_inside_admit(run)
        run = [s for s in run if s[2] not in CHILDREN]
        names = [s[2] for s in run]
        if "decode_step" not in names:
            assert names == ["sched_housekeeping", "sched_admit"], names
            continue
        steps += 1
        assert names == STEP_SPANS, names
        by = {s[2]: s for s in run}
        step, disp, wait = (by["decode_step"], by["sched_dispatch"],
                            by["sched_wait_logits"])
        assert step[3] <= disp[3] <= disp[4] <= wait[3] <= wait[4] \
            <= step[4]
        top = [by[n] for n in TOP_LEVEL]
        for a, b in zip(top, top[1:]):
            assert b[3] >= a[4]                        # no overlap
            gaps.append(b[3] - a[4])
        gaps += [disp[3] - step[3], wait[3] - disp[4], step[4] - wait[4]]
    assert steps == stats["decode_steps"] == MAX_NEW - 1
    assert admitted == stats["prefills"] == SLOTS
    # no gap to speak of: what lies between two phases is one span's
    # exit and the next one's entry (the tail tolerates a loaded CPU)
    assert np.median(gaps) < 1e-3, np.median(gaps)
    assert np.percentile(gaps, 90) < 10e-3, sorted(gaps)[-5:]


@pytest.mark.parametrize("live", [1, SLOTS])
def test_spans_per_step_do_not_grow_with_live_slots(ring, paged_dir, live):
    _run_engine(paged_dir, _prompts(live, seed=live))
    runs = [r for r in _iterations(_scheduler_lane(ring))
            if any(s[2] == "decode_step" for s in r)]
    assert len(runs) == MAX_NEW - 1
    # an iteration's spans grow with its admissions, three each (the
    # first admits them all), never with its live slots
    admitted = [sum(s[2] == "admit_launch" for s in r) for r in runs]
    assert admitted == [live] + [0] * (MAX_NEW - 2)
    assert {len(r) - 3 * n for r, n in zip(runs, admitted)} == {
        len(STEP_SPANS)}
    steps = [s for r in runs for s in r if s[2] == "decode_step"]
    assert {s[5]["slots"] for s in steps} == {live}


def test_an_iteration_that_admits_nothing_emits_no_child(ring, paged_dir):
    """Without the scheduler thread: an empty queue, and a request
    deferred under block pressure, leave ``sched_admit`` childless; a
    prefix hit mounts cached blocks and launches nothing."""
    eng = GenerationEngine(load_stepwise(paged_dir), prefix_cache=True)

    def admit():
        ring.drain()
        with eng._phase(span_name="sched_admit"):
            eng._admit()
            eng._prefill_chunk_step()
        return [s[2] for s in _scheduler_lane(ring)]

    assert admit() == ["sched_admit"]                   # empty queue
    prompt = _prompts(1, seed=5)[0]
    eng.submit(prompt, max_new=MAX_NEW)
    assert admit() == ["sched_admit"] + CHILDREN        # a cold prefill
    eng.submit(prompt, max_new=MAX_NEW)
    assert admit() == ["sched_admit"]                   # a hit: no program
    assert eng.stats()["prefix_cache_hits"] == 1
    # no free block left: the next cold prompt is deferred, no child
    eng.cache.pool.alloc(eng.cache.pool.free_count)
    eng.cache.prefix = None
    eng.submit(_prompts(1, seed=6)[0], max_new=MAX_NEW)
    assert admit() == ["sched_admit"]
    assert eng.stats()["prefills"] == 1 and len(eng._queue) == 1


def test_kv_bytes_is_the_live_slots_positions(ring, paged_dir):
    """Driven without the scheduler thread, so the positions are exact."""
    eng = GenerationEngine(load_stepwise(paged_dir), prefix_cache=False)
    pool = eng._pool
    per_token = sum(int(v.nbytes) for v in pool.values()) // (
        int(pool["cache_k"].shape[1]) * int(pool["cache_k"].shape[2]))
    assert eng.stats()["decode_kv_bytes"] == 0
    for p in _prompts(3, seed=7):
        eng.submit(p, max_new=MAX_NEW)
    eng._admit()
    want = []
    while eng._live:
        want.append(sum(s.pos for s in eng._live.values()) * per_token)
        eng._shared_step()
    got = [s[5]["kv_bytes"] for s in ring.drain()
           if s[2] == "decode_step"]
    assert got == want and all(w > 0 for w in want)
    assert eng.stats()["decode_kv_bytes"] == sum(want)


def test_kv_blocks_is_the_live_rows_table_entries(ring, paged_dir):
    """``decode_step`` spans carry ``kv_blocks``: over the live slots,
    the table entries up to each one's pos (``pos // block + 1``), what
    the paged kernel works on of its slots x blocks_per_slot a call."""
    eng = GenerationEngine(load_stepwise(paged_dir), prefix_cache=False)
    for p in _prompts(3, seed=11):
        eng.submit(p, max_new=MAX_NEW)
    eng._admit()
    want = []
    while eng._live:
        want.append(sum(s.pos // BLOCK + 1 for s in eng._live.values()))
        eng._shared_step()
    steps = [s[5] for s in ring.drain() if s[2] == "decode_step"]
    assert [s["kv_blocks"] for s in steps] == want
    assert all(s["slots"] <= s["kv_blocks"]
               <= s["slots"] * eng.blocks_per_slot for s in steps)


@pytest.mark.parametrize("session", [False, True])
def test_phase_counters_add_up_to_the_spans(ring, paged_dir, tmp_path,
                                            session):
    """With and without a profiler session recording the spans: a
    session adds the live annotation to each span and nothing to a
    phase (no second clock, no argument)."""
    if session:
        jax.profiler.start_trace(str(tmp_path))
    try:
        stats = _run_engine(paged_dir, _prompts(SLOTS, seed=3))
    finally:
        if session:
            jax.profiler.stop_trace()
    phases = stats["sched_phase_seconds"]
    assert list(phases) == list(SCHED_PHASES) + CHILDREN
    by_name: dict = {}
    for s in ring.drain():
        if s[2].startswith("sched_") or s[2] in CHILDREN:
            d = by_name.setdefault(s[2].removeprefix("sched_"), [0.0, 0])
            d[0] += s[4] - s[3]
            d[1] += 1
            assert set(s[5] or {}) <= {"program", "seq"}, s
    assert set(by_name) == set(phases)
    for ph, (seconds, n) in by_name.items():
        # the counter is stamped around the span: never less, and more
        # by no more than the spans' own entry and exit
        assert phases[ph] >= seconds - 1e-6 * n, (ph, phases[ph], seconds)
        assert phases[ph] - seconds < 200e-6 * n + 10e-3, (ph, phases[ph],
                                                           seconds, n)
    assert phases["wait_logits"] > 0 and phases["dispatch"] > 0
    assert phases["admit"] >= sum(phases[ch] for ch in CHILDREN) > 0
    snap = GenerationEngine(load_stepwise(paged_dir)).registry.snapshot()
    assert {f"serving_sched_{ph}_seconds_total" for ph in phases} == {
        name for name in snap if name.startswith("serving_sched_")}


def test_compiles_are_counted_and_flat_over_a_second_request(ring,
                                                             paged_dir):
    eng = GenerationEngine(load_stepwise(paged_dir),
                           prefix_cache=False).start()
    try:
        prompt = _prompts(1, seed=11)[0]
        assert eng.stats()["jit_compiles"] == 0        # exposed at zero
        first = eng.generate(prompt, max_new=MAX_NEW)
        warm = eng.stats()
        assert warm["jit_compiles"] >= 2               # prefill, decode
        assert warm["jit_compile_s"] > 0
        assert eng.generate(prompt, max_new=MAX_NEW) == first
        assert eng.stats()["jit_compiles"] == warm["jit_compiles"]
        snap = eng.metrics_snapshot()
        assert snap["jit_compiles_total"]["value"] == warm["jit_compiles"]
    finally:
        eng.close()
    spans = [s for s in ring.drain() if s[2] == "xla_compile"]
    assert len(spans) == warm["jit_compiles"]
    assert {(s[0], s[1]) for s in spans} == {("serving", "compile")}
    # the wrappers serving.py builds carry their program's name (they
    # were all ``fn``): the executed programs are jit_prefill, jit_decode
    names = {s[5]["fun_name"] for s in spans}
    assert {"jit(prefill)", "jit(decode)"} <= names, names


def test_an_engine_counts_its_own_compiles_and_no_one_elses(ring, paged_dir,
                                                            tmp_path):
    """The compile listener is the process's, its effect the engine's
    scheduler thread's: an engine built and run FIRST, a Trainer after it
    in the same process (the order that once put process ``serving`` into
    the trainer's dump), and the engine still open while the trainer
    compiles its step on this thread."""
    import json

    from distributed_tensorflow_example_tpu.config import (
        DataConfig, MeshShape, ObservabilityConfig, OptimizerConfig)
    from distributed_tensorflow_example_tpu.data.mnist import \
        synthetic_mnist
    from distributed_tensorflow_example_tpu.parallel.mesh import \
        local_mesh
    from distributed_tensorflow_example_tpu.train.trainer import Trainer

    eng = GenerationEngine(load_stepwise(paged_dir),
                           prefix_cache=False).start()
    try:
        eng.generate(_prompts(1, seed=12)[0], max_new=MAX_NEW)
        served = eng.stats()["jit_compiles"]
        ring.drain()
        # a program this process has not compiled yet, on THIS thread
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
        trace_path = str(tmp_path / "train.trace.json")
        cfg = TrainConfig(
            model="mlp", train_steps=2, mesh=MeshShape(data=2),
            data=DataConfig(batch_size=32, seed=3),
            optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
            obs=ObservabilityConfig(trace_path=trace_path,
                                    trace_buffer_events=1024),
            seed=7)
        data = synthetic_mnist(num_train=64, num_test=16, seed=0)
        tr = Trainer(get_model("mlp", cfg), cfg,
                     {"x": data["train_x"], "y": data["train_y"]},
                     mesh=local_mesh(2), process_index=0, num_processes=1)
        try:
            tr.train()
        finally:
            tr.close()
        assert eng.stats()["jit_compiles"] == served
    finally:
        eng.close()
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    assert {e["args"]["name"] for e in events
            if e.get("name") == "process_name"} == {"training"}
    assert "xla_compile" not in {e["name"] for e in events}


# ---- PR 37: which launch a span was --------------------------------------

def _export(tmp_path_factory, name, **kw):
    d = str(tmp_path_factory.mktemp(name))
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    export_generator(m, m.init(jax.random.key(0)), d,
                     prompt_len=PROMPT_LEN, max_new_tokens=MAX_NEW,
                     batch_size=1, ragged=True, stepwise=True, slots=SLOTS,
                     platforms=("cpu",), **kw)
    return d


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory, paged_dir):
    """A slab, a paged, a chunked and a per-request-state artifact."""
    import test_kimi_decoder as kimi
    model, params = kimi.build("float32")
    state = str(tmp_path_factory.mktemp("state"))
    export_generator(model, params, state, ragged=True, stepwise=True,
                     paged=True, slots=kimi.SLOTS, block_size=kimi.BS,
                     prompt_len=kimi.PROMPT, max_new_tokens=kimi.NEW,
                     prefill_chunk=kimi.CHUNK, platforms=("cpu",))
    return {"slab": _export(tmp_path_factory, "slab"),
            "paged": paged_dir,
            "chunked": _export(tmp_path_factory, "chunked", paged=True,
                               block_size=BLOCK, prefill_chunk=BLOCK),
            "state": state}


#: the generator's programs; the engine's own is ``copy``
PROGRAMS = ("prefill", "prefill_chunk", "decode", "verify", "block_step",
            "zero_slot")


def _count_executions(eng):
    """Shadow every program's callable with one that counts its calls."""
    ran = dict.fromkeys(PROGRAMS + ("copy",), 0)

    def counting(program, fn):
        def call(*a):
            ran[program] += 1
            return fn(*a)
        return call
    for program in PROGRAMS:
        setattr(eng.sw, program, counting(program, getattr(eng.sw, program)))
    if eng.paged:
        eng._copy_block = counting("copy", eng._copy_block)
    return ran


def _launches(spans):
    """{program: [seq, ...]} of the spans that are a launch, in order,
    and the (program, seq) of the admit_read spans."""
    launched, read = {}, []
    for s in sorted(spans, key=lambda s: s[3]):
        args = s[5] or {}
        if s[2] == "admit_read":
            assert set(args) == {"program", "seq"}, s
            read.append((args["program"], args["seq"]))
        elif "program" in args:
            assert s[2] in ("admit_launch", "cow_copy", "decode_step",
                            "verify_step", "block_step"), s
            launched.setdefault(args["program"], []).append(args["seq"])
    return launched, read


@pytest.mark.parametrize("kind", ["slab", "paged", "chunked", "state"])
def test_every_executed_program_is_one_program_seq_pair(ring, artifacts,
                                                        kind):
    """``seq`` rises by one a launch of each ``program`` from 0 and ends
    at the engine's own counts; every execution of a program is one
    launch span, every blocking read of an admission names the launch it
    read."""
    chunked = kind in ("chunked", "state")
    eng = GenerationEngine(
        load_stepwise(artifacts[kind]), prefix_cache=kind == "paged",
        prefill_chunk_tokens=BLOCK if kind == "chunked" else 0)
    ran = _count_executions(eng)
    prompts = _prompts(SLOTS + 2, seed=9)
    if kind == "paged":
        # a repeat mounts the cached blocks and copies the tail on write
        prompts += [prompts[0], prompts[1]]
    handles = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    eng.start()
    try:
        for h in handles:
            h.result(timeout=300)
        stats = eng.stats()
    finally:
        eng.close()
    launched, read = _launches(ring.drain())
    assert {p: len(v) for p, v in launched.items()} == {
        p: n for p, n in ran.items() if n}
    for program, seqs in launched.items():
        assert seqs == list(range(len(seqs))), (program, seqs)
    want = {"decode": stats["decode_steps"] + stats["redispatches"]}
    if chunked:
        want["prefill_chunk"] = stats["prefill_chunks"]
    else:
        want["prefill"] = stats["prefills"]
    if kind == "state":
        want["zero_slot"] = len(prompts)
    if kind == "paged":
        want["copy"] = stats["cow_copies"]
        assert stats["cow_copies"] >= 1 and stats["prefix_cache_hits"] == 2
    assert {p: len(v) for p, v in launched.items()} == want
    # a read follows its launch: the same pairs, in the same order (a
    # per-request-state engine marks a step's launch and read by spans
    # of their own too: its decode_step may hold a chunk's launch, PR 43)
    first = "prefill_chunk" if chunked else "prefill"
    assert [r for r in read if r[0] == first] == [
        (first, q) for q in launched[first]]
    assert [r for r in read if r[0] != first] == (
        [("decode", q) for q in launched["decode"]] if kind == "state"
        else [])


def test_a_redispatch_is_a_launch_of_its_own(ring, paged_dir):
    """A shared step that fails once before it consumed the pool is
    dispatched again: two launches, two ordinals, one decode step."""
    eng = GenerationEngine(load_stepwise(paged_dir), prefix_cache=False)
    real, calls = eng.sw.decode, []

    def flaky(feats):
        calls.append(len(calls))
        if len(calls) == 2:
            raise RuntimeError("transient")
        return real(feats)
    eng.sw.decode = flaky
    eng.submit(_prompts(1, seed=4)[0], max_new=MAX_NEW)
    eng._admit()
    while eng._live:
        eng._shared_step()
    stats = eng.stats()
    launched, _ = _launches(ring.drain())
    assert stats["redispatches"] == 1
    assert launched["decode"] == list(range(stats["decode_steps"] + 1))
    assert len(calls) == stats["decode_steps"] + 1


def test_no_program_is_launched_past_the_helper():
    """Every launch in ``serving_batch.py`` is ``self._launch(program,
    callable, operands, on=span)``: no program's callable is called
    anywhere else in the engine."""
    src = inspect.getsource(serving_batch)
    for direct in (r"self\.sw\.(prefill|prefill_chunk|decode|verify|"
                   r"block_step|zero_slot)\(", r"self\._copy_block\(",
                   r"\bcall\(feats\)"):
        assert not re.search(direct, src), direct
    body = inspect.getsource(GenerationEngine._launch)
    assert body.rstrip().endswith("return call(*operands)")
    launches = re.findall(r'self\._launch\(\s*"?(\w+)"?', src)
    assert sorted(set(launches)) == ["copy", "prefill", "prefill_chunk",
                                     "program", "zero_slot"], launches


def test_a_phase_reads_the_wall_clock_and_no_other():
    """The thread's CPU clock is a system call on the serving cells'
    host (6-20 us a reading, in ticks of 10 ms): read a phase, it moved
    the chat cell's median latency by 4-12 % untraced and the
    ``sched_idle_*`` yardsticks by 0.3-0.4 ms a step traced
    (``benchmark/records/pr37``). The scheduler stamps ``perf_counter``
    alone, traced or not."""
    src = inspect.getsource(serving_batch)
    clocks = set(re.findall(r"\btime\.(\w+)\(", src))
    assert clocks <= {"perf_counter", "monotonic", "time", "sleep"}, clocks
    assert set(re.findall(r"\btime\.(\w+)\(", inspect.getsource(
        serving_batch._Phase))) == {"perf_counter"}


def test_a_launch_reaches_the_profilers_host_plane(ring, paged_dir,
                                                   tmp_path):
    """What a launch notes on the span around it (``program``, ``seq``)
    is an event stat of the capture, beside the arguments the span was
    opened with; ``admit_read`` names the launch it reads."""
    eng = GenerationEngine(load_stepwise(paged_dir), prefix_cache=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with eng._phase(span_name="sched_admit"):
            with eng._phase(span_name="admit_launch") as launch:
                assert eng._launch("prefill", lambda x: x + 1, 41,
                                   on=launch) == 42
            with eng._admit_read("prefill"):
                time.sleep(0.003)
        with span("decode_step", lane="scheduler", slots=2) as step:
            eng._launch("decode", lambda: None, on=step)
            eng._launch("decode", lambda: None, on=step)
    finally:
        jax.profiler.stop_trace()
    events = {name: (dur, stats) for name, dur, stats
              in _host_events(str(tmp_path))}
    assert events["admit_launch"][1] == {"program": "prefill", "seq": 0}
    dur, stats = events["admit_read"]
    assert stats == {"program": "prefill", "seq": 0} and dur >= 3e6
    assert events["sched_admit"][1] == {}
    # the last note stands: the step's span names its last launch
    assert events["decode_step"][1] == {"slots": 2, "program": "decode",
                                        "seq": 1}
    in_ring = {s[2]: s[5] for s in ring.drain()}
    assert in_ring["admit_read"] == dict(events["admit_read"][1])
