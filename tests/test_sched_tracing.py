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
- ``jit_compiles_total`` is flat over a second identical request, and
  the served programs compile under their own names.
"""

import glob
import os
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
from distributed_tensorflow_example_tpu.serving_batch import (
    SCHED_PHASES, GenerationEngine)

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
    assert {s[2] for s in spans} == set(STEP_SPANS)
    gaps, steps = [], 0
    for run in _iterations(spans):
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
    assert {len(r) for r in runs} == {len(STEP_SPANS)}
    assert {r[4][5]["slots"] for r in runs} == {live}


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


def test_phase_counters_add_up_to_the_spans(ring, paged_dir):
    stats = _run_engine(paged_dir, _prompts(SLOTS, seed=3))
    phases = stats["sched_phase_seconds"]
    assert list(phases) == list(SCHED_PHASES)
    by_name: dict = {}
    for s in ring.drain():
        if s[2].startswith("sched_"):
            d = by_name.setdefault(s[2][len("sched_"):], [0.0, 0])
            d[0] += s[4] - s[3]
            d[1] += 1
    assert set(by_name) == set(SCHED_PHASES)
    for ph, (seconds, n) in by_name.items():
        # the counter is stamped around the span: never less, and more
        # by no more than the spans' own entry and exit
        assert phases[ph] >= seconds - 1e-6 * n, (ph, phases[ph], seconds)
        assert phases[ph] - seconds < 200e-6 * n + 10e-3, (ph, phases[ph],
                                                          seconds, n)
    assert phases["wait_logits"] > 0 and phases["dispatch"] > 0


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
