"""The chunk program queued behind the shared step (PR 43): a
per-request-state engine that holds live slots AND a parked slot
launches the step, launches the parked slot's next chunk on the step's
returned pool, and only then blocks on the step, once:

- (a) a mixed queue of short and multi-chunk prompts gives every request
  the tokens it gets when served alone, whichever of the three state
  decoders serves it, and chunks did go behind steps;
- (b) in the ring the launches read ``decode k``, ``prefill_chunk``,
  ``decode k+1``, the chunk's inside the step's span between its launch
  and its read; only a prompt's last chunk is ever read; an iteration
  blocks on the device at most twice;
- (c) a step's and a block step's results leave the device in one round
  trip: every copy is started before one is waited for;
- (d) with a chunk in flight the protocol holds: the prefill seam fails
  one request, a cancel and a deadline give the blocks back and drop the
  late result, a step that fails twice evicts the newest live slot
  alone, ``close()`` leaves nothing on the device.
"""

import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE)):
    if path not in sys.path:
        sys.path.insert(0, path)

from distributed_tensorflow_example_tpu import serving            # noqa: E402
from distributed_tensorflow_example_tpu import serving_batch      # noqa: E402
from distributed_tensorflow_example_tpu.obs.trace import (        # noqa: E402
    TraceRecorder, recorder, set_recorder)
from distributed_tensorflow_example_tpu.runtime import faults     # noqa: E402
from distributed_tensorflow_example_tpu.serving_batch import (    # noqa: E402
    DeadlineExceededError, GenerationEngine, PoisonedRequestError,
    RequestCancelledError)

KINDS = ("kimi_linear_tiny", "dots3_note_tiny", "laguna_tiny")
SLOTS, BS, CHUNK, PROMPT, NEW = 3, 16, 32, 96, 24
#: inside one chunk, over three, on a chunk boundary: some prompts are
#: parked over several iterations while others decode
LENS = (5, 70, 64, 33, 96, 17, 90)
MAX_NEW = (NEW, 9, 16, NEW, 12, 7, 10)


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """The three state decoders' test artifacts, exported on demand."""
    made = {}

    def get(kind: str) -> str:
        if kind not in made:
            mod = __import__({"kimi_linear_tiny": "test_kimi_decoder",
                              "dots3_note_tiny": "test_dots3_decoder",
                              "laguna_tiny": "test_laguna_decoder"}[kind])
            model, params = mod.build("float32")
            made[kind] = str(tmp_path_factory.mktemp(kind))
            serving.export_generator(
                model, params, made[kind], ragged=True, stepwise=True,
                paged=True, slots=SLOTS, block_size=BS, prompt_len=PROMPT,
                max_new_tokens=NEW, prefill_chunk=CHUNK,
                platforms=("cpu",))
        return made[kind]
    return get


@pytest.fixture
def ring():
    old = recorder()
    rec = set_recorder(TraceRecorder(max_events=1 << 16))
    rec.start()
    yield rec
    rec.stop()
    set_recorder(old)


def _prompts(seed=11, lens=LENS):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 384, n).tolist() for n in lens]


def _engine(artifacts, kind="kimi_linear_tiny", **kw):
    return GenerationEngine(serving.load_stepwise(artifacts(kind)), **kw)


def _drive(eng, done=lambda: False, limit=2000):
    """The scheduler's iterations without its thread (exact order)."""
    n = 0
    while (eng._queue or eng._live or eng._prefilling or eng._behind
           or eng._due) and not done():
        eng._iterate()
        n += 1
        assert n < limit
    return n


def _alone(eng, prompts, new):
    return [eng.submit(p, max_new=k).result(timeout=300)
            for p, k in zip(prompts, new)]


# ---- (a) the tokens are the request's own -------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_mixed_queue_gives_each_request_its_own_tokens(artifacts, kind):
    prompts = _prompts()
    eng = _engine(artifacts, kind)
    handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, MAX_NEW)]
    eng.start()
    try:
        got = [h.result(timeout=600) for h in handles]
        st = eng.stats()
        assert st["prefill_chunks"] == sum(-(-n // CHUNK) for n in LENS)
        assert 0 < st["chunks_behind_step"] <= st["prefill_chunks"]
        # the chunk's expert layers are counted whoever read them
        moe = st["state"]["ffns"].count("moe")
        bounded = st["moe_bounded_layers"] + st["moe_whole_layers"]
        assert bounded in (0, moe * st["prefill_chunks"])
        assert got == _alone(eng, prompts, MAX_NEW)
    finally:
        eng.close()
    assert eng._behind is None and eng._due is None


# ---- (b) what the ring holds of a rotated iteration ---------------------

def _launches_of(lane, program):
    return sum(1 for s in lane if s[2] == "admit_launch"
               and s[5]["program"] == program)


def _sched(rec):
    return sorted((s for s in rec.drain()), key=lambda s: (s[3], -s[4]))


def test_the_chunk_is_launched_inside_the_step_and_read_a_step_later(
        ring, artifacts):
    eng = _engine(artifacts)
    prompts = _prompts()
    handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, MAX_NEW)]
    reads = []
    while eng._queue or eng._live or eng._prefilling or eng._behind \
            or eng._due:
        before = eng.stats()["host_reads"]
        eng._iterate()
        reads.append(eng.stats()["host_reads"] - before)
    assert [h.result(timeout=1) for h in handles]
    st = eng.stats()
    assert max(reads) <= 2, reads
    spans = _sched(ring)
    lane = [s for s in spans if s[1] == "scheduler"]
    # a state engine's step marks its launch and its read by short spans
    # of their own inside decode_step (which holds another launch)
    marks = [s for s in lane if s[2] == "admit_launch"
             and s[5]["program"] == "decode"]
    steps = {m[5]["seq"]: next(s for s in lane if s[2] == "decode_step"
                               and s[3] <= m[3] and m[4] <= s[4])
             for m in marks}
    assert len(steps) == st["decode_steps"]
    assert not any("program" in (s[5] or {}) for s in steps.values())
    chunks = [s for s in spans if s[2] == "prefill_chunk"]
    launches = {s[5]["seq"]: s for s in lane if s[2] == "admit_launch"
                and s[5]["program"] == "prefill_chunk"}
    read = {s[5]["seq"]: s for s in lane if s[2] == "admit_read"
            and s[5]["program"] == "prefill_chunk"}
    step_reads = [s for s in lane if s[2] == "admit_read"
                  and s[5]["program"] == "decode"]
    assert sorted(s[5]["seq"] for s in step_reads) == sorted(steps)
    assert len(chunks) == len(launches) == st["prefill_chunks"]
    admits = [s for s in lane if s[2] == "sched_admit"]
    behind = 0
    # a prompt's chunks in launch order: the k-th prefill_chunk span is
    # launch k (one engine, one thread)
    for seq, chunk in enumerate(sorted(chunks, key=lambda s: s[3])):
        launch, args = launches[seq], chunk[5]
        last = args["start"] + args["tokens"] == args["prompt_tokens"]
        assert any(a[3] <= launch[3] and launch[4] <= a[4]
                   for a in admits)              # inside a sched_admit
        if "behind" not in args:
            # in turn: launched, read, emitted, nothing else between
            assert read[seq][3] >= launch[4]
            continue
        behind += 1
        step = steps[args["behind"]]
        inner = [s for s in lane if step[3] <= s[3] and s[4] <= step[4]
                 and s is not step]
        names = [s[2] for s in inner]
        # decode k's launch, then the chunk's, then decode k's read; no
        # read of anything between the two launches
        i_disp, i_launch, i_wait = (names.index("sched_dispatch"),
                                    inner.index(launch),
                                    names.index("sched_wait_logits"))
        assert i_disp < i_launch < i_wait, names
        assert "admit_read" not in names[:i_launch], names
        assert names[i_disp + 1] == "admit_launch"      # the step's own
        assert chunk[3] <= launch[3] and chunk[4] >= step[4]
        if not last:
            assert seq not in read              # never fetched
            continue
        # read inside a LATER step's span, after that step's launch
        got = read[seq]
        later = [(q, s) for q, s in steps.items()
                 if s[3] <= got[3] and got[4] <= s[4]]
        if later:
            q, holds = later[0]
            assert q > args["behind"]
            disp = next(s for s in lane if s[2] == "sched_dispatch"
                        and holds[3] <= s[3] and s[4] <= holds[4])
            assert disp[4] <= got[3]
    assert behind == st["chunks_behind_step"] > 0
    # device order, as the launch spans give it by start: after a decode
    # comes the chunk queued behind it, then the next decode
    order = sorted([(s[3], "decode", q) for q, s in steps.items()]
                   + [(s[3], "prefill_chunk", q)
                      for q, s in launches.items()])
    for (_, pa, qa), (_, pb, qb), (_, pc, qc) in zip(order, order[1:],
                                                     order[2:]):
        if pb == "prefill_chunk" and "behind" in sorted(
                chunks, key=lambda s: s[3])[qb][5]:
            assert (pa, pc) == ("decode", "decode") and qc == qa + 1
    # every read names a launch that was made; a step is one read
    assert set(read) <= set(launches)
    assert st["host_reads"] == st["decode_steps"] + len(read)
    # the benchmark's reader pairs such a capture by order: a device that
    # runs each program right behind its launch and the program before it
    # is causal under the spans' own clock (launch before start, end
    # before the read), with no edge cut
    from benchmark.readers import launch_pairs
    by_name = {}
    for s in spans:
        by_name.setdefault(s[2], []).append((s[3], s[4], s[5] or {}))
    found, mods, t = launch_pairs.launches(by_name), [], 0.0
    for start, _, program, _ in found:
        t = max(start, t) + 2e-6
        mods.append((t - 1e-6, t, program))
    paired = launch_pairs.pair(by_name, mods)
    assert paired is not None and len(paired["pairs"]) == len(found)
    assert paired["shift"] == 0.0 and paired["lo"] <= 0.0 <= paired["hi"]
    unread = sum(1 for launch in found if launch[1] is None)
    assert unread == st["prefill_chunks"] - len(read) + _launches_of(
        lane, "zero_slot")
    # a capture that opens or ends INSIDE a step (nearly every one does:
    # the span lasts the iteration) loses the spans open at its edges and
    # keeps the short ones: what is left still pairs by order
    queued = [launches[q] for q, c in enumerate(
        sorted(chunks, key=lambda s: s[3])) if "behind" in c[5]]
    for launch in queued[1::4]:
        for t0, t1 in ((spans[0][3], launch[4] + 1e-7),
                       (launch[3] - 1e-7, spans[-1][4])):
            cut = {name: [s for s in rows if t0 <= s[0] and s[1] <= t1]
                   for name, rows in by_name.items()}
            inside = [m for m in mods if t0 <= m[0] <= t1]
            got = launch_pairs.pair(cut, inside)
            assert got is not None and got["lo"] <= got["hi"], (t0, t1)


def test_a_gpt2_engine_reads_once_a_prefill_and_once_a_step(tmp_path):
    """The engine without a chunk to queue makes the parent's reads: one
    a paged prefill, one a decode step (no state, nothing behind)."""
    import jax

    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    d = str(tmp_path / "gpt")
    serving.export_generator(m, m.init(jax.random.key(0)), d, prompt_len=8,
                             max_new_tokens=6, batch_size=1, ragged=True,
                             stepwise=True, slots=4, paged=True,
                             block_size=4, platforms=("cpu",))
    eng = GenerationEngine(serving.load_stepwise(d), prefix_cache=False)
    rs = np.random.RandomState(0)
    for _ in range(3):
        eng.submit(rs.randint(1, 200, 6).tolist(), max_new=6)
    _drive(eng)
    st = eng.stats()
    assert st["chunks_behind_step"] == 0
    assert st["host_reads"] == st["prefills"] + st["decode_steps"] == 3 + 5


# ---- (c) one round trip a program ---------------------------------------

class _Recorded:
    """Stands in for a device array: notes when its copy was started and
    when it was waited for."""

    def __init__(self, name, value, log):
        self.name, self.value, self.log = name, np.asarray(value), log

    def copy_to_host_async(self):
        self.log.append(("start", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("wait", self.name))
        return self.value


def _one_round_trip(log, names):
    assert [what for what, _ in log] == ["start"] * len(names) \
        + ["wait"] * len(names), log
    assert {n for _, n in log} == set(names)


def test_a_steps_results_leave_in_one_round_trip(artifacts):
    log = []
    out = {"ids": _Recorded("ids", np.arange(3), log),
           "expert_rows": _Recorded("expert_rows", 5, log),
           "max_expert_load": _Recorded("max_expert_load", 1.5, log)}
    eng = _engine(artifacts)
    due = _Recorded("moe_whole", 1, log)
    eng._whole_due.append(due)
    ids = eng._fetch_state_step(out)
    assert ids.tolist() == [0, 1, 2] and eng._expert_rows_last == 5
    _one_round_trip(log, ["ids", "expert_rows", "max_expert_load",
                          "moe_whole"])
    assert eng._whole_due == [] and eng.stats()["moe_whole_layers"] == 1


def test_a_block_steps_results_leave_in_one_round_trip():
    log = []
    out = {"ids": _Recorded("ids", np.zeros((2, 4), np.int32), log),
           "conf": _Recorded("conf", np.ones((2, 4), np.float32), log),
           "expert_rows": _Recorded("expert_rows", 7, log),
           "max_expert_load": _Recorded("max_expert_load", 2.0, log)}
    ids, conf, rows, load = serving_batch._fetch_block(out)
    assert ids.shape == conf.shape == (2, 4) and (rows, load) == (7, 2.0)
    assert isinstance(rows, int) and isinstance(load, float)
    _one_round_trip(log, list(out))


# ---- (d) the protocol with a chunk in flight ----------------------------

#: two short prompts go live at once, the third is parked over three
#: chunks behind their steps, the fourth waits for a slot
D_LENS, D_NEW = (10, 12, 90, 70), (NEW, NEW, 6, 5)


def _served_alone(eng, prompts, new):
    """Each request through the (healthy) engine with nothing beside it,
    driven without the thread."""
    out = []
    for p, k in zip(prompts, new):
        h = eng.submit(p, max_new=k)
        _drive(eng)
        out.append(h.result(timeout=1))
    return out


def _disturbed(artifacts, **kw):
    eng = _engine(artifacts, **kw)
    prompts = _prompts(seed=3, lens=D_LENS)
    handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, D_NEW)]
    # two live slots and the long prompt's second chunk on the device
    _drive(eng, done=lambda: len(eng._live) == 2 and eng._behind is not None
           and eng._behind.start > 0)
    assert len(eng._live) == 2 and eng._behind.slot.req is handles[2].req
    return eng, prompts, handles


def _the_rest_is_undisturbed(eng, prompts, handles, failed, err, match=None):
    _drive(eng)
    want = _served_alone(eng, prompts, D_NEW)
    for h, w in zip(handles, want):
        if h.req is failed:
            with pytest.raises(err, match=match):
                h.result(timeout=1)
        else:
            assert h.result(timeout=1) == w
    assert eng.cache.pool.in_use == 0 and not eng._live and not eng._prefilling
    assert eng._behind is None and eng._due is None


def test_the_prefill_seam_fails_one_request_behind_a_step(artifacts):
    eng, prompts, handles = _disturbed(artifacts)
    victim = eng._behind.slot           # its last chunk is launched next
    faults.install(faults.parse_spec("engine.prefill:step=1", seed=0))
    try:
        eng._iterate()
    finally:
        faults.install(None)
    # failed on the host with the step in flight: the step was read, the
    # pool is the step's; the victim's chunk on the device is dropped
    assert eng._prefilling.get(victim.index) is not victim
    assert eng._pool_alive() and eng._behind is None
    assert eng.stats()["requests_failed"] == 1
    _the_rest_is_undisturbed(eng, prompts, handles, victim.req,
                             PoisonedRequestError, "prefill chunk")


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_gone_while_its_chunk_runs_leaves_no_trace(artifacts, how):
    eng, prompts, handles = _disturbed(artifacts, shed_policy="off")
    gone = eng._behind.slot
    held = [int(b) for b in eng.cache.tables[gone.index] if b]
    free = eng.cache.pool.free_count
    if how == "cancel":
        assert eng.cancel(gone.req.request_id)
    else:
        gone.req.deadline_ms = 1
        gone.req.deadline_t = time.perf_counter() - 1.0
    chunks = eng.stats()["prefill_chunks"]
    with eng._phase(span_name="sched_housekeeping"):
        eng._apply_cancellations()
        eng._expire_deadlines()
    # the slot and its blocks went back at the boundary, with the chunk
    # still on the device, unread
    assert eng._prefilling.get(gone.index) is not gone
    assert eng.cache.pool.free_count == free + len(held) and eng._behind
    assert gone.req.future.done()
    eng._iterate()
    assert eng.stats()["prefill_chunks"] == chunks + 1   # it ran; dropped
    _the_rest_is_undisturbed(
        eng, prompts, handles, gone.req,
        RequestCancelledError if how == "cancel" else DeadlineExceededError)


def test_a_step_that_fails_twice_evicts_the_newest_live_slot_alone(
        artifacts):
    eng, prompts, handles = _disturbed(artifacts)
    newest = max(eng._live.values(), key=lambda s: s.admit_seq)
    parked = eng._behind.slot
    faults.install(faults.parse_spec(
        "engine.decode_step:step=1;engine.decode_step:step=1", seed=0))
    try:
        eng._iterate()
    finally:
        faults.install(None)
    assert eng._live.get(newest.index) is not newest
    assert eng.stats()["redispatches"] == 2 and len(eng._live) == 1
    # the survivor stepped, with the parked prompt's last chunk behind it
    assert eng._behind is not None and eng._behind.slot is parked
    _the_rest_is_undisturbed(eng, prompts, handles, newest.req,
                             PoisonedRequestError, "evicted")


class _FailsOnRead:
    """What a program that faulted on the device hands back: the error
    surfaces where the host materializes the result."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("simulated async device fault")


def _faulty_chunk(eng, at_start):
    """The chunk program, really run (pool donated), its id unreadable
    for the chunk of ``at_start`` = (first token, tokens)."""
    real = eng.sw.prefill_chunk

    def chunk(feats):
        out = real(feats)
        if (int(feats["start"]), int(feats["n_valid"])) == at_start:
            return {**out, "ids": _FailsOnRead()}
        return out
    eng.sw.prefill_chunk = chunk


def test_a_device_fault_found_behind_a_later_step_is_engine_fatal(artifacts):
    """The faulted chunk's pool was given to the next step before its
    read: nothing is left to quarantine over. Every request fails, the
    pool is rebuilt, the engine serves again."""
    eng = _engine(artifacts)
    _faulty_chunk(eng, at_start=(32, 8))    # the 40-token prompt's last
    prompts = _prompts(seed=3, lens=(10, 40))
    handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, (NEW, 4))]
    eng.start()
    try:
        for h in handles:
            with pytest.raises(RuntimeError, match="scheduler step"):
                h.result(timeout=120)
        st = eng.stats()
        assert st["redispatches"] == 0 and st["chunks_behind_step"] == 2
        assert eng._behind is None and eng._due is None
        assert len(eng.generate(prompts[0], max_new=3, timeout=120)) == 3
    finally:
        eng.close()


def test_a_device_fault_found_with_no_step_to_queue_behind_is_fatal_too(
        artifacts):
    """A chunk launched behind the last live slot's last step is proven
    alone: ``_pool`` names what the chunk was given while it is read."""
    eng = _engine(artifacts)
    _faulty_chunk(eng, at_start=(0, 32))    # the 90-token prompt's first
    prompts = _prompts(seed=3, lens=(10, 90))
    for p, k in zip(prompts, (2, 6)):
        eng.submit(p, max_new=k)
    _drive(eng, done=lambda: eng._behind is not None)
    assert not eng._live and eng._behind.start == 0 and eng._pool_alive()
    with pytest.raises(RuntimeError, match="simulated"):
        eng._iterate()
    assert not eng._pool_alive()


def test_close_waits_for_the_chunk_in_flight(artifacts):
    eng = _engine(artifacts)
    handles = [eng.submit(p, max_new=NEW) for p in _prompts(seed=5)]
    eng.start()
    deadline = time.time() + 120
    while not eng.stats()["chunks_behind_step"] and time.time() < deadline:
        time.sleep(0.002)
    eng.close()
    assert eng._behind is None and eng._due is None
    assert eng._thread is None
    for arr in eng._pool.values():
        assert not arr.is_deleted()
        arr.block_until_ready()
    for h in handles:
        assert h.done()
