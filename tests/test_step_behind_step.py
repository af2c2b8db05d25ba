"""The decode step queued behind the decode step (PR 45): a
per-request-state engine that has launched step N and has no chunk to
queue behind it launches step N+1 on the ids step N leaves on the
device, before it reads step N; the next shared step ADOPTS the step in
flight in the place of a launch:

- (a) whichever of the state decoders serves it, a request gets the
  tokens the in-turn sequence gives it, whether it ends by length, by an
  end-of-sequence id found while the step ahead runs, by a cancellation
  or by a deadline between the two reads; so does a row that crosses a
  block boundary in the step ahead, and a request admitted into a slot
  freed while a step was in flight;
- (b) where the block for ``pos + 1`` cannot be had, nothing is launched
  ahead and the tokens are the same;
- (c) steps go behind steps where no chunk waits, a chunk waiting goes
  first, and a GPT-2 engine and a block-diffusion engine launch none;
- (d) in the ring a step ahead is launched inside the span of the step
  before it, under marks of its own, and read in the span that adopts
  it; the benchmark's reader pairs such a capture by order, cut
  anywhere;
- (e) with a step in flight the protocol holds: the step seam retries or
  evicts as today, ``close()`` leaves nothing on the device.
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
from distributed_tensorflow_example_tpu.runtime import faults     # noqa: E402
from distributed_tensorflow_example_tpu.serving_batch import (    # noqa: E402
    DeadlineExceededError, GenerationEngine, PoisonedRequestError,
    RequestCancelledError)
from test_chunk_behind_step import _sched, ring         # noqa: E402, F401

KINDS = ("kimi_linear_tiny", "laguna_tiny", "axk1_tiny")
MODULES = {"kimi_linear_tiny": "test_kimi_decoder",
           "laguna_tiny": "test_laguna_decoder",
           "axk1_tiny": "test_axk1_decoder"}
SLOTS, BS, CHUNK, PROMPT, NEW = 3, 16, 32, 96, 24
#: every prompt is one chunk: once the three slots decode, no chunk waits
LENS = (5, 12, 30, 9, 20)
MAX_NEW = (NEW, 9, 16, 12, 7)


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """The state decoders' test artifacts, exported on demand."""
    made = {}

    def get(kind: str) -> str:
        if kind not in made:
            model, params = __import__(MODULES[kind]).build("float32")
            made[kind] = str(tmp_path_factory.mktemp(kind))
            serving.export_generator(
                model, params, made[kind], ragged=True, stepwise=True,
                paged=True, slots=SLOTS, block_size=BS, prompt_len=PROMPT,
                max_new_tokens=NEW, prefill_chunk=CHUNK,
                platforms=("cpu",))
        return made[kind]
    return get


def _prompts(seed=11, lens=LENS):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 384, n).tolist() for n in lens]


def _engine(artifacts, kind="kimi_linear_tiny", in_turn=False, **kw):
    eng = GenerationEngine(serving.load_stepwise(artifacts(kind)), **kw)
    if in_turn:
        # the parent's sequence: every step launched after the read of
        # the step before it
        eng._next_step_of = lambda feats, out, pool: None
    return eng


def _busy(eng):
    return bool(eng._queue or eng._live or eng._prefilling or eng._behind
                or eng._due or eng._step_ahead)


def _drive(eng, done=lambda: False, limit=2000):
    """The scheduler's iterations without its thread (exact order)."""
    n = 0
    while _busy(eng) and not done():
        eng._iterate()
        n += 1
        assert n < limit
    return n


def _served(eng, prompts, new, **kw):
    handles = [eng.submit(p, max_new=k, **kw) for p, k in zip(prompts, new)]
    _drive(eng)
    return [h.result(timeout=1) for h in handles]


def _settled(eng):
    assert eng.cache.pool.in_use == 0 and not eng._live and not eng._prefilling
    assert eng._behind is None and eng._due is None
    assert eng._step_ahead is None and eng._pool_alive()


def _in_flight(artifacts, kind, lens=(10, 12), seed=3, **kw):
    """An engine whose requests all decode, with a step in flight."""
    eng = _engine(artifacts, kind, **kw)
    prompts = _prompts(seed=seed, lens=lens)
    handles = [eng.submit(p, max_new=NEW) for p in prompts]
    _drive(eng, done=lambda: eng._step_ahead is not None)
    assert eng._step_ahead is not None and len(eng._live) == len(lens)
    return eng, prompts, handles


# ---- (a) the tokens are the in-turn sequence's --------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_requests_that_end_by_length_get_the_in_turn_tokens(artifacts,
                                                              kind):
    prompts = _prompts()
    ref = _engine(artifacts, kind, in_turn=True)
    want = _served(ref, prompts, MAX_NEW)
    assert ref.stats()["steps_behind_step"] == 0
    eng = _engine(artifacts, kind)
    assert _served(eng, prompts, MAX_NEW) == want
    st, was = eng.stats(), ref.stats()
    assert 0 < st["steps_behind_step"] < st["decode_steps"]
    # the host knows a step early that a row ends by length: none is
    # computed for nothing, and nothing is waited for but the steps
    assert st["step_ahead_dead_rows"] == 0
    assert st["decode_slot_steps"] == was["decode_slot_steps"]
    assert st["tokens_out"] == was["tokens_out"] == sum(MAX_NEW)
    assert st["host_reads"] == st["decode_steps"] + len(LENS)
    _settled(eng)


@pytest.mark.parametrize("kind", KINDS)
def test_a_an_end_of_sequence_id_found_in_a_step_ahead_ends_the_row_alone(
        artifacts, kind):
    """The id that ends a request is known at its step's read, when the
    step ahead computes the row already: the row's next id is dropped,
    its neighbours' are the in-turn sequence's, and the request admitted
    into its slot starts from zeroed rows."""
    prompts = _prompts(seed=5, lens=(10, 14, 8, 11))
    new = (NEW, NEW, NEW, 10)
    ref = _engine(artifacts, kind, in_turn=True)
    free = _served(ref, prompts, new)
    # the first request's id at its fifth step, if that is its first
    # occurrence, else the first id that is
    k = next(j for j in range(4, NEW) if free[0][j] not in free[0][:j])
    eos = free[0][k]

    def run(eng):
        handles = [eng.submit(p, max_new=n, eos_id=eos if i == 0 else None)
                   for i, (p, n) in enumerate(zip(prompts, new))]
        _drive(eng)
        return [h.result(timeout=1) for h in handles]
    want = run(ref)
    assert want[0][:k + 1] == free[0][:k + 1] and len(want[0]) == NEW
    assert want[0][k + 1:] != free[0][k + 1:]          # padded, not decoded
    eng = _engine(artifacts, kind)
    assert run(eng) == want
    st = eng.stats()
    assert st["steps_behind_step"] > 0
    assert st["step_ahead_dead_rows"] >= 1
    _settled(eng)


@pytest.mark.parametrize("how", ["cancel", "deadline"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_request_gone_between_the_two_reads_leaves_no_trace(
        artifacts, kind, how):
    eng, prompts, handles = _in_flight(artifacts, kind, shed_policy="off",
                                       lens=(10, 12, 9))
    flying = eng._step_ahead
    gone = eng._live[max(flying.rows)]
    assert flying.rows[gone.index] is gone
    held = [int(b) for b in eng.cache.tables[gone.index] if b]
    free = eng.cache.pool.free_count
    if how == "cancel":
        assert eng.cancel(gone.req.request_id)
    else:
        gone.req.deadline_ms = 1
        gone.req.deadline_t = time.perf_counter() - 1.0
    dead = eng.stats()["step_ahead_dead_rows"]
    eng._iterate()
    # the slot and its blocks went back at the boundary with the step
    # that computes the row still unread; its id was dropped at the read
    assert eng._live.get(gone.index) is not gone and gone.req.future.done()
    assert eng.cache.pool.free_count >= free + len(held) - 1
    assert eng.stats()["step_ahead_dead_rows"] == dead + 1
    _drive(eng)
    want = _served(_engine(artifacts, kind, in_turn=True), prompts,
                   (NEW,) * 3)
    for h, w in zip(handles, want):
        if h.req is gone.req:
            with pytest.raises(RequestCancelledError if how == "cancel"
                               else DeadlineExceededError):
                h.result(timeout=1)
        else:
            assert h.result(timeout=1) == w
    _settled(eng)


@pytest.mark.parametrize("kind", KINDS)
def test_a_row_crosses_a_block_boundary_in_the_step_ahead(artifacts, kind):
    """A prompt that ends one row short of a block's end: the first step
    writes the block's last row, the step launched behind it the next
    block's first, which is secured before that launch."""
    prompts = _prompts(seed=9, lens=(BS - 1, 2 * BS - 2))
    eng = _engine(artifacts, kind)
    handles = [eng.submit(p, max_new=NEW) for p in prompts]
    crossed = []
    while _busy(eng):
        eng._iterate()
        flying = eng._step_ahead
        for i, s in (flying.rows if flying else {}).items():
            pos = int(flying.feats["pos"][i])
            if pos % BS == 0:
                # the block the step in flight writes is the row's own
                block = int(flying.feats["block_tables"][i, pos // BS])
                assert block and eng.cache.pool.refcount(block) == 1
                crossed.append((i, pos))
    assert len({i for i, _ in crossed}) == 2 and len(crossed) >= 3
    want = _served(_engine(artifacts, kind, in_turn=True), prompts,
                   (NEW, NEW))
    assert [h.result(timeout=1) for h in handles] == want
    _settled(eng)


@pytest.mark.parametrize("kind", KINDS)
def test_a_request_takes_a_slot_freed_while_a_step_was_in_flight(
        artifacts, kind):
    """An end-of-sequence id frees a slot at step N's read with step N+1
    on the device, the dead row's recurrent rows moved once more: the
    queued request admitted into the slot starts from zeroed rows behind
    that step, and gets the tokens it gets alone."""
    prompts = _prompts(seed=21, lens=(10, 12, 9, 40, 26))
    alone = _engine(artifacts, kind, in_turn=True)
    free = _served(alone, prompts[:1], (NEW,))[0]
    k = next(j for j in range(10, NEW) if free[j] not in free[:j])
    want = [_served(alone, [p], (NEW,))[0] for p in prompts[1:]]
    eng = _engine(artifacts, kind)
    handles = [eng.submit(p, max_new=NEW, eos_id=free[k] if i == 0 else None)
               for i, p in enumerate(prompts)]
    first, index, heirs = handles[0].req, [], []
    while _busy(eng):
        eng._iterate()
        for i, s in eng._live.items():
            if s.req is first:
                index[:] = [i]
            elif first.future.done() and [i] == index:
                heirs.append(s.req)
    assert heirs and handles[0].result(timeout=1)[:k + 1] == free[:k + 1]
    assert [h.result(timeout=1) for h in handles[1:]] == want
    # the request ended with a step in flight that computed its row
    assert eng.stats()["step_ahead_dead_rows"] >= 1
    _settled(eng)


# ---- (b) no block, no step ahead ----------------------------------------

def test_b_without_a_block_for_the_next_row_the_step_is_launched_in_turn(
        artifacts):
    """With no block free, steps go behind steps as long as the row
    writes the block it holds; the step that would open a block is not
    launched ahead, and is launched in turn once a block can be had."""
    prompts = _prompts(seed=9, lens=(BS - 3,))
    eng = _engine(artifacts)
    handle = eng.submit(prompts[0], max_new=NEW)
    _drive(eng, done=lambda: eng._step_ahead is not None)
    spare = eng.cache.pool.alloc(eng.cache.pool.free_count)
    fell = []
    while _busy(eng):
        before = eng.stats()["steps_behind_step"]
        eng._iterate()
        if spare and eng._live and eng._step_ahead is None:
            # nothing was launched ahead, and nobody was failed for it
            assert eng.stats()["steps_behind_step"] == before
            fell.append(next(iter(eng._live.values())).pos)
            eng.cache.pool.release(spare)
            spare = None
    assert fell == [BS]         # the row's next write opens a block
    assert handle.result(timeout=1) == _served(
        _engine(artifacts, in_turn=True), prompts, (NEW,))[0]
    st = eng.stats()
    assert 0 < st["steps_behind_step"] < st["decode_steps"] - 1
    assert st["requests_failed"] == 0
    _settled(eng)


# ---- (c) who launches a step ahead, and when ----------------------------

def test_c_a_chunk_waiting_goes_first(artifacts):
    """While a parked prompt has chunks left, each goes behind the shared
    step and no step does; once its last chunk is read and its row has
    joined, steps go behind steps."""
    eng = _engine(artifacts)
    prompts = _prompts(seed=3, lens=(10, 90))
    handles = [eng.submit(p, max_new=NEW) for p in prompts]
    seen = []
    while _busy(eng):
        parked = bool(eng._next_chunk())
        before = eng.stats()
        eng._iterate()
        st = eng.stats()
        seen.append((parked and bool(eng._live),
                     st["chunks_behind_step"] - before["chunks_behind_step"],
                     st["steps_behind_step"] - before["steps_behind_step"]))
        assert eng._behind is None or eng._step_ahead is None
    assert [h.result(timeout=1) for h in handles] == _served(
        _engine(artifacts, in_turn=True), prompts, (NEW, NEW))
    with_chunk = [row for row in seen if row[1]]
    assert len(with_chunk) >= 2 and not any(a for _, _, a in with_chunk)
    assert all(c for waiting, c, _ in seen[1:] if waiting)
    assert sum(a for _, _, a in seen) == eng.stats()["steps_behind_step"] > 0
    _settled(eng)


def test_c_a_gpt2_engine_launches_no_step_ahead(tmp_path):
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
    assert st["steps_behind_step"] == st["step_ahead_dead_rows"] == 0
    assert st["host_reads"] == st["prefills"] + st["decode_steps"] == 3 + 5


def test_c_a_block_diffusion_engine_launches_no_step_ahead(tmp_path):
    model, params = __import__("test_block_decoder").build("float32")
    d = str(tmp_path / "sdar")
    serving.export_generator(model, params, d, ragged=True, stepwise=True,
                             paged=True, slots=3, block_size=16,
                             prompt_len=32, max_new_tokens=16,
                             platforms=("cpu",))
    eng = GenerationEngine(serving.load_stepwise(d))
    rs = np.random.RandomState(0)
    for n in (5, 20, 9):
        eng.submit(rs.randint(0, 384, n).tolist(), max_new=8)
    _drive(eng)
    st = eng.stats()
    assert st["block_steps"] > 0 and st["requests_done"] == 3
    assert st["steps_behind_step"] == st["step_ahead_dead_rows"] == 0


# ---- (d) what the ring holds of a step ahead ----------------------------

def test_d_the_step_ahead_is_marked_where_it_is_launched_and_where_it_is_read(
        ring, artifacts):
    eng = _engine(artifacts)
    prompts = _prompts()
    handles = [eng.submit(p, max_new=k) for p, k in zip(prompts, MAX_NEW)]
    reads = []
    while _busy(eng):
        before = eng.stats()["host_reads"]
        eng._iterate()
        reads.append(eng.stats()["host_reads"] - before)
    assert [h.result(timeout=1) for h in handles]
    st = eng.stats()
    assert max(reads) <= 2, reads
    spans = _sched(ring)
    lane = [s for s in spans if s[1] == "scheduler"]
    steps = [s for s in lane if s[2] == "decode_step"]
    assert len(steps) == st["decode_steps"]
    assert not any("program" in (s[5] or {}) for s in steps)
    marks = {s[5]["seq"]: s for s in lane if s[2] == "admit_launch"
             and s[5]["program"] == "decode"}
    read = {s[5]["seq"]: s for s in lane if s[2] == "admit_read"
            and s[5]["program"] == "decode"}
    assert sorted(marks) == sorted(read) == list(range(len(steps)))

    def holder(mark):
        return next(q for q, s in enumerate(steps)
                    if s[3] <= mark[3] and mark[4] <= s[4])
    ahead = 0
    for seq in sorted(marks):
        # a step is read inside the seq-th decode_step span, after every
        # launch that span holds; it is launched there (in turn), or
        # inside the span before (ahead), after that span's own launch
        # or adoption and before its read
        assert holder(read[seq]) == seq
        at = holder(marks[seq])
        assert at in (seq, seq - 1)
        if at == seq:
            continue
        ahead += 1
        assert marks[seq][4] <= read[seq - 1][3]
        if seq - 1 in marks and holder(marks[seq - 1]) == seq - 1:
            assert marks[seq - 1][4] <= marks[seq][3]
        # both launches sit in a sched_dispatch; no chunk's launch lies
        # in a span that holds a step ahead
        inner = [s for s in lane if steps[at][3] <= s[3]
                 and s[4] <= steps[at][4] and s is not steps[at]]
        assert not any(s[2] == "admit_launch"
                       and s[5]["program"] == "prefill_chunk"
                       for s in inner)
        assert any(s[2] == "sched_dispatch" and s[3] <= marks[seq][3]
                   and marks[seq][4] <= s[4] for s in inner)
    assert ahead == st["steps_behind_step"] > 0
    assert st["host_reads"] == st["decode_steps"] + st["prefill_chunks"]
    # the benchmark's reader pairs such a capture by order: a device
    # that runs each program right behind its launch and the program
    # before it is causal under the spans' own clock, with no edge cut
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
    # every decode launch finds its read: none is left without one
    assert all(launch[1] is not None for launch in found
               if launch[2] == "decode")
    # a capture that opens or ends INSIDE a step (nearly every one does)
    # loses the spans open at its edges and keeps the short ones: what
    # is left still pairs by order
    queued = [marks[q] for q in sorted(marks) if holder(marks[q]) == q - 1]
    for launch in queued[1::3]:
        for t0, t1 in ((spans[0][3], launch[4] + 1e-7),
                       (launch[3] - 1e-7, spans[-1][4])):
            cut = {name: [s for s in rows if t0 <= s[0] and s[1] <= t1]
                   for name, rows in by_name.items()}
            inside = [m for m in mods if t0 <= m[0] <= t1]
            got = launch_pairs.pair(cut, inside)
            assert got is not None and got["lo"] <= got["hi"], (t0, t1)


# ---- (e) the protocol with a step in flight -----------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_e_a_step_seam_fault_with_a_step_in_flight_is_retried(artifacts,
                                                              kind):
    """The seam fires on the host at the dispatch that adopts the step in
    flight: the retry adopts the same step, and every token is the
    in-turn sequence's."""
    eng, prompts, handles = _in_flight(artifacts, kind)
    flying = eng._step_ahead
    faults.install(faults.parse_spec("engine.decode_step:step=1", seed=0))
    try:
        eng._iterate()
    finally:
        faults.install(None)
    st = eng.stats()
    assert st["redispatches"] == 1 and st["requests_failed"] == 0
    assert eng._step_ahead is not flying and len(eng._live) == 2
    _drive(eng)
    assert [h.result(timeout=1) for h in handles] == _served(
        _engine(artifacts, kind, in_turn=True), prompts, (NEW, NEW))
    _settled(eng)


def test_e_a_step_that_fails_twice_with_a_step_in_flight_evicts_the_newest(
        artifacts):
    eng, prompts, handles = _in_flight(artifacts, "kimi_linear_tiny",
                                       lens=(10, 12, 9))
    newest = max(eng._live.values(), key=lambda s: s.admit_seq)
    dead = eng.stats()["step_ahead_dead_rows"]
    faults.install(faults.parse_spec(
        "engine.decode_step:step=1;engine.decode_step:step=1", seed=0))
    try:
        eng._iterate()
    finally:
        faults.install(None)
    st = eng.stats()
    assert eng._live.get(newest.index) is not newest and len(eng._live) == 2
    assert st["redispatches"] == 2
    # the step in flight computed the evicted row: dropped, not re-run
    assert st["step_ahead_dead_rows"] == dead + 1
    _drive(eng)
    want = _served(_engine(artifacts, in_turn=True), prompts, (NEW,) * 3)
    for h, w in zip(handles, want):
        if h.req is newest.req:
            with pytest.raises(PoisonedRequestError, match="evicted"):
                h.result(timeout=1)
        else:
            assert h.result(timeout=1) == w
    _settled(eng)


def test_e_a_launch_ahead_that_fails_on_the_host_leaves_the_step_to_its_turn(
        artifacts):
    """The program's callable raises before it takes the pool: nothing
    is in flight, the next step is launched in turn, nobody fails."""
    eng, prompts, handles = _in_flight(artifacts, "laguna_tiny")
    real, raised = eng.sw.decode, []

    def decode(feats):
        if not raised:
            raised.append(1)
            raise RuntimeError("simulated launch failure")
        return real(feats)
    eng.sw.decode = decode
    before = eng.stats()
    eng._iterate()              # adopts the step in flight, launches none
    st = eng.stats()
    assert raised and eng._step_ahead is None and eng._pool_alive()
    assert st["decode_steps"] == before["decode_steps"] + 1
    assert st["steps_behind_step"] == before["steps_behind_step"]
    assert st["redispatches"] == st["requests_failed"] == 0
    _drive(eng)
    assert [h.result(timeout=1) for h in handles] == _served(
        _engine(artifacts, "laguna_tiny", in_turn=True), prompts,
        (NEW, NEW))
    assert eng.stats()["steps_behind_step"] > before["steps_behind_step"]
    _settled(eng)


def test_e_an_allocation_fault_at_the_step_ahead_leaves_the_step_to_its_turn(
        artifacts):
    """The ``pool.alloc`` seam fires where the step ahead asks for the
    block its row opens next: nothing is launched ahead, nobody is
    failed for it, and the step in turn gets the block."""
    prompts = _prompts(seed=9, lens=(BS - 3,))
    eng = _engine(artifacts, "axk1_tiny")
    handle = eng.submit(prompts[0], max_new=NEW)
    slot = lambda: next(iter(eng._live.values()))           # noqa: E731
    _drive(eng, done=lambda: eng._step_ahead is not None
           and slot().pos == BS - 1)
    faults.install(faults.parse_spec("pool.alloc:step=1", seed=0))
    try:
        eng._iterate()          # the step at BS - 1 adopted; BS needs a block
    finally:
        faults.install(None)
    assert eng._step_ahead is None and slot().pos == BS
    assert int(eng.cache.tables[slot().index, 1]) == 0
    _drive(eng)
    assert handle.result(timeout=1) == _served(
        _engine(artifacts, "axk1_tiny", in_turn=True), prompts, (NEW,))[0]
    assert eng.stats()["requests_failed"] == 0
    _settled(eng)


class _FailsOnRead:
    """What a program that faulted on the device hands back: the error
    surfaces where the host materializes the result."""

    def copy_to_host_async(self):
        pass

    def block_until_ready(self):
        raise RuntimeError("simulated async device fault")

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("simulated async device fault")


def test_e_a_device_fault_in_the_step_in_flight_is_engine_fatal(artifacts):
    """The faulted step's pool is what everything since was launched on:
    nothing is left to quarantine over. Every request fails, the pool is
    rebuilt, the engine serves again."""
    eng = _engine(artifacts)
    real, calls = eng.sw.decode, []

    def decode(feats):
        out = real(feats)
        calls.append(1)
        if len(calls) == 4:
            return {**out, "expert_rows": _FailsOnRead()}
        return out
    eng.sw.decode = decode
    prompts = _prompts(seed=3, lens=(10, 12))
    handles = [eng.submit(p, max_new=NEW) for p in prompts]
    eng.start()
    try:
        for h in handles:
            with pytest.raises(RuntimeError, match="scheduler step"):
                h.result(timeout=120)
        assert eng._step_ahead is None and eng.stats()["redispatches"] == 0
        assert len(eng.generate(prompts[0], max_new=3, timeout=120)) == 3
    finally:
        eng.close()


@pytest.mark.parametrize("kind", KINDS)
def test_e_close_waits_for_the_step_in_flight(artifacts, kind):
    eng = _engine(artifacts, kind)
    handles = [eng.submit(p, max_new=NEW) for p in _prompts(seed=5)]
    eng.start()
    deadline = time.time() + 120
    while not eng.stats()["steps_behind_step"] and time.time() < deadline:
        time.sleep(0.002)
    eng.close()
    assert eng._step_ahead is None and eng._behind is None
    assert eng._thread is None
    for arr in eng._pool.values():
        assert not arr.is_deleted()
        arr.block_until_ready()
    for h in handles:
        assert h.done()


def test_e_with_nobody_left_the_step_in_flight_is_waited_for_and_dropped(
        artifacts):
    """Every row of the step in flight is cancelled between the two
    reads: the step is waited for (one read), its rows are counted dead,
    and the pool it returned is the engine's."""
    eng, prompts, handles = _in_flight(artifacts, "laguna_tiny")
    for h in handles:
        assert eng.cancel(h.req.request_id)
    before = eng.stats()
    eng._iterate()
    st = eng.stats()
    assert eng._step_ahead is None and not eng._live
    assert st["step_ahead_dead_rows"] == before["step_ahead_dead_rows"] + 2
    assert st["decode_steps"] == before["decode_steps"]
    assert st["host_reads"] == before["host_reads"] + 1
    for h in handles:
        with pytest.raises(RequestCancelledError):
            h.result(timeout=1)
    _settled(eng)
    assert len(_served(eng, prompts[:1], (5,))[0]) == 5
