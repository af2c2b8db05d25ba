"""What the one-token decode step hands the host (PR 36):

- the GPT-2 programs (prefill, decode, a prefill chunk; paged and slab)
  return each row's greedy ``ids`` beside the logits, ``export.json``
  says so (``stepwise.decode.returns``) and the verify program does not,
- an all-greedy engine run fetches ids alone (``decode_logits_steps``
  0) and gives the tokens of the same run served from the logits, which
  is how an artifact whose ``export.json`` lacks the key is served,
- a batch that mixes greedy and ``temperature > 0`` rows gives every
  row the tokens the logits fetch gave it (fixed per-request seed),
  fetches logits while a sampled row samples and returns to ids steps
  when it retires; a sampled row that is being teacher-forced through a
  cached prefix's suffix does not hold the logits fetch,
- the ``decode_step`` span's ``host_bytes`` and the registry's three
  counters say which it was.
"""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
from distributed_tensorflow_example_tpu.obs.trace import (
    TraceRecorder, recorder, set_recorder)
from distributed_tensorflow_example_tpu.serving import (export_generator,
                                                        load_stepwise)
from distributed_tensorflow_example_tpu.serving_batch import GenerationEngine

PROMPT_LEN = 8
MAX_NEW = 6
SLOTS = 4
BLOCK = 4
KINDS = ("paged", "slab")


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """A paged and a slab gpt_tiny artifact, and beside each a copy whose
    ``export.json`` lacks ``stepwise.decode.returns`` (what an export
    made before the programs returned ids looks like to the loader)."""
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    params = m.init(jax.random.key(0))
    out = {"vocab": m.cfg.vocab_size}
    for kind in KINDS:
        d = str(tmp_path_factory.mktemp(kind))
        export_generator(
            m, params, d, prompt_len=PROMPT_LEN, max_new_tokens=MAX_NEW,
            batch_size=1, ragged=True, stepwise=True, slots=SLOTS,
            platforms=("cpu",),
            **(dict(paged=True, block_size=BLOCK, num_blocks=48)
               if kind == "paged" else {}))
        old = d + "_no_returns"
        shutil.copytree(d, old)
        path = os.path.join(old, "export.json")
        with open(path) as f:
            meta = json.load(f)
        del meta["stepwise"]["decode"]["returns"]
        with open(path, "w") as f:
            json.dump(meta, f)
        out[kind] = (d, old)
    return out


@pytest.fixture
def ring():
    old = recorder()
    rec = set_recorder(TraceRecorder())
    rec.start()
    yield rec
    set_recorder(old)


def _prompts(n, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 1000, (int(rs.randint(2, PROMPT_LEN + 1)),))
            .astype(np.int32) for _ in range(n)]


def _run(directory, requests, **engine_kw):
    """One wave: every request queued before the scheduler starts, so
    admission order, steps and counters are exact. Returns the token
    streams and the engine's closing ``/stats``."""
    eng = GenerationEngine(load_stepwise(directory), **engine_kw)
    futs = [eng.submit(p, **kw) for p, kw in requests]
    eng.start()
    try:
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.close()
    return got, eng.stats()


def test_export_records_what_each_program_returns(artifacts, tmp_path):
    for kind in KINDS:
        d, old = artifacts[kind]
        want = {"prefill": ["ids", "logits"] + ["pad"] * (kind == "slab"),
                "decode": ["ids", "logits"]}
        assert load_stepwise(d).returns == want
        assert load_stepwise(old).returns == {}
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    d = str(tmp_path)
    export_generator(m, m.init(jax.random.key(0)), d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=SLOTS, paged=True,
                     block_size=BLOCK, spec_tokens=2, prefill_chunk=BLOCK,
                     platforms=("cpu",))
    with open(os.path.join(d, "export.json")) as f:
        got = json.load(f)["stepwise"]["decode"]["returns"]
    # speculation keeps its [slots, K, V] logits: the host's rejection
    # rule reads every lane
    assert got == {"prefill": ["ids", "logits"], "decode": ["ids", "logits"],
                   "verify": ["logits"], "prefill_chunk": ["ids", "logits"]}


@pytest.mark.parametrize("kind", KINDS)
def test_programs_ids_are_the_argmax_of_their_logits(artifacts, kind):
    sw = load_stepwise(artifacts[kind][0])
    rs = np.random.RandomState(3)
    feats = {"tok": rs.randint(1, 1000, (SLOTS,)).astype(np.int32),
             "pos": np.full((SLOTS,), 2, np.int32),
             "pad": np.zeros((SLOTS,), np.int32),
             "alive": np.ones((SLOTS,), np.int32), **sw.make_pool()}
    if kind == "paged":
        per_slot = sw.step_meta["blocks_per_slot"]
        feats["block_tables"] = np.arange(
            1, 1 + SLOTS * per_slot, dtype=np.int32).reshape(SLOTS, per_slot)
    out = sw.decode(feats)
    ids = np.asarray(out["ids"])
    assert ids.dtype == np.int32 and ids.shape == (SLOTS,)
    np.testing.assert_array_equal(
        ids, np.argmax(np.asarray(out["logits"]), axis=-1))


@pytest.mark.parametrize("kind", KINDS)
def test_greedy_run_fetches_ids_and_matches_the_logits_fetch(artifacts,
                                                             kind):
    d, old = artifacts[kind]
    requests = [(p, {}) for p in _prompts(SLOTS + 2, seed=1)]
    got, stats = _run(d, requests)
    want, old_stats = _run(old, requests)
    assert got == want
    steps = stats["decode_steps"]
    assert steps == old_stats["decode_steps"] > 0
    assert (stats["decode_ids_steps"], stats["decode_logits_steps"]) == (
        steps, 0)
    assert stats["decode_host_bytes"] == steps * SLOTS * 4
    # without the key: every step's logits, as the parent served it
    assert (old_stats["decode_ids_steps"],
            old_stats["decode_logits_steps"]) == (0, steps)
    assert old_stats["decode_host_bytes"] == (
        steps * SLOTS * artifacts["vocab"] * 4)


@pytest.mark.parametrize("kind", KINDS)
def test_sampled_rows_keep_their_tokens_and_ids_steps_return(artifacts,
                                                             kind):
    """One wave of a sampled row that retires after its third token and
    greedy rows that run to ``MAX_NEW``: the sampled row's first token
    comes from its prefill's logits, its other two from two logits
    steps; the remaining steps fetch ids again."""
    d, old = artifacts[kind]
    prompts = _prompts(SLOTS, seed=2)
    requests = [(prompts[0], {"max_new": 3, "temperature": 0.9, "top_k": 50,
                              "seed": 7})]
    requests += [(p, {}) for p in prompts[1:]]
    got, stats = _run(d, requests)
    want, old_stats = _run(old, requests)
    assert got == want
    # the same request alone, greedy: the sample is no argmax in disguise
    assert got[0] != _run(old, [(prompts[0], {"max_new": 3})])[0][0]
    assert stats["decode_steps"] == MAX_NEW - 1
    assert (stats["decode_logits_steps"], stats["decode_ids_steps"]) == (
        2, MAX_NEW - 3)
    assert stats["decode_host_bytes"] == 4 * SLOTS * (
        2 * artifacts["vocab"] + MAX_NEW - 3)
    assert old_stats["decode_ids_steps"] == 0


def test_forced_sampled_row_does_not_hold_the_logits_fetch(artifacts):
    """A sampled request whose prompt shares its first block with a
    cached one is teacher-forced through the rest of its prompt: those
    steps' outputs are scaffolding and stay on the device; its samples
    are the ones the logits fetch alone gave it."""
    d, old = artifacts["paged"]
    first = np.arange(11, 11 + PROMPT_LEN, dtype=np.int32)
    second = np.concatenate([first[:BLOCK], first[BLOCK:] + 500])
    knobs = {"max_new": 3, "temperature": 0.8, "seed": 11}

    def run(directory):
        eng = GenerationEngine(load_stepwise(directory), prefix_cache=True)
        eng.start()
        try:
            a = eng.submit(first).result(timeout=120)
            before = eng.stats()
            b = eng.submit(second, **knobs).result(timeout=120)
        finally:
            eng.close()
        return a, b, before, eng.stats()

    a, b, before, after = run(d)
    assert (a, b) == run(old)[:2]
    assert after["prefix_cache_hits"] == 1
    # forced over PROMPT_LEN - BLOCK - 1 steps (ids), then the step that
    # feeds the last prompt token and two more sample (logits)
    forced = PROMPT_LEN - BLOCK - 1
    assert after["decode_ids_steps"] - before["decode_ids_steps"] == forced
    assert (after["decode_logits_steps"]
            - before["decode_logits_steps"]) == 3


def test_decode_step_span_carries_host_bytes(artifacts, ring):
    prompts = _prompts(2, seed=4)
    _run(artifacts["paged"][0],
         [(prompts[0], {"max_new": 2, "temperature": 1.0, "seed": 3}),
          (prompts[1], {"max_new": 4})])
    steps = [s[5] for s in ring.drain() if s[2] == "decode_step"]
    assert [s["host_bytes"] for s in steps] == [
        4 * SLOTS * artifacts["vocab"], 4 * SLOTS, 4 * SLOTS]
    assert all({"slots", "kv_bytes", "kv_blocks"} <= set(s) for s in steps)


def test_chunked_prefill_first_token_follows_the_same_rule(tmp_path):
    """GPT-2's chunk program returns ids too: a greedy request's first
    token is its last chunk's id, a sampled request's is drawn from that
    chunk's logits, and both equal the whole-prompt prefill's."""
    m = get_model("gpt_tiny", TrainConfig(model="gpt_tiny"))
    d = str(tmp_path)
    export_generator(m, m.init(jax.random.key(0)), d, prompt_len=PROMPT_LEN,
                     max_new_tokens=MAX_NEW, batch_size=1, ragged=True,
                     stepwise=True, slots=SLOTS, paged=True,
                     block_size=BLOCK, prefill_chunk=BLOCK,
                     platforms=("cpu",))
    prompts = _prompts(3, seed=5)
    requests = [(prompts[0], {}), (prompts[1], {}),
                (prompts[2], {"temperature": 0.7, "seed": 5})]
    whole, _ = _run(d, requests, prefix_cache=False)
    chunked, stats = _run(d, requests, prefix_cache=False,
                          prefill_chunk_tokens=BLOCK)
    assert chunked == whole
    assert stats["prefill_chunks"] >= len(prompts)
