"""Speculative decoding (round 16): drafter/estimator units, the
engine's draft-and-verify loop, stop_sequences, and the HTTP surface.

The headline contract is EXACTNESS: greedy output with ``spec_tokens=K``
on is byte-identical to speculation off — tested here at the engine and
HTTP levels across 8 concurrent ragged requests, including under int8
decode weights + int8 paged KV (the load-harness level rides the
``serving_load --smoke`` spec legs). The satellites pin the
``stop_sequences`` truncation boundary, the Retry-After
tokens-per-dispatch math, the spec-off bitwise no-op, and the
auto-off/validation surface of the knobs.
"""

import json
import os
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "experiments"))

from serving_load import build_export  # noqa: E402

from distributed_tensorflow_example_tpu.serving import \
    load_stepwise  # noqa: E402
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    GenerationEngine, NgramDrafter, RetryAfterEstimator)
from distributed_tensorflow_example_tpu.serving_http import \
    PredictServer  # noqa: E402

SLOTS = 8
PROMPT_LEN = 12
MAX_NEW = 16


@pytest.fixture(scope="module")
def spec_dir(tmp_path_factory):
    """One verify-program paged export (slots=8 — the 8-concurrent-
    ragged-requests acceptance shape) shared by the engine and HTTP
    tests."""
    d = str(tmp_path_factory.mktemp("spec"))
    vocab = build_export(d, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                         slots=SLOTS, seed=0, paged=True, block_size=4,
                         spec_tokens=4, repeating=True)
    return d, vocab


@pytest.fixture(scope="module")
def spec_int8_dir(tmp_path_factory):
    """The fully quantized twin: int8 decode weights + int8 paged KV
    pool + the verify program — speculation must stay EXACT against
    the same artifact's spec-off path (the int8-vs-bf16 drift bound is
    a separate, pre-existing contract)."""
    d = str(tmp_path_factory.mktemp("spec_int8"))
    vocab = build_export(d, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                         slots=SLOTS, seed=0, paged=True, block_size=4,
                         weight_quant="int8", kv_cache_dtype="int8",
                         spec_tokens=4, repeating=True)
    return d, vocab


def ragged_prompts(vocab: int, n: int = SLOTS, seed: int = 7):
    """n mixed-length repetitive prompts (the drafter's workload) —
    'ragged' in the engine sense: every length differs, nothing padded
    by the client."""
    rs = np.random.RandomState(seed)
    pattern = rs.randint(0, vocab, (3,)).astype(np.int32)
    return [np.tile(pattern, 5)[:int(rs.randint(2, PROMPT_LEN + 1))]
            .astype(np.int32) for _ in range(n)]


@pytest.fixture(scope="module")
def oracle(spec_dir):
    """ONE spec-off engine pass over the standard 8 ragged prompts —
    the byte-parity oracle several tests compare against (greedy rows
    are computationally independent, so any test may also compare a
    prompt SUBSET against the matching oracle rows)."""
    d, vocab = spec_dir
    prompts = ragged_prompts(vocab)
    outs, stats, _ = run_engine(d, prompts, spec=0)
    return prompts, outs, stats


def run_engine(d, prompts, *, spec: int, max_new: int = MAX_NEW, **kw):
    eng = GenerationEngine(load_stepwise(d), prefix_cache=False,
                           spec_tokens=spec).start()
    try:
        handles = [eng.submit(p, max_new=max_new, **kw)
                   for p in prompts]
        outs = [h.result(timeout=300) for h in handles]
        stats = eng.stats()
        assert eng.cache.pool.in_use == 0, "blocks leaked past retirement"
        return outs, stats, [h.timings for h in handles]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# units: the drafter and the Retry-After math
# ---------------------------------------------------------------------------

def test_ngram_drafter_proposes_continuation_of_latest_match():
    dr = NgramDrafter([1, 2, 3, 9, 1, 2, 3, 7, 1, 2, 3])
    # suffix [1,2,3] last PRIOR occurrence starts at index 4 -> the
    # continuation is [7, 1, 2] (most recent match wins, not the first)
    assert dr.propose(3) == [7, 1, 2]
    assert dr.propose(1) == [7]


def test_ngram_drafter_never_matches_its_own_suffix():
    # the only occurrence of every suffix IS the suffix — no proposal
    assert NgramDrafter([1, 2, 3, 4]).propose(4) == []
    # a 1-token context has nothing prior to continue from
    assert NgramDrafter([5]).propose(2) == []


def test_ngram_drafter_extends_incrementally():
    dr = NgramDrafter([4, 5, 6])
    assert dr.propose(2) == []
    for t in (4, 5):
        dr.extend(t)
    # context [4,5,6,4,5]: suffix [4,5] recurs at 0 -> continuation
    # [6, 4] (the proposal may include the current last token — it is
    # still a prediction about what FOLLOWS the suffix)
    assert dr.propose(2) == [6, 4]
    assert dr.propose(1) == [6]
    assert len(dr) == 5


def test_ngram_drafter_falls_back_to_shorter_ngrams():
    # no 3- or 2-gram recurs, but the 1-gram [2] does (latest at
    # index 2 -> continuation 9)
    dr = NgramDrafter([2, 8, 2, 9, 3, 2], max_ngram=3)
    assert dr.propose(2) == [9, 3]


def test_ngram_drafter_validates_max_ngram():
    with pytest.raises(ValueError, match="max_ngram"):
        NgramDrafter([1], max_ngram=0)


def test_retry_after_counts_accepted_tokens_per_dispatch():
    """The satellite fix: steps-to-free must count accepted TOKENS per
    dispatch, not dispatches — at accept-driven 3 tokens/dispatch, 30
    remaining row-steps are ~10 dispatches, not 30 (the pre-fix
    estimate overestimated Retry-After by ~1/accept_rate)."""
    est = RetryAfterEstimator(alpha=0.5)
    assert est.dispatches_for(30.0) == 30.0        # spec-off identity
    for _ in range(64):
        est.observe_advance(3.0)
    assert est.ema_tokens_per_dispatch == pytest.approx(3.0, rel=1e-3)
    assert est.dispatches_for(30.0) == pytest.approx(10.0, rel=1e-2)
    # the estimate itself consumes the converted hint
    est.observe(0.1)
    assert est.estimate(est.dispatches_for(30.0)) \
        == pytest.approx(1.0, rel=0.05)


def test_retry_after_advance_clamped_at_one_dispatch_per_step():
    est = RetryAfterEstimator(alpha=1.0)
    est.observe_advance(0.25)      # a degenerate feed must not blow up
    assert est.dispatches_for(8.0) == 8.0


# ---------------------------------------------------------------------------
# engine level: exactness across 8 concurrent ragged requests
# ---------------------------------------------------------------------------

def test_engine_spec_greedy_byte_parity_and_dispatch_win(spec_dir,
                                                         oracle):
    d, _ = spec_dir
    prompts, off, s_off = oracle
    on, s_on, timings = run_engine(d, prompts, spec=4)
    assert on == off, "speculative greedy output diverged"
    assert s_off["verify_steps"] == 0
    assert s_on["spec_accepted"] > 0 and s_on["accept_rate"] > 0
    # the decode economy: strictly fewer total shared dispatches, and
    # strictly fewer verify dispatches than emitted tokens
    assert (s_on["decode_steps"] + s_on["verify_steps"]
            < s_off["decode_steps"])
    assert s_on["verify_steps"] < s_on["tokens_out"]
    # rejections genuinely happened — so the pos-rewind/trailing-block
    # path ran, and the exact in_use == 0 check inside run_engine plus
    # the BlockPool's own double-release assertions covered it
    assert s_on["spec_proposed"] > s_on["spec_accepted"]
    # per-request accounting reaches the timings breakdown
    assert sum(t["spec_accepted"] for t in timings) \
        == s_on["spec_accepted"]


def test_engine_spec_exact_under_int8_weights_and_kv(spec_int8_dir):
    """The acceptance criterion's quant leg: speculation must stay
    byte-exact when the verify program runs int8 stacked weights AND
    the int8 paged pool (quantize-on-write + fused-dequant gathers) —
    the verify body is the decode body over expanded rows, so the
    whole quant surface rides along."""
    d, vocab = spec_int8_dir
    prompts = ragged_prompts(vocab)
    off, s_off, _ = run_engine(d, prompts, spec=0)
    on, s_on, _ = run_engine(d, prompts, spec=4)
    assert on == off, "int8 speculative output diverged from int8 oracle"
    assert s_on["spec_accepted"] > 0
    assert (s_on["decode_steps"] + s_on["verify_steps"]
            < s_off["decode_steps"])


def test_engine_spec_exact_for_sampled_requests(spec_dir):
    """Sampled requests never draft (the exact rule is greedy-only):
    their per-seed determinism contract is untouched and no verify
    dispatch carries their lanes beyond width 1."""
    d, vocab = spec_dir
    prompts = ragged_prompts(vocab, n=4)
    kw = dict(temperature=0.8, top_k=5, seed=11)
    off, _, _ = run_engine(d, prompts, spec=0, **kw)
    on, s_on, _ = run_engine(d, prompts, spec=4, **kw)
    assert on == off
    assert s_on["spec_proposed"] == 0 and s_on["verify_steps"] == 0


def test_engine_spec_off_is_bitwise_noop(spec_dir, tmp_path):
    """--spec_tokens 0 (the default) over a verify-program artifact is
    a BITWISE no-op: identical outputs, identical dispatch counts, and
    identical pool bytes vs the same engine over a plain paged export
    of the same seed (zero verify dispatches, zero drafting work)."""
    d, vocab = spec_dir
    plain = str(tmp_path / "plain")
    build_export(plain, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                 slots=SLOTS, seed=0, paged=True, block_size=4,
                 repeating=True)
    prompts = ragged_prompts(vocab, n=SLOTS)

    def run_preloaded(dir_):
        """Pre-load the queue before start() so the admission wave —
        and therefore the dispatch sequence — is deterministic."""
        eng = GenerationEngine(load_stepwise(dir_), prefix_cache=False)
        handles = [eng.submit(p, max_new=8) for p in prompts]
        eng.start()
        try:
            outs = [h.result(timeout=300) for h in handles]
            s = eng.stats()
            pool = {k: np.asarray(v) for k, v in eng._pool.items()}
            return outs, (s["decode_steps"], s["prefills"],
                          s["verify_steps"]), pool
        finally:
            eng.close()

    outs_a, counts_a, pool_a = run_preloaded(d)
    outs_b, counts_b, pool_b = run_preloaded(plain)
    assert outs_a == outs_b
    assert counts_a == counts_b and counts_a[2] == 0
    assert sorted(pool_a) == sorted(pool_b)
    for k in pool_a:
        assert np.array_equal(pool_a[k], pool_b[k]), \
            f"pool tensor {k} diverged bitwise under spec-off"


def test_engine_spec_knob_validation(spec_dir, tmp_path):
    d, _ = spec_dir
    sw = load_stepwise(d)
    with pytest.raises(ValueError, match="spec_tokens"):
        GenerationEngine(sw, spec_tokens=1)
    with pytest.raises(ValueError, match="verify width"):
        GenerationEngine(sw, spec_tokens=9)
    plain = str(tmp_path / "noverify")
    build_export(plain, prompt_len=8, max_new=4, slots=2, seed=0,
                 paged=True, block_size=4)
    with pytest.raises(ValueError, match="verify program"):
        GenerationEngine(load_stepwise(plain), spec_tokens=4)


def test_engine_per_request_spec_optout_and_cap(spec_dir, oracle):
    d, _ = spec_dir
    prompts, off, _ = oracle
    prompts = prompts[:4]
    # spec_tokens=0 per request: no drafting at all, bytes identical
    # to the oracle's matching rows (rows are independent)
    outs, s, _ = run_engine(d, prompts, spec=4, spec_tokens=0)
    assert s["spec_proposed"] == 0 and s["verify_steps"] == 0
    assert outs == off[:4]
    # a cap above the engine width is a loud client error
    eng = GenerationEngine(load_stepwise(d), spec_tokens=4)
    try:
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(prompts[0], spec_tokens=9)
        with pytest.raises(ValueError, match="spec_tokens"):
            eng.submit(prompts[0], spec_tokens=1)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# stop_sequences: truncation at the boundary, spec on and off
# ---------------------------------------------------------------------------

def _expected_stopped(base, ss, pad):
    """Host-side recomputation of the truncation contract: base
    outputs cut before the FIRST completed stop-sequence match (first
    in list order per position), then padded to max_new."""
    out = []
    for b in base:
        exp = list(b)
        done = False
        for i in range(1, len(exp) + 1):
            for s in ss:
                if i >= len(s) and exp[i - len(s):i] == list(s):
                    exp = exp[:i - len(s)] + [pad] * (
                        MAX_NEW - (i - len(s)))
                    done = True
                    break
            if done:
                break
        out.append(exp)
    return out


def test_stop_sequences_truncate_at_boundary(spec_dir, oracle):
    d, _ = spec_dir
    prompts, base, _ = oracle
    # stop on the 2-token suffix that opens request 0's output: its
    # result must be truncated to NOTHING (match excluded), padded to
    # max_new with pad_id
    ss = [list(map(int, base[0][:2]))]
    outs, _, _ = run_engine(d, prompts, spec=0, stop_sequences=ss)
    pad = load_stepwise(d).meta.get("pad_id", 0)
    assert outs[0] == [pad] * MAX_NEW
    assert outs == _expected_stopped(base, ss, pad)


def test_stop_sequences_identical_with_speculation(spec_dir, oracle):
    d, _ = spec_dir
    prompts, base, _ = oracle
    # stop sequences drawn from the middle of a real output, so a
    # match routinely completes INSIDE an accepted draft run; the
    # speculative truncation must land exactly where the recomputed
    # non-speculative contract says (== where the spec-off engine
    # lands, per test_stop_sequences_truncate_at_boundary)
    donor = max(base, key=len)
    ss = [list(map(int, donor[2:4])), list(map(int, base[0][:1]))]
    pad = load_stepwise(d).meta.get("pad_id", 0)
    on, _, _ = run_engine(d, prompts, spec=4, stop_sequences=ss)
    assert on == _expected_stopped(base, ss, pad), \
        "stop_sequences boundary moved under speculation"


def test_stop_sequences_validation(spec_dir):
    d, vocab = spec_dir
    eng = GenerationEngine(load_stepwise(d))
    try:
        p = np.array([1, 2, 3], np.int32)
        with pytest.raises(ValueError, match="stop_sequences"):
            eng.submit(p, stop_sequences="abc")
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit(p, stop_sequences=[[]])
        with pytest.raises(ValueError, match="non-integer"):
            eng.submit(p, stop_sequences=[[1, "x"]])
        with pytest.raises(ValueError, match="at most 16"):
            eng.submit(p, stop_sequences=[[1]] * 17)
        with pytest.raises(ValueError, match="64"):
            eng.submit(p, stop_sequences=[[1] * 65])
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# HTTP level
# ---------------------------------------------------------------------------

def _post(port, name, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.read().decode()


def _serve_concurrent(d, prompts, *, spec_tokens, **payload_kw):
    outs: list = [None] * len(prompts)
    with PredictServer(d, prefix_cache=False,
                       spec_tokens=spec_tokens) as srv:
        def client(i):
            outs[i] = _post(srv.port, srv.name, {
                "inputs": {"input_ids": [prompts[i].tolist()]},
                "max_new": 10, **payload_kw})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = json.loads(_get(srv.port, "/stats"))
        prom = _get(srv.port, "/metrics")
    return outs, stats, prom


def test_http_spec_parity_stats_and_metrics(spec_dir):
    """8 concurrent ragged :generate requests: byte parity spec-on vs
    spec-off, accept_rate visible in /stats AND /metrics, and
    spec_accepted riding every response's timings row."""
    d, vocab = spec_dir
    prompts = ragged_prompts(vocab)
    off, _, _ = _serve_concurrent(d, prompts, spec_tokens=0)
    on, stats, prom = _serve_concurrent(d, prompts, spec_tokens=4)
    assert [o["generations"] for o in on] \
        == [o["generations"] for o in off]
    g = stats["generate"]
    assert g["spec_tokens"] == 4
    assert g["spec_accepted"] > 0 and g["accept_rate"] > 0
    assert g["verify_steps"] < g["tokens_out"]
    assert "serving_spec_accept_rate" in prom
    assert "serving_verify_steps_total" in prom
    assert all("spec_accepted" in o["timings"][0] for o in on)
    assert sum(o["timings"][0]["spec_accepted"] for o in on) \
        == g["spec_accepted"]


def test_http_payload_spec_and_stop_knobs(spec_dir):
    d, vocab = spec_dir
    prompts = ragged_prompts(vocab, n=2)
    with PredictServer(d, prefix_cache=False, spec_tokens=4) as srv:
        base = _post(srv.port, srv.name, {
            "inputs": {"input_ids": [prompts[0].tolist()]},
            "max_new": 8})["generations"][0]
        # per-request opt-out serves identically (exactness, again)
        opt = _post(srv.port, srv.name, {
            "inputs": {"input_ids": [prompts[0].tolist()]},
            "max_new": 8, "spec_tokens": 0})["generations"][0]
        assert opt == base
        # stop_sequences truncates at the boundary over HTTP
        stop = _post(srv.port, srv.name, {
            "inputs": {"input_ids": [prompts[0].tolist()]},
            "max_new": 8, "stop_sequences": [base[:2]]})
        pad = srv.servable.meta.get("pad_id", 0)
        assert stop["generations"][0] == [pad] * 8
        # invalid knobs are clean 400s naming the field
        for bad in ({"spec_tokens": 99}, {"spec_tokens": 1},
                    {"stop_sequences": [[]]},
                    {"stop_sequences": "x"}):
            try:
                _post(srv.port, srv.name, {
                    "inputs": {"input_ids": [prompts[0].tolist()]},
                    "max_new": 4, **bad})
                raise AssertionError(f"{bad} was not rejected")
            except urllib.error.HTTPError as e:
                assert e.code == 400, (bad, e.code)


def test_http_engine_only_knobs_rejected_on_scheduler_off(spec_dir):
    """The monolithic (scheduler-off) path cannot honor
    stop_sequences or spec_tokens — a payload carrying them must be a
    clear 400 naming the scheduler requirement, never a 200 that
    silently dropped the contract."""
    d, _ = spec_dir
    with PredictServer(d, scheduler="off") as srv:
        for bad in ({"stop_sequences": [[1, 2]]}, {"spec_tokens": 2}):
            try:
                _post(srv.port, srv.name, {
                    "inputs": {"input_ids": [[1, 2, 3]]}, **bad})
                raise AssertionError(f"{bad} accepted on scheduler-off")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "scheduler" in json.loads(e.read())["error"]


def test_http_spec_tokens_auto_off_without_verify_program(tmp_path):
    """--spec_tokens over an artifact without a verify program serves
    spec-off (warning, not refusal) — the auto-off contract."""
    d = str(tmp_path / "plain")
    vocab = build_export(d, prompt_len=8, max_new=4, slots=2, seed=0,
                         paged=True, block_size=4)
    with PredictServer(d, spec_tokens=4) as srv:
        assert srv.engine is not None
        assert srv.engine.spec_tokens == 0
        out = _post(srv.port, srv.name, {
            "inputs": {"input_ids": [[1, 2, 3]]}, "max_new": 2})
        assert len(out["generations"][0]) == 2
        g = json.loads(_get(srv.port, "/stats"))["generate"]
        assert g["spec_tokens"] == 0 and g["verify_steps"] == 0


def test_http_spec_tokens_clamped_to_artifact_width(spec_dir):
    d, _ = spec_dir
    with PredictServer(d, spec_tokens=9) as srv:
        assert srv.engine.spec_tokens == 4
