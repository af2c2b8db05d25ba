#!/usr/bin/env python
"""Serving chaos soak: seeded dirty-failure scenarios against the
continuous-batching engine with asserted self-healing invariants — the
serving twin of experiments/chaos_soak.py (training).

Each scenario builds a real engine over a tiny seeded paged GPT export
(CPU works: ``JAX_PLATFORMS=cpu``), injects one failure class through
the :mod:`~.runtime.faults` seams (``engine.prefill`` /
``engine.decode_step`` / ``engine.admit`` / ``pool.alloc``) or the
engine's own control surface (cancel/drain/deadlines), and asserts the
round-14 contract:

- ``deadline_storm``   — a wave of 1 ms-deadline requests races a wave
                         with no deadline: every tight request fails
                         with DeadlineExceededError, every loose
                         request's greedy bytes MATCH an undisturbed
                         run, and ``blocks_free`` recovers exactly.
- ``poison_step``      — a shared decode step fails twice at the same
                         invocation: the newest-admitted request is
                         evicted (PoisonedRequestError), survivors'
                         bytes match an undisturbed run ("repaired,
                         not survived"), ``redispatches >= 2``.
- ``blocks_cancel``    — a tight block pool over-committed by design:
                         mid-decode exhaustion fails exactly the
                         starved request loudly; cancelling a live
                         neighbor frees its blocks IMMEDIATELY (not at
                         retirement), the survivor finishes to parity,
                         and the pool recovers to the exact free count.
- ``drain_under_load`` — drain() with the queue still full: zero
                         dropped requests (all bytes to parity), new
                         admissions refused with DrainingError,
                         ``serving_drain_ms`` within budget, engine
                         dead after.
- ``flaky_dispatch``   — a one-shot transient decode fault: the
                         bounded re-dispatch heals it invisibly (zero
                         failed requests, bytes to parity, exactly one
                         extra dispatch counted).
- ``watchdog_trip``    — a wedged decode dispatch: /healthz flips
                         live -> stalled, close() raises
                         EngineStalledError naming the heartbeat age
                         instead of silently leaking the thread, and a
                         released engine still tears down clean.
- ``queue_full_retry`` — clients hammering a 2-deep admission queue
                         honor 429/Retry-After semantics in a retry
                         loop: every request eventually lands, bytes
                         to parity.
- ``overload_storm``   — ~2x sustainable offered load (round 18):
                         every admitted interactive request finishes
                         within its deadline to byte parity with zero
                         failures, best_effort is shed 429-class with
                         a measured Retry-After once the pressure
                         ladder leaves healthy, shed accounting is
                         exact, and pressure/blocks recover after.
- ``long_prompt_storm``— chunked prefill (round 18): full-length
                         prompts admit chunk by chunk while a live
                         short decoder keeps stepping — the dispatch
                         order proves decode steps interleave between
                         one prompt's chunks, bytes match the
                         chunk-off engine exactly, chunk accounting
                         is exact, blocks recover.
- ``spec_verify_fault``— a seeded ``engine.decode_step`` fault lands
                         DURING a K-token speculative verify dispatch
                         (round 16): the transient heals via the same
                         bounded re-dispatch protocol (byte parity,
                         exactly one extra dispatch, zero failures);
                         a repeat failure at the same dispatch evicts
                         the newest-admitted request with survivors
                         byte-identical and every per-row ``pos``
                         rewound exactly (pinned by byte parity plus
                         exact ``blocks_free`` recovery).

Usage::

    JAX_PLATFORMS=cpu python experiments/serving_chaos.py \
        [--scenario all] [--seed 0] [--smoke]

Prints one JSON line per scenario ({"scenario", "ok", "detail",
"metrics"}) — ``metrics`` carries the engine-registry counters the
scenario advanced (``serving_requests_failed_total`` /
``serving_cancelled_total`` / ``serving_deadline_expired_total`` /
``serving_redispatches_total`` / ``serving_drain_ms``) — plus a final
summary line. Exits nonzero if any scenario fails.
tests/test_serving_chaos.py runs the full soak as a ``slow`` test and
keeps a fast smoke of every scenario in tier-1.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from distributed_tensorflow_example_tpu.runtime import faults

# one tiny seeded export shared by all scenarios (built once in main);
# per-request max_new stays well under the exported depth so scenarios
# pick short runs for speed and long runs where they need a live window
PROMPT_LEN = 8
MAX_NEW = 16
SLOTS = 4
BLOCK = 4


def _bps() -> int:
    """Blocks per full-depth slot at the shared export shapes."""
    return -(-(PROMPT_LEN + MAX_NEW) // BLOCK)


def build_chaos_export(out_dir: str, *, seed: int,
                       num_blocks: int | None = None) -> int:
    """The scenario artifact: paged stepwise export at the module
    shapes (paged so block accounting is observable; ``num_blocks``
    lets the exhaustion scenario under-provision deliberately)."""
    from serving_load import build_export
    return build_export(
        out_dir, prompt_len=PROMPT_LEN, max_new=MAX_NEW, slots=SLOTS,
        seed=seed, paged=True, block_size=BLOCK,
        num_blocks=(1 + 4 * SLOTS * _bps()
                    if num_blocks is None else num_blocks))


def fresh_engine(export_dir: str, **kw):
    """A started engine over the shared artifact. Prefix cache OFF by
    default: every scenario asserts EXACT ``blocks_free`` recovery,
    and cached prefixes legitimately retain block references."""
    from distributed_tensorflow_example_tpu.serving import load_stepwise
    from distributed_tensorflow_example_tpu.serving_batch import \
        GenerationEngine
    kw.setdefault("prefix_cache", False)
    return GenerationEngine(load_stepwise(export_dir), **kw).start()


def seeded_prompts(n: int, seed: int, vocab: int):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (int(rs.randint(1, PROMPT_LEN + 1)),))
            .astype(np.int32) for _ in range(n)]


def reference_run(export_dir: str, prompts, max_new: int,
                  sequential: bool = False) -> list:
    """The undisturbed oracle: the same prompts through a clean engine
    (greedy rows are computationally independent, so any surviving
    subset of a chaos run must byte-match its rows here).
    ``sequential`` serves one request at a time — the oracle for the
    deliberately under-provisioned pool, where a concurrent reference
    would hit the very exhaustion the scenario injects."""
    eng = fresh_engine(export_dir)
    try:
        if sequential:
            return [eng.submit(p, max_new=max_new).result(timeout=120)
                    for p in prompts]
        handles = [eng.submit(p, max_new=max_new) for p in prompts]
        return [h.result(timeout=120) for h in handles]
    finally:
        eng.close()


def counters(eng) -> dict:
    """The scenario's published-metrics view: the self-healing counters
    this PR added, straight from the engine registry snapshot."""
    snap = eng.registry.snapshot()

    def v(name):
        m = snap.get(name)
        return (m.get("value", 0) if isinstance(m, dict) else m) or 0

    return {k: v(k) for k in (
        "serving_requests_failed_total", "serving_cancelled_total",
        "serving_deadline_expired_total", "serving_redispatches_total",
        "serving_drain_ms", "serving_shed_total",
        "serving_shed_infeasible_total", "serving_prefill_chunks_total",
        "serving_pressure_transitions_total")}


def _wait(pred, timeout=30.0, what="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# scenarios — each returns (detail, metrics)
# ---------------------------------------------------------------------------

def scenario_deadline_storm(d: str, seed: int, vocab: int):
    """Round-18 note: a 1 ms deadline can now be SHED (429-class
    ShedError, deadline infeasible at the measured rate) instead of
    expiring into the 504-class DeadlineExceededError once the
    engine's decode EMA has a signal — both are the fail-fast-and-
    return-blocks outcome this storm pins, so either counts; the
    accounting assertion covers their sum."""
    from distributed_tensorflow_example_tpu.serving_batch import (
        DeadlineExceededError, ShedError)
    prompts = seeded_prompts(2 * SLOTS, seed, vocab)
    tight, loose = prompts[::2], prompts[1::2]
    ref = reference_run(d, loose, max_new=6)
    eng = fresh_engine(d)
    try:
        free0 = eng.stats()["blocks_free"]
        handles = []
        for i in range(len(prompts)):
            if i % 2 == 0:          # the storm: 1 ms — expires queued
                handles.append(eng.submit(prompts[i], max_new=MAX_NEW,
                                          deadline_ms=1))
            else:
                handles.append(eng.submit(prompts[i], max_new=6))
        expired = shed = survived = 0
        for i, h in enumerate(handles):
            if i % 2 == 0:
                try:
                    h.result(timeout=120)
                    raise AssertionError(
                        f"1 ms-deadline request {h.request_id} was "
                        "never expired or shed")
                except ShedError:
                    shed += 1
                except DeadlineExceededError:
                    expired += 1
            else:
                toks = h.result(timeout=120)
                assert toks == ref[i // 2], \
                    f"survivor {i} diverged from the undisturbed run"
                survived += 1
        _wait(lambda: eng.stats()["blocks_free"] == free0,
              what="exact blocks_free recovery")
        met = counters(eng)
        assert met["serving_deadline_expired_total"] == expired, met
        assert met["serving_shed_infeasible_total"] == shed, met
        return (f"{expired} expired (504-class) + {shed} shed "
                f"(429-class feasibility), {survived} survivors to "
                f"byte parity, blocks_free recovered to {free0}",
                met)
    finally:
        eng.close()


def scenario_poison_step(d: str, seed: int, vocab: int):
    from distributed_tensorflow_example_tpu.serving_batch import \
        PoisonedRequestError
    prompts = seeded_prompts(3, seed + 1, vocab)
    ref = reference_run(d, prompts, max_new=8)
    # two rules on the SAME invocation: the retry (attempt 1) re-probes
    # index 2 and hits the second rule — the repeat failure that
    # triggers the newest-admitted eviction
    faults.install(faults.parse_spec(
        "engine.decode_step:step=2;engine.decode_step:step=2",
        seed=seed))
    try:
        eng = fresh_engine(d)
        try:
            handles = [eng.submit(p, max_new=8) for p in prompts]
            outs, poisoned = [], []
            for i, h in enumerate(handles):
                try:
                    outs.append((i, h.result(timeout=120)))
                except PoisonedRequestError:
                    poisoned.append(i)
            assert poisoned == [2], \
                f"expected exactly the newest admission evicted, got " \
                f"{poisoned}"
            for i, toks in outs:
                assert toks == ref[i], \
                    f"survivor {i} diverged after the re-dispatch"
            met = counters(eng)
            assert met["serving_redispatches_total"] >= 2, met
            assert met["serving_requests_failed_total"] == 1, met
            return (f"decode step 2 failed twice; request {poisoned[0]} "
                    f"evicted, {len(outs)} survivors to byte parity, "
                    f"{met['serving_redispatches_total']} re-dispatches",
                    met)
        finally:
            eng.close()
    finally:
        faults.install(None)


def scenario_blocks_cancel(d_tight: str, seed: int, vocab: int):
    from distributed_tensorflow_example_tpu.serving_batch import (
        BlocksExhaustedError, RequestCancelledError)
    rs = np.random.RandomState(seed + 2)
    # full-length prompts: 2 blocks each at admission, growing to
    # _bps() at full depth — three full-depth requests need 3*_bps()
    # blocks against a pool of 2*_bps(): one MUST starve mid-decode
    prompts = [rs.randint(0, vocab, (PROMPT_LEN,)).astype(np.int32)
               for _ in range(3)]
    ref = reference_run(d_tight, prompts, max_new=MAX_NEW,
                        sequential=True)
    eng = fresh_engine(d_tight)
    try:
        free0 = eng.stats()["blocks_free"]
        handles = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
        _wait(lambda: eng.stats()["live_slots"] >= 2,
              what="two live slots")
        # cancel the FIRST live request mid-decode: its blocks must
        # come back at the next step boundary, not at retirement
        free_before = eng.stats()["blocks_free"]
        assert handles[0].cancel(), "cancel() found nothing to cancel"
        _wait(lambda: eng.stats()["blocks_free"] > free_before,
              what="cancelled request's blocks returning to the pool")
        outcomes = {"done": 0, "exhausted": 0, "cancelled": 0}
        for i, h in enumerate(handles):
            try:
                toks = h.result(timeout=120)
                assert toks == ref[i], \
                    f"survivor {i} diverged from the undisturbed run"
                outcomes["done"] += 1
            except RequestCancelledError:
                outcomes["cancelled"] += 1
            except BlocksExhaustedError:
                outcomes["exhausted"] += 1
        assert outcomes["cancelled"] == 1, outcomes
        assert outcomes["done"] >= 1, outcomes
        _wait(lambda: eng.stats()["blocks_free"] == free0,
              what="exact blocks_free recovery")
        # the pool must still SERVE after recovery, not just count right
        probe = eng.submit(prompts[0], max_new=2).result(timeout=120)
        assert probe == ref[0][:2], "post-recovery probe diverged"
        met = counters(eng)
        return (f"{outcomes} against a {free0}-block pool; recovery "
                "exact; post-recovery probe served to parity", met)
    finally:
        eng.close()


def scenario_drain_under_load(d: str, seed: int, vocab: int):
    from distributed_tensorflow_example_tpu.serving_batch import \
        DrainingError
    prompts = seeded_prompts(2 * SLOTS, seed + 3, vocab)
    ref = reference_run(d, prompts, max_new=4)
    eng = fresh_engine(d, drain_timeout_s=60.0)
    try:
        handles = [eng.submit(p, max_new=4) for p in prompts]

        # drain in the background so THIS thread can probe the
        # draining window deterministically (the flag flips at
        # drain() entry; the 2*SLOTS-deep backlog keeps the window
        # open for hundreds of CPU decode steps)
        result: dict = {}
        th = threading.Thread(
            target=lambda: result.setdefault("ms", eng.drain()))
        th.start()
        _wait(lambda: eng.health()["draining"], what="drain flag")
        try:
            eng.submit(prompts[0], max_new=2)
            raise AssertionError("admission accepted during drain")
        except DrainingError as e:
            assert e.retry_after > 0, e
        th.join(timeout=120)
        drain_ms = result["ms"]
        for i, h in enumerate(handles):
            toks = h.result(timeout=1)       # drained = already done
            assert toks == ref[i], f"drained request {i} diverged"
        assert drain_ms <= 60_000, drain_ms
        assert eng.health()["status"] == "dead", eng.health()
        met = counters(eng)
        assert met["serving_drain_ms"] == drain_ms, met
        return (f"{len(handles)} in-flight requests finished to parity "
                f"under drain ({drain_ms:.0f} ms); late admission "
                "refused 503-class; engine dead after", met)
    finally:
        try:
            eng.close()
        except RuntimeError:
            pass
    return None


def scenario_flaky_dispatch(d: str, seed: int, vocab: int):
    prompts = seeded_prompts(3, seed + 4, vocab)
    ref = reference_run(d, prompts, max_new=6)
    # ONE one-shot rule: attempt 0 raises, the retry re-probes the same
    # spent rule and heals — the transient class
    faults.install(faults.parse_spec("engine.decode_step:step=2",
                                     seed=seed))
    try:
        eng = fresh_engine(d)
        try:
            handles = [eng.submit(p, max_new=6) for p in prompts]
            outs = [h.result(timeout=120) for h in handles]
            assert outs == ref, "transient retry changed greedy bytes"
            met = counters(eng)
            assert met["serving_redispatches_total"] == 1, met
            assert met["serving_requests_failed_total"] == 0, met
            return ("one-shot decode fault healed by a single "
                    "re-dispatch; all bytes to parity", met)
        finally:
            eng.close()
    finally:
        faults.install(None)


def scenario_watchdog_trip(d: str, seed: int, vocab: int):
    from distributed_tensorflow_example_tpu.serving_batch import \
        EngineStalledError
    eng = fresh_engine(d, stall_after_s=0.05)
    wedged, release = threading.Event(), threading.Event()
    orig = eng.sw.decode

    def wedge(feats):
        wedged.set()
        release.wait(timeout=60)
        return orig(feats)

    eng.sw.decode = wedge
    try:
        prompt = seeded_prompts(1, seed + 5, vocab)[0]
        h = eng.submit(prompt, max_new=4)
        assert wedged.wait(timeout=30), "decode never dispatched"
        assert eng.health()["status"] in ("live", "stalled")
        _wait(lambda: eng.health()["status"] == "stalled",
              what="watchdog flipping to stalled")
        age = eng.health()["heartbeat_age_s"]
        try:
            eng.close(timeout=0.2)
            raise AssertionError(
                "close() returned with the scheduler thread wedged")
        except EngineStalledError as e:
            assert "heartbeat" in str(e), e
        release.set()
        eng.close(timeout=30)               # parks clean once released
        assert eng.health()["status"] == "dead"
        try:
            h.result(timeout=1)
        except RuntimeError:
            pass                            # failed loudly by close()
        met = counters(eng)
        return (f"watchdog saw heartbeat_age {age:.2f}s > 0.05s; "
                "close() raised EngineStalledError while wedged; "
                "released engine parked clean", met)
    finally:
        release.set()
        try:
            eng.close()
        except RuntimeError:
            pass


def scenario_queue_full_retry(d: str, seed: int, vocab: int):
    from distributed_tensorflow_example_tpu.serving_batch import \
        QueueFullError
    n = 8
    prompts = seeded_prompts(n, seed + 6, vocab)
    ref = reference_run(d, prompts, max_new=4)
    eng = fresh_engine(d, max_queue=2)
    try:
        outs: list = [None] * n
        rejections = [0] * n                 # per-thread, no sharing

        def client(i):
            while True:
                try:
                    h = eng.submit(prompts[i], max_new=4)
                    break
                except QueueFullError as e:
                    rejections[i] += 1
                    time.sleep(min(e.retry_after, 0.02))
            outs[i] = h.result(timeout=120)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert outs == ref, "retried requests diverged from parity"
        assert sum(rejections) > 0, \
            "a 2-deep queue never refused an 8-request hammer"
        met = counters(eng)
        assert met["serving_requests_failed_total"] == 0, met
        return (f"{n} requests through a 2-deep queue with "
                f"{sum(rejections)} 429-class refusals, all to parity",
                met)
    finally:
        eng.close()


def scenario_spec_verify_fault(d: str, seed: int, vocab: int):
    """Round-16 coverage: the decode-step fault seam fires DURING a
    speculative verify dispatch. Builds its own verify-program export
    (the shared scenario artifact carries none) over a repetitive
    workload so verify dispatches genuinely happen, locates the first
    one via a seeded instrumented run (everything is deterministic, so
    the same global dispatch index is a verify dispatch in every
    re-run), then asserts the PR-10 protocol end-to-end on that exact
    dispatch."""
    from serving_load import build_export
    from distributed_tensorflow_example_tpu.serving_batch import \
        PoisonedRequestError
    rs = np.random.RandomState(seed + 7)
    pattern = rs.randint(0, vocab, (3,)).astype(np.int32)
    prompts = [np.tile(pattern, 3)[:int(rs.randint(4, PROMPT_LEN + 1))]
               .astype(np.int32) for _ in range(3)]
    with tempfile.TemporaryDirectory() as ds:
        # max_new=10 (not the module MAX_NEW): the scenario's requests
        # cap at 10 tokens, and the smaller monolithic scan keeps this
        # tier-1 smoke's export cheap
        build_export(ds, prompt_len=PROMPT_LEN, max_new=10,
                     slots=SLOTS, seed=seed, paged=True,
                     block_size=BLOCK,
                     num_blocks=1 + 4 * SLOTS * _bps(), spec_tokens=4,
                     repeating=True)

        def run(spec: int, wrap: bool = False):
            eng = fresh_engine(ds, spec_tokens=spec)
            order: list[str] = []
            if wrap:
                od, ov = eng.sw.decode, eng.sw.verify
                eng.sw.decode = \
                    lambda f: (order.append("decode"), od(f))[1]
                eng.sw.verify = \
                    lambda f: (order.append("verify"), ov(f))[1]
            try:
                free0 = eng.stats()["blocks_free"]
                handles = [eng.submit(p, max_new=10) for p in prompts]
                outs: list = []
                poisoned: list[str] = []
                for h in handles:
                    try:
                        outs.append(h.result(timeout=120))
                    except PoisonedRequestError:
                        outs.append(None)
                        poisoned.append(h.request_id)
                _wait(lambda: eng.stats()["blocks_free"] == free0,
                      what="exact blocks_free recovery")
                return outs, poisoned, counters(eng), eng.stats(), order
            finally:
                eng.close()

        ref, p0, _, _, _ = run(0)
        und, p1, _, s1, order = run(4, wrap=True)
        assert not p0 and not p1
        assert und == ref, \
            "undisturbed spec run diverged from the spec-off oracle"
        assert s1["spec_accepted"] > 0, s1
        assert "verify" in order, \
            "the repetitive workload never dispatched a verify step"
        v_idx = order.index("verify") + 1     # 1-based seam index
        # transient: one fault at exactly that verify dispatch — the
        # bounded re-dispatch heals it invisibly
        faults.install(faults.parse_spec(
            f"engine.decode_step:step={v_idx}", seed=seed))
        try:
            outs_t, pois_t, met_t, st_t, _ = run(4)
        finally:
            faults.install(None)
        assert not pois_t and outs_t == ref, \
            "transient verify fault was not healed to byte parity"
        assert met_t["serving_redispatches_total"] == 1, met_t
        assert met_t["serving_requests_failed_total"] == 0, met_t
        assert st_t["verify_steps"] > 0, st_t
        # repeat failure at the SAME verify dispatch: newest-admitted
        # evicted, survivors byte-identical, per-row pos rewound
        # exactly (byte parity + the exact blocks_free recovery inside
        # run() are the rewind's observables)
        faults.install(faults.parse_spec(
            f"engine.decode_step:step={v_idx};"
            f"engine.decode_step:step={v_idx}", seed=seed))
        try:
            outs_p, pois_p, met_p, _, _ = run(4)
        finally:
            faults.install(None)
        assert len(pois_p) == 1, \
            f"expected exactly one eviction, got {pois_p}"
        survivors = [(i, o) for i, o in enumerate(outs_p)
                     if o is not None]
        assert all(o == ref[i] for i, o in survivors), \
            "a survivor diverged after the verify-dispatch eviction"
        assert met_p["serving_requests_failed_total"] == 1, met_p
        assert met_p["serving_redispatches_total"] >= 2, met_p
    return (f"verify dispatch {v_idx}: transient healed to byte parity "
            f"(1 re-dispatch, 0 failures); repeat fault evicted "
            f"{pois_p[0]} with {len(survivors)} survivors to parity "
            "and exact pos/blocks recovery", met_p)


def scenario_overload_storm(d: str, seed: int, vocab: int):
    """Round-18 overload gate: ~2x sustainable offered load against a
    small admission queue. Every ADMITTED interactive request finishes
    within its (generous) deadline with zero client-visible failures
    and byte parity; once the pressure ladder leaves healthy,
    best_effort submissions are shed with 429-class ShedError carrying
    a measured Retry-After (never a timeout); the shed accounting is
    exact per class; and pressure returns to healthy with blocks_free
    recovered exactly once the storm drains."""
    from distributed_tensorflow_example_tpu.serving_batch import (
        QueueFullError, ShedError)
    prompts = seeded_prompts(3 * SLOTS, seed + 8, vocab)
    ref = reference_run(d, prompts, max_new=8)
    eng = fresh_engine(d, max_queue=3 * SLOTS)
    try:
        free0 = eng.stats()["blocks_free"]
        # the interactive base load: 3x the slot count, generous
        # deadlines — the class the ladder protects
        handles = [eng.submit(p, max_new=8, deadline_ms=120_000)
                   for p in prompts]
        _wait(lambda: eng._pressure_level >= 1,
              what="pressure ladder leaving healthy under backlog")
        shed = 0
        retry_afters = []
        probe = seeded_prompts(1, seed + 9, vocab)[0]
        probe_handles = []
        for _ in range(200):
            try:
                probe_handles.append(
                    eng.submit(probe, max_new=2,
                               priority="best_effort"))
            except ShedError as e:
                shed += 1
                retry_afters.append(e.retry_after)
                if shed >= 3:
                    break
            except QueueFullError:
                pass        # full below the ladder: plain pushback
            time.sleep(0.002)
        assert shed > 0, "the ladder never shed best_effort traffic"
        assert all(ra >= 0.0 for ra in retry_afters), retry_afters
        for i, h in enumerate(handles):
            toks = h.result(timeout=120)
            assert toks == ref[i], \
                f"interactive request {i} diverged under overload"
        for h in probe_handles:     # admitted below the ladder: fine
            try:
                h.result(timeout=120)
            except ShedError:
                # admitted at healthy, then swept by a later
                # interactive_only rung while still queued — the same
                # 429-class outcome, counted in the same ledger
                shed += 1
        _wait(lambda: eng.stats()["blocks_free"] == free0,
              what="exact blocks_free recovery")
        _wait(lambda: eng.stats()["pressure"] == "healthy",
              what="pressure returning to healthy after the storm")
        met = counters(eng)
        st = eng.stats()
        assert met["serving_shed_total"] == shed, (met, shed)
        assert st["shed_best_effort"] == shed, st
        assert met["serving_deadline_expired_total"] == 0, met
        assert met["serving_requests_failed_total"] == 0, met
        assert met["serving_pressure_transitions_total"] >= 2, met
        return (f"{len(handles)} interactive requests to byte parity "
                f"with zero failures under 2x load; {shed} "
                f"best_effort shed 429-class with measured "
                f"Retry-After; pressure healthy again, blocks "
                f"recovered to {free0}", met)
    finally:
        eng.close()


def scenario_long_prompt_storm(d_unused: str, seed: int,
                               vocab_unused: int):
    """Round-18 chunked-prefill gate: a live short decoder keeps
    decoding WHILE a wave of full-length prompts admits chunk by chunk
    — the dispatch order proves decode steps interleave between a
    single prompt's chunks (impossible with the monolithic prefill),
    greedy bytes stay byte-identical to the chunk-off engine over the
    same export, the chunk accounting is exact, and blocks_free
    recovers."""
    from serving_load import build_export
    rs = np.random.RandomState(seed + 10)
    with tempfile.TemporaryDirectory() as ds:
        # its own export: the shared scenario artifact carries no
        # chunk program; 16-token prompts over 4-token blocks = 4
        # chunks per long admission
        pl = 16
        vocab = build_export(ds, prompt_len=pl, max_new=MAX_NEW,
                             slots=SLOTS, seed=seed, paged=True,
                             block_size=BLOCK, prefill_chunk=BLOCK,
                             num_blocks=1 + 4 * SLOTS
                             * -(-(pl + MAX_NEW) // BLOCK))
        long_prompts = [rs.randint(0, vocab, (pl,)).astype(np.int32)
                        for _ in range(2)]
        short = rs.randint(0, vocab, (3,)).astype(np.int32)

        def run(chunk, wrap=False):
            eng = fresh_engine(ds, prefill_chunk_tokens=chunk)
            order: list[str] = []
            if wrap:
                od, oc = eng.sw.decode, eng.sw.prefill_chunk
                eng.sw.decode = \
                    lambda f: (order.append("decode"), od(f))[1]
                eng.sw.prefill_chunk = \
                    lambda f: (order.append("chunk"), oc(f))[1]
            try:
                free0 = eng.stats()["blocks_free"]
                h0 = eng.submit(short, max_new=MAX_NEW)
                _wait(lambda: eng.stats()["live_slots"] >= 1,
                      what="the short decoder going live")
                hs = [eng.submit(p, max_new=4) for p in long_prompts]
                outs = [h.result(timeout=120) for h in [h0, *hs]]
                _wait(lambda: eng.stats()["blocks_free"] == free0,
                      what="exact blocks_free recovery")
                return outs, counters(eng), order
            finally:
                eng.close()

        ref, met0, _ = run(0)
        outs, met1, order = run(BLOCK, wrap=True)
    assert outs == ref, \
        "chunked admission diverged from the monolithic prefill"
    assert met0["serving_prefill_chunks_total"] == 0, met0
    # short prompt: 1 chunk; each long prompt: pl/BLOCK chunks
    want = 1 + 2 * (pl // BLOCK)
    assert met1["serving_prefill_chunks_total"] == want, (met1, want)
    first, last = order.index("chunk"), len(order) - 1 - \
        order[::-1].index("chunk")
    interleaved = "decode" in order[first:last]
    assert interleaved, \
        f"no decode step ever ran between prefill chunks: {order}"
    assert met1["serving_requests_failed_total"] == 0, met1
    return (f"{want} chunk dispatches interleaved with shared decode "
            f"steps (order window {order[first:last + 1][:12]}...), "
            "all bytes to chunk-off parity, blocks recovered", met1)


SCENARIOS = {
    "deadline_storm": scenario_deadline_storm,
    "poison_step": scenario_poison_step,
    "blocks_cancel": scenario_blocks_cancel,
    "drain_under_load": scenario_drain_under_load,
    "flaky_dispatch": scenario_flaky_dispatch,
    "watchdog_trip": scenario_watchdog_trip,
    "queue_full_retry": scenario_queue_full_retry,
    "spec_verify_fault": scenario_spec_verify_fault,
    "overload_storm": scenario_overload_storm,
    "long_prompt_storm": scenario_long_prompt_storm,
}

#: scenarios that need the deliberately under-provisioned block pool
TIGHT_POOL = {"blocks_cancel"}


#: the tight-pool export's block count: 2 full-depth slots' worth
#: MINUS two blocks, so even after one of the exhaustion scenario's
#: three requests is cancelled the remaining two cannot BOTH reach
#: full depth — mid-decode exhaustion is guaranteed, not
#: timing-dependent
def tight_pool_blocks() -> int:
    return 1 + 2 * _bps() - 2


def run_scenarios(names, *, seed: int, export_dir: str | None = None,
                  tight_dir: str | None = None,
                  vocab: int | None = None) -> list[dict]:
    """Build the shared exports (unless the caller passes pre-built
    ones — the tier-1 smoke amortizes ONE export across tests), run
    ``names`` against them, and return one result dict per scenario
    (the test harness entry)."""
    results = []
    with tempfile.TemporaryDirectory() as scratch:
        d, d_tight = export_dir, tight_dir
        if d is None and any(n not in TIGHT_POOL for n in names):
            d = os.path.join(scratch, "ample")
            vocab = build_chaos_export(d, seed=seed)
        if d_tight is None and any(n in TIGHT_POOL for n in names):
            d_tight = os.path.join(scratch, "tight")
            v = build_chaos_export(d_tight, seed=seed,
                                   num_blocks=tight_pool_blocks())
            vocab = vocab if vocab is not None else v
        assert vocab is not None, \
            "pass vocab= alongside pre-built export dirs"
        for name in names:
            export = d_tight if name in TIGHT_POOL else d
            try:
                detail, met = SCENARIOS[name](export, seed, vocab)
                results.append({"scenario": name, "ok": True,
                                "detail": detail, "metrics": met})
            except Exception as e:   # a failed invariant is the signal
                results.append({"scenario": name, "ok": False,
                                "detail": f"{type(e).__name__}: {e}",
                                "metrics": {}})
            finally:
                faults.install(None)   # never leak a registry forward
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default="all",
                    help="comma-separated scenario names, or 'all': "
                         + ", ".join(SCENARIOS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="alias kept for symmetry with serving_load "
                    "(the scenarios are already CPU-tiny; --smoke "
                    "changes nothing today)")
    args = ap.parse_args(argv)
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    names = (list(SCENARIOS) if args.scenario == "all"
             else [s.strip() for s in args.scenario.split(",")
                   if s.strip()])
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}; have {list(SCENARIOS)}")
    results = run_scenarios(names, seed=args.seed)
    for r in results:
        print(json.dumps(r), flush=True)
    failed = sum(1 for r in results if not r["ok"])
    print(json.dumps({"summary": True, "scenarios": len(results),
                      "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
