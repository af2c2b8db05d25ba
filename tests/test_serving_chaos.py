"""Self-healing serving (round 14): chaos-soak fast smoke + targeted
regressions.

- the tier-1 smoke runs ALL seven seeded scenarios from
  experiments/serving_chaos.py against one shared export (the full CLI
  soak is the slow-lane twin);
- regression tests pin the satellite contracts individually: the
  EngineHandle timeout leak (a timed-out wait must cancel and return
  blocks, not keep decoding to max_new), close() raising
  EngineStalledError instead of silently leaking a hung scheduler
  thread (engine AND micro-batcher), queue-full 429/Retry-After parity
  between :predict and :generate, fault-seam inertness (an armed-but-
  never-firing registry is byte- and dispatch-identical to none), and
  the HTTP failure surface (504 deadline, /cancel 200/404/409,
  /healthz, 503 + Retry-After while draining, the http.read seam).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "experiments"))

import serving_chaos  # noqa: E402

from distributed_tensorflow_example_tpu.runtime import faults  # noqa: E402
from distributed_tensorflow_example_tpu.serving import (  # noqa: E402
    load_servable, load_stepwise)
from distributed_tensorflow_example_tpu.serving_batch import (  # noqa: E402
    DeadlineExceededError, EngineStalledError, GenerationEngine,
    MicroBatcher, QueueFullError, RequestCancelledError)
from distributed_tensorflow_example_tpu.serving_http import (  # noqa: E402
    PredictServer)


@pytest.fixture(scope="module")
def chaos_dir(tmp_path_factory):
    """ONE ample-pool paged export shared by the smoke and the
    regressions (the scenarios' shapes live in serving_chaos)."""
    d = str(tmp_path_factory.mktemp("chaos"))
    vocab = serving_chaos.build_chaos_export(d, seed=0)
    return d, vocab


@pytest.fixture(scope="module")
def tight_dir(tmp_path_factory):
    """The deliberately under-provisioned pool for the exhaustion
    scenario."""
    d = str(tmp_path_factory.mktemp("chaos_tight"))
    vocab = serving_chaos.build_chaos_export(
        d, seed=0, num_blocks=serving_chaos.tight_pool_blocks())
    return d, vocab


def _engine(d, **kw):
    kw.setdefault("prefix_cache", False)
    return GenerationEngine(load_stepwise(d), **kw).start()


def _assert_ok(results):
    bad = [r for r in results if not r["ok"]]
    assert not bad, f"chaos scenario(s) failed: {bad}"


def _wait(pred, timeout=30.0, what="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


def _post(port, name, payload, request_id=None, verb="generate"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:{verb}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 **({"X-Request-Id": request_id} if request_id
                    else {})})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _get(port, path):
    """(status, body) without raising on 4xx/5xx."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post_raw(port, path, data=b""):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=data)
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# the chaos smoke: all seven scenarios, shared export
# ---------------------------------------------------------------------------

def test_chaos_smoke_failure_injection(chaos_dir):
    """deadline storm / poison step / transient flaky dispatch: the
    quarantine + deadline invariants named in the round-14 acceptance
    criteria (expired requests return blocks exactly; a poisoned step
    fails exactly one request with survivors to byte parity)."""
    d, vocab = chaos_dir
    _assert_ok(serving_chaos.run_scenarios(
        ["deadline_storm", "poison_step", "flaky_dispatch"],
        seed=0, export_dir=d, vocab=vocab))


def test_chaos_smoke_lifecycle(chaos_dir):
    """drain-under-load parity (zero dropped requests), the watchdog
    trip, and the queue-full client retry loop."""
    d, vocab = chaos_dir
    _assert_ok(serving_chaos.run_scenarios(
        ["drain_under_load", "watchdog_trip", "queue_full_retry"],
        seed=0, export_dir=d, vocab=vocab))


def test_chaos_smoke_blocks_exhausted_cancel(tight_dir):
    """Mid-decode exhaustion + live cancellation: blocks come back
    IMMEDIATELY on cancel, the pool recovers to the exact free count,
    and the engine still serves after."""
    d, vocab = tight_dir
    _assert_ok(serving_chaos.run_scenarios(
        ["blocks_cancel"], seed=0, tight_dir=d, vocab=vocab))


def test_chaos_smoke_spec_verify_fault(chaos_dir):
    """Round-16: the decode-step fault seam firing DURING a K-token
    speculative verify dispatch must quarantine/re-dispatch per the
    PR-10 protocol — transient healed to byte parity with one extra
    dispatch, repeat failure evicting exactly the newest admission
    with survivors byte-identical and per-row pos rewound exactly
    (exact blocks_free recovery)."""
    d, vocab = chaos_dir
    _assert_ok(serving_chaos.run_scenarios(
        ["spec_verify_fault"], seed=0, export_dir=d, vocab=vocab))


def test_chaos_smoke_overload_and_long_prompts(chaos_dir):
    """Round-18: the overload storm (interactive protected to byte
    parity at 2x load, best_effort shed 429-class with measured
    Retry-After, exact shed accounting, pressure recovers) and the
    long-prompt storm (chunked prefill interleaves shared decode steps
    between one prompt's chunks, bytes identical to the chunk-off
    engine, exact chunk accounting)."""
    d, vocab = chaos_dir
    _assert_ok(serving_chaos.run_scenarios(
        ["overload_storm", "long_prompt_storm"],
        seed=0, export_dir=d, vocab=vocab))


@pytest.mark.slow
def test_chaos_soak_cli_all_scenarios():
    """The full soak through the CLI entry (fresh process — the
    slow-lane gate)."""
    script = os.path.join(ROOT, "experiments", "serving_chaos.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script, "--scenario", "all"],
                          capture_output=True, text=True, env=env,
                          timeout=1200)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
    summary = lines[-1]
    assert summary["failed"] == 0 and summary["scenarios"] == 8, lines


# ---------------------------------------------------------------------------
# satellite regressions: the handle leak
# ---------------------------------------------------------------------------

def test_handle_timeout_cancels_and_frees_blocks(chaos_dir):
    """The round-9 leak: EngineHandle.result(timeout) must CANCEL on
    timeout — slot retired, blocks back (exact), decoding stopped —
    instead of abandoning a request that runs to max_new."""
    d, vocab = chaos_dir
    eng = _engine(d)
    try:
        free0 = eng.stats()["blocks_free"]
        prompt = (np.arange(1, 8) % vocab).astype(np.int32)
        h = eng.submit(prompt, max_new=16)
        with pytest.raises(TimeoutError, match="cancelled"):
            h.result(timeout=0.02)
        with pytest.raises(RequestCancelledError):
            h.req.future.result(timeout=30)
        _wait(lambda: eng.stats()["blocks_free"] == free0,
              what="cancelled request's blocks returning")
        s = eng.stats()
        assert s["live_slots"] == 0 and s["cancelled"] == 1, s
        # decoding actually STOPPED (the leak kept burning dispatches)
        steps = eng.stats()["decode_steps"]
        time.sleep(0.15)
        assert eng.stats()["decode_steps"] == steps
        # the slot is reallocatable: the engine still serves
        assert len(eng.generate(prompt, timeout=120, max_new=2)) == 2
    finally:
        eng.close()


def test_default_deadline_ms_applies_engine_wide(chaos_dir):
    d, _ = chaos_dir
    eng = _engine(d, default_deadline_ms=1)
    try:
        with pytest.raises(DeadlineExceededError, match="deadline"):
            eng.submit(np.array([1, 2, 3], np.int32),
                       max_new=8).result(timeout=60)
        assert eng.stats()["deadline_expired"] == 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# satellite regressions: close() must not lie about a hung thread
# ---------------------------------------------------------------------------

def test_engine_close_raises_stalled_on_hung_scheduler(chaos_dir):
    d, _ = chaos_dir
    eng = _engine(d)
    wedged, release = threading.Event(), threading.Event()
    orig = eng.sw.decode

    def wedge(feats):
        wedged.set()
        release.wait(timeout=60)
        return orig(feats)

    eng.sw.decode = wedge
    try:
        eng.submit(np.array([1, 2, 3], np.int32), max_new=4)
        assert wedged.wait(timeout=30)
        with pytest.raises(EngineStalledError, match="heartbeat"):
            eng.close(timeout=0.2)
    finally:
        release.set()
        eng.close(timeout=30)            # parks clean once released
    assert eng.health()["status"] == "dead"


def test_microbatcher_close_raises_stalled_when_wedged(tmp_path):
    """Same contract for the :predict batcher thread."""
    import jax

    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.serving import (
        export_model, serving_signature)
    d = str(tmp_path / "predict")
    m = get_model("mlp", TrainConfig(model="mlp"))
    out = m.init(jax.random.key(0))
    params, extras = out if isinstance(out, tuple) else (out, {})
    export_model(m, params, extras, d, platforms=("cpu",))
    feats = serving_signature(m.dummy_batch(4))
    mb = MicroBatcher(load_servable(d), batch_max_size=4,
                      batch_max_wait_ms=1.0).start()
    wedged, release = threading.Event(), threading.Event()
    inner = mb.servable

    def wedge(cols):
        wedged.set()
        release.wait(timeout=60)
        return inner(cols)

    mb.servable = wedge
    try:
        x = np.asarray(feats["x"])
        fut = mb.submit({"x": x[:1]}, 1)
        assert wedged.wait(timeout=30)
        with pytest.raises(EngineStalledError, match="park"):
            mb.close(timeout=0.2)
    finally:
        release.set()
        mb.close(timeout=30)
    assert np.asarray(fut.result(timeout=5)).shape[0] == 1


# ---------------------------------------------------------------------------
# satellite regressions: queue-full parity between the two paths
# ---------------------------------------------------------------------------

def test_microbatcher_queue_full_carries_measured_retry_after(tmp_path):
    """The :predict 429 now rides RetryAfterEstimator semantics (a
    measured hint, not the old hard-coded 1.0)."""
    import jax

    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.serving import (
        export_model, serving_signature)
    d = str(tmp_path / "predict")
    m = get_model("mlp", TrainConfig(model="mlp"))
    out = m.init(jax.random.key(0))
    params, extras = out if isinstance(out, tuple) else (out, {})
    export_model(m, params, extras, d, platforms=("cpu",))
    feats = serving_signature(m.dummy_batch(4))
    x = np.asarray(feats["x"])
    mb = MicroBatcher(load_servable(d), batch_max_size=1,
                      batch_max_wait_ms=1.0, max_queue=2).start()
    # wedge the dispatch so submissions pile into the bounded queue
    wedged, release = threading.Event(), threading.Event()
    inner = mb.servable

    def wedge(cols):
        wedged.set()
        release.wait(timeout=60)
        return inner(cols)

    mb.servable = wedge
    try:
        futs = [mb.submit({"x": x[:1]}, 1)]
        assert wedged.wait(timeout=30)
        futs += [mb.submit({"x": x[:1]}, 1) for _ in range(2)]
        with pytest.raises(QueueFullError) as e:
            mb.submit({"x": x[:1]}, 1)
        assert e.value.retry_after > 0
        release.set()
        for f in futs:                   # nothing queued was dropped
            assert np.asarray(f.result(timeout=60)).shape[0] == 1
    finally:
        release.set()
        mb.close()


def test_queue_full_status_and_headers_agree_across_paths(chaos_dir,
                                                          tmp_path):
    """429 + Retry-After must look the same whether the :generate
    engine or the :predict batcher said 'full'."""
    import jax

    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.serving import (
        export_model, serving_signature)
    dp = str(tmp_path / "predict")
    m = get_model("mlp", TrainConfig(model="mlp"))
    out = m.init(jax.random.key(0))
    params, extras = out if isinstance(out, tuple) else (out, {})
    export_model(m, params, extras, dp, platforms=("cpu",))
    feats = serving_signature(m.dummy_batch(4))

    def full(payload, request_id=None, trace=None):
        raise QueueFullError("full", retry_after=2.6)

    seen = {}
    for d, verb, payload in (
            (chaos_dir[0], "generate",
             {"inputs": {"input_ids": [[1, 2]]}}),
            (dp, "predict",
             {"inputs": {"x": np.asarray(feats["x"])[:1].tolist()}})):
        with PredictServer(d) as srv:
            setattr(srv, verb, full)
            try:
                _post(srv.port, srv.name, payload, verb=verb)
                raise AssertionError("QueueFullError not surfaced")
            except urllib.error.HTTPError as e:
                seen[verb] = (e.code, e.headers.get("Retry-After"))
    assert seen["generate"] == seen["predict"] == (429, "3"), seen


# ---------------------------------------------------------------------------
# satellite regressions: fault-seam inertness
# ---------------------------------------------------------------------------

def test_serving_seams_inert_when_silent(chaos_dir):
    """The armed-vs-plain parity harness (the PR-9 pattern): a
    registry whose rules never fire must leave the engine byte- AND
    dispatch-identical to no registry at all — so the inert-by-default
    None-check seams provably cost zero behavior. (No-registry ==
    pre-PR behavior is additionally pinned by the whole pre-existing
    parity suite running over the seamed engine.)"""
    d, vocab = chaos_dir
    prompts = serving_chaos.seeded_prompts(6, 7, vocab)

    def run(spec):
        if spec:
            faults.install(faults.parse_spec(spec, seed=0))
        try:
            eng = _engine(d)
            try:
                handles = [eng.submit(p, max_new=6) for p in prompts]
                outs = [h.result(timeout=120) for h in handles]
                s = eng.stats()
                return outs, (s["decode_steps"], s["prefills"],
                              s["requests_done"], s["redispatches"])
            finally:
                eng.close()
        finally:
            faults.install(None)

    plain = run(None)
    armed = run("engine.decode_step:step=999999;"
                "engine.prefill:step=999999;engine.admit:step=999999;"
                "pool.alloc:step=999999;http.read:step=999999;"
                "router.probe:step=999999;router.forward:step=999999;"
                "replica.crash:step=999999")
    assert plain == armed


def test_spec_seams_inert_when_silent(tmp_path):
    """The armed-vs-plain inertness harness extended to the SPEC path:
    an armed-but-silent fault registry over an engine running
    speculative decoding (verify dispatches probe the same
    engine.decode_step seam) must stay byte- and dispatch-identical —
    including the verify-dispatch and accept counters — to no registry
    at all."""
    sys.path.insert(0, os.path.join(ROOT, "experiments"))
    from serving_load import build_export, make_repetitive_requests

    d = str(tmp_path / "spec")
    vocab = build_export(d, prompt_len=8, max_new=16, slots=4, seed=0,
                         paged=True, block_size=4, spec_tokens=4,
                         repeating=True)
    matrix = make_repetitive_requests(1, 4, prompt_len=8, max_new=12,
                                      vocab=vocab, seed=0)
    prompts = [p for row in matrix for p, _ in row]

    def run(spec):
        if spec:
            faults.install(faults.parse_spec(spec, seed=0))
        try:
            eng = _engine(d, spec_tokens=4)
            try:
                handles = [eng.submit(p, max_new=12) for p in prompts]
                outs = [h.result(timeout=120) for h in handles]
                s = eng.stats()
                return outs, (s["decode_steps"], s["verify_steps"],
                              s["prefills"], s["spec_proposed"],
                              s["spec_accepted"], s["requests_done"],
                              s["redispatches"])
            finally:
                eng.close()
        finally:
            faults.install(None)

    plain = run(None)
    armed = run("engine.decode_step:step=999999;"
                "engine.prefill:step=999999;engine.admit:step=999999;"
                "pool.alloc:step=999999")
    assert plain == armed
    # the workload genuinely exercised the spec path (else the parity
    # above would be vacuous)
    assert plain[1][1] > 0 and plain[1][4] > 0, plain[1]


# ---------------------------------------------------------------------------
# the HTTP failure surface
# ---------------------------------------------------------------------------

def test_http_deadline_ms_answers_504(chaos_dir):
    d, _ = chaos_dir
    with PredictServer(d) as srv:
        try:
            _post(srv.port, srv.name,
                  {"inputs": {"input_ids": [[1, 2, 3]]},
                   "max_new": 16, "deadline_ms": 1})
            raise AssertionError("1 ms deadline never expired")
        except urllib.error.HTTPError as e:
            assert e.code == 504
            assert "deadline" in json.loads(e.read())["error"]
        # the server keeps serving afterwards
        out = _post(srv.port, srv.name,
                    {"inputs": {"input_ids": [[1, 2, 3]]},
                     "max_new": 2})
        assert len(out["generations"][0]) == 2


def test_http_cancel_route(chaos_dir):
    """POST /cancel/<rid>: 404 for unknown ids; a live request's
    waiter gets 409 and the cancel itself 200."""
    d, _ = chaos_dir
    with PredictServer(d) as srv:
        code, body = _post_raw(srv.port, "/cancel/never-submitted")
        assert code == 404 and "never-submitted" in body["error"]

        waiter: dict = {}

        def post_long():
            try:
                waiter["ok"] = _post(srv.port, srv.name,
                                     {"inputs": {"input_ids": [[5, 6]]},
                                      "max_new": 16},
                                     request_id="cancel-me")
            except urllib.error.HTTPError as e:
                waiter["code"] = e.code
                waiter["err"] = json.loads(e.read())["error"]

        th = threading.Thread(target=post_long)
        th.start()
        deadline = time.monotonic() + 30

        def try_cancel():
            c, b = _post_raw(srv.port, "/cancel/cancel-me")
            return c == 200 and b == {"cancelled": "cancel-me"}

        while time.monotonic() < deadline and not try_cancel():
            time.sleep(0.005)
        th.join(timeout=60)
        assert waiter.get("code") == 409, waiter
        assert "cancelled" in waiter["err"]


def test_http_healthz(chaos_dir):
    d, _ = chaos_dir
    with PredictServer(d) as srv:
        code, body = _get(srv.port, "/healthz")
        assert code == 200 and body["status"] == "live"
        assert {"heartbeat_age_s", "stall_after_s", "queue_depth",
                "inflight", "draining"} <= set(body)
    # a watchdog threshold of zero makes ANY heartbeat age 'stalled':
    # /healthz must answer 503 so the LB stops routing here
    with PredictServer(d, stall_after_s=0.0) as srv:
        _wait(lambda: _get(srv.port, "/healthz")[0] == 503,
              what="healthz flipping to 503 at stall_after_s=0")
        code, body = _get(srv.port, "/healthz")
        assert code == 503 and body["status"] == "stalled"


def test_http_healthz_without_engine(tmp_path):
    import jax

    from distributed_tensorflow_example_tpu.config import TrainConfig
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.serving import export_model
    d = str(tmp_path / "predict")
    m = get_model("mlp", TrainConfig(model="mlp"))
    out = m.init(jax.random.key(0))
    params, extras = out if isinstance(out, tuple) else (out, {})
    export_model(m, params, extras, d, platforms=("cpu",))
    with PredictServer(d) as srv:          # no scheduler thread at all
        code, body = _get(srv.port, "/healthz")
        assert code == 200 and body["status"] == "live"


def test_http_draining_answers_503_with_retry_after(chaos_dir):
    d, _ = chaos_dir
    srv = PredictServer(d).start()
    try:
        bg: dict = {}

        def post_long():
            bg["out"] = _post(srv.port, srv.name,
                              {"inputs": {"input_ids": [[7, 8, 9]]},
                               "max_new": 16})

        th = threading.Thread(target=post_long)
        th.start()
        _wait(lambda: srv.engine.health()["inflight"] > 0,
              what="the long request going in flight")
        dr = threading.Thread(target=srv.engine.drain)
        dr.start()
        _wait(lambda: srv.engine.health()["draining"],
              what="drain flag")
        try:
            _post(srv.port, srv.name,
                  {"inputs": {"input_ids": [[1]]}, "max_new": 2})
            raise AssertionError("admission accepted during drain")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert int(e.headers["Retry-After"]) >= 1
            assert "drain" in json.loads(e.read())["error"]
        dr.join(timeout=120)
        th.join(timeout=120)
        # zero dropped: the in-flight request finished under the drain
        assert len(bg["out"]["generations"][0]) == 16
    finally:
        srv.stop(drain=False)


def test_http_read_fault_seam(chaos_dir):
    """The http.read seam: an injected body-read fault answers 400 —
    and once the one-shot rule is spent the server serves clean."""
    d, _ = chaos_dir
    with PredictServer(d) as srv:
        faults.install(faults.parse_spec("http.read:step=1", seed=0))
        try:
            try:
                _post(srv.port, srv.name,
                      {"inputs": {"input_ids": [[1, 2]]}, "max_new": 2})
                raise AssertionError("http.read fault never surfaced")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "injected fault" in json.loads(e.read())["error"]
            out = _post(srv.port, srv.name,
                        {"inputs": {"input_ids": [[1, 2]]},
                         "max_new": 2})
            assert len(out["generations"][0]) == 2
        finally:
            faults.install(None)


def test_cancel_during_block_pressure_deferral_not_lost(chaos_dir):
    """Review regression: a cancel accepted while its request is
    MID-ADMISSION must survive a block-pressure deferral (which
    re-queues the request and drops its in-flight id) — the
    _apply_cancellations queue sweep honors it at the next boundary
    instead of silently admitting the request later."""
    d, vocab = chaos_dir
    eng = _engine(d)
    orig_alloc = eng.cache.pool.alloc
    state = {"armed": True}

    def alloc(n):
        # the victim's first admission: a racing client cancels while
        # the request is in _inflight_ids, then the allocator reports
        # exhaustion so the engine re-queues it at the head
        if state["armed"] and eng._admitting is victim.req:
            state["armed"] = False
            assert eng.cancel(victim.request_id)
            from distributed_tensorflow_example_tpu.serving_batch \
                import BlocksExhaustedError
            raise BlocksExhaustedError("injected block pressure")
        return orig_alloc(n)

    try:
        # a long-running neighbor keeps _live non-empty, so the
        # exhaustion path DEFERS (re-queues) instead of failing loudly
        neighbor = eng.submit((np.arange(1, 8) % vocab)
                              .astype(np.int32), max_new=16)
        _wait(lambda: eng.stats()["live_slots"] == 1,
              what="neighbor going live")
        eng.cache.pool.alloc = alloc
        victim = eng.submit(np.array([3, 1, 4], np.int32), max_new=16)
        with pytest.raises(RequestCancelledError):
            victim.req.future.result(timeout=60)
        assert eng.stats()["cancelled"] == 1
        assert len(neighbor.result(timeout=120)) == 16  # undisturbed
    finally:
        eng.cache.pool.alloc = orig_alloc
        eng.close()


def test_http_multirow_failure_cancels_sibling_rows(chaos_dir):
    """Review regression: when one row of a multi-row :generate fails,
    the single-error response must not leave sibling rows decoding to
    max_new holding slots and blocks — they are cancelled before the
    error surfaces."""
    d, _ = chaos_dir
    with PredictServer(d) as srv:
        faults.install(faults.parse_spec("engine.admit:step=1", seed=0))
        try:
            try:
                _post(srv.port, srv.name,
                      {"inputs": {"input_ids": [[1, 2, 3],
                                                [4, 5, 6]]},
                       "max_new": 16})
                raise AssertionError("poisoned admission answered 200")
            except urllib.error.HTTPError as e:
                assert e.code == 500
            eng = srv.engine

            def settled():
                # live==0 + queue==0 alone is also true MID-admission
                # (popped, not yet live) — wait for both rows to be
                # terminally accounted for
                s = eng.stats()
                return (s["live_slots"] == 0
                        and s["queue_depth"] == 0
                        and s["cancelled"] + s["requests_failed"] >= 2)

            _wait(settled, what="both rows retiring")
            s = eng.stats()
            # nothing retired successfully: the poisoned row failed,
            # the sibling was CANCELLED well short of its max_new=16
            # (the leak would be it decoding to completion for nobody)
            assert s["requests_done"] == 0, s
            assert s["cancelled"] == 1 and s["requests_failed"] == 1, s
            assert s["tokens_out"] < 16, s
        finally:
            faults.install(None)


def test_stop_closes_listener_even_when_drain_stalls(chaos_dir):
    """Review regression: stop() on a wedged scheduler raises
    EngineStalledError — but the HTTP listener must STILL come down,
    or SIGTERM would leave an unkillable server refusing traffic."""
    d, _ = chaos_dir
    srv = PredictServer(d, drain_timeout_s=0.5).start()
    eng = srv.engine
    wedged, release = threading.Event(), threading.Event()
    orig = eng.sw.decode

    def wedge(feats):
        wedged.set()
        release.wait(timeout=60)
        return orig(feats)

    eng.sw.decode = wedge
    try:
        eng.submit(np.array([1, 2, 3], np.int32), max_new=8)
        assert wedged.wait(timeout=30)
        with pytest.raises(EngineStalledError):
            srv.stop()
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=2)
    finally:
        release.set()
        eng.close(timeout=30)


def test_async_decode_fault_escalates_to_pool_rebuild(chaos_dir):
    """Review regression: on an async backend a device fault surfaces
    at the blocking logits materialization, AFTER the dispatch donated
    the pool. The engine must still treat it as pool-consuming —
    engine-fatal fail-all + rebuild — NOT adopt the failed call's
    outputs, judge them alive, and retry a dispatch whose input
    buffers were deleted (which would serially evict every live slot
    as 'poisoned')."""
    d, vocab = chaos_dir
    eng = _engine(d)
    orig = eng.sw.decode
    armed = {"on": True}

    class _FailsOnRead:
        # numpy materialization raises — the async-error surface
        def __array__(self, dtype=None):
            raise RuntimeError("simulated async device fault")

    def decode(feats):
        out = orig(feats)          # REAL dispatch: pool donated
        if armed["on"]:
            armed["on"] = False
            # whichever result the host reads (ids, or logits while a
            # row samples) is where the fault surfaces
            return {**out, "logits": _FailsOnRead(),
                    "ids": _FailsOnRead()}
        return out

    eng.sw.decode = decode
    try:
        handles = [eng.submit((np.arange(1, 4 + i) % vocab)
                              .astype(np.int32), max_new=6)
                   for i in range(2)]
        for h in handles:
            with pytest.raises(RuntimeError, match="scheduler step"):
                h.req.future.result(timeout=60)
        s = eng.stats()
        # engine-fatal, not quarantine: no bogus retry over deleted
        # buffers, no poisoned-eviction of innocent slots
        assert s["redispatches"] == 0, s
        # the rebuilt pool serves again
        out = eng.generate(np.array([5, 6], np.int32), timeout=120,
                           max_new=3)
        assert len(out) == 3
    finally:
        eng.sw.decode = orig
        eng.close()
