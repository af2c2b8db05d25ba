"""obs/ unit tests: registry semantics (atomic snapshot, merge,
disabled fast path), Prometheus exposition round-trip, and the trace
recorder / chrome emitter — plus the overhead guards (a disabled
registry/recorder must reduce every call site to one branch: zero
spans recorded, zero counter movement).
"""

import json
import threading
import time

import pytest

from distributed_tensorflow_example_tpu.obs import prom
from distributed_tensorflow_example_tpu.obs.registry import (
    Registry, all_registries, merge_snapshots)
from distributed_tensorflow_example_tpu.obs.trace import (
    ChromeTraceWriter, TraceContext, TraceRecorder, add_span,
    arm_always_on, parse_traceparent, recorder, set_recorder, span)


@pytest.fixture
def fresh_recorder():
    """Install a fresh process recorder for span()/add_span() tests and
    restore the previous one after (other tests/servers share the
    process global)."""
    old = recorder()
    rec = set_recorder(TraceRecorder())
    yield rec
    set_recorder(old)


# ---------------------------------------------------------------- registry
def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("x_total", "help text")
    g = reg.gauge("depth")
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    c.inc()
    c.inc(4)
    g.set(7)
    g.dec(2)
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)
    snap = reg.snapshot()
    assert snap["x_total"] == {"type": "counter", "value": 5,
                               "help": "help text"}
    assert snap["depth"]["value"] == 5
    hh = snap["lat_seconds"]
    assert hh["buckets"] == [(0.1, 1), (1.0, 1)]
    assert hh["inf"] == 1
    assert hh["count"] == 3
    assert hh["sum"] == pytest.approx(99.55)


def test_counter_is_monotonic_and_types_conflict_loudly():
    reg = Registry()
    c = reg.counter("n_total")
    with pytest.raises(ValueError, match="monotonic"):
        c.inc(-1)
    # re-registration returns the SAME metric; a type change is a bug
    assert reg.counter("n_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("n_total")


def test_disabled_registry_fast_path_is_inert():
    reg = Registry(enabled=False)
    c = reg.counter("n_total")
    h = reg.histogram("h_seconds")
    for _ in range(1000):
        c.inc()
        h.observe(0.1)
    assert c.value == 0
    assert h.count == 0
    assert reg.lint_untouched() == ["h_seconds", "n_total"]


def test_atomic_group_never_observed_torn():
    """Two counters updated under registry.atomic() must move together
    in every snapshot — the /stats-race regression at its core."""
    reg = Registry()
    a = reg.counter("a_total")
    b = reg.counter("b_total")
    stop = threading.Event()
    torn = []

    def mutate():
        while not stop.is_set():
            with reg.atomic():
                a.inc()
                b.inc()

    t = threading.Thread(target=mutate)
    t.start()
    try:
        for _ in range(2000):
            s = reg.snapshot()
            if s["a_total"]["value"] != s["b_total"]["value"]:
                torn.append(s)
    finally:
        stop.set()
        t.join()
    assert not torn, f"torn snapshot observed: {torn[0]}"


def test_merge_snapshots_counters_histograms_and_conflicts():
    r1, r2 = Registry(), Registry()
    for r, n in ((r1, 3), (r2, 4)):
        r.counter("c_total").inc(n)
        h = r.histogram("h_seconds", buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(5.0)
        r.gauge("g").set(n)
    m = merge_snapshots(r1.snapshot(), r2.snapshot())
    assert m["c_total"]["value"] == 7
    assert m["h_seconds"]["buckets"] == [(1.0, 2), (2.0, 0)]
    assert m["h_seconds"]["inf"] == 2
    assert m["h_seconds"]["count"] == 4
    assert m["g"]["value"] == 4            # gauge: last writer
    r3 = Registry()
    r3.gauge("c_total").set(1)
    with pytest.raises(ValueError, match="cannot merge"):
        merge_snapshots(r1.snapshot(), r3.snapshot())
    r4 = Registry()
    r4.histogram("h_seconds", buckets=(9.0,)).observe(1)
    with pytest.raises(ValueError, match="bucket bounds differ"):
        merge_snapshots(r1.snapshot(), r4.snapshot())


def test_registry_process_tracking_and_lint():
    reg = Registry()
    reg.counter("dead_total")
    reg.counter("live_total").inc()
    assert reg in all_registries()
    assert reg.lint_untouched() == ["dead_total"]
    # touched even when the VALUE is still zero (inc(0) counts)
    reg.counter("zero_total").inc(0)
    assert "zero_total" not in reg.lint_untouched()


# ------------------------------------------------------------------ prom
def test_prometheus_text_format_and_roundtrip():
    reg = Registry()
    reg.counter("req_total", "requests").inc(12)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(0.7)
    h.observe(7.0)
    text = prom.render(reg.snapshot())
    lines = text.splitlines()
    assert "# TYPE req_total counter" in lines
    assert "# HELP req_total requests" in lines
    assert "req_total 12" in lines
    assert "# TYPE depth gauge" in lines
    assert "depth 3" in lines
    # histogram: cumulative buckets in le order, then +Inf, sum, count
    i = lines.index('lat_seconds_bucket{le="0.5"} 1')
    assert lines[i + 1] == 'lat_seconds_bucket{le="1"} 2'
    assert lines[i + 2] == 'lat_seconds_bucket{le="+Inf"} 3'
    assert any(ln.startswith("lat_seconds_sum ") for ln in lines)
    assert "lat_seconds_count 3" in lines
    assert text.endswith("\n")
    parsed = prom.parse(text)
    assert parsed["req_total"] == 12
    assert parsed['lat_seconds_bucket{le="+Inf"}'] == 3
    assert parsed["lat_seconds_count"] == 3


def test_prometheus_render_matches_stats_numbers_exactly():
    """The byte-for-byte contract: a counter's exposition value parses
    back to exactly the snapshot int the /stats view reads."""
    reg = Registry()
    c = reg.counter("big_total")
    c.inc(123456789)
    snap = reg.snapshot()
    assert prom.parse(prom.render(snap))["big_total"] \
        == snap["big_total"]["value"]


# ----------------------------------------------------------------- trace
def test_span_records_complete_events_with_lanes():
    rec = TraceRecorder(max_events=100)
    rec.start()
    t0 = time.perf_counter()
    rec.add("serving", "slot0", "prefill", t0, t0 + 0.001,
            {"request_id": "r1"})
    rec.add("serving", "slot1", "decode", t0, t0 + 0.002, None)
    rec.add("training", "data", "data_wait", t0, t0 + 0.003, None)
    rec.stop()
    out = rec.to_chrome()
    assert json.loads(json.dumps(out))          # JSON-serializable
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 3
    for e in xs:
        for k in ("ts", "dur", "pid", "tid", "name"):
            assert k in e, f"X event missing {k}: {e}"
    # two processes, lanes as threads
    names = {e["args"]["name"] for e in out["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"serving", "training"}
    by_name = {e["name"]: e for e in xs}
    assert by_name["prefill"]["args"]["request_id"] == "r1"
    assert by_name["prefill"]["tid"] != by_name["decode"]["tid"]


def test_ring_buffer_bounds_and_drop_count():
    rec = TraceRecorder(max_events=4)
    rec.start()
    t = time.perf_counter()
    for i in range(10):
        rec.add("p", "l", f"e{i}", t + i, t + i + 0.5, None)
    out = rec.to_chrome()
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["e6", "e7", "e8", "e9"]
    assert out["metadata"]["events_dropped"] == 6
    assert rec.spans_recorded == 10


def test_disabled_recorder_records_nothing(fresh_recorder):
    """The overhead guard: with tracing off, span() must not touch the
    recorder at all — span count stays 0 and the per-call cost is one
    attribute check (bounded here at < 2 µs/call, ~100x headroom on
    the observed sub-100ns)."""
    rec = fresh_recorder
    assert not rec.enabled
    before = rec.spans_recorded
    n = 10000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("x", lane="slot0", request_id="r"):
            pass
        add_span("y", 0.0, 1.0, lane="slot0")
    dt = time.perf_counter() - t0
    assert rec.spans_recorded == before
    assert dt / (2 * n) < 2e-6, f"disabled span path too slow: {dt}"


def test_span_context_manager_times_the_block(fresh_recorder):
    rec = fresh_recorder
    rec.start()
    with span("work", process="p", lane="l", request_id="abc"):
        time.sleep(0.01)
    rec.stop()
    xs = [e for e in rec.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 1
    assert xs[0]["dur"] >= 9_000            # ≥ 9ms in µs
    assert xs[0]["args"]["request_id"] == "abc"


def test_chrome_writer_is_shared_shape():
    """The one-emitter contract: events built directly through
    ChromeTraceWriter (the trace_summary --chrome producer) carry the
    same schema the recorder dump yields."""
    w = ChromeTraceWriter()
    pid = w.pid("proc")
    tid = w.tid(pid, "line")
    w.complete(pid=pid, tid=tid, name="op", ts_us=1.0, dur_us=0.0,
               args={"full_name": "op = f(x)"})
    d = w.to_dict()
    assert d["displayTimeUnit"] == "ms"
    ms = [e for e in d["traceEvents"] if e["ph"] == "M"]
    assert {m["name"] for m in ms} == {"process_name", "thread_name"}
    x = [e for e in d["traceEvents"] if e["ph"] == "X"][0]
    assert x["dur"] > 0                      # zero-dur clamped


def test_recorder_restart_clears_previous_capture():
    rec = TraceRecorder()
    rec.start()
    t = time.perf_counter()
    rec.add("p", "l", "old", t, t + 1, None)
    rec.start()                              # re-arm
    rec.add("p", "l", "new", t, t + 1, None)
    rec.stop()
    xs = [e for e in rec.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["new"]


# ------------------------------------------------- distributed tracing
def test_traceparent_roundtrip_and_malformed():
    ctx = TraceContext("ab" * 16, "cd" * 8, sampled=True)
    assert ctx.to_traceparent() == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(ctx.to_traceparent())
    assert back == ctx
    off = TraceContext("ab" * 16, "cd" * 8, sampled=False)
    assert parse_traceparent(off.to_traceparent()).sampled is False
    # malformed values degrade to None, never raise (propagation is
    # best-effort — a garbled header must not 4xx a request)
    for bad in (None, "", "00-zz-cd-01", "junk", "00-" + "a" * 32,
                "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",
                "00-" + "ab" * 16 + "-" + "0" * 16 + "-01"):
        assert parse_traceparent(bad) is None, bad


def test_trace_context_child_and_span_args():
    ctx = TraceContext("ab" * 16, "cd" * 8, sampled=True)
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id and len(child.span_id) == 16
    assert ctx.span_args() == {"trace_id": ctx.trace_id,
                               "parent_id": ctx.span_id}
    # unsampled: ids still propagate, but receivers attach nothing
    assert TraceContext("ab" * 16, "cd" * 8,
                        sampled=False).span_args() == {}


def test_recorder_drain_per_process_and_tail():
    """drain(process=...) removes ONLY that label's spans (the shared
    in-process-fleet ring contract); tail() is non-destructive."""
    rec = TraceRecorder()
    rec.start()
    t = time.perf_counter()
    rec.add("replica0", "slot0", "prefill", t, t + 1, None)
    rec.add("replica1", "slot0", "prefill", t + 2, t + 3, None)
    rec.add("replica0", "slot0", "decode", t + 4, t + 5, None)
    assert [s[2] for s in rec.tail(10, process="replica0")] \
        == ["prefill", "decode"]
    assert [s[2] for s in rec.tail(1, process="replica0")] == ["decode"]
    drained = rec.drain(process="replica0")
    assert [s[0] for s in drained] == ["replica0", "replica0"]
    # replica1's span survived the other replica's export
    assert [s[0] for s in rec.drain()] == ["replica1"]
    assert rec.drain() == []


def test_arm_always_on_never_clears_an_active_capture():
    old = recorder()
    try:
        rec = set_recorder(TraceRecorder())
        rec.start()
        t = time.perf_counter()
        rec.add("serving", "main", "prefill", t, t + 1, None)
        # a second server arming always-on must neither clear nor
        # resize the live capture
        assert arm_always_on(max_events=128) is rec
        assert rec.spans_recorded == 1 and rec.max_events != 128
        rec.stop()
        # disarmed: arming starts recording again
        arm_always_on()
        assert recorder().enabled
    finally:
        set_recorder(old)


#: per-call ceiling of the armed span path, generous on purpose: the
#: path measures ~3 us (one lock, a deque append, and since PR 24 a
#: profiler annotation's inactive check) on an idle core and is timed
#: here under six xdist workers; what guards the engine's cost per step
#: is deterministic (tests/test_sched_tracing.py: spans per step do not
#: depend on the live slots), this only catches a path grown 10x
ARMED_SPAN_CEILING_S = 40e-6


def test_armed_recorder_overhead_within_budget(fresh_recorder):
    """The sampled-ON twin of the disabled-path guard: with the
    always-on flight-recorder ring armed, span()/add_span() stay under
    a per-call ceiling that holds on a loaded CPU (best of 7 loops
    rejects scheduler noise), and every call is recorded."""
    rec = fresh_recorder
    rec.start()
    n = 2000
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("prefill", lane="slot0", request_id="r"):
                pass
            add_span("decode", 0.0, 1.0, lane="slot0")
        best = min(best, time.perf_counter() - t0)
    assert rec.spans_recorded == 7 * 2 * n
    assert best / (2 * n) < ARMED_SPAN_CEILING_S, \
        f"armed span path too slow: {best / (2 * n) * 1e6:.2f} µs/call"


# ------------------------------------------------- prom round-trip
def test_parse_snapshot_render_roundtrip_is_exact():
    """parse_snapshot(render(s)) == s EXACTLY — counters, gauges,
    histograms with +Inf overflow, float values, and escaped help text
    (backslashes + newlines) all round-trip; plus every metric gets a
    # TYPE line and every helped metric a # HELP line."""
    reg = Registry()
    reg.counter("a_total", "plain help").inc(7)
    reg.counter("f_total", "").inc(2.5)               # float counter
    reg.gauge("g", "multi\nline \\ help").set(-3.25)
    h = reg.histogram("lat_seconds", "hist\nhelp", buckets=(0.5, 1.0))
    for v in (0.1, 0.7, 99.0):                        # +Inf overflow
        h.observe(v)
    reg.histogram("empty_seconds", "never observed", buckets=(1.0,))
    snap = reg.snapshot()
    text = prom.render(snap)
    assert prom.parse_snapshot(text) == snap
    lines = text.splitlines()
    for name in snap:
        assert any(ln.startswith(f"# TYPE {name} ") for ln in lines)
    for name, rec in snap.items():
        if rec["help"]:
            assert any(ln.startswith(f"# HELP {name} ")
                       for ln in lines)
    # and the escape itself is lossless through a SECOND round trip
    again = prom.render(prom.parse_snapshot(text))
    assert again == text


def test_parse_snapshot_roundtrip_property_style():
    """Seeded randomized round-trip over many registry shapes — the
    completeness contract, not one hand-picked example."""
    import random
    rng = random.Random(17)
    for case in range(25):
        reg = Registry()
        for i in range(rng.randint(1, 5)):
            kind = rng.choice(("counter", "gauge", "histogram"))
            help_text = rng.choice(
                ("", "plain", "with \\ backslash", "two\nlines"))
            name = f"m{case}_{i}_{kind}"
            if kind == "counter":
                c = reg.counter(name + "_total", help_text)
                for _ in range(rng.randint(0, 4)):
                    c.inc(rng.choice((1, 2, 0.5)))
            elif kind == "gauge":
                reg.gauge(name, help_text).set(
                    rng.choice((0, -1, 3.5, 1e9)))
            else:
                bounds = sorted(rng.sample(
                    (0.001, 0.01, 0.1, 1.0, 10.0, 100.0),
                    rng.randint(1, 4)))
                hh = reg.histogram(name + "_seconds", help_text,
                                   buckets=bounds)
                for _ in range(rng.randint(0, 6)):
                    hh.observe(rng.uniform(0, 200))
        snap = reg.snapshot()
        assert prom.parse_snapshot(prom.render(snap)) == snap, case


def test_quantile_from_parsed():
    reg = Registry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05,) * 5 + (0.5,) * 4 + (5.0,):
        h.observe(v)
    parsed = prom.parse(prom.render(reg.snapshot()))
    p50 = prom.quantile_from_parsed(parsed, "lat_seconds", 0.5)
    assert 0.0 < p50 <= 0.1
    p90 = prom.quantile_from_parsed(parsed, "lat_seconds", 0.9)
    assert 0.1 < p90 <= 1.0
    assert prom.quantile_from_parsed(parsed, "absent", 0.5) == 0.0
    with pytest.raises(ValueError, match="q must be"):
        prom.quantile_from_parsed(parsed, "lat_seconds", 1.5)


def test_quantile_edge_cases_pinned():
    """The edge cases the window-quantile queries (obs/timeseries.py)
    lean on, pinned BEFORE the SLO layer trusts them: an EMPTY
    histogram is 0.0 (no observations, no percentile); an
    all-mass-in-+Inf histogram (every observation beyond the last
    finite bound — the saturated case the load harness's bucket audit
    hunts) clamps to the largest FINITE bound at every q; a
    single-bucket histogram interpolates from 0 within its one bound
    and never exceeds it."""
    reg = Registry()
    # empty: count == 0
    reg.histogram("empty_seconds", buckets=(0.1, 1.0))
    parsed = prom.parse(prom.render(reg.snapshot()))
    for q in (0.0, 0.5, 0.95, 1.0):
        assert prom.quantile_from_parsed(parsed, "empty_seconds",
                                         q) == 0.0
    # saturated: all observations in +Inf -> the conventional clamp,
    # the largest finite bound, at EVERY rank (never inf, never 0)
    h = reg.histogram("sat_seconds", buckets=(0.1, 1.0))
    for _ in range(7):
        h.observe(50.0)
    parsed = prom.parse(prom.render(reg.snapshot()))
    for q in (0.01, 0.5, 0.99):
        assert prom.quantile_from_parsed(parsed, "sat_seconds",
                                         q) == 1.0
    # single bucket: linear interpolation from 0 within the one bound
    h1 = reg.histogram("one_seconds", buckets=(2.0,))
    for _ in range(4):
        h1.observe(1.0)
    parsed = prom.parse(prom.render(reg.snapshot()))
    assert prom.quantile_from_parsed(parsed, "one_seconds",
                                     0.5) == pytest.approx(1.0)
    assert prom.quantile_from_parsed(parsed, "one_seconds",
                                     1.0) == pytest.approx(2.0)
    # single bucket + +Inf mass: rank inside the finite bucket still
    # interpolates; rank beyond it clamps to the finite bound
    h1.observe(10.0)
    parsed = prom.parse(prom.render(reg.snapshot()))
    assert prom.quantile_from_parsed(parsed, "one_seconds",
                                     0.4) == pytest.approx(1.0)
    assert prom.quantile_from_parsed(parsed, "one_seconds",
                                     0.99) == 2.0


# ----------------------------------------------------- training telemetry
def test_trainer_registry_and_trace_lanes(tmp_path):
    """The trainer side of the telemetry story: train() with
    --trace_path dumps a Perfetto-loadable timeline with data/step/
    checkpoint lanes, and the trainer registry holds the one metric
    something reads, the data-wait histogram."""
    from distributed_tensorflow_example_tpu.config import (
        CheckpointConfig, DataConfig, MeshShape, ObservabilityConfig,
        OptimizerConfig, TrainConfig)
    from distributed_tensorflow_example_tpu.data.mnist import \
        synthetic_mnist
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.parallel.mesh import \
        local_mesh
    from distributed_tensorflow_example_tpu.train.trainer import Trainer

    trace_path = str(tmp_path / "train.trace.json")
    cfg = TrainConfig(
        model="mlp", train_steps=4, mesh=MeshShape(data=4),
        data=DataConfig(batch_size=64, seed=3),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "ckpt"),
                                    save_steps=2),
        obs=ObservabilityConfig(
            log_every_steps=2,
            metrics_path=str(tmp_path / "metrics.jsonl"),
            trace_path=trace_path, trace_buffer_events=4096),
        seed=7)
    data = synthetic_mnist(num_train=256, num_test=64, seed=0)
    tr = Trainer(get_model("mlp", cfg), cfg,
                 {"x": data["train_x"], "y": data["train_y"]},
                 mesh=local_mesh(4), process_index=0, num_processes=1)
    try:
        tr.train()
    finally:
        tr.close()

    # the trainer serves no /metrics page: its registry keeps only what
    # something reads (the benchmark's train_data_wait_ms); steps, saves
    # and rollbacks are in the JSONL log and on the trace lanes below
    snap = tr.registry.snapshot()
    assert set(snap) == {"train_data_wait_seconds"}
    assert snap["train_data_wait_seconds"]["count"] == 4

    with open(trace_path) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    for e in xs:
        for k in ("ts", "dur", "pid", "tid", "name"):
            assert k in e
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "thread_name"}
    assert {"data", "step", "checkpoint"} <= lanes, lanes
    names = {e["name"] for e in xs}
    assert {"data_wait", "step_dispatch", "checkpoint_save"} <= names
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "process_name"}
    assert procs == {"training"}
