"""The join of the capture's host spans with its device operations, and the
readers built on it: on hand-made intervals, and on one small serving
capture recorded on the chip at rehearsal size
(``record_sched_fixture.py``; the program's engine, 8 slots, a two-layer
decoder, a quarter of a second)."""

import json
import os
import shutil
import tempfile

import pytest

from benchmark import trace_reduce
from benchmark.manifest import ROOT, Manifest, load_module
from benchmark.readers import xplane_join

HERE = os.path.dirname(__file__)
FIXTURE = os.path.join(HERE, "fixtures", "sched_tpu.xplane.pb")
NO_SPANS = os.path.join(HERE, "fixtures", "tiny_tpu.xplane.pb")
PHASES = ["wait_logits", "sample_emit", "admit", "launch", "unattributed"]
NEW_SERVING = ([f"sched_idle_{p}_ms.{c}" for c in ("backlog", "chat")
                for p in PHASES]
               + [f"sched_join_{w}.{c}" for c in ("backlog", "chat")
                  for w in ("one_while_pct", "shift_ms")]
               + ["sched_decode_span_ms_p50.backlog",
                  "sched_decode_span_ms_p50.chat",
                  "serve_decode_attn_roofline_pct.backlog"])


def reader(name):
    return load_module(os.path.join(ROOT, "benchmark", "readers",
                                    name + ".py"))


def ctx_of(path):
    m = Manifest(ROOT)
    return {"trace": trace_reduce.reduce(path), "xplane_path": path,
            "peak": m.peak("TPU v5 lite"), "values": {}}


def read(name, ctx):
    m = Manifest(ROOT)
    metric = next(x for x in m.doc["per_layer"] if x["name"] == name)
    return m.read_metric(metric, ctx)


def test_intervals_merge_and_overlap():
    assert xplane_join.merged([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
    gaps = [(1.0, 2.0), (5.0, 6.0), (8.0, 8.5)]
    spans = [(0.0, 1.5), (1.75, 5.25), (5.5, 9.0)]
    assert xplane_join.overlap(gaps, spans) == pytest.approx(
        0.5 + 0.25 + 0.25 + 0.5 + 0.5)
    assert xplane_join.overlap(gaps, []) == 0.0


def hand_made():
    """Two decode steps. Device: a copy [0.25, 0.4), idle [0.4, 1), while
    [1, 5) holding a custom-call [2, 3), idle [5, 7), while [7, 11) with
    its custom-call [8, 9), idle [11, 12), a copy [12, 12.5). Host: the
    first step's dispatch [0.5, 0.75) lies in the first gap; in the
    second lie wait_logits [5, 5.5), sample_emit [5.5, 6), housekeeping
    and admit [6, 6.25), secure/build/dispatch [6.25, 6.75), and
    [6.75, 7) is under no span; the second step's wait_logits
    [11, 11.5) lies in the third."""
    ops = [(0.25, 0.4, "copy"),
           (1.0, 5.0, "while"), (2.0, 3.0, "custom-call"),
           (7.0, 11.0, "while"), (8.0, 9.0, "custom-call"),
           (12.0, 12.5, "copy")]
    busy = xplane_join.merged([(a, b) for a, b, _ in ops])
    spans = {
        "decode_step": [(0.5, 5.5, {"kv_bytes": 4000, "slots": 2}),
                        (6.5, 11.5, {"kv_bytes": 6000, "slots": 2})],
        "sched_wait_logits": [(5.0, 5.5, {}), (11.0, 11.5, {})],
        "sched_sample_emit": [(5.5, 6.0, {})],
        "sched_housekeeping": [(6.0, 6.125, {})],
        "sched_admit": [(6.125, 6.25, {})],
        "sched_secure_blocks": [(6.25, 6.375, {})],
        "sched_build_feats": [(6.375, 6.5, {})],
        "sched_dispatch": [(0.5, 0.75, {}), (6.5, 6.75, {})],
    }
    return {"spans": spans,
            "chips": [{"ops": ops, "busy": busy,
                       "window": (busy[0][0], busy[-1][1])}]}


def test_idle_gaps_are_charged_to_the_phase_the_host_was_in():
    found = hand_made()
    idle = reader("sched_idle_ms").idle_by_phase(found)
    assert idle["steps"] == 2
    assert idle["seconds"] == pytest.approx({
        "wait_logits": 0.5 + 0.5, "sample_emit": 0.5, "admit": 0.25,
        "launch": 0.5 + 0.25, "unattributed": 0.35 + 0.25 + 0.5})
    ctx = {"_xplane_join": found}
    got = {p: reader("sched_idle_ms").read(ctx, p) for p in PHASES}
    assert sum(got.values()) == pytest.approx(1e3 * 3.6 / 2)
    assert xplane_join.steps_with_one_while(found) == (2, 2)
    assert reader("span_ms_p50").read(ctx, "decode_step") == \
        pytest.approx(5000.0)


def test_decode_attention_roofline_counts_agreeing_steps_only():
    found = hand_made()
    ctx = {"_xplane_join": found, "peak": {"hbm_bytes_per_s": 1000.0}}
    # 10,000 bytes at 1000 bytes/s are 10 s, against 2 s of kernel
    assert reader("decode_attn_roofline").read(ctx) == pytest.approx(500.0)
    # a step whose span holds no device while (clocks apart) is left out
    found["spans"]["decode_step"][1] = (11.2, 11.5, {"kv_bytes": 6000})
    ctx = {"_xplane_join": found, "peak": {"hbm_bytes_per_s": 1000.0}}
    assert reader("decode_attn_roofline").read(ctx) == pytest.approx(400.0)
    # no kv_bytes argument (a program before PR 24): nothing to read
    for i, s in enumerate(found["spans"]["decode_step"]):
        found["spans"]["decode_step"][i] = (s[0], s[1], {})
    assert reader("decode_attn_roofline").read(
        {"_xplane_join": found, "peak": {"hbm_bytes_per_s": 1.0}}) is None


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded serving capture in this checkout")
    ctx = ctx_of(FIXTURE)
    assert xplane_join.join(ctx) is not None
    return ctx


def test_every_decode_step_span_holds_exactly_one_device_while(recorded):
    found = xplane_join.join(recorded)
    one, of = xplane_join.steps_with_one_while(found)
    assert of >= 50 and one == of
    # the children lie inside their step, on the profiler's clock too
    steps = found["spans"]["decode_step"]
    for name in ("sched_dispatch", "sched_wait_logits"):
        inside = sum(1 for c in found["spans"][name]
                     if any(s[0] <= c[0] and c[1] <= s[1] for s in steps))
        assert inside >= len(found["spans"][name]) - 1
    assert all(s[2]["kv_bytes"] > 0 and 1 <= s[2]["slots"] <= 8
               for s in steps)


@pytest.mark.parametrize("skew_ms", [-1.15, -1.05])
def test_a_capture_whose_clocks_disagree_is_shifted_until_causal(skew_ms):
    """A capture's two clocks agree to about a millisecond (one run of a
    cell read every decode program as starting BEFORE its span): the
    device's timeline is shifted by the least that puts each ``while``
    inside the span that dispatched it."""
    found = xplane_join.parse(FIXTURE)
    assert found["device_shift_s"] == 0.0
    for chip in found["chips"]:
        chip["ops"] = [(a + skew_ms * 1e-3, b + skew_ms * 1e-3, c)
                       for a, b, c in chip["ops"]]
        chip["busy"] = [(a + skew_ms * 1e-3, b + skew_ms * 1e-3)
                        for a, b in chip["busy"]]
        chip["window"] = tuple(x + skew_ms * 1e-3 for x in chip["window"])
    one, of = xplane_join.steps_with_one_while(found)
    assert one < 0.5 * of                   # the skew broke containment
    fixed = xplane_join.align(found)
    one, of = xplane_join.steps_with_one_while(fixed)
    assert one >= of - 1 and of >= 50
    # by no more than was needed: it stops where the first while touches
    assert 0 < fixed["device_shift_s"] < abs(skew_ms) * 1e-3
    # and the line says that this join was forced, and by how much: the
    # agreement is published as the capture's own clocks had it
    ctx = {"_xplane_join": fixed}
    check = reader("join_check")
    assert check.read(ctx, "one_while_pct") < 50
    assert check.read(ctx, "shift_ms") == pytest.approx(
        1e3 * fixed["device_shift_s"])


def test_a_shift_moves_idle_time_between_launch_and_wait_logits_only():
    """A step's ``while`` starts inside ``sched_dispatch`` and ends inside
    ``sched_wait_logits``, which tile ``decode_step``: what a skew of the
    device's clock takes from one it gives to the other."""
    def found(skew):
        ops = [(1.0 + skew, 5.0 + skew, "while"),
               (7.0 + skew, 11.0 + skew, "while")]
        return {"spans": {
            "decode_step": [(0.5, 5.5, {}), (6.5, 11.5, {})],
            "sched_dispatch": [(0.5, 1.5, {}), (6.5, 7.5, {})],
            "sched_wait_logits": [(1.5, 5.5, {}), (7.5, 11.5, {})],
            "sched_sample_emit": [(5.5, 6.0, {})],
            "sched_admit": [(6.0, 6.25, {})],
            "sched_build_feats": [(6.25, 6.5, {})]},
            "chips": [{"ops": ops, "busy": [(a, b) for a, b, _ in ops],
                       "window": (ops[0][0], ops[-1][1])}]}
    idle = reader("sched_idle_ms").idle_by_phase
    true, early = idle(found(0.0))["seconds"], idle(found(-0.125))["seconds"]
    assert true == pytest.approx({
        "wait_logits": 0.5, "sample_emit": 0.5, "admit": 0.25,
        "launch": 0.75, "unattributed": 0.0})
    assert early == pytest.approx(dict(true, wait_logits=0.625,
                                       launch=0.625))


def test_a_late_device_clock_is_shifted_back():
    found = hand_made()
    late = 0.75                 # the whiles now end after their spans
    chip = found["chips"][0]
    chip["ops"] = [(a + late, b + late, c) for a, b, c in chip["ops"]]
    assert xplane_join.causal_shift(found) == pytest.approx(-0.25)
    assert xplane_join.causal_shift(hand_made()) == 0.0


@pytest.mark.parametrize("cell", ["backlog", "chat"])
def test_the_idle_metrics_add_up_to_the_reduced_traces_idle(recorded, cell):
    t = recorded["trace"]
    steps = t["opcodes"]["while"]["calls"]
    idle_ms = 1e3 * (t["window_s"] - t["busy_s"]) / steps
    got = {p: read(f"sched_idle_{p}_ms.{cell}", recorded) for p in PHASES}
    assert all(v is not None and v >= 0 for v in got.values())
    assert sum(got.values()) == pytest.approx(idle_ms, rel=1e-6)
    # the loop tiles: little of the idle time is under no span
    assert got["unattributed"] < 0.1 * idle_ms
    idle_pct = read(f"serve_device_idle_pct.{cell}", recorded)
    assert sum(got.values()) == pytest.approx(
        idle_pct / 100 * t["window_s"] * 1e3 / steps, rel=1e-6)


def test_the_span_and_roofline_readers_read_the_recorded_capture(recorded):
    # this capture's clocks agreed as recorded: nothing was forced
    assert read("sched_join_one_while_pct.chat", recorded) == 100.0
    assert read("sched_join_shift_ms.chat", recorded) == 0.0
    p50 = read("sched_decode_span_ms_p50.backlog", recorded)
    assert 0.5 < p50 < 20                  # ms: a rehearsal-size step
    share = read("serve_decode_attn_roofline_pct.backlog", recorded)
    assert 0 < share < 100


@pytest.mark.parametrize("name", NEW_SERVING)
def test_a_capture_without_the_spans_gives_none(name):
    """The parent of PR 24 records no span on the profiler's clock: the
    metric is left out of the line, nothing raises."""
    ctx = ctx_of(NO_SPANS)
    assert read(name, ctx) is None


def test_the_capture_is_found_by_what_it_reduces_to(monkeypatch, tmp_path):
    run = tmp_path / "benchmark_run_abc" / "trace" / "plugins" / "profile"
    (run / "2026_09_27").mkdir(parents=True)
    shutil.copy(NO_SPANS, run / "2026_09_27" / "host.xplane.pb")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    reduced = trace_reduce.reduce(NO_SPANS)
    assert xplane_join.find_capture(reduced) == str(
        run / "2026_09_27" / "host.xplane.pb")
    # another run's capture is not this run's
    other = dict(reduced, busy_s=reduced["busy_s"] * 2)
    assert xplane_join.find_capture(other) is None
    ctx = {"trace": other}
    assert xplane_join.join(ctx) is None and xplane_join.join(ctx) is None
    # only the newest is looked at: this process wrote it a moment ago
    older = tmp_path / "benchmark_run_old" / "trace" / "plugins" / "profile"
    (older / "2026_09_26").mkdir(parents=True)
    shutil.copy(FIXTURE, older / "2026_09_26" / "host.xplane.pb")
    os.utime(older / "2026_09_26" / "host.xplane.pb", (1, 1))
    assert xplane_join.find_capture(trace_reduce.reduce(FIXTURE)) is None


@pytest.mark.parametrize("metric,names,want", [
    ("train_flash_fwd_ms", {"jvp_flash_fwd_": 0.6}, 300.0),
    ("train_flash_bwd_ms", {"transpose_jvp_flash_bwd_dq__": 0.5,
                            "transpose_jvp_flash_bwd_dkv__": 0.7}, 600.0),
    ("train_flash_fwd_ms", {"jvp__": 0.6}, None),
    ("train_flash_bwd_ms", {"transpose_jvp___": 1.2}, None)])
def test_flash_forward_and_backward_are_told_apart_by_name(metric, names,
                                                          want):
    """Data files only, through ``op_ms_per_call``: the kernels' names as
    the compiled step carries them since PR 24; before it (``jvp__``)
    there is nothing to read."""
    reduced = {"all_ops": {k: {"seconds": v, "calls": 12}
                           for k, v in names.items()},
               "opcodes": {}, "modules": {"jit__auto_step": [0.3, 0.3]}}
    got = read(metric, {"trace": reduced})
    assert got == (None if want is None else pytest.approx(want))


def test_every_new_metric_has_its_file_reader_and_one_cell():
    m = Manifest(ROOT)
    by_name = {x["name"]: x for x in m.doc["per_layer"]}
    for name in NEW_SERVING + ["train_flash_fwd_ms", "train_flash_bwd_ms"]:
        entry = by_name[name]
        assert len(entry["workloads"]) == 1
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
        want = {"backlog": "serve_tokens_per_s",
                "chat": "req_latency_p50_ms"}.get(name.rsplit(".", 1)[-1],
                                                  "train_tokens_per_s")
        assert entry["moves"] == want
