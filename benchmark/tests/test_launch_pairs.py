"""``readers/launch_pairs.py``: launch spans paired with executed programs
by ORDER, the clocks aligned from the pairs, the admit split read on that
alignment. On hand-made intervals, and on two
captures recorded on the chip at rehearsal size
(``record_pair_fixture.py``): a run shaped as ``gpt2s-serve-backlog`` is
(programs ``prefill``, ``decode``, ``copy``) and the Kimi cell's
rehearsal artifact (``zero_slot``, ``prefill_chunk``, ``decode``; no
``while`` the older join could anchor on)."""

import os

import pytest

from benchmark import trace_reduce
from benchmark.manifest import ROOT, Manifest
from benchmark.readers import launch_pairs, sched_idle_ms, xplane_join

HERE = os.path.dirname(__file__)
FIXTURES = {"gpt": os.path.join(HERE, "fixtures", "pair_tpu.xplane.pb"),
            "state": os.path.join(HERE, "fixtures",
                                  "pair_state_tpu.xplane.pb")}
BEFORE_PR37 = os.path.join(HERE, "fixtures", "sched_tpu.xplane.pb")
WHATS = {"sched_idle_admit_launch_ms": "admit_launch",
         "sched_idle_admit_read_ms": "admit_read",
         "sched_idle_admit_emit_ms": "admit_emit",
         "sched_idle_admit_self_ms": "admit_self",
         "sched_pair_shift_ms": "shift", "sched_pair_slack_ms": "slack"}
SERVING = ["gpt2s-serve-backlog", "sdar-serve-backlog",
           "kimi-serve-backlog", "dots3-serve-longctx"]


def ctx_of(path):
    return {"trace": trace_reduce.reduce(path), "xplane_path": path,
            "values": {}}


def moved(found, seconds):
    """``found`` with the device's clock ``seconds`` later."""
    return dict(found, chips=launch_pairs.moved(found["chips"], seconds))


# ---- hand-made intervals ------------------------------------------------

def hand_made(skew=0.0):
    """Two iterations on the host: a prefill admitted (launch [1, 2),
    read [2, 6), emit [6, 7) inside sched_admit [0.5, 7.5)), a copy
    [8, 8.5), a decode step [9, 14). The device, ``skew`` late: prefill
    [1.5, 5), copy [8.75, 9.25), decode [9.5, 13]."""
    spans = {
        "sched_housekeeping": [(0.0, 0.5, {})],
        "sched_admit": [(0.5, 7.5, {})],
        "admit_launch": [(1.0, 2.0, {"program": "prefill", "seq": 7})],
        "admit_read": [(2.0, 6.0, {"program": "prefill", "seq": 7})],
        "admit_emit": [(6.0, 7.0, {})],
        "sched_secure_blocks": [(7.5, 8.75, {})],
        "cow_copy": [(8.0, 8.5, {"program": "copy", "seq": 3})],
        "sched_build_feats": [(8.75, 9.0, {})],
        "decode_step": [(9.0, 14.0, {"program": "decode", "seq": 40,
                                     "slots": 2})],
        "sched_dispatch": [(9.0, 10.0, {})],
        "sched_wait_logits": [(10.0, 14.0, {})],
        "sched_sample_emit": [(14.0, 15.0, {})],
    }
    mods = [(1.5 + skew, 5.0 + skew, "prefill"),
            (8.75 + skew, 9.25 + skew, "copy"),
            (9.5 + skew, 13.0 + skew, "decode")]
    ops = [(a, b, "fusion") for a, b, _ in mods]
    busy = xplane_join.merged([(a, b) for a, b, _ in ops])
    # the device's window is wider than its programs: an operation
    # before and after, so that every host span lies inside it
    busy = [(-1.0 + skew, -0.5 + skew)] + busy + [(15.5 + skew,
                                                   16.0 + skew)]
    found = {"spans": spans, "device_shift_s": 0.0,
             "chips": [{"ops": ops, "busy": busy,
                        "window": (busy[0][0], busy[-1][1])}]}
    return found, mods


@pytest.mark.parametrize("as_read", [int, str, float])
def test_launches_carry_where_their_result_was_read(as_read):
    """Whatever type the capture hands the read's ``seq`` back as."""
    found, _ = hand_made()
    found["spans"]["admit_read"][0][2]["seq"] = as_read(7)
    assert launch_pairs.launches(found["spans"]) == [
        (1.0, 6.0, "prefill", 7), (8.0, None, "copy", 3),
        (9.0, 14.0, "decode", 40)]


@pytest.mark.parametrize("skew, shift", [(0.0, 0.0), (0.25, 0.0),
                                         (-0.75, 0.25), (1.5, -0.5),
                                         (-40.0, 39.5), (40.0, -39.0)])
def test_the_least_causal_shift_of_the_feasible_interval(skew, shift):
    """Launches bound the shift from below (prefill -0.5, copy -0.75,
    decode -0.5: a program cannot start before its launch), reads from
    above (prefill +1, decode +1): [-0.5, +1] less the skew; the member
    nearest 0 is applied."""
    found, mods = hand_made(skew)
    paired = launch_pairs.pair(found["spans"], mods)
    assert len(paired["pairs"]) == 3
    assert (paired["lo"], paired["hi"]) == pytest.approx(
        (-0.5 - skew, 1.0 - skew))
    assert paired["shift"] == pytest.approx(shift)
    assert paired["paired"] == paired["launches"] == paired["modules"] \
        == {"copy": 1, "decode": 1, "prefill": 1}
    assert "3 of 3 launch spans" in launch_pairs.describe(paired)


def test_the_four_admit_parts():
    """Device idle: [-0.5, 1.5) [5, 8.75) [9.25, 9.5) [13, 15.5).
    Inside admit_launch [1, 1.5) = 0.5; admit_read [5, 6) = 1;
    admit_emit [6, 7) = 1; housekeeping + admit outside them [0, 1)
    and [7, 7.5) = 1.5."""
    found, mods = hand_made()
    paired = launch_pairs.pair(found["spans"], mods)
    at = launch_pairs.aligned(found, paired["shift"])
    idle = launch_pairs.admit_idle(at)
    assert idle == pytest.approx({"admit_launch": 0.5, "admit_read": 1.0,
                                  "admit_emit": 1.0, "admit_self": 1.5})
    assert sum(idle.values()) == pytest.approx(
        sched_idle_ms.idle_by_phase(at)["seconds"]["admit"])


def test_a_child_whose_parent_the_captures_edge_cut_is_left_out():
    """The capture began inside ``sched_admit``: its children are there
    and it is not. Their idle time stays outside the admit phase, as
    ``sched_idle_ms`` has it."""
    found, mods = hand_made()
    del found["spans"]["sched_admit"]
    at = launch_pairs.aligned(found, 0.0)
    idle = launch_pairs.admit_idle(at)
    assert idle == pytest.approx({"admit_launch": 0.0, "admit_read": 0.0,
                                  "admit_emit": 0.0, "admit_self": 0.5})
    assert sum(idle.values()) == pytest.approx(
        sched_idle_ms.idle_by_phase(at)["seconds"]["admit"])
    # the launches still pair: the alignment needs no parent
    assert len(launch_pairs.pair(found["spans"], mods)["pairs"]) == 3


def test_counts_that_disagree_give_none():
    found, mods = hand_made()
    # a program the capture holds no launch span for, in the middle
    extra = mods[:1] + [(6.0, 6.5, "decode")] + mods[1:]
    assert launch_pairs.pair(found["spans"], extra) is None
    # no span carries a program (the parent): nothing to pair
    bare = {k: [(a, b, {}) for a, b, _ in v]
            for k, v in found["spans"].items()}
    assert launch_pairs.launches(bare) == []
    assert launch_pairs.pair(bare, mods) is None
    # a launch span the profiler dropped: the ordinals have a hole
    found["spans"]["decode_step"].append(
        (15.0, 19.0, {"program": "decode", "seq": 42}))
    assert launch_pairs.pair(
        found["spans"], mods + [(15.5, 18.0, "decode")]) is None
    # no single shift makes every pair causal
    found, mods = hand_made()
    mods[2] = (9.5 - 3.0, 13.0 - 3.0, "decode")
    mods[0] = (1.5 + 3.0, 5.0 + 3.0, "prefill")
    assert launch_pairs.pair(found["spans"], sorted(mods)) is None
    assert "do not pair" in launch_pairs.describe(None)


def test_a_capture_cut_at_its_edges_still_pairs():
    """The host's recorder started a launch later than the device's,
    and stopped one earlier."""
    found, mods = hand_made()
    wider = ([(-3.0, -2.5, "decode")] + mods + [(17.0, 18.0, "prefill")])
    paired = launch_pairs.pair(found["spans"], wider)
    assert [m for _, m in paired["pairs"]] == mods
    assert paired["modules"] == {"copy": 1, "decode": 2, "prefill": 2}
    assert (paired["lo"], paired["hi"]) == pytest.approx((-0.5, 1.0))


# ---- the recorded captures ----------------------------------------------

@pytest.fixture(scope="module", params=sorted(FIXTURES))
def recorded(request):
    path = FIXTURES[request.param]
    if not os.path.exists(path):
        pytest.skip("no recorded capture in this checkout")
    ctx = ctx_of(path)
    got = launch_pairs.reading(ctx)
    assert got is not None
    return request.param, path, ctx, got


def test_every_launch_span_is_paired_with_one_executed_program(recorded):
    kind, path, ctx, got = recorded
    paired = got["paired"]
    want = {"gpt": {"copy", "decode", "prefill"},
            "state": {"decode", "prefill_chunk", "zero_slot"}}[kind]
    assert set(paired["launches"]) == want
    n = sum(paired["launches"].values())
    # all but what the capture's edges cut
    assert n >= 40 and len(paired["pairs"]) >= n - 2
    assert paired["lo"] <= paired["shift"] <= paired["hi"]
    assert 0 < paired["hi"] - paired["lo"] < 3e-3
    for launch, mod in paired["pairs"]:
        assert launch[2] == mod[2]
        assert launch[0] <= mod[0] + paired["shift"] + 1e-9
        if launch[1] is not None:
            assert mod[1] + paired["shift"] <= launch[1] + 1e-9
    assert got["steps"] >= 20


@pytest.mark.parametrize("skew_ms", [-5.0, -1.1, 1.1, 5.0])
def test_pairing_by_order_survives_a_moved_device_clock(recorded, skew_ms):
    """The case ``causal_shift``'s nearest-start pairing loses: with the
    device's clock moved by more than a step, the nearest span is
    another step's. The order-pairing pairs the same launches with the
    same programs and undoes the move, so every reading stays."""
    kind, path, ctx, got = recorded
    skew = skew_ms * 1e-3
    found = xplane_join.join(ctx)
    mods = [(a + skew, b + skew, p) for a, b, p in
            launch_pairs.modules(path)]
    paired = launch_pairs.pair(found["spans"], mods)
    assert [ln for ln, _ in paired["pairs"]] == [
        ln for ln, _ in got["paired"]["pairs"]]
    assert [m[0] - skew for _, m in paired["pairs"]] == pytest.approx(
        [m[0] for _, m in got["paired"]["pairs"]])
    assert paired["lo"] == pytest.approx(got["paired"]["lo"] - skew)
    assert paired["hi"] == pytest.approx(got["paired"]["hi"] - skew)
    # the alignment it ends at differs from the unmoved capture's by
    # less than the slack, whatever the move
    raw = launch_pairs.aligned(found, 0.0)
    at = launch_pairs.aligned(moved(raw, skew), paired["shift"])
    apart = abs(at["chips"][0]["window"][0]
                - got["aligned"]["chips"][0]["window"][0])
    assert apart <= got["paired"]["hi"] - got["paired"]["lo"] + 1e-9
    if kind == "gpt" and abs(skew_ms) >= 5:
        # the older join, on the same moved capture: it cannot put the
        # whiles back inside their steps
        old = xplane_join.align(dict(
            moved(raw, skew), spans=found["spans"]))
        one, of = xplane_join.steps_with_one_while(old)
        assert one < 0.9 * of


def test_the_four_admit_parts_add_up_on_the_same_alignment(recorded):
    kind, path, ctx, got = recorded
    idle = sched_idle_ms.idle_by_phase(got["aligned"])["seconds"]
    assert sum(got["idle"].values()) == pytest.approx(idle["admit"])
    assert all(v >= -1e-12 for v in got["idle"].values()), got["idle"]
    assert got["idle"]["admit_read"] > 0


def test_a_capture_whose_counts_disagree_gives_none(recorded):
    kind, path, ctx, got = recorded
    found = xplane_join.join(ctx)
    mods = launch_pairs.modules(path)
    # every third program of the capture's middle lost
    middle = [m for i, m in enumerate(mods)
              if not (len(mods) // 4 < i < 3 * len(mods) // 4 and i % 3 == 0)]
    assert launch_pairs.pair(found["spans"], middle) is None
    # the device's recorder stopped half way
    assert launch_pairs.pair(found["spans"], mods[:len(mods) // 2]) is None


def test_each_new_metric_reads_a_number_through_the_manifest(recorded):
    """The twelve entries of ``BENCHMARK.json``: in their cells, each
    reads what ``launch_pairs.read`` does, a finite number."""
    kind, path, ctx, got = recorded
    m = Manifest(ROOT)
    by_name = {x["name"]: x for x in m.doc["per_layer"]}
    for name, what in WHATS.items():
        for full, cells, moves in (
                (name, SERVING, "serve_tokens_per_s"),
                (name + ".chat", ["gpt2s-serve-chat"],
                 "req_latency_p50_ms")):
            metric = by_name[full]
            assert metric["workloads"] == cells
            assert (metric["moves"], metric["layer"], metric["source"],
                    metric["unit"]) == (moves, "scheduler",
                                        "program_span", "ms")
            value = m.read_metric(metric, ctx)
            assert value == launch_pairs.read(ctx, what)
            assert value is not None and -1 < value < 1e3
    parts = [launch_pairs.read(ctx, w) for w in (
        "admit_launch", "admit_read", "admit_emit", "admit_self")]
    old = sched_idle_ms.idle_by_phase(got["aligned"])
    assert sum(parts) == pytest.approx(
        1e3 * old["seconds"]["admit"] / got["steps"])


def test_a_capture_from_before_pr37_reads_nothing_and_does_not_raise():
    if not os.path.exists(BEFORE_PR37):
        pytest.skip("no recorded capture in this checkout")
    ctx = ctx_of(BEFORE_PR37)
    assert xplane_join.join(ctx) is not None
    for what in WHATS.values():
        assert launch_pairs.read(ctx, what) is None
