"""The reduction from a trace to busy time, operations and idle gaps: on
hand-made events, and on one small xplane recorded on the chip."""

import os

import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny_tpu.xplane.pb")


def test_names_lose_their_numbering():
    assert trace_reduce.op_name("%fusion.123") == "fusion"
    assert trace_reduce.op_name("%copy-done.4") == "copy-done"
    assert trace_reduce.op_name("while") == "while"
    assert trace_reduce.op_name("jit_step(1234567)") == "jit_step"
    assert trace_reduce.op_name("%convolution_convert_fusion.7.1") == \
        "convolution_convert_fusion"


def test_nested_operations_count_once_and_gaps_follow_their_operation():
    # a while [0, 10) holding two fusions, then idle [10, 12), then a copy
    events = [(0.0, 10.0, "while"), (1.0, 3.0, "fusion"),
              (5.0, 2.0, "fusion"), (12.0, 1.0, "copy"),
              (13.5, 0.5, "fusion")]
    r = trace_reduce.reduce_plane(events)
    assert r["busy_s"] == pytest.approx(11.5)
    assert r["window_s"] == pytest.approx(14.0)
    assert r["top_ops"] == pytest.approx(
        {"while": 10.0, "copy": 1.0, "fusion": 0.5})
    assert sum(r["top_ops"].values()) == pytest.approx(r["busy_s"])
    assert r["all_ops"]["fusion"] == {"seconds": pytest.approx(5.5),
                                      "calls": 3}
    assert r["idle_gaps"] == pytest.approx({"while": 2.0, "copy": 0.5})
    b = trace_reduce.breakdown({"top_ops": r["top_ops"],
                                "idle_gaps": r["idle_gaps"]}, "train")
    assert b["device_ops"][0] == ["_while", pytest.approx(10.0)]
    assert b["idle_gaps"][0] == ["train_after__while", pytest.approx(2.0)]


def test_a_trace_without_a_device_plane_reports_no_chip(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    r = trace_reduce.reduce(trace_reduce.find_xplane(str(tmp_path)))
    assert r["chips"] == 0 and r["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded TPU trace in this checkout")
def test_the_recorded_tpu_trace_reduces():
    r = trace_reduce.reduce(FIXTURE)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["top_ops"].values()) == pytest.approx(r["busy_s"])
    assert r["modules"], "no executed program found on the XLA Modules line"
    assert all(not k[-1].isdigit() or "." not in k for k in r["top_ops"])
