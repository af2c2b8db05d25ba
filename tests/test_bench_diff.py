"""tools/bench_diff.py: the machine-checkable BENCH comparison.

Fabricated files through main() — exit 1 on regression, 0 within
tolerance, direction inference per key, --key overrides, --json —
plus the real-capture shape (tail-embedded metric lines, the
BENCH_rNN.json layout).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import bench_diff  # noqa: E402


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_load_metrics_plain_and_tail_shapes(tmp_path):
    plain = _write(tmp_path, "plain.json",
                   {"gpt_serving_tps": 100.0, "comment": "prose",
                    "ok": True})
    assert bench_diff.load_metrics(plain) == {"gpt_serving_tps": 100.0}
    tail = _write(tmp_path, "tail.json", {
        "n": 1, "rc": 0,
        "tail": ("noise line\n"
                 '{"metric": "mnist_eps_chip", "value": 10.0, '
                 '"extra": {"gpt_serving_tps": 5.0, '
                 '"suspect": false}}\n')})
    assert bench_diff.load_metrics(tail) == {
        "mnist_eps_chip": 10.0, "gpt_serving_tps": 5.0}
    empty = _write(tmp_path, "empty.json", {"comment": "nothing"})
    with pytest.raises(ValueError, match="no numeric"):
        bench_diff.load_metrics(empty)


def test_direction_inference():
    assert bench_diff.lower_is_better("gpt_serving_p95_ms")
    assert bench_diff.lower_is_better("serving_errors")
    assert bench_diff.lower_is_better("serving_int8_drift_rate")
    assert bench_diff.lower_is_better("serving_bytes_resident_peak")
    assert bench_diff.lower_is_better("wall_s")
    assert not bench_diff.lower_is_better("gpt_serving_tps")
    assert not bench_diff.lower_is_better("bert_base_mfu")
    assert not bench_diff.lower_is_better("serving_prefix_hit_rate")
    # *_per_s rates (the serving-row shape) are throughput: the bare
    # "_s" latency marker must NOT claim them — a throughput collapse
    # read as "improved" would invert the whole gate
    assert not bench_diff.lower_is_better("tokens_per_s")
    assert not bench_diff.lower_is_better("requests_per_s")


def test_per_s_throughput_collapse_is_a_regression(tmp_path, capsys):
    old = _write(tmp_path, "old.json", {"tokens_per_s": 100.0})
    new = _write(tmp_path, "new.json", {"tokens_per_s": 50.0})
    assert bench_diff.main([old, new]) == 1
    capsys.readouterr()
    assert bench_diff.main([new, old]) == 0
    capsys.readouterr()


def test_regression_flags_and_exit_codes(tmp_path, capsys):
    old = _write(tmp_path, "old.json",
                 {"gpt_serving_tps": 100.0, "gpt_serving_p95_ms": 50.0,
                  "gpt_serving_goodput_tps": 90.0})
    # tps -20% (regression), p95 +30% (regression), goodput +5% (ok)
    new = _write(tmp_path, "new.json",
                 {"gpt_serving_tps": 80.0, "gpt_serving_p95_ms": 65.0,
                  "gpt_serving_goodput_tps": 94.5})
    assert bench_diff.main([old, new]) == 1
    out = capsys.readouterr().out
    assert "2 regression(s)" in out
    # the improvement direction never trips: swap the files
    assert bench_diff.main([new, old]) == 0
    capsys.readouterr()
    # widened tolerance forgives both moves
    assert bench_diff.main([old, new, "--tolerance", "0.4"]) == 0
    capsys.readouterr()
    # per-key override: forgive tps, p95 still regresses
    rc = bench_diff.main([old, new, "--key", "gpt_serving_tps=0.5",
                          "--json"])
    assert rc == 1
    rec = json.loads(capsys.readouterr().out)
    rows = {r["key"]: r for r in rec["rows"]}
    assert rows["gpt_serving_tps"]["status"] == "ok"
    assert rows["gpt_serving_p95_ms"]["status"] == "regression"
    assert rec["ok"] is False


def test_missing_and_zero_keys_are_not_regressions(tmp_path, capsys):
    old = _write(tmp_path, "old.json",
                 {"a_tps": 10.0, "gone_tps": 5.0, "z_errors": 0.0})
    new = _write(tmp_path, "new.json",
                 {"a_tps": 10.0, "fresh_tps": 7.0, "z_errors": 2.0})
    assert bench_diff.main([old, new, "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    rows = {r["key"]: r for r in rec["rows"]}
    assert rows["gone_tps"]["status"] == "missing_new"
    assert rows["fresh_tps"]["status"] == "missing_old"
    # zero baseline: reported, skipped (0 -> 2 errors has no relative
    # scale; the serving-keys gate pins error counts at 0 elsewhere)
    assert rows["z_errors"]["status"] == "zero_baseline"
    assert rec["ok"] is True


def test_force_direction_overrides(tmp_path, capsys):
    old = _write(tmp_path, "old.json", {"weird_count": 10.0})
    new = _write(tmp_path, "new.json", {"weird_count": 5.0})
    # default higher-is-better: -50% = regression
    assert bench_diff.main([old, new]) == 1
    capsys.readouterr()
    assert bench_diff.main([old, new, "--lower", "weird_count"]) == 0
    capsys.readouterr()


def test_real_capture_round_trip():
    """A pair of driver-shaped captures (tests/fixtures: the round-4 and
    round-5 chip records' metric lines behind a log line the parser
    must skip) must load and compare clean — r05 did not regress on
    r04, that is why it landed."""
    fixtures = os.path.join(ROOT, "tests", "fixtures")
    rows = bench_diff.diff(
        bench_diff.load_metrics(
            os.path.join(fixtures, "bench_capture_old.json")),
        bench_diff.load_metrics(
            os.path.join(fixtures, "bench_capture_new.json")),
        tolerance=0.2)
    assert rows
    assert not [r for r in rows if r["status"] == "regression"]
