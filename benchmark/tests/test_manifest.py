"""The harness is driven by data: a cell, a configuration, a traffic mix
and a per-layer metric are each added as NEW files plus entries in
``BENCHMARK.json``; no file that is there is edited. Also holds
``BENCHMARK.json`` to the limits of the builder's contract."""

import json
import os
import re
import shutil

import pytest

from benchmark.manifest import ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "records",
                                                  "tests"))
    return tmp_path


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hash(fh.read())
    return out


def test_a_cell_config_traffic_and_metric_are_added_as_files_only(copy):
    before = _digest(copy / "benchmark")
    bench = copy / "benchmark"
    # a configuration: its file of sizes, its plain reference beside it
    cfg = json.loads((bench / "configs" / "gpt2-small.json").read_text())
    cfg["n_layer"] = 24
    (bench / "configs" / "new-config.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "reference" / "gpt2-small.py",
                bench / "reference" / "new-config.py")
    # a traffic mix: a data file one general generator reads
    mix = json.loads((bench / "traffic" / "chat-poisson.json").read_text())
    mix["mix"]["rate_rps"] = 3.0
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    # a per-layer metric: a reader of its own, found by name
    (bench / "metrics" / "new_metric.json").write_text(
        json.dumps({"reader": "new_reader", "args": {"scale": 2.0}}))
    (bench / "readers" / "new_reader.py").write_text(
        "def read(ctx, scale):\n"
        "    return None if 'x' not in ctx else ctx['x'] * scale\n")
    (bench / "limits" / "new-cell.json").write_text(
        json.dumps({"limits": {"served_logit_gap": 0.1}}))
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "new-config", "source": "somewhere",
                           "file": "benchmark/configs/new-config.json",
                           "reduced": [], "why": "deeper"})
    doc["workloads"].append({"name": "new-cell", "config": "new-config",
                             "traffic": "new-mix", "chips": 1, "why": "w"})
    for m in doc["end_to_end"]:
        if m["name"].startswith("req_latency"):
            m["workloads"].append("new-cell")
    doc["per_layer"].append({"name": "new_metric", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "scheduler",
                             "moves": "req_latency_p50_ms",
                             "workloads": ["new-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))

    m = Manifest(str(copy))
    cell = m.cell("new-cell")
    assert m.config(cell)["n_layer"] == 24
    assert m.traffic(cell)["mix"]["rate_rps"] == 3.0
    assert m.kind(m.traffic(cell)).__name__.endswith("kinds_serve_py")
    assert hasattr(m.reference(m.config(cell)), "logits")
    assert m.limits("new-cell")["limits"]["served_logit_gap"] == 0.1
    names = [x["name"] for x in m.per_layer("new-cell")]
    assert "new_metric" in names and "train_mfu_pct" not in names
    metric = next(x for x in m.per_layer("new-cell")
                  if x["name"] == "new_metric")
    assert m.read_metric(metric, {"x": 21.0}) == 42.0
    # a reader that finds nothing to read returns nothing
    assert m.read_metric(metric, {}) is None
    assert {x["name"] for x in m.end_to_end("new-cell")} == {
        "req_latency_p50_ms", "req_latency_p95_ms", "setup_s"}
    # no file that was there has changed
    after = _digest(copy / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_are_errors(copy):
    m = Manifest(str(copy))
    with pytest.raises(KeyError):
        m.cell("no-such-cell")
    with pytest.raises(KeyError):
        m.peak("TPU v99")
    assert m.peak("TPU v5 lite")["bf16_flops"] == 197e12


def test_benchmark_json_keeps_the_contract():
    m = Manifest(ROOT)
    doc = m.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmark"]
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    e2e = {x["name"]: x for x in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in doc["workloads"]}
    assert len(cells) == len(doc["workloads"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in m.configs
        m.traffic(w), m.config(w), m.limits(w["name"])
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and c["file"].startswith(
            "benchmark/")
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|intermediate|n_embd"
                                 r"|n_inner|head)", key)
    for x in doc["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    seen = set(e2e)
    for x in doc["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["name"] not in seen
        seen.add(x["name"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert x["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", x["name"] + ".json"))
        # every cell that reports the metric reports what it moves
        for cell in x.get("workloads", cells):
            assert cell in cells
            assert x["moves"] in {y["name"] for y in m.end_to_end(cell)}
    for cell in cells:
        assert len(m.end_to_end(cell)) >= 2 and m.per_layer(cell)
