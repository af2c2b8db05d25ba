"""``laguna-serve-mixed`` rehearsed on the CPU at the configuration's tiny
sizes (float32: the configuration's ``why_float32``; window 9, a ring of
16, blocks of 16 and chunks of 32 inside prompts of 20-96 tokens): a run
comes out correct; the fp8 control comes out NOT correct; and each fault
``benchmark/planted_gqa.py`` plants comes out ``"correct": false``. Also
the manifest's view of the cell, as files and entries only, and the
readers that came with it."""

import argparse
import json
import os

import pytest

from benchmark import planted_gqa, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "laguna-serve-mixed"
# ``per_layer`` holds at most 128 metrics and the parent had 117: the 13
# quantities whose reader and arguments are dots3's are read under dots3's
# entries (the cell appended to their ``workloads``), the 8 that are new
# to this PR under their own
SHARED = ("sched_decode_step_ms", "sched_slot_occupancy_pct",
          "sched_prefill_share_pct", "sched_idle_wait_logits_ms",
          "sched_idle_sample_emit_ms", "sched_idle_admit_ms",
          "sched_idle_launch_ms", "sched_idle_unattributed_ms",
          "serve_completed_tokens_per_s", "http_overhead_ms_p50",
          "serve_device_idle_pct", "serve_hbm_peak_gib",
          "serve_prefill_chunk_ms")
ADDED = ("serve_gqa_attn_ms", "serve_gqa_attn_roofline_pct",
         "serve_window_attn_ms", "serve_window_attn_roofline_pct",
         "serve_chunk_attn_roofline_pct", "serve_moe_ms",
         "serve_moe_roofline_pct", "serve_window_cache_share_pct")


def _run(capsys, seed, main=bench_run.main, extra=()):
    rc = main([*extra, "--workload", CELL, "--seed", str(seed),
               "--seconds", "1.5", "--trace", "0", "--rehearse", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    return json.loads(out[-1]), out


def test_rehearsal_is_correct_and_counts_its_chunks(capsys):
    line, out = _run(capsys, 3_000_000_019)
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True and line["failed"] == 0
    c = line["counts"]
    assert c["finished"] > 0 and c["decode_steps"] > 0
    # prompts of 20-96 tokens in chunks of 32: one to three chunks each
    assert c["finished"] <= c["prefill_chunks"] <= 3 * (c["requests"] + 8)
    assert c["prefill_chunk_tokens"] >= 20 * c["finished"]
    assert c["moe_rows"] > 0
    assert sum(1 for ln in out if ln.startswith("compared ")) == 3


@pytest.mark.parametrize("seed", [2_200_000_000, 2_200_007_919])
def test_the_control_comes_out_not_correct(seed):
    ns = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0, trace=0,
                            rehearse=1)
    env = bench_run.Env(Manifest(ROOT), ns)
    try:
        compared = env.manifest.kind(env.traffic).control(env)
        assert bench_run.decide(env, compared) is False
        limits = env.pick(env.manifest.limits(CELL), "limits")
        for key in ("served_logit_gap_mean",
                    "served_logit_gap_request_max"):
            assert compared[key] > limits[key]
    finally:
        env.cleanup()


@pytest.mark.parametrize("fault", sorted(planted_gqa.FAULTS))
def test_a_planted_fault_comes_out_not_correct(capsys, fault):
    line, out = _run(capsys, 2_200_007_919, main=planted_gqa.main,
                     extra=("--fault", fault))
    assert line["correct"] is False, out[-6:]
    assert line["failed"] == 0


def test_the_new_cell_is_files_and_entries_only():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", "mixed-length-backlog", 1)
    assert len(m.doc["workloads"]) >= 9
    assert sum(1 for w in m.doc["workloads"] if w["chips"] == 4) == 1
    cfg = m.config(cell)
    entry = m.configs["laguna-s-2.1"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (9, 32, 12544)
    # the floors: two whole periods after the dense layer, 32 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - len(cfg["mlp_only_layers"]) == 2 * 4
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # every other number as the catalog has it (the guide's rule: a key
    # that differs and is not in `reduced` is refused before any run)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"Laguna-S-2.1"' in ln)
        assert entry["source"] == cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    assert (cfg["share"]["chips_a_layer"],
            cfg["share"]["pipeline_stages"]) == (8, 6)
    for key in ("router", "shared_expert", "qk_norm", "head_gate",
                "rotary_full", "rotary_sliding", "sliding_window",
                "window_ring", "kv_rows", "precision"):
        assert key in cfg["assumed"]
    t = m.traffic(cell)
    assert t["kind"] == "serve_state" and t["loop"] == "closed"
    assert (t["mix"]["clients"], t["mix"]["requests_per_client"],
            t["engine"]["slots"], t["engine"]["block_size"],
            t["engine"]["prompt_len"], t["engine"]["max_new_tokens"],
            t["engine"]["prefill_chunk"], t["ramp_s"],
            t["check_requests"], t["trace_seconds"]) == (
                48, 8, 24, 128, 15360, 1024, 1024, 30, 6, 3.0)
    assert "drain_cap_why" in t
    assert t["mix"]["prompt_tokens"] == {"median": 4096, "sigma": 0.8,
                                         "lo": 512, "hi": 15360}
    assert t["mix"]["output_tokens"] == {"median": 512, "sigma": 0.5,
                                         "lo": 128, "hi": 1024}
    ref = m.reference(cfg)
    assert {"FAMILY", "param_spec", "logits", "hidden", "head",
            "inverse_frequencies"} <= set(dir(ref))
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    names = {x["name"] for x in m.per_layer(CELL)}
    assert len(m.doc["per_layer"]) <= 128
    assert {n + ".laguna" for n in ADDED} | {
        n + ".dots3" for n in SHARED} | {
        "export_s", "serve_compile_s", "sched_idle_admit_launch_ms",
        "sched_idle_admit_read_ms", "sched_idle_admit_emit_ms",
        "sched_idle_admit_self_ms", "sched_pair_shift_ms",
        "sched_pair_slack_ms"} <= names
    # no cell the benchmark had reports a metric this PR added
    for w in m.doc["workloads"]:
        if w["name"] != CELL:
            assert not any(x["name"].endswith(".laguna")
                           for x in m.per_layer(w["name"]))
    # a new reader finds nothing in a trace that lacks its programs (the
    # parent's, every other cell's): it returns None and does not raise
    empty = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1,
                       "opcodes": {}, "all_ops": {}, "modules": {}},
             "peak": m.peak("TPU v5 lite"), "values": {},
             "xplane_path": os.path.join(ROOT, "benchmark", "tests",
                                         "fixtures", "sched_tpu.xplane.pb")}
    others = ({"specs": {"cache_state": {}, "cache_latent": {}}},
              {"specs": {"cache_latent": {}, "cache_index": {},
                         "cache_window": {}}})
    for x in m.per_layer(CELL):
        if x["name"].endswith(".laguna"):
            assert m.read_metric(x, dict(empty)) is None, x["name"]
        if "gqa" in x["name"] or "window" in x["name"] or "moe" in x["name"]:
            # nor in another per-request-state artifact's run
            for state in others:
                assert m.read_metric(x, dict(empty, state=state)) is None


def _ctx(m):
    cfg = m.config(m.cell(CELL))
    t = m.traffic(m.cell(CELL))
    pool = {"shape": [3, 3073, 128, 1024], "dtype": "bfloat16",
            "per": "block"}
    ring = {"shape": [6, 24, 512, 1024], "dtype": "bfloat16", "per": "slot"}
    state = {"specs": {"cache_k": pool, "cache_v": pool,
                       "cache_window_k": ring, "cache_window_v": ring},
             "ffns": ["dense"] + ["moe"] * 8, "layers": 9, "experts": 256,
             "experts_held": 32, "index_topk": 0, "window": 512,
             "moe_rows": {"prefill_chunk": {"pairs": 10240, "bound": 2560},
                          "decode": {"pairs": 240, "bound": 240}}}
    return {"state": state, "ref_cfg": cfg, "engine": t["engine"],
            "peak": m.peak("TPU v5 lite")}


def test_gqa_readers_attribute_operations_by_name_and_shape():
    """``readers/gqa_steps.py``: the kernels by their names, the two
    kinds of layer told apart by their head counts, the expert layers by
    the rows they run over (the whole pairs or the bound); a loop's body
    is not counted beside the loop; the shares of a roofline stay under
    100 %."""
    from benchmark import flops_gqa
    from benchmark.readers import (gqa_op_ms, gqa_roofline, gqa_steps,
                                   gqa_window_share, moe_roofline_gqa,
                                   prefill_share, state_op_ms)
    m = Manifest(ROOT)
    ctx = _ctx(m)
    chunk = [
        (0.000, 0.003, "%gqa_chunk_attn.1 = f32[48,1024,128] custom-call("
                       "s32[120] %t, s32[2] %at, bf16[48,1024,128] %q, "
                       "bf16[9219,128,1024] %k)"),
        (0.003, 0.004, "%fusion.8 = bf16[48,1024,128] fusion("
                       "f32[1024,48,128] %q)"),
        (0.004, 0.006, "%gqa_chunk_attn.2 = f32[72,1024,128] custom-call("
                       "s32[12] %t, s32[2] %at, bf16[72,1024,128] %q, "
                       "bf16[12,128,1024] %k)"),
        (0.006, 0.007, "%fusion.9 = bf16[1536,1024] fusion("
                       "bf16[512,1024] %ring, bf16[1024,1024] %k)"),
        (0.007, 0.012, "%conditional.3 = f32[1024,3072] conditional("
                       "s32[10240] %order, f32[1024,10] %w)"),
        (0.008, 0.009, "%ragged-dot-none.3 = f32[2560,1024] custom-call("),
        (0.012, 0.020, "%fusion.200 = f32[1024,12288] fusion(")]
    dec = [
        (0.030, 0.034, "%paged_gqa_attn.3 = f32[24,48,1024] custom-call("
                       "s32[24,128] %ft, s32[24] %pos, bf16[24,48,1024] %q)"),
        (0.034, 0.035, "%fusion.4 = f32[24,48,128] fusion("
                       "f32[24,48,8,128] %o)"),
        (0.035, 0.036, "%fusion.12 = f32[24,72,512] fusion("
                       "bf16[24,512,1024] %ring, bf16[24,72,1024] %q)"),
        (0.036, 0.037, "%fusion.13 = f32[24,72,1024] fusion("
                       "f32[24,72,512] %p)"),
        (0.037, 0.041, "%ragged-dot-none.9 = f32[240,1024] custom-call("),
        (0.041, 0.042, "%fusion.6 = bf16[240,3072] fusion(s32[240] %at)"),
        (0.042, 0.046, "%fusion.300 = f32[24,12544] fusion(")]
    rows, start = 1024, 4096
    contexts = start + 1 + sum(range(rows)) / rows      # mean context
    args = {"tokens": rows, "start": start,
            "context_rows": 3 * int(contexts * rows),
            "kv_bytes": (start + rows) * 3 * 4096,
            "window_bytes": rows * 512 * 6 * 4096}
    dargs = {"slots": 24, "expert_rows": 150,
             "context_rows": 3 * 24 * 6000, "kv_bytes": 24 * 6000 * 3 * 4096,
             "window_bytes": 24 * 512 * 6 * 4096}
    ctx["_state_steps"] = {
        "prefill_chunk": [{"args": args, "module": (0.0, 0.020),
                           "ops": chunk}],
        "decode": [{"args": dargs, "module": (0.030, 0.046), "ops": dec}],
        "modules_s": 0.036}
    by = gqa_steps.totals(ctx, "prefill_chunk")[0][1]
    assert by == pytest.approx({"attn": 0.004, "window": 0.003,
                                "moe": 0.005, "other": 0.008})
    by = gqa_steps.totals(ctx, "decode")[0][1]
    assert by == pytest.approx({"attn": 0.005, "window": 0.002,
                                "moe": 0.005, "other": 0.004})
    assert gqa_op_ms.read(ctx, "decode", "attn") == pytest.approx(5.0)
    assert gqa_op_ms.read(ctx, "decode", "window") == pytest.approx(2.0)
    assert gqa_op_ms.read(ctx, "decode", "moe") == pytest.approx(5.0)
    assert state_op_ms.read(ctx, "prefill_chunk", "program"
                            ) == pytest.approx(20.0)
    assert prefill_share.read(ctx) == pytest.approx(100 * 0.020 / 0.036)
    peak = ctx["peak"]
    want = 100 * flops_gqa.decode_attn_bytes(
        dargs["kv_bytes"], 24, 3, 48, 128) / peak["hbm_bytes_per_s"] / 0.005
    assert gqa_roofline.read(ctx, "attn") == pytest.approx(want)
    want = 100 * flops_gqa.window_attn_bytes(
        dargs["window_bytes"], 24, 6, 72, 128) / peak[
            "hbm_bytes_per_s"] / 0.002
    assert gqa_roofline.read(ctx, "window") == pytest.approx(want)
    want = 100 * (flops_gqa.attn_flops(args["context_rows"], 48, 128)
                  + flops_gqa.attn_flops(rows * 512 * 6, 72, 128)
                  ) / peak["bf16_flops"] / 0.007
    assert gqa_roofline.read(ctx, "chunk") == pytest.approx(want)
    for what in ("attn", "window", "chunk"):
        assert 0 < gqa_roofline.read(ctx, what) < 100
    assert 0 < moe_roofline_gqa.read(ctx) < 100
    assert gqa_window_share.read(ctx) == pytest.approx(
        100 * dargs["window_bytes"]
        / (dargs["window_bytes"] + dargs["kv_bytes"]))
    assert gqa_op_ms.read(dict(_ctx(m), _state_steps=None),
                          "decode", "moe") is None


def test_gqa_readers_on_a_recorded_capture():
    """One chunk program and one decode step of a traced run of the cell
    on the chip (PR 41, call 1; ``fixtures/gqa_steps_laguna.json``: each
    program's span arguments and its outermost operations' start, end and
    text, as ``readers/gqa_steps.sample`` keeps them): every computation
    finds its operations, the kernels are charged by their names and the
    two kinds of layer told apart, what no pattern claims is the
    projections' share, and no share of a roofline passes 100 %."""
    from benchmark.readers import (gqa_op_ms, gqa_roofline, gqa_steps,
                                   gqa_window_share, moe_roofline_gqa)
    m = Manifest(ROOT)
    ctx = _ctx(m)
    rec = json.load(open(os.path.join(ROOT, "benchmark", "tests", "fixtures",
                                      "gqa_steps_laguna.json")))
    progs = {k: [{"args": rec[k]["args"], "module": tuple(rec[k]["module"]),
                  "ops": [tuple(o) for o in rec[k]["ops"]]}]
             for k in ("prefill_chunk", "decode")}
    seconds = {k: v[0]["module"][1] - v[0]["module"][0]
               for k, v in progs.items()}
    ctx["_state_steps"] = dict(progs, modules_s=sum(seconds.values()))
    chunk = gqa_steps.totals(ctx, "prefill_chunk")[0][1]
    step = gqa_steps.totals(ctx, "decode")[0][1]
    for by, total in ((chunk, seconds["prefill_chunk"]),
                      (step, seconds["decode"])):
        assert all(by[k] > 0 for k in gqa_steps.ORDER), by
        assert 0.9 * total < sum(by.values()) <= total * 1.001
        assert by["other"] < 0.45 * total       # projections, norms, head
    for program, kernel, kind, calls in (
            ("decode", "paged_gqa_attn", "attn", 3),
            ("prefill_chunk", "gqa_chunk_attn", "attn", 3),
            ("prefill_chunk", "gqa_chunk_attn", "window", 6)):
        ops = [(b - a, text) for a, b, text in progs[program][0]["ops"]
               if kernel in text.split(" = ")[0]]
        heads = "[72," if kind == "window" else "[48,"
        mine = [t for t, text in ops
                if program == "decode" or heads in text.split(" custom-call")[0]]
        assert len(mine) == calls, (program, kind, len(mine))
        by = chunk if program == "prefill_chunk" else step
        assert sum(mine) <= by[kind] < 1.6 * sum(mine)
    assert gqa_op_ms.read(ctx, "decode", "attn") == pytest.approx(
        1e3 * step["attn"])
    for what in ("attn", "window", "chunk"):
        assert 0 < gqa_roofline.read(ctx, what) < 100, what
    assert 0 < moe_roofline_gqa.read(ctx) < 100
    args = progs["decode"][0]["args"]
    assert gqa_window_share.read(ctx) == pytest.approx(
        100 * float(args["window_bytes"])
        / (float(args["window_bytes"]) + float(args["kv_bytes"])))
