"""``dots3-serve-longctx`` rehearsed on the CPU at the configuration's tiny
sizes (float32: the configuration's ``why_float32``; window 9, top-16,
blocks of 16 and chunks of 32 inside prompts of 20-96 tokens): a run comes
out correct; the fp8 control comes out NOT correct; and each fault
``benchmark/planted_dsa.py`` plants comes out ``"correct": false``. Also
the manifest's view of the cell, as files and entries only, and the
readers that came with it."""

import argparse
import json
import os

import pytest

from benchmark import planted_dsa, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "dots3-serve-longctx"


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


@pytest.mark.parametrize("fault", sorted(planted_dsa.FAULTS))
def test_a_planted_fault_comes_out_not_correct(capsys, fault):
    line, out = _run(capsys, 2_200_007_919, main=planted_dsa.main,
                     extra=("--fault", fault))
    assert line["correct"] is False, out[-6:]
    assert line["failed"] == 0


def test_the_new_cell_is_files_and_entries_only():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-prev", "sparse-longctx-backlog", 1)
    assert len(m.doc["workloads"]) >= 8
    assert sum(1 for w in m.doc["workloads"] if w["chips"] == 4) == 1
    cfg = m.config(cell)
    entry = m.configs["dots3-note-prev"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 46,
                                "n_routed_experts": 256,
                                "vocab_size": 152064}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 32, 19008)
    # every other number as the catalog has it (the guide's rule: a key
    # that differs and is not in `reduced` is refused before any run)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"dots3-note-prev"' in ln)
        assert entry["source"] == cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    assert cfg["share"]["chips_a_layer"] == 8
    for key in ("indexer", "indexer_layernorm", "indexer_rope",
                "index_key_dtype", "attention_gate", "mla_qkv_lora_rescale",
                "n_group", "sliding_window_size", "latent_rows",
                "not_built", "precision"):
        assert key in cfg["assumed"]
    t = m.traffic(cell)
    assert t["kind"] == "serve_state" and t["loop"] == "closed"
    assert (t["mix"]["clients"], t["mix"]["requests_per_client"],
            t["engine"]["slots"], t["engine"]["block_size"],
            t["engine"]["prompt_len"], t["engine"]["max_new_tokens"],
            t["engine"]["prefill_chunk"], t["ramp_s"],
            t["check_requests"], t["trace_seconds"]) == (
                48, 6, 24, 128, 32256, 512, 1024, 30, 6, 3.0)
    assert "drain_cap_why" in t
    assert t["mix"]["prompt_tokens"] == {"median": 8192, "sigma": 0.6,
                                         "lo": 4096, "hi": 32256}
    assert t["mix"]["output_tokens"] == {"median": 128, "sigma": 0.5,
                                         "lo": 32, "hi": 512}
    ref = m.reference(cfg)
    assert {"FAMILY", "param_spec", "logits", "hidden", "head",
            "selection"} <= set(dir(ref))
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    names = {x["name"] for x in m.per_layer(CELL)}
    assert names == {"export_s", "serve_compile_s"} | {
        n + ".dots3" for n in (
            "sched_decode_step_ms", "sched_slot_occupancy_pct",
            "serve_completed_tokens_per_s", "http_overhead_ms_p50",
            "serve_device_idle_pct", "serve_hbm_peak_gib",
            "sched_idle_wait_logits_ms", "sched_idle_sample_emit_ms",
            "sched_idle_admit_ms", "sched_idle_launch_ms",
            "sched_idle_unattributed_ms", "serve_prefill_chunk_ms",
            "sched_prefill_share_pct", "serve_moe_ms",
            "serve_moe_roofline_pct", "serve_dsa_index_ms",
            "serve_dsa_index_roofline_pct", "serve_dsa_attn_ms",
            "serve_dsa_attn_roofline_pct",
            "serve_window_attn_roofline_pct", "serve_dsa_kept_pct",
            "sched_dsa_share_pct")}
    # no cell the benchmark had reports a metric this PR added
    for w in m.doc["workloads"]:
        if w["name"] != CELL:
            assert not any(x["name"].endswith(".dots3")
                           for x in m.per_layer(w["name"]))
    # a new reader finds nothing in a trace that lacks its programs (the
    # parent's, every other cell's): it returns None and does not raise
    empty = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1,
                       "opcodes": {}, "all_ops": {}, "modules": {}},
             "peak": m.peak("TPU v5 lite"), "values": {},
             "xplane_path": os.path.join(ROOT, "benchmark", "tests",
                                         "fixtures", "sched_tpu.xplane.pb")}
    kimi_state = {"specs": {"cache_state": {}, "cache_latent": {}}}
    for x in m.per_layer(CELL):
        if x["name"].endswith(".dots3") and x["name"] not in (
                "serve_device_idle_pct.dots3", "serve_hbm_peak_gib.dots3"):
            assert m.read_metric(x, dict(empty)) is None, x["name"]
        if "dsa" in x["name"] or "moe" in x["name"]:
            # nor in another per-request-state artifact's run
            assert m.read_metric(x, dict(empty, state=kimi_state)) is None


def _ctx(m):
    cfg = m.config(m.cell(CELL))
    t = m.traffic(m.cell(CELL))
    state = {"specs": {
        "cache_latent": {"shape": [2, 6145, 128, 640], "dtype": "bfloat16",
                         "per": "block"},
        "cache_index": {"shape": [2, 6145, 128, 128], "dtype": "bfloat16",
                        "per": "block"},
        "cache_window": {"shape": [3, 24, 528, 1152], "dtype": "bfloat16",
                         "per": "slot"}},
        "ffns": ["dense", "moe", "moe", "moe", "moe"], "experts": 256,
        "experts_held": 32, "index_topk": 2048, "window": 513}
    return {"state": state, "ref_cfg": cfg, "engine": t["engine"],
            "peak": m.peak("TPU v5 lite")}


def test_dsa_readers_attribute_operations_by_shape():
    """``readers/dsa_steps.py``: an operation belongs to the first
    computation whose tensors its text names (as the programs compiled
    for a described v5e name them); a loop's body is not counted beside
    the loop; the shares of a roofline stay under 100 %."""
    from benchmark import flops_dsa
    from benchmark.readers import (dsa_kept, dsa_op_ms, dsa_roofline,
                                   dsa_share, dsa_steps, moe_roofline_dsa,
                                   prefill_share, state_op_ms)
    m = Manifest(ROOT)
    ctx = _ctx(m)
    chunk = [
        (0.000, 0.004, "%while.13 = (s32[], f32[1024,32768]{1,0}, s32[], "
                       "s32[256], bf16[12290,128,128], bf16[1024,64,128], "
                       "f32[1024,64]) while("),
        (0.001, 0.002, "%fusion.3 = f32[1024,512] fusion(f32[65536,512] "
                       "%dot)"),
        (0.004, 0.009, "%while.15 = (s32[], u32[1024,1], u32[1024,32768], "
                       "s32[]) while("),
        (0.009, 0.010, "%fusion.77 = pred[1024,32768]{0,1} fusion("
                       "u32[1024,32768] %u, u32[1024,1] %tau)"),
        (0.010, 0.030, "%while.11 = (s32[], f32[128,1024], f32[128,1024], "
                       "f32[128,1024,128], s32[], s32[256], "
                       "bf16[12290,128,640], pred[1024,32768]) while("),
        (0.012, 0.013, "%fusion.5 = f32[128,1024,512] fusion("),
        (0.030, 0.032, "%fusion.91 = f32[64,512,1024] fusion("
                       "bf16[1536,64,320] %kv)"),
        (0.032, 0.040, "%ragged-dot-none.3 = f32[8192,1536] custom-call("),
        (0.040, 0.041, "%fusion.6 = bf16[8192,5120] fusion(bf16[1024,5120]"
                       " %x, s32[8192] %picks)"),
        (0.041, 0.050, "%fusion.200 = f32[1024,13824] fusion(")]
    dec = [
        (0.060, 0.061, "%fusion.45 = f32[24,32768] fusion("
                       "bf16[24,32768,128] %keys, f32[24,64] %w)"),
        (0.061, 0.063, "%sort = (f32[24,32768], s32[24,32768]) sort("),
        (0.063, 0.064, "%fusion.9 = bf16[49152,640] fusion(s32[49152] %at)"),
        (0.064, 0.065, "%convolution.40 = f32[24,128,2048] convolution("),
        (0.065, 0.066, "%fusion.12 = f32[24,64,528] fusion("
                       "bf16[24,528,1152] %ring)"),
        (0.066, 0.074, "%ragged-dot-none.9 = f32[192,5120] custom-call("),
        (0.074, 0.080, "%fusion.300 = f32[24,19008] fusion(")]
    rows = 1024
    contexts = 8192 + 1 + sum(range(rows)) / rows      # mean context
    args = {"tokens": rows, "start": 8192,
            "context_rows": 2 * int(contexts * rows),
            "selected_rows": 2 * rows * 2048,
            "index_bytes": (8192 + rows) * 2 * 256,
            "kv_bytes": 2 * rows * 2048 * 1280,
            "window_bytes": rows * 513 * 3 * 2304}
    dargs = {"slots": 24, "expert_rows": 100, "context_rows": 2 * 24 * 9000,
             "selected_rows": 2 * 24 * 2048}
    ctx["_state_steps"] = {
        "prefill_chunk": [{"args": args, "module": (0.0, 0.050),
                           "ops": chunk}],
        "decode": [{"args": dargs, "module": (0.060, 0.080), "ops": dec}],
        "modules_s": 0.070}
    by = dsa_steps.totals(ctx, "prefill_chunk")[0][1]
    assert by == pytest.approx({"index": 0.010, "attn": 0.020,
                                "window": 0.002, "moe": 0.009,
                                "other": 0.009})
    by = dsa_steps.totals(ctx, "decode")[0][1]
    assert by == pytest.approx({"index": 0.003, "attn": 0.002,
                                "window": 0.001, "moe": 0.008,
                                "other": 0.006})
    assert dsa_op_ms.read(ctx, "prefill_chunk", "index"
                          ) == pytest.approx(10.0)
    assert dsa_op_ms.read(ctx, "prefill_chunk", "attn"
                          ) == pytest.approx(20.0)
    assert dsa_op_ms.read(ctx, "decode", "moe") == pytest.approx(8.0)
    assert state_op_ms.read(ctx, "prefill_chunk", "program"
                            ) == pytest.approx(50.0)
    assert prefill_share.read(ctx) == pytest.approx(100 * 0.050 / 0.070)
    peak = ctx["peak"]
    want = 100 * flops_dsa.index_flops(args["context_rows"], 64, 128) \
        / peak["bf16_flops"] / 0.010
    assert dsa_roofline.read(ctx, "index") == pytest.approx(want)
    want = 100 * flops_dsa.selected_attn_flops(
        args["selected_rows"], 128, 192, 128) / peak["bf16_flops"] / 0.020
    assert dsa_roofline.read(ctx, "attn") == pytest.approx(want)
    for what in ("index", "attn", "window"):
        assert 0 < dsa_roofline.read(ctx, what) < 100
    assert 0 < moe_roofline_dsa.read(ctx) < 100
    assert dsa_kept.read(ctx) == pytest.approx(
        100 * (args["selected_rows"] + dargs["selected_rows"])
        / (args["context_rows"] + dargs["context_rows"]))
    assert dsa_share.read(ctx) == pytest.approx(
        100 * (0.010 + 0.020 + 0.003 + 0.002) / 0.070)
    assert dsa_op_ms.read(dict(_ctx(m), _state_steps=None),
                          "decode", "moe") is None


def test_dsa_readers_on_a_recorded_capture():
    """One chunk program and one decode step of a traced run of the cell
    on the chip (PR 35; ``fixtures/dsa_steps_dots3.json``: each
    program's span arguments and its outermost operations' start, end and
    text, as ``readers/dsa_steps.sample`` keeps them): every computation
    finds its operations, the kernel is charged by its name, what no
    pattern claims is the projections' share, and no share of a roofline
    passes 100 %."""
    from benchmark.readers import (dsa_kept, dsa_op_ms, dsa_roofline,
                                   dsa_share, dsa_steps, moe_roofline_dsa)
    m = Manifest(ROOT)
    ctx = _ctx(m)
    rec = json.load(open(os.path.join(ROOT, "benchmark", "tests", "fixtures",
                                      "dsa_steps_dots3.json")))
    progs = {k: [{"args": rec[k]["args"], "module": tuple(rec[k]["module"]),
                  "ops": [tuple(o) for o in rec[k]["ops"]]}]
             for k in ("prefill_chunk", "decode")}
    seconds = {k: v[0]["module"][1] - v[0]["module"][0]
               for k, v in progs.items()}
    ctx["_state_steps"] = dict(progs, modules_s=sum(seconds.values()))
    chunk = dsa_steps.totals(ctx, "prefill_chunk")[0][1]
    step = dsa_steps.totals(ctx, "decode")[0][1]
    for by, total in ((chunk, seconds["prefill_chunk"]),
                      (step, seconds["decode"])):
        assert all(by[k] > 0 for k in dsa_steps.ORDER), by
        assert 0.9 * total < sum(by.values()) <= total * 1.001
        assert by["other"] < 0.45 * total       # projections, norms, head
    kernel = sum(b - a for a, b, text in progs["prefill_chunk"][0]["ops"]
                 if "dsa_selected_attn" in text.split(" = ")[0])
    assert 0 < kernel <= chunk["attn"] < 1.2 * kernel
    assert dsa_op_ms.read(ctx, "prefill_chunk", "attn") == pytest.approx(
        1e3 * chunk["attn"])
    for what in ("index", "attn", "window"):
        assert 0 < dsa_roofline.read(ctx, what) < 100, what
    assert 0 < moe_roofline_dsa.read(ctx) < 100
    args = [progs[k][0]["args"] for k in ("prefill_chunk", "decode")]
    assert dsa_kept.read(ctx) == pytest.approx(
        100 * sum(a["selected_rows"] for a in args)
        / sum(a["context_rows"] for a in args))
    assert dsa_share.read(ctx) == pytest.approx(
        100 * (chunk["index"] + chunk["attn"] + step["index"] + step["attn"])
        / sum(seconds.values()))
