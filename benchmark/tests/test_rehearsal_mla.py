"""``axk1-serve-longctx`` rehearsed on the CPU at the configuration's tiny
sizes (float32; 5 layers of dense latent attention, 4 heads, YaRN's ramp
inside 8 rope values, 16 experts in 4 groups of which 2 are kept and 4
experts held, blocks of 16 and chunks of 32 inside prompts of 20-96
tokens): a run comes out correct; the fp8 control comes out NOT correct;
and each fault ``benchmark/planted_mla.py`` plants comes out ``"correct":
false``. Also the manifest's view of the cell, as files and entries only,
and the readers that came with it."""

import argparse
import json
import os

import pytest

from benchmark import planted_mla, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "axk1-serve-longctx"
# ``per_layer`` holds at most 128 metrics and the parent had 125: the 13
# quantities whose reader and arguments are dots3's are read under dots3's
# entries (the cell appended to their ``workloads``, as PR 41 did), the 3
# that are new to this PR under their own
SHARED = ("sched_decode_step_ms", "sched_slot_occupancy_pct",
          "sched_prefill_share_pct", "sched_idle_wait_logits_ms",
          "sched_idle_sample_emit_ms", "sched_idle_admit_ms",
          "sched_idle_launch_ms", "sched_idle_unattributed_ms",
          "serve_completed_tokens_per_s", "http_overhead_ms_p50",
          "serve_device_idle_pct", "serve_hbm_peak_gib",
          "serve_prefill_chunk_ms")
ADDED = ("serve_mla_step_roofline_pct", "serve_mla_chunk_roofline_pct",
         "sched_mla_share_pct")


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


@pytest.mark.parametrize("fault", sorted(planted_mla.FAULTS))
def test_a_planted_fault_comes_out_not_correct(capsys, fault):
    line, out = _run(capsys, 2_200_007_919, main=planted_mla.main,
                     extra=("--fault", fault))
    assert line["correct"] is False, out[-6:]
    assert line["failed"] == 0


def test_the_new_cell_is_files_and_entries_only():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "a.x-k1", "mla-longctx-backlog", 1)
    assert len(m.doc["workloads"]) >= 10
    assert sum(1 for w in m.doc["workloads"] if w["chips"] == 4) == 1
    cfg = m.config(cell)
    entry = m.configs["a.x-k1"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 12, 20480)
    # the floors: four layers after the dense one, at least 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # every other number as the catalog has it (the guide's rule: a key
    # that differs and is not in `reduced` is refused before any run)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"name": "A.X-K1"' in ln)
        assert entry["source"] == cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    # every published width stands
    for key, value in (("hidden_size", 7168), ("num_attention_heads", 64),
                       ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 128), ("q_lora_rank", 1536),
                       ("kv_lora_rank", 512), ("intermediate_size", 18432),
                       ("moe_intermediate_size", 2048),
                       ("num_experts_per_tok", 8), ("n_group", 8),
                       ("topk_group", 4), ("routed_scaling_factor", 2.5)):
        assert cfg[key] == value, key
    assert cfg["rope_scaling"]["factor"] == 32
    assert cfg["share"]["chips_a_layer"] == 16
    for key in ("router", "router_bias", "rotary", "yarn", "latent_rows",
                "attention_forms", "seq_aux_ep_size", "precision",
                "sampling", "prompt_ids"):
        assert key in cfg["assumed"]
    assert cfg["model_cfg"] == {"experts_held": 12, "first_expert": 0,
                                "vocab_held": 20480, "first_vocab": 0}
    t = m.traffic(cell)
    assert t["kind"] == "serve_state" and t["loop"] == "closed"
    assert (t["mix"]["clients"], t["mix"]["requests_per_client"],
            t["engine"]["slots"], t["engine"]["block_size"],
            t["engine"]["prompt_len"], t["engine"]["max_new_tokens"],
            t["engine"]["prefill_chunk"], t["ramp_s"],
            t["check_requests"], t["trace_seconds"]) == (
                48, 6, 24, 128, 28672, 1024, 1024, 30, 6, 3.0)
    # 232 blocks a slot: whole grid steps of the step kernel's 8
    assert (28672 + 1024) // 128 % 8 == 0
    assert "drain_cap_why" in t and t["drain_cap_s"] >= 150
    assert t["mix"]["prompt_tokens"] == {"median": 8192, "sigma": 0.7,
                                         "lo": 2048, "hi": 28672}
    assert t["mix"]["output_tokens"] == {"median": 512, "sigma": 0.5,
                                         "lo": 128, "hi": 1024}
    ref = m.reference(cfg)
    assert {"FAMILY", "param_spec", "logits", "hidden", "head",
            "inverse_frequencies", "pick"} <= set(dir(ref))
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    names = {x["name"] for x in m.per_layer(CELL)}
    assert len(m.doc["per_layer"]) == 128
    assert names == {n + ".axk1" for n in ADDED} | {
        n + ".dots3" for n in SHARED} | {
        "export_s", "serve_compile_s", "sched_idle_admit_launch_ms",
        "sched_idle_admit_read_ms", "sched_idle_admit_emit_ms",
        "sched_idle_admit_self_ms", "sched_pair_shift_ms",
        "sched_pair_slack_ms"}
    # no cell the benchmark had reports a metric this PR added
    for w in m.doc["workloads"]:
        if w["name"] != CELL:
            assert not any(x["name"].endswith(".axk1")
                           for x in m.per_layer(w["name"]))
    # a new reader finds nothing in a trace that lacks its programs (the
    # parent's, every other cell's): it returns None and does not raise
    empty = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1,
                       "opcodes": {}, "all_ops": {}, "modules": {}},
             "peak": m.peak("TPU v5 lite"), "values": {},
             "xplane_path": os.path.join(ROOT, "benchmark", "tests",
                                         "fixtures", "sched_tpu.xplane.pb")}
    others = (None,
              {"specs": {"cache_state": {}, "cache_latent": {}},
               "mixers": ["kda", "kda", "kda", "mla"]},
              {"specs": {"cache_latent": {}, "cache_index": {},
                         "cache_window": {}},
               "mixers": ["mla_sparse", "mla_window"]},
              {"specs": {"cache_k": {}, "cache_window_k": {}},
               "mixers": ["gqa_full", "gqa_window"]},
              {"specs": {"cache_latent": {}}})      # a parent's export.json
    for x in m.per_layer(CELL):
        if x["name"].endswith(".axk1"):
            for state in others:
                assert m.read_metric(x, dict(empty, state=state)) is None


def _ctx(m):
    cfg = m.config(m.cell(CELL))
    t = m.traffic(m.cell(CELL))
    pool = {"shape": [5, 5569, 128, 640], "dtype": "bfloat16",
            "per": "block"}
    state = {"specs": {"cache_latent": pool},
             "mixers": ["mla_dense"] * 5, "ffns": ["dense"] + ["moe"] * 4,
             "layers": 5, "experts": 192, "experts_held": 12,
             "index_topk": 0, "window": 0, "expert_groups": 8,
             "moe_rows": {"prefill_chunk": {"pairs": 8192, "bound": 1024},
                          "decode": {"pairs": 192, "bound": 192}}}
    return {"state": state, "ref_cfg": cfg, "engine": t["engine"],
            "peak": m.peak("TPU v5 lite")}


def test_required_counts_are_the_cheaper_form():
    """``flops_mla_dense``: a chunk of 1,024 queries over one context
    expands (640 FLOPs a pair a head + an expansion a context row), one
    query a slot absorbs (2,176 a pair a head + an absorption a query);
    the required count is the smaller, whatever ran."""
    from benchmark import flops_mla_dense as f
    dims = (64, 512, 128, 64, 128)
    pairs, keys, queries = 1024 * 8192.0, 8192.0, 1024.0
    ex = f.expanded_flops(pairs, keys, *dims)
    ab = f.absorbed_flops(pairs, queries, *dims)
    assert ex == 2 * 64 * (pairs * 320 + keys * 512 * 256)
    assert ab == 2 * 64 * (pairs * 1088 + queries * 512 * 256)
    assert f.required_flops(pairs, keys, queries, *dims) == ex < ab
    # one query a slot over its own context: absorbed is the cheaper
    pairs = keys = 24 * 10000.0
    assert f.required_flops(pairs, keys, 24.0, *dims) == f.absorbed_flops(
        pairs, 24.0, *dims) < f.expanded_flops(pairs, keys, *dims)
    assert f.required_bytes(1e9, 24.0, 64, 128, 64, 128) == 1e9 + 24 * 64 * (
        192 * 2 + 128 * 4)


def test_mla_readers_read_the_kernels_by_name():
    """``readers/mla_dense_steps.py``: the two attention forms by their
    kernels' names, the expert layers by ``ragged-dot``, the
    ``conditional`` of a bounded layer and the rows they run over; a
    loop's body is not counted beside the loop; the shares of a roofline
    stay under 100 % and are the hand computation."""
    from benchmark import flops, flops_mla_dense
    from benchmark.readers import (mla_dense_roofline, mla_dense_share,
                                   mla_dense_steps, prefill_share,
                                   state_op_ms)
    m = Manifest(ROOT)
    ctx = _ctx(m)
    chunk = [
        (0.000, 0.002, "%fusion.1 = bf16[64,1024,192] fusion("
                       "f32[1024,64,192] %q)"),
        (0.002, 0.032, "%mla_chunk_attn.1 = f32[64,1024,128] custom-call("
                       "s32[224] %t, s32[2] %at, bf16[64,1024,192] %q, "
                       "bf16[64,512,256] %w, bf16[27845,128,640] %pool)"),
        (0.032, 0.033, "%copy.3 = f32[1024,64,128] copy(f32[64,1024,128])"),
        (0.033, 0.038, "%conditional.3 = f32[1024,7168] conditional("
                       "s32[8192] %order, f32[1024,8] %w)"),
        (0.034, 0.035, "%ragged-dot-none.3 = f32[1024,2048] custom-call("),
        (0.038, 0.046, "%fusion.200 = f32[1024,18432] fusion(")]
    dec = [
        (0.050, 0.051, "%fusion.4 = bf16[24,64,640] fusion("
                       "f32[24,64,128] %q, bf16[512,64,128] %w)"),
        (0.051, 0.056, "%paged_latent_attn.3 = f32[24,64,512] custom-call("
                       "s32[24,232] %bt, s32[24] %last, bf16[24,64,640] %q)"),
        (0.056, 0.060, "%ragged-dot-none.9 = f32[192,2048] custom-call("),
        (0.060, 0.061, "%fusion.6 = bf16[192,7168] fusion(s32[192] %at)"),
        (0.061, 0.065, "%fusion.300 = f32[24,20480] fusion(")]
    rows, start = 1024, 7168
    args = {"tokens": rows, "start": start,
            "context_rows": 5 * sum(range(start + 1, start + rows + 1)),
            "kv_bytes": (start + rows) * 6400}
    dargs = {"slots": 24, "expert_rows": 30, "routed_rows": 31,
             "context_rows": 5 * 24 * 10000, "kv_bytes": 24 * 10000 * 6400}
    ctx["_state_steps"] = {
        "prefill_chunk": [{"args": args, "module": (0.0, 0.046),
                           "ops": chunk}],
        "decode": [{"args": dargs, "module": (0.050, 0.065), "ops": dec}],
        "modules_s": 0.061}
    by = mla_dense_steps.totals(ctx, "prefill_chunk")[0][1]
    assert by == pytest.approx({"attn": 0.030, "moe": 0.005,
                                "other": 0.011})
    by = mla_dense_steps.totals(ctx, "decode")[0][1]
    assert by == pytest.approx({"attn": 0.005, "moe": 0.005,
                                "other": 0.005})
    assert state_op_ms.read(ctx, "prefill_chunk", "program"
                            ) == pytest.approx(46.0)
    assert prefill_share.read(ctx) == pytest.approx(100 * 0.046 / 0.061)
    peak = ctx["peak"]
    dims = (64, 512, 128, 64, 128)
    want = 100 * max(
        flops_mla_dense.required_flops(
            float(dargs["context_rows"]), float(dargs["context_rows"]),
            24.0 * 5, *dims) / peak["bf16_flops"],
        flops_mla_dense.required_bytes(
            float(dargs["kv_bytes"]), 24.0 * 5, 64, 128, 64, 128)
        / peak["hbm_bytes_per_s"]) / 0.005
    assert mla_dense_roofline.read(ctx, "step") == pytest.approx(want)
    want = 100 * flops_mla_dense.expanded_flops(
        float(args["context_rows"]), 5.0 * (start + rows), *dims) / peak[
            "bf16_flops"] / 0.030
    assert mla_dense_roofline.read(ctx, "chunk") == pytest.approx(want)
    for what in ("step", "chunk"):
        assert 0 < mla_dense_roofline.read(ctx, what) < 100
    assert mla_dense_share.read(ctx) == pytest.approx(
        100 * (0.030 + 0.005) / 0.061)
    assert flops.roofline_pct(1.0, 1.0, 1.0, 1.0, 1.0)[0] == 100.0
    # a capture without the programs: nothing to read
    gone = dict(_ctx(m), _state_steps=None)
    assert mla_dense_roofline.read(gone, "step") is None
    assert mla_dense_share.read(gone) is None
