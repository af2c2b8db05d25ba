"""``kimi-serve-backlog`` rehearsed on the CPU at the configuration's tiny
sizes (float32: the configuration's ``why_float32``): a run comes out
correct; the fp8 control comes out NOT correct; and the two faults
``benchmark/planted_state.py`` plants come out ``"correct": false``: a
recurrent state not carried across one chunk boundary, and a reused
slot's state not zeroed. Also the manifest's view of the cell, as files
and entries only, and the readers that came with it."""

import argparse
import json
import os

import pytest

from benchmark import planted_state, run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "kimi-serve-backlog"


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
    # prompts of 4-96 tokens in chunks of 32: one to three chunks each
    assert c["finished"] <= c["prefill_chunks"] <= 3 * (c["requests"] + 8)
    assert c["prefill_chunk_tokens"] >= 4 * c["finished"]
    assert c["moe_rows"] > 0
    # the widest logit gap is read beside the mean, not compared
    assert sum(1 for ln in out if ln.startswith("compared ")) == 3
    assert sum(1 for ln in out if ln.startswith(
        "read served_logit_gap: widest ")) == 1


@pytest.mark.parametrize("seed", [2_200_000_000, 2_200_007_919,
                                  2_200_015_838])
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


@pytest.mark.parametrize("fault", sorted(planted_state.FAULTS))
def test_a_planted_fault_comes_out_not_correct(capsys, fault):
    line, out = _run(capsys, 2_200_007_919, main=planted_state.main,
                     extra=("--fault", fault))
    assert line["correct"] is False, out[-6:]
    assert line["failed"] == 0


def test_the_new_cell_is_files_and_entries_only():
    m = Manifest(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "longctx-backlog", 1)
    assert len(m.doc["workloads"]) >= 7
    assert sum(1 for w in m.doc["workloads"] if w["chips"] == 4) == 1
    cfg = m.config(cell)
    entry = m.configs["kimi-linear-48b-a3b"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_token"],
            cfg["num_attention_heads"], cfg["num_shared_experts"],
            cfg["routed_scaling_factor"],
            cfg["linear_attn_config"]["head_dim"],
            cfg["linear_attn_config"]["num_heads"]) == (
                2304, 9216, 1024, 512, 128, 64, 128, 8, 32, 1, 2.446, 128,
                32)
    assert cfg["linear_attn_config"]["full_attn_layers"][-1] == 27
    for key in ("kda_gate_rank", "a_log", "dt_bias", "conv_bias",
                "router_bias", "latent_row", "param_dtype", "sampling"):
        assert key in cfg["assumed"]
    t = m.traffic(cell)
    assert t["kind"] == "serve_state"
    assert (t["mix"]["clients"], t["mix"]["requests_per_client"],
            t["engine"]["slots"], t["engine"]["block_size"],
            t["engine"]["prompt_len"], t["engine"]["max_new_tokens"],
            t["engine"]["prefill_chunk"], t["ramp_s"], t["drain_cap_s"],
            t["check_requests"], t["trace_seconds"]) == (
                256, 8, 128, 128, 16384, 1024, 1024, 30, 100, 6, 3.0)
    assert "drain_cap_why" in t         # the issue's 30 cut 97 requests
    assert t["mix"]["prompt_tokens"] == {"median": 2048, "sigma": 0.8,
                                         "lo": 256, "hi": 16384}
    assert t["mix"]["output_tokens"] == {"median": 384, "sigma": 0.5,
                                         "lo": 128, "hi": 1024}
    assert hasattr(m.kind(t), "run") and hasattr(m.kind(t), "control")
    ref = m.reference(cfg)
    assert {"FAMILY", "param_spec", "logits", "hidden", "head"} <= set(
        dir(ref))
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    names = {x["name"] for x in m.per_layer(CELL)}
    assert names == {"export_s", "serve_compile_s"} | {
        n + ".kimi" for n in (
            "sched_decode_step_ms", "sched_slot_occupancy_pct",
            "serve_completed_tokens_per_s", "http_overhead_ms_p50",
            "serve_device_idle_pct", "serve_hbm_peak_gib",
            "sched_idle_wait_logits_ms", "sched_idle_sample_emit_ms",
            "sched_idle_admit_ms", "sched_idle_launch_ms",
            "sched_idle_unattributed_ms", "serve_kda_step_ms",
            "serve_kda_step_roofline_pct", "serve_kda_chunk_roofline_pct",
            "serve_mla_attn_roofline_pct", "serve_moe_ms",
            "serve_moe_roofline_pct", "serve_prefill_chunk_ms",
            "sched_prefill_share_pct")}
    # a new reader finds nothing in a trace that lacks its programs (the
    # parent's, every other cell's): it returns None and does not raise
    empty = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1,
                       "opcodes": {}, "all_ops": {}, "modules": {}},
             "peak": m.peak("TPU v5 lite"), "values": {},
             "xplane_path": os.path.join(ROOT, "benchmark", "tests",
                                         "fixtures", "sched_tpu.xplane.pb")}
    for x in m.per_layer(CELL):
        if x["name"].endswith(".kimi") and x["name"] not in (
                "serve_device_idle_pct.kimi", "serve_hbm_peak_gib.kimi"):
            assert m.read_metric(x, dict(empty)) is None, x["name"]


def test_state_step_readers_attribute_operations_by_shape():
    """``readers/state_steps.py``: an operation belongs to the computation
    whose tensors its text names; a loop's body is not counted beside the
    loop."""
    from benchmark import flops_kda, flops_mla
    from benchmark.readers import (kda_chunk_roofline, kda_step_roofline,
                                   mla_attn_roofline, moe_roofline_state,
                                   prefill_share, state_op_ms, state_steps)
    m = Manifest(ROOT)
    cfg = m.config(m.cell(CELL))
    state = {"specs": {"cache_state": {"shape": [4, 128, 32, 128, 128],
                                       "dtype": "float32", "per": "slot"},
                       "cache_conv": {"shape": [4, 128, 36864],
                                      "dtype": "float32", "per": "slot"},
                       "cache_latent": {"shape": [1, 17409, 128, 640],
                                        "dtype": "bfloat16",
                                        "per": "block"}},
             "ffns": ["dense", "moe", "moe", "moe", "moe"], "experts": 256,
             "experts_held": 128}
    dec = [
        (0.000, 0.004, "%f.1 = f32[4,128,32,128,128]{4,3,2,1,0} fusion("
                       "f32[4,128,32,128,128] %p)"),
        (0.004, 0.005, "%paged_latent_attn = f32[128,32,512] custom-call("),
        (0.005, 0.017, "%ragged-dot.3 = f32[1024,1024] ragged-dot("),
        (0.017, 0.018, "%fusion.9 = bf16[128,2304] fusion("),
        (0.018, 0.019, "%fusion.6 = bf16[1024,2304] fusion(bf16[128,2304] "
                       "%x, s32[1024] %picks)")]
    chunk = [
        (0.020, 0.030, "%while.2 = (s32[], f32[32,128,128]) while("),
        (0.021, 0.022, "%f.7 = f32[32,64,128] fusion(f32[32,128,128] %s)"),
        (0.030, 0.031, "%ts = f32[16,32,64,256] triangular-solve("),
        (0.031, 0.040, "%ragged-dot.1 = f32[8192,1024] ragged-dot(")]
    found = {
        # every recurrent byte of the live slots, the conv tails too
        "decode": [{"args": {"state_bytes": 2 * 128 * 4 * 4 * (
                                 32 * 128 * 128 + 36864),
                             "kv_bytes": 128 * 3000 * 1280,
                             "expert_rows": 500, "slots": 128},
                    "module": (0.0, 0.019), "ops": dec}],
        "prefill_chunk": [{"args": {"tokens": 1024, "start": 0},
                           "module": (0.020, 0.040), "ops": chunk}],
        "modules_s": 0.039}
    ctx = {"_state_steps": found, "state": state, "ref_cfg": cfg,
           "engine": {"prefill_chunk": 1024, "slots": 128},
           "peak": m.peak("TPU v5 lite")}
    pat = state_steps.patterns(ctx, "prefill_chunk")
    assert state_steps.seconds(chunk, pat["kda_chunk"]) == pytest.approx(
        0.011)                      # the loop once, its body not again
    bw = ctx["peak"]["hbm_bytes_per_s"]
    assert state_op_ms.read(ctx, "decode", "kda_step") == pytest.approx(4.0)
    # the grouped matmul and the gather of 128 rows x 8 picks
    assert state_op_ms.read(ctx, "decode", "moe") == pytest.approx(13.0)
    assert state_op_ms.read(ctx, "prefill_chunk", "moe"
                            ) == pytest.approx(9.0)
    assert state_op_ms.read(ctx, "prefill_chunk", "program"
                            ) == pytest.approx(20.0)
    assert kda_step_roofline.read(ctx) == pytest.approx(
        100 * flops_kda.kda_step_bytes(128, 4, 32, 128, 128) / bw / 0.004)
    assert mla_attn_roofline.read(ctx) == pytest.approx(
        100 * flops_mla.mla_decode_bytes(128 * 3000, 640) / bw / 0.001)
    assert 0 < moe_roofline_state.read(ctx) < 100
    assert 0 < kda_chunk_roofline.read(ctx) < 100
    assert prefill_share.read(ctx) == pytest.approx(100 * 0.020 / 0.039)
    assert state_op_ms.read(dict(ctx, _state_steps=None), "decode",
                            "moe") is None


@pytest.mark.parametrize("variant", ["cell", "rehearsal"])
def test_every_seed_posts_the_same_lengths_in_the_same_order(variant):
    """The multiset is the generator's; its order is no seed's to move, and
    every wave of ``slots`` requests holds one length of every stratum."""
    import numpy as np

    from benchmark import datagen
    m = Manifest(ROOT)
    t = m.traffic(m.cell(CELL))
    if variant == "rehearsal":
        t = dict(t, **t["rehearsal"])
    mix, slots = t["mix"], t["engine"]["slots"]
    mix = dict(mix, prompt_tokens=dict(mix["prompt_tokens"], hi=min(
        512, mix["prompt_tokens"]["hi"]), median=min(
            64, mix["prompt_tokens"]["median"])))      # short ids: quick
    n, per = mix["clients"], mix["requests_per_client"]
    same_work = m.kind(t).same_work
    a, b = (same_work(datagen.closed_schedule(mix, 500, seed, n * per),
                      slots) for seed in (3_000_000_019, 7))

    def lengths(clients):
        return [[(len(r["prompt"]), r["max_new"]) for r in q]
                for q in clients]

    assert lengths(a) == lengths(b)
    assert a[0][0]["prompt"] != b[0][0]["prompt"]       # the ids are seeded
    assert [r["idx"] for r in a[3]] == [3 + n * r for r in range(per)]
    for col, key in ((0, "prompt_tokens"), (1, "output_tokens")):
        posted = np.array([lengths(a)[c][r][col] for r in range(per)
                           for c in range(n)])
        grid = np.sort(datagen.lognormal_grid(n * per, **mix[key]))
        assert sorted(posted) == grid.tolist()
        strata = grid.reshape(slots, -1)
        for wave in posted.reshape(-1, slots):
            w = np.sort(wave)
            assert (strata[:, 0] <= w).all() and (w <= strata[:, -1]).all()
