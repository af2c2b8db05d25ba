"""``sdar-serve-backlog`` rehearsed on the CPU at the configuration's tiny
sizes: a run comes out correct; the fp8 control comes out NOT correct;
and two broken paths come out ``"correct": false``: a commit forward
that never stores the block's final tokens (the pool keeps what a
denoising forward wrote), and a router computed in a lower precision.
Also the manifest's view of the two cells PR 27 added, as files only, the
readers that came with them, and the fault ``benchmark/planted.py`` plants
in ``gpt2s-train-dp4`` (every chip fed the first chip's rows)."""

import argparse
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest

CELL = "sdar-serve-backlog"


def _run(capsys, seed, env_hook=None):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "1.5", "--trace", "0",
                         "--rehearse", "1"], env_hook=env_hook)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    return json.loads(out[-1]), out


def test_rehearsal_is_correct_and_counts_three_forwards_a_block(capsys):
    line, out = _run(capsys, 3_000_000_019)
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True and line["failed"] == 0
    c = line["counts"]
    assert c["finished"] > 0 and c["block_steps"] > 0
    # two denoising forwards and one commit a block, whatever the seed:
    # no confidence reaches the threshold under random weights
    assert c["denoise_forwards"] <= 2 * c["commit_forwards"] + 2 * 4
    assert c["denoise_forwards"] >= 2 * c["commit_forwards"] - 2 * 4 - (
        c["commit_forwards"] // 3)          # a prompt's remainder: 1 step
    # the widest logit gap is read beside them, not compared
    assert sum(1 for ln in out if ln.startswith("compared ")) == 4
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
        assert "served_logit_gap" not in compared
        assert (compared["served_logit_gap_mean"]
                > limits["served_logit_gap_mean"]
                or compared["served_confidence_gap"]
                > limits["served_confidence_gap"])
    finally:
        env.cleanup()


def _commit_skipped(server=None, **_):
    """Commit rows carry masks, not the block's final tokens: what the
    pool keeps for a block is not what was committed."""
    if server is None:
        return
    eng = server.engine
    real = eng._build_block_feats

    def feats():
        f = real()
        f["tok"][f["commit"] != 0] = eng.block["mask_id"]
        return f

    eng._build_block_feats = feats


def _low_precision_router(model=None, **_):
    if model is not None:
        import jax.numpy as jnp
        model.router_dtype = jnp.float8_e4m3fn


@pytest.mark.parametrize("breaker", [_commit_skipped,
                                     _low_precision_router])
def test_a_broken_path_comes_out_not_correct(capsys, breaker):
    def hook(env):
        env.break_program = breaker
    line, out = _run(capsys, 2_200_007_919, env_hook=hook)
    assert line["correct"] is False, out[-6:]


def test_the_two_new_cells_are_files_and_entries_only():
    m = Manifest(ROOT)
    sdar = m.cell(CELL)
    assert (sdar["config"], sdar["traffic"], sdar["chips"]) == (
        "sdar-30b-a3b-chat", "blockdiff-backlog", 1)
    cfg = m.config(sdar)
    entry = m.configs["sdar-30b-a3b-chat"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["vocab_size"], cfg["rope_theta"], cfg["num_hidden_layers"]
            ) == (2048, 32, 4, 128, 128, 8, 768, 151936, 1000000, 6)
    for key in ("block_length", "schedule", "confidence_threshold",
                "qk_norm", "mask_id", "param_dtype"):
        assert key in cfg["assumed"]
    t = m.traffic(sdar)
    assert t["kind"] == "serve_blocks" and t["schedule"][
        "denoising_steps"] == 2
    assert hasattr(m.kind(t), "run") and hasattr(m.kind(t), "control")
    ref = m.reference(cfg)
    assert {"FAMILY", "param_spec", "logits", "block_step"} <= set(dir(ref))
    assert {x["name"] for x in m.end_to_end(CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    names = {x["name"] for x in m.per_layer(CELL)}
    assert {"sched_block_step_ms.sdar", "sched_forwards_per_block.sdar",
            "sched_tokens_per_step.sdar", "sched_slot_occupancy_pct.sdar",
            "serve_moe_ms.sdar", "serve_moe_roofline_pct.sdar",
            "serve_block_attn_roofline_pct.sdar", "serve_sample_ms.sdar",
            "serve_device_idle_pct.sdar", "serve_hbm_peak_gib.sdar",
            "http_overhead_ms_p50.sdar", "serve_completed_tokens_per_s.sdar",
            "sched_idle_wait_logits_ms.sdar", "sched_idle_sample_emit_ms.sdar",
            "sched_idle_admit_ms.sdar", "sched_idle_launch_ms.sdar",
            "sched_idle_unattributed_ms.sdar",
            "export_s", "serve_compile_s"} == names
    dp4 = m.cell("gpt2s-train-dp4")
    assert (dp4["config"], dp4["traffic"], dp4["chips"]) == (
        "gpt2-small", "lm-s1024-dp4", 4)
    assert sum(1 for w in m.doc["workloads"] if w["chips"] == 4) == 1
    t4 = m.traffic(dp4)
    assert t4["kind"] == "train" and t4["data"]["batch_size"] == 64
    assert "data=4" in t4["flags"]
    assert {"train_allreduce_ms.dp4", "train_flash_fwd_ms.dp4",
            "train_flash_bwd_ms.dp4", "train_flash_roofline_pct.dp4"} <= {
        x["name"] for x in m.per_layer("gpt2s-train-dp4")}
    # the one-chip cell's flash metrics stay its own: their reader sums
    # program executions over chips
    assert "train_flash_fwd_ms" not in {
        x["name"] for x in m.per_layer("gpt2s-train-dp4")}
    # a new reader finds nothing in a trace that lacks its span (the
    # parent's, every other cell's): it returns None and does not raise
    empty = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1,
                       "opcodes": {}, "all_ops": {}, "modules": {}},
             "peak": m.peak("TPU v5 lite"), "values": {},
             "xplane_path": os.path.join(ROOT, "benchmark", "tests",
                                         "fixtures", "sched_tpu.xplane.pb")}
    for x in m.per_layer(CELL) + m.per_layer("gpt2s-train-dp4"):
        if x["name"].endswith((".sdar", ".dp4")) and x["name"] not in (
                "serve_device_idle_pct.sdar", "serve_hbm_peak_gib.sdar"):
            assert m.read_metric(x, dict(empty)) is None, x["name"]


def test_block_step_readers_on_a_recorded_capture():
    """``readers/block_steps.py`` on the recorded scheduler capture, told
    to take its decode programs for block steps: every executed program
    gets the span that dispatched it (the device's clock runs ~1 ms ahead
    of the host's there) and its operations by name."""
    from benchmark.readers import (block_attn_roofline, block_step_ops_ms,
                                   block_steps)
    path = os.path.join(ROOT, "benchmark", "tests", "fixtures",
                        "sched_tpu.xplane.pb")
    steps = block_steps.parse(path, module="decode", span="decode_step")
    assert len(steps) >= 70
    assert all("kv_bytes" in s["args"] and s["ops"] for s in steps)
    ctx = {"_block_steps": steps, "peak": {"hbm_bytes_per_s": 819e9}}
    kernel = block_step_ops_ms.read(ctx, pattern="paged_decode_attn")
    tail = block_step_ops_ms.read(ctx, after="paged_decode_attn")
    assert 0 < tail < kernel < 1.0
    assert block_step_ops_ms.read(ctx, pattern="no_such_kernel") is None
    share = block_attn_roofline.read(ctx, pattern="paged_decode_attn")
    assert 0 < share < 100
    # and nothing of a block step in it as recorded
    assert block_steps.parse(path) is None


def test_one_shard_fed_to_every_chip_comes_out_not_correct(capsys):
    """``benchmark/planted.py``'s fault in the four-chip cell, rehearsed:
    the step reads a quarter of its batch, as a chip does whose gradient
    exchange was left out."""
    from benchmark import planted
    rc = planted.main(["--fault", "one_shard_batch", "--workload",
                       "gpt2s-train-dp4", "--seed", "3000000023",
                       "--seconds", "1.5", "--trace", "0", "--rehearse",
                       "1"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    assert json.loads(out[-1])["correct"] is False, out[-6:]


def test_idle_by_phase_per_block_step():
    """``readers/block_idle_ms.py``: the idle seconds of
    ``sched_idle_ms.idle_by_phase`` over the executed block-step programs
    (a block step holds no device ``while`` to count)."""
    from benchmark.readers import block_idle_ms
    chip = {"busy": [(0.0, 1.0), (1.5, 2.0), (2.25, 3.0)],
            "ops": [(0.0, 1.0, "fusion"), (1.5, 2.0, "fusion"),
                    (2.25, 3.0, "fusion")], "window": (0.0, 3.0)}
    found = {"chips": [chip], "spans": {
        "sched_wait_logits": [(0.9, 1.4, {})],
        "sched_sample_emit": [(1.4, 1.5, {}), (2.0, 2.1, {})]}}
    trace = {"chips": 1, "busy_s": 2.25, "window_s": 3.0,
             "modules": {"jit_block_step": [1.0, 0.5],
                         "jit_prefill": [0.75]}}
    ctx = {"trace": trace, "_xplane_join": found}
    read = block_idle_ms.read
    assert read(ctx, "wait_logits") == pytest.approx(1e3 * 0.4 / 2)
    assert read(ctx, "sample_emit") == pytest.approx(1e3 * 0.2 / 2)
    assert read(ctx, "admit") == 0.0
    assert read(ctx, "unattributed") == pytest.approx(1e3 * 0.15 / 2)
    # the five add up to the window's idle time over the block steps
    assert sum(read(ctx, p) for p in (
        "wait_logits", "sample_emit", "admit", "launch", "unattributed")
        ) == pytest.approx(1e3 * (3.0 - 2.25) / 2)
    # no block step in the capture (every one-token cell): nothing to read
    assert read({"trace": dict(trace, modules={"jit_decode": [1.0]}),
                 "_xplane_join": found}, "admit") is None


def test_flash_roofline_on_four_chips_is_one_chips():
    """A step program that runs once on each of four chips with a quarter
    of the batch reads what the one-chip reader reads of one of them."""
    from benchmark.readers import (flash_roofline, flash_roofline_per_chip,
                                   op_ms_per_chip_call)
    m = Manifest(ROOT)
    base = {"family": "gpt", "peak": m.peak("TPU v5 lite"),
            "ref_cfg": {"n_head": 12, "n_embd": 768, "n_layer": 12}}
    ops = {"flash_fwd": {"seconds": 0.08}, "flash_bwd_fused": {
        "seconds": 0.16}, "fusion": {"seconds": 1.0}}
    one = dict(base, data={"batch_size": 16, "seq_len": 1024}, trace={
        "chips": 1, "all_ops": ops, "opcodes": {},
        "modules": {"jit_step": [0.14] * 10}})
    four = dict(base, data={"batch_size": 64, "seq_len": 1024}, trace={
        "chips": 4, "all_ops": ops, "opcodes": {},
        "modules": {"jit_step": [0.15] * 40}})
    kw = dict(pattern="flash_fwd|flash_bwd", per_module="step")
    want = flash_roofline.read(one, **kw)
    assert 0 < want < 100
    assert flash_roofline_per_chip.read(four, **kw) == pytest.approx(want)
    assert flash_roofline_per_chip.read(one, **kw) == pytest.approx(want)
    assert op_ms_per_chip_call.read(four, pattern="flash_bwd",
                                    per_module="step") == pytest.approx(16.0)
    assert flash_roofline_per_chip.read(
        dict(four, family="bert"), **kw) is None
