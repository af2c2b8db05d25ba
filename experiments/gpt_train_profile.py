#!/usr/bin/env python
"""GPT-small training-step profile + loss-chunk sweep (VERDICT r4 #5).

GPT-small trains at 34.4% MFU (172.6 ms/step, s512 b32) vs BERT-base's
51.0% at comparable scale, and no profile names the gap's owner. The
candidate suspects: the weight-tied vocab-einsum LM head (+ its
embedding gradient), the chunked-loss recompute (each chunk re-runs the
[B, chunk, V] logits under jax.checkpoint in the backward), and the
causal-attention structure. This script:

  time CHUNK — step time at s512 b32 with lm_loss_chunk=CHUNK
               (0 = full logits: measures what the chunked path costs)
  trace DIR  — jax.profiler capture of the round-4 bench config
               (chunk=512), reduced to PROFILE_r05_gpt.txt via
               utils.trace_summary

Fresh process per cell; one JSON line per cell; findings in BASELINE.md.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

BATCH, SEQ = 32, 512


def _build(chunk: int):
    import numpy as np

    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           OptimizerConfig,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_example_tpu.parallel.sync_replicas import (
        SyncReplicas)
    from distributed_tensorflow_example_tpu.train.optimizers import (
        make_optimizer)

    cfg = TrainConfig(model="gpt", dtype="bfloat16",
                      data=DataConfig(batch_size=BATCH, seq_len=SEQ),
                      optimizer=OptimizerConfig(name="adamw",
                                                learning_rate=1e-4),
                      lm_loss_chunk=chunk)
    model = get_model("gpt", cfg)
    mesh = build_mesh()
    sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
    state = sync.init(model.init, seed=0, prng_impl="rbg")
    rs = np.random.RandomState(0)
    placed = sync.shard_batch({
        "input_ids": rs.randint(0, cfg.data.vocab_size, (BATCH, SEQ),
                                dtype=np.int32),
        "attention_mask": np.ones((BATCH, SEQ), np.int32),
    })
    return sync, state, placed


def timed_cell(chunk: int, *, steps=20, warmup=5) -> dict:
    import jax

    sync, state, placed = _build(chunk)
    compiled = sync.step.lower(state, placed).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    ma = compiled.memory_analysis()
    for _ in range(warmup):
        state, m = compiled(state, placed)
    jax.block_until_ready(state.params)

    def timed():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = compiled(state, placed)
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    dt = max(timed(), timed())
    step_s = dt / steps
    flops = float(ca.get("flops", 0.0))
    return {
        "chunk": chunk, "step_ms": round(step_s * 1e3, 1),
        "eps_chip": round(BATCH / step_s, 1),
        "mfu": round(flops / step_s / 197e12, 4),
        "flops_T": round(flops / 1e12, 3),
        "bytes_GB": round(float(ca.get("bytes accessed", 0.0)) / 1e9, 2),
        "temp_MiB": round(ma.temp_size_in_bytes / 2**20),
    }


def trace(outdir: str, chunk: int = 512) -> dict:
    import jax

    sync, state, placed = _build(chunk)
    compiled = sync.step.lower(state, placed).compile()
    for _ in range(3):
        state, m = compiled(state, placed)
    jax.block_until_ready(state.params)
    jax.profiler.start_trace(outdir)
    for _ in range(5):
        state, m = compiled(state, placed)
    jax.block_until_ready(state.params)
    jax.profiler.stop_trace()
    return {"trace": outdir, "chunk": chunk}


def main() -> None:
    if sys.argv[1:2] == ["--all"]:
        run_cells(os.path.abspath(__file__),
                  [("time", c) for c in (512, 0, 128, 256)])
        return
    mode, arg = sys.argv[1], sys.argv[2]
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        if mode == "time":
            out = timed_cell(int(arg))
        elif mode == "trace":
            out = trace(arg)
        else:
            raise SystemExit(f"unknown mode {mode!r}")
        print(json.dumps(out), flush=True)
    except Exception as e:  # noqa: BLE001 — OOM at compile is a finding
        fail({"mode": mode, "arg": arg}, e)


if __name__ == "__main__":
    main()
