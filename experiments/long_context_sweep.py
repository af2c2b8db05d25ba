#!/usr/bin/env python
"""Long-context gate-config sweep (VERDICT r4 task #4 / weak #1).

The bert_long gate workload (S=4096 b4 flash) was configured with
``remat=full`` when the knob was built at S=1024 b8 — but at the gate
shape the step uses ~12% of HBM, which suggests a cheaper checkpoint
policy (or none) fits and is faster: the gate may be measuring an
over-conservative config. This sweep measures remat x {none, dots,
full} for BOTH long-context programs — bert_long (non-causal MLM) and
gpt_long (causal + chunked LM loss) — at the gate shape: step time,
XLA temp memory, examples/sec.

One fresh process per cell (a compile-time OOM costs one row, and no
cell inherits another's device memory); one JSON line per cell; the
decision table lives in BASELINE.md.

Usage: python experiments/long_context_sweep.py MODEL REMAT   # one cell
       python experiments/long_context_sweep.py --all         # loop
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

MODELS = ("bert", "gpt")
REMATS = ("none", "dots", "full")


def measure(model_name: str, remat: str, *, batch=4, seq=4096,
            steps=6, warmup=2) -> dict:
    import jax
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

    cfg = TrainConfig(model=model_name, dtype="bfloat16",
                      data=DataConfig(batch_size=batch, seq_len=seq),
                      optimizer=OptimizerConfig(name="adamw",
                                                learning_rate=1e-4),
                      attention_impl="flash", remat=remat,
                      lm_loss_chunk=512 if model_name == "gpt" else None)
    model = get_model(model_name, cfg)
    mesh = build_mesh()
    sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
    state = sync.init(model.init, seed=0, prng_impl="rbg")
    rs = np.random.RandomState(0)
    if model_name == "gpt":
        batch_np = {
            "input_ids": rs.randint(0, cfg.data.vocab_size, (batch, seq),
                                    dtype=np.int32),
            "attention_mask": np.ones((batch, seq), np.int32),
        }
    else:
        c = model.cfg
        m = c.max_predictions
        batch_np = {
            "input_ids": rs.randint(0, c.vocab_size, (batch, seq),
                                    dtype=np.int32),
            "token_type_ids": np.zeros((batch, seq), np.int32),
            "attention_mask": np.ones((batch, seq), np.int32),
            "masked_positions": np.tile(np.arange(m, dtype=np.int32),
                                        (batch, 1)),
            "masked_labels": rs.randint(0, c.vocab_size, (batch, m),
                                        dtype=np.int32),
            "masked_weights": np.ones((batch, m), np.float32),
        }
    placed = sync.shard_batch(batch_np)
    compiled = sync.step.lower(state, placed).compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]

    for _ in range(warmup):
        state, m_ = compiled(state, placed)
    jax.block_until_ready(state.params)

    def timed():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m_ = compiled(state, placed)
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    dt = max(timed(), timed())
    step_ms = dt / steps * 1e3
    peak = 197e12
    flops = float(ca.get("flops", 0.0))
    return {
        "model": model_name, "remat": remat,
        "step_ms": round(step_ms, 1),
        "eps_chip": round(batch / (dt / steps), 2),
        "temp_MiB": round(ma.temp_size_in_bytes / 2**20),
        "peak_MiB": round(ma.peak_memory_in_bytes / 2**20),
        "mfu": round(flops / (dt / steps) / peak, 4) if flops else None,
        "loss_finite": bool(np.isfinite(float(jax.device_get(m_["loss"])))),
    }


def main() -> None:
    if sys.argv[1:2] == ["--all"]:
        run_cells(os.path.abspath(__file__),
                  [(mn, r) for mn in MODELS for r in REMATS])
        return
    mn, r = sys.argv[1], sys.argv[2]
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        print(json.dumps(measure(mn, r)), flush=True)
    except Exception as e:  # noqa: BLE001 — OOM at compile is a finding
        fail({"model": mn, "remat": r}, e)


if __name__ == "__main__":
    main()
