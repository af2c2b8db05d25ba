#!/usr/bin/env python
"""MoE top-2 routing cost on the real chip (VERDICT r4 task #8/weak #6).

The gate measures MoE-BERT at top-1 (Switch) routing only; top-2 — the
GShard/ST-MoE default — is implemented and oracle-tested but has no
measured cost story. This sweep measures the moe_bert bench config
(b64, seq 128, adamw, rbg, bf16) at top-2 across the standard capacity
factors, recording step time AND the routing-health metrics the round-4
visibility work exposed (dropped_fraction — top-2 doubles assignments,
so capacity pressure is the central trade).

One fresh process per cell; one JSON line per cell; the BASELINE.md
table holds the verdicts.

Usage: python experiments/moe_top2.py TOPK CAPACITY
       python experiments/moe_top2.py --all
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

CELLS = [(1, 1.25), (2, 1.0), (2, 1.25), (2, 2.0)]


def measure(top_k: int, capacity: float, *, batch=64, steps=20,
            warmup=5) -> dict:
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

    cfg = TrainConfig(model="moe_bert", dtype="bfloat16",
                      data=DataConfig(batch_size=batch),
                      optimizer=OptimizerConfig(name="adamw",
                                                learning_rate=1e-4),
                      moe_top_k=top_k, moe_capacity_factor=capacity)
    model = get_model("moe_bert", cfg)
    mesh = build_mesh()
    sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
    state = sync.init(model.init, seed=0, prng_impl="rbg")
    placed = sync.shard_batch(model.dummy_batch(batch))
    compiled = sync.step.lower(state, placed).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]

    for _ in range(warmup):
        state, m = compiled(state, placed)
    jax.block_until_ready(state.params)

    def timed():
        nonlocal state, m
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = compiled(state, placed)
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    dt = max(timed(), timed())
    step_ms = dt / steps * 1e3
    host = {k: float(np.mean(np.asarray(jax.device_get(v))))
            for k, v in m.items()
            if k in ("loss", "dropped_token_fraction", "aux_loss",
                     "expert_load_min", "expert_load_max")}
    return {
        "top_k": top_k, "capacity_factor": capacity,
        "step_ms": round(step_ms, 1),
        "eps_chip": round(batch / (dt / steps), 1),
        "flops_T": round(float(ca.get("flops", 0.0)) / 1e12, 3),
        **{k: round(v, 4) for k, v in host.items()},
    }


def main() -> None:
    if sys.argv[1:2] == ["--all"]:
        run_cells(os.path.abspath(__file__), CELLS)
        return
    k, c = int(sys.argv[1]), float(sys.argv[2])
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        print(json.dumps(measure(k, c)), flush=True)
    except Exception as e:  # noqa: BLE001
        fail({"top_k": k, "capacity_factor": c}, e)


if __name__ == "__main__":
    main()
