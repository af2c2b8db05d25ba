#!/usr/bin/env python
"""Sync-replica scaling curve: step time vs N chips (BASELINE.json:2).

Runs the sync data-parallel step at data-axis sizes 1/2/4/8 (and every
power of two up to the available device count) with a FIXED per-replica
batch (weak scaling — the reference's N-worker regime), and emits one JSON
line per N::

    {"n": 4, "model": "mlp", "step_ms": 1.2, "examples_per_sec": ...,
     "examples_per_sec_per_chip": ..., "platform": "tpu",
     "device_kind": "TPU v5 lite", "device_count": 4}

On real multi-chip hardware this IS the scaling-curve row; on a single
chip or the virtual CPU mesh it validates shape/sharding correctness and
the harness itself (the numbers are only meaningful on real chips — CPU
step times are not TPU step times, and every row names the platform,
device kind and device count it ran on so one can never be read as the
other).

Usage: python bench_scaling.py [--model mlp] [--per_replica_batch 1024]
       [--cpu]  (force the virtual CPU mesh)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp")
    ap.add_argument("--per_replica_batch", type=int, default=1024)
    # Default None -> platform-resolved below: 300 on TPU (the MLP step
    # is dispatch-latency-bound; 30-step runs track dispatch jitter —
    # observed 4.8-13.2 ms swings — not device throughput, the same
    # methodology lesson as bench.py), 30 on the virtual CPU mesh
    # (shape-validation only, and long oversubscribed 8-way collective
    # runs can trip XLA:CPU's collective executor)
    ap.add_argument("--steps", type=int, default=None,
                    help="measured steps (default: 300 on TPU, 30 on CPU)")
    ap.add_argument("--warmup", type=int, default=None,
                    help="warmup steps (default: 30 on TPU, 5 on CPU)")
    ap.add_argument("--cpu", action="store_true",
                    help="force an 8-device virtual CPU mesh")
    args = ap.parse_args()

    if args.cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           MeshShape,
                                                           OptimizerConfig,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_example_tpu.parallel.sync_replicas import (
        SyncReplicas)
    from distributed_tensorflow_example_tpu.train.optimizers import (
        make_optimizer)

    from bench import robust_time   # shared timing core

    devices = jax.devices()
    platform = devices[0].platform
    if args.steps is None:
        args.steps = 300 if platform == "tpu" else 30
    if args.warmup is None:
        args.warmup = 30 if platform == "tpu" else 5
    sizes = [n for n in (1, 2, 4, 8, 16, 32, 64) if n <= len(devices)]

    for n in sizes:
        batch = args.per_replica_batch * n      # weak scaling
        cfg = TrainConfig(model=args.model, dtype="bfloat16",
                          data=DataConfig(batch_size=batch),
                          optimizer=OptimizerConfig(name="sgd",
                                                    learning_rate=0.1))
        model = get_model(args.model, cfg)
        mesh = build_mesh(MeshShape(data=n), devices=devices[:n])
        sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
        state = sync.init(model.init, seed=0)
        placed = sync.shard_batch(model.dummy_batch(batch))

        for _ in range(args.warmup):
            state, m = sync.step(state, placed)
        jax.block_until_ready(state.params)

        def timed_pass():
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(args.steps):
                state, m = sync.step(state, placed)
            jax.block_until_ready(state.params)
            return time.perf_counter() - t0

        total, suspect = robust_time(timed_pass, steps=args.steps)
        dt = total / args.steps

        rec = {
            "n": n,
            "model": args.model,
            "per_replica_batch": args.per_replica_batch,
            "step_ms": round(dt * 1e3, 3),
            "examples_per_sec": round(batch / dt, 1),
            "examples_per_sec_per_chip": round(batch / dt / n, 1),
            "platform": platform,
            "device_kind": devices[0].device_kind,
            "device_count": n,
        }
        if suspect:
            rec["suspect"] = True
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
