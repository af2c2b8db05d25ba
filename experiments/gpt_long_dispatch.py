#!/usr/bin/env python
"""GPT long-context queued-dispatch failure bisection (VERDICT r4 task #1).

Round 4 left ONE open reliability defect (BASELINE.md GPT row, commit
b450165): the composed GPT long-context training step (S=4096, b=4,
causal flash + remat + sequence-chunked LM loss) trains reliably when
every dispatch is host-blocked, but intermittently died with a
runtime-reported INVALID_ARGUMENT when several ~1.35 s steps were queued
back-to-back — while the same-shape non-causal bert_long program queues
8 steps reliably in every bench run and the raw causal flash kernel is
clean standalone. This script bisects the program delta:

  repro          — the failing config: b4 S4096 causal flash, remat=full,
                   loss_chunk=512 (queue 8, expect intermittent failure)
  noncausal      — identical program with causal=False in the flash call
                   (the bert_long-like control inside the GPT body)
  nochunk_b1     — causal flash + remat, chunk=0 at b1 (full logits fit):
                   removes the chunked-loss lax.scan from the program
  chunk256/1024  — chunk-size sensitivity
  remat_dots     — checkpoint policy sensitivity (dots vs full)
  remat_none_b2  — no remat at b2 (memory-safe): removes the
                   rematerialized causal flash bwd entirely
  inflight{1,2,4}— the candidate MITIGATION on the repro config: cap the
                   number of un-blocked dispatches in flight

Each variant runs T trials of queue-N-steps-then-block in a FRESH
process (a failed trial must not poison the next variant's runtime
state); one JSON line per variant with per-trial
outcomes. Intermittency means a clean single trial proves nothing —
only fail COUNTS across trials discriminate.

Usage: python experiments/gpt_long_dispatch.py VARIANT [trials] [queue]
       python experiments/gpt_long_dispatch.py --all   # subprocess loop
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

VARIANTS = ("repro", "noncausal", "nochunk_b1", "chunk256", "chunk1024",
            "remat_dots", "remat_none_b2", "inflight1", "inflight2",
            "inflight4")


def measure(variant: str, trials: int, queue: int) -> dict:
    import jax

    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           OptimizerConfig,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.ops.attention import (
        multi_head_attention)
    from distributed_tensorflow_example_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_example_tpu.parallel.sync_replicas import (
        SyncReplicas)
    from distributed_tensorflow_example_tpu.train.optimizers import (
        make_optimizer)
    import numpy as np

    batch, chunk, remat, inflight = 4, 512, "full", 0
    causal = True
    if variant == "noncausal":
        causal = False
    elif variant == "nochunk_b1":
        batch, chunk = 1, 0
    elif variant == "chunk256":
        chunk = 256
    elif variant == "chunk1024":
        chunk = 1024
    elif variant == "remat_dots":
        remat = "dots"
    elif variant == "remat_none_b2":
        batch, remat = 2, "none"
    elif variant.startswith("inflight"):
        inflight = int(variant[len("inflight"):])

    cfg = TrainConfig(model="gpt", dtype="bfloat16",
                      data=DataConfig(batch_size=batch, seq_len=4096),
                      optimizer=OptimizerConfig(name="adamw",
                                                learning_rate=1e-4),
                      attention_impl="flash", remat=remat,
                      lm_loss_chunk=chunk)
    model = get_model("gpt", cfg)
    if not causal:
        # same program shape, causal=False in the flash kernel — the
        # one-bit delta between this body and the reliable bert_long one
        model.attention_fn = lambda q, k, v, mask, causal: \
            multi_head_attention(q, k, v, mask=mask[:, None, None, :],
                                 causal=False, impl="flash")
    mesh = build_mesh()
    sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
    state = sync.init(model.init, seed=0, prng_impl="rbg")
    rs = np.random.RandomState(0)
    placed = sync.shard_batch({
        "input_ids": rs.randint(0, cfg.data.vocab_size, (batch, 4096),
                                dtype=np.int32),
        "attention_mask": np.ones((batch, 4096), np.int32),
    })
    compiled = sync.step.lower(state, placed).compile()

    # blocked warmup (known-reliable regime)
    for _ in range(2):
        state, m = compiled(state, placed)
        jax.block_until_ready(state.params)

    outcomes, step_ms = [], None
    for t in range(trials):
        t0 = time.perf_counter()
        try:
            for i in range(queue):
                state, m = compiled(state, placed)
                if inflight and (i + 1) % inflight == 0:
                    jax.block_until_ready(state.params)
            jax.block_until_ready(state.params)
            dt = time.perf_counter() - t0
            step_ms = dt / queue * 1e3
            loss = float(jax.device_get(m["loss"]))
            outcomes.append("ok" if np.isfinite(loss) else "nonfinite")
        except Exception as e:  # noqa: BLE001 — record and continue
            outcomes.append(f"FAIL:{type(e).__name__}")
            err = f"{type(e).__name__}: {str(e)[:200]}"
            # the device may be wedged for this process; report what we
            # have rather than cascade misattributed failures
            return {"variant": variant, "outcomes": outcomes,
                    "error": err, "step_ms": step_ms,
                    "aborted_at_trial": t}
    return {"variant": variant, "outcomes": outcomes,
            "fails": sum(o != "ok" for o in outcomes),
            "step_ms": round(step_ms, 1) if step_ms else None}


def main() -> None:
    if sys.argv[1:2] == ["--all"]:
        variants = sys.argv[2:] or list(VARIANTS)
        # fresh process per variant
        run_cells(os.path.abspath(__file__), [(v,) for v in variants])
        return
    variant, trials, queue = (sys.argv[1],
                              int(sys.argv[2]) if len(sys.argv) > 2 else 5,
                              int(sys.argv[3]) if len(sys.argv) > 3 else 8)
    if variant not in VARIANTS:
        raise SystemExit(f"unknown variant {variant!r} (have {VARIANTS})")
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        out = measure(variant, trials, queue)
    except Exception as e:  # noqa: BLE001 — compile/init failure
        fail({"variant": variant}, e)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
