#!/usr/bin/env python
"""Vocab-chain sweep: the [B, S, V] LM-head logits chain, impl x block x
shape (ISSUE 3 tentpole; the flash_sweep.py discipline applied to the
loss end of the model).

The GPT-small round-5 profile attributes ~21 ms of the 170 ms step to
the 30,522-vocab logits chain (logits fwd 4.0 + backward recompute 4.0
+ tied-embedding grad 4.4 + softmax reductions 5.3 + accuracy argmax
3.2 — BASELINE.md "Vocab chain"), and the [B, S, V] tensor is the
causal-LM memory wall (b64 s512 OOMs without chunking). The fused
blockwise cross-entropy (``--lm_loss_impl fused``,
ops/losses.py lm_head_xent) removes the tensor from BOTH passes; this
script makes the choice reproducible: an analytic bytes/flops model of
the chain per impl, and measured fresh-process train-step cells over
impl x vocab block x the gate shapes.

Modes (one JSON line per cell; fresh process per cell via --all/--smoke,
so the b64 s512 OOM cell costs one row and not the sweep):

  --roofline        analytic model, runs anywhere: matmul FLOPs and HBM
                    bytes of the logits chain per impl (full / chunked /
                    fused at each block), the peak logits residency, and
                    the implied MXU/HBM floors per (B, S) gate shape.
  cell MODEL B S IMPL [SIZE]
                    one measured train-step cell on the current backend:
                    step ms, eps/chip, temp/peak MiB. IMPL: full |
                    chunked | fused; SIZE is the seq chunk (chunked) or
                    vocab block (fused). Records OOM as an error line —
                    the full-vs-fused crossover table needs the OOM rows
                    (full at b64 s512 is EXPECTED to be one on the v5e).
  --all             the committed TPU grid: gpt x {b32 s512, b64 s512,
                    b4 s4096} x (full + chunked 512 + fused blocks
                    {1024, 2048, 4096, 8192}).
  --smoke           tiny CPU grid (gpt_tiny, b4 s64, fused blocks incl.
                    a non-divisible one) — the CI end-to-end path; the
                    numbers are meaningless off-TPU, the exercise is
                    that every impl runs and the JSON contract holds.

Measured cells are TPU cells (off-TPU timings are meaningless):
--all/--cell refuse to run off-TPU unless VOCAB_SWEEP_CPU=1 (--smoke
sets it for its subprocesses). --roofline is platform-independent.

Pre-committed decision rule (BASELINE.md "Vocab chain"): at the next
TPU window, if fused beats the incumbent (full at gpt_small b32,
chunked 512 at gpt_long) at the gate shapes, the gate configs stay
fused and re-base with a methodology note; if not, the losing cells get
committed and full/chunked return as the gate configs — either way the
winning vocab block becomes the configs' --lm_loss_vocab_block.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

#: the gate-adjacent (batch, seq) cells: the gpt_small bench shape, the
#: shape that OOMs the full path on the v5e, and the gpt_long shape
SHAPES = ((32, 512), (64, 512), (4, 4096))
BLOCKS = (1024, 2048, 4096, 8192)
CHUNK = 512                    # the incumbent gpt_long chunk
HIDDEN, LAYERS, VOCAB = 768, 12, 30522
PEAK_FLOPS = 197e12            # v5e bf16
HBM_BPS = 819e9                # v5e


# ---------------------------------------------------------------------------
# analytic model — pass counts mirror what each impl executes
# ---------------------------------------------------------------------------

def chain_flops(impl: str, n: int, *, h: int = HIDDEN,
                v: int = VOCAB) -> float:
    """Matmul FLOPs of the logits chain for one train step over n
    tokens. full: fwd (2nhv) + bwd dh (2nhv) + bwd tied-embed grad
    (2nhv) = 6nhv. chunked AND fused both regenerate the logits once in
    backward (jax.checkpoint / the custom VJP) = 8nhv — the fused win
    is bytes and residency, not flops."""
    return (6.0 if impl == "full" else 8.0) * n * h * v


def chain_bytes(impl: str, n: int, *, h: int = HIDDEN, v: int = VOCAB,
                block: int = 0, chunk: int = 0, batch: int = 0,
                seq: int = 0, op_bytes: int = 2) -> dict:
    """HBM byte model of the chain (f32 logits tiles, ``op_bytes``
    matmul operands). Documented pass counts:

    - full: the [n, v] f32 tensor is written (fwd), read by the
      logsumexp/softmax reductions, read by the accuracy argmax, and in
      backward d_logits is written then read by BOTH grad matmuls —
      ~6 full-tensor passes. Table read twice (fwd + dE), dE written.
    - chunked: same logits traffic PLUS one more pass (the recompute),
      and the table is re-streamed per seq chunk in each of the 4
      matmul passes (fwd, recompute, dh, dE) — the chunked tax that
      grows with S/chunk. Peak residency: one [b, chunk, v] tile.
    - fused: per vocab block the [n, block] tile is produced and
      consumed in-scan (write+read, fwd and bwd = ~4 passes of n·v
      f32 in BLOCK tiles — never resident at once), h is re-streamed
      once per block per pass (3 passes: fwd, bwd-regen+dE, dh), the
      table twice, dE written once. Peak residency: one [n, block]
      tile + the dh accumulator.
    """
    logits_f32 = 4.0 * n * v
    table = v * h * op_bytes
    h_stream = n * h * op_bytes
    if impl == "full":
        return {
            "logits_GB": 6.0 * logits_f32 / 1e9,
            "table_GB": (2 * table + v * h * 4) / 1e9,
            "h_GB": 3.0 * h_stream / 1e9,
            "peak_logits_MiB": logits_f32 / 2**20,
        }
    if impl == "chunked":
        n_chunks = max(1, seq // max(chunk, 1))
        return {
            "logits_GB": 7.0 * logits_f32 / 1e9,
            "table_GB": (4 * n_chunks * table + v * h * 4) / 1e9,
            "h_GB": 4.0 * h_stream / 1e9,
            "peak_logits_MiB": 4.0 * batch * chunk * v / 2**20,
        }
    nb = max(1, -(-v // max(block, 1)))
    return {
        "logits_GB": 4.0 * logits_f32 / 1e9,
        "table_GB": (2 * table + v * h * 4) / 1e9,
        "h_GB": 3.0 * nb * h_stream / 1e9,
        "peak_logits_MiB": (4.0 * n * block + 4.0 * n * h) / 2**20,
    }


def roofline_row(impl: str, b: int, s: int, size: int) -> dict:
    n = b * s
    chunk = size if impl == "chunked" else 0
    block = size if impl == "fused" else 0
    flops = chain_flops(impl, n)
    by = chain_bytes(impl, n, block=block, chunk=chunk, batch=b, seq=s)
    total_gb = by["logits_GB"] + by["table_GB"] + by["h_GB"]
    return {
        "impl": impl, "batch": b, "seq": s,
        "size": size or None,
        "chain_TF": round(flops / 1e12, 2),
        **{k: round(x, 2) for k, x in by.items()},
        "chain_GB": round(total_gb, 2),
        "mxu_floor_ms": round(flops / PEAK_FLOPS * 1e3, 2),
        "hbm_floor_ms": round(total_gb * 1e9 / HBM_BPS * 1e3, 2),
    }


def roofline() -> None:
    print("# Analytic vocab-chain roofline (v5e: 197 TFLOP/s bf16, "
          "819 GB/s HBM); per TRAIN STEP, logits chain only")
    print("# chain_TF = matmul FLOPs of the chain; chain_GB = modeled "
          "HBM traffic; peak_logits_MiB = largest resident logits tile")
    for b, s in SHAPES:
        for impl, sizes in (("full", (0,)), ("chunked", (CHUNK,)),
                            ("fused", BLOCKS)):
            for size in sizes:
                print(json.dumps(roofline_row(impl, b, s, size)))


# ---------------------------------------------------------------------------
# measured cells
# ---------------------------------------------------------------------------

def measure(model_name: str, b: int, s: int, impl: str, size: int,
            *, steps: int = 6, warmup: int = 2) -> dict:
    import time

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

    # shared with the gate: ONE batch-builder and ONE timing
    # implementation (flash_sweep.py:206 principle) — sweep cells must
    # measure exactly what the bench rows measure
    from bench import _gpt_batch_at, robust_time

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not os.environ.get("VOCAB_SWEEP_CPU"):
        raise SystemExit("measured cells are TPU cells (CPU timings are "
                         "meaningless); set VOCAB_SWEEP_CPU=1 for a CI "
                         "smoke run, or use --smoke")
    if impl not in ("full", "chunked", "fused"):
        raise SystemExit(f"IMPL must be full/chunked/fused, got {impl!r}")
    cfg = TrainConfig(
        model=model_name, dtype="bfloat16",
        data=DataConfig(batch_size=b, seq_len=s),
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-4),
        remat="none",
        # long-S cells ride the tuned gate attention config; short-S
        # cells keep xla attention like the gpt_small gate row
        attention_impl="flash" if s >= 4096 else "xla",
        lm_loss_impl=impl if impl != "chunked" else None,
        lm_loss_chunk=size if impl == "chunked" else None,
        lm_loss_vocab_block=(size or None) if impl == "fused" else None)
    model = get_model(model_name, cfg)
    mesh = build_mesh()
    sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
    state = sync.init(model.init, seed=0,
                      prng_impl="rbg" if on_tpu else None)
    placed = sync.shard_batch(_gpt_batch_at(s)(model, b, 0))
    compiled = sync.step.lower(state, placed).compile()
    ma = compiled.memory_analysis()
    if isinstance(ma, (list, tuple)):
        ma = ma[0]

    state, m_ = compiled(state, placed)      # prime (binds metrics too)
    for _ in range(max(0, warmup - 1)):
        state, m_ = compiled(state, placed)
    jax.block_until_ready(state.params)

    def timed():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m_ = compiled(state, placed)
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    # robust_time rejects faster-than-roofline readings; a suspect
    # cell must never pick the winning impl/block for the gate re-base
    dt, suspect = robust_time(timed, steps=steps)
    step_ms = dt / steps * 1e3
    return {
        "model": model_name, "batch": b, "seq": s, "impl": impl,
        "size": size or None,
        "step_ms": round(step_ms, 1),
        "eps_chip": round(b / (dt / steps), 2),
        # CPU jax builds lack the peak stat; 0 = unavailable, not "fits"
        "temp_MiB": round(getattr(ma, "temp_size_in_bytes", 0) / 2**20),
        "peak_MiB": round(getattr(ma, "peak_memory_in_bytes", 0) / 2**20),
        "loss_finite": bool(np.isfinite(float(jax.device_get(
            m_["loss"])))),
        "suspect": bool(suspect),
    }


def main() -> None:
    if sys.argv[1:2] == ["--roofline"]:
        roofline()
        return
    if sys.argv[1:2] == ["--all"]:
        cells = []
        for b, s in SHAPES:
            cells.append(("cell", "gpt", b, s, "full", 0))
            cells.append(("cell", "gpt", b, s, "chunked", CHUNK))
            cells += [("cell", "gpt", b, s, "fused", blk)
                      for blk in BLOCKS]
        run_cells(os.path.abspath(__file__), cells)
        return
    if sys.argv[1:2] == ["--smoke"]:
        # tiny CPU end-to-end pass: every impl executes and emits the
        # JSON contract; block 200 exercises the vocab-not-divisible
        # padding (gpt_tiny vocab = 1000). b8 so the batch still shards
        # when the host is a virtual 8-device CPU mesh (the test rig)
        cells = [("cell", "gpt_tiny", 8, 64, "full", 0),
                 ("cell", "gpt_tiny", 8, 64, "chunked", 32),
                 ("cell", "gpt_tiny", 8, 64, "fused", 128),
                 ("cell", "gpt_tiny", 8, 64, "fused", 200)]
        run_cells(os.path.abspath(__file__), cells,
                  env=dict(os.environ, VOCAB_SWEEP_CPU="1",
                           JAX_PLATFORMS="cpu"))
        return
    if sys.argv[1:2] != ["cell"]:
        raise SystemExit(__doc__)
    mn, b, s, impl = (sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
                      sys.argv[5])
    size = int(sys.argv[6]) if len(sys.argv) > 6 else 0
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        print(json.dumps(measure(mn, b, s, impl, size)), flush=True)
    except Exception as e:  # noqa: BLE001 — OOM at compile is a finding
        fail({"model": mn, "batch": b, "seq": s, "impl": impl,
              "size": size or None}, e)


if __name__ == "__main__":
    main()
