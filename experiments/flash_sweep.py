#!/usr/bin/env python
"""Flash-attention kernel lever sweep + long-context analytic roofline
(VERDICT r5 missing #1 / weak #2, ISSUE 2 tentpole).

The long-context gate family (bert_long/gpt_long, S=4096 b4) is the one
family with no profile and no analytic bound: the gate numbers imply
~10% of peak with nothing explaining the other 90%, and the prime
suspect is the Pallas flash kernel's hardwired DEFAULT_BLOCK=128 grid —
~2 MFLOP per grid-step matmul, ~1.8M grid steps per train step at the
gate shape (see ``--roofline``), small enough that Mosaic per-step
overhead plausibly dominates. ``block_q``/``block_k``/``bwd_block``/
``bwd_variant`` existed as parameters no caller ever varied; they are
now plumbed through config/CLI (``--attention_block_q`` etc.) and this
script sweeps them.

Modes (one JSON line per measured cell; fresh process per cell via
``--all``, so a compile-time OOM costs one row and no cell inherits
another's device memory):

  --roofline         analytic model, runs anywhere: dense + attention
                     FLOPs, kernel HBM streaming bytes AS A FUNCTION OF
                     BLOCK SIZE, grid-step counts, and the implied
                     MXU/HBM/overhead floors per (S, block, variant);
                     BASELINE.md "Long-context envelope" discusses it.
  cell MODEL S IMPL [BLOCK] [VARIANT] [BWD_BLOCK]
                     one measured cell on the current backend: step
                     time, eps/chip, temp/peak MiB, MFU (analytic basis
                     when the kernel engages). IMPL: xla | flash.
                     BLOCK sets both forward tiles (0 = the kernel's
                     choice); VARIANT auto (the kernel's choice) |
                     split | fused, as in TrainConfig; the row records
                     the variant that ran.
                     Records OOM as an error line — the flash-vs-XLA
                     crossover table needs the OOM rows too.
  --all              the committed grid: MODEL x S in {512, 1024, 2048,
                     4096} x (xla + flash blocks {128, 256, 512} x
                     bwd {split, fused}); b=4 long-context batch.
  --trace DIR MODEL  5-step profiler capture of the S=4096 b4 gate
                     step (reduce with utils.trace_summary).
  kernels [OUT]      the kernels alone, one process (PR 25): one layer's
                     attention forward + backward at the gpt2s-train
                     cell's shape (B=16, S=1024, H=12, D=64, causal) for
                     tiles {256, 512, 1024}^2 x {split, fused} x {bf16,
                     f32 operands}, device ms BY KERNEL NAME from a
                     profiler capture of each row, plus the parent's
                     128 tiles, --attention xla, the schedule's own
                     choice, one ablation (dead causal steps fetching
                     again) and the other shapes the schedule serves
                     (the serving prefill, GPT-small at S=512, D=128,
                     S=2048 and 4096). Rows also go to OUT (JSON lines).
                     ``flash_schedule`` is what the rows chose from.
  ragged [OUT]       the expert layer's grouped matmul alone (PR 28): one
                     bfloat16 ``lax.ragged_dot`` over 128 groups at the
                     widths ``sdar-serve-backlog`` runs, 1,024 to 32,768
                     pairs, under XLA's own tile and each
                     ``ragged_dot_tiling`` of the grid: device ms of
                     ``ragged-dot*`` and ``max_abs_diff`` against XLA's
                     own. ``ops/moe.ragged_tiling`` chose from these rows.
  ragged OUT wide    the same at the widths ``kimi-serve-backlog`` runs
                     (PR 32): 128 groups of [2304, 1024] and [1024, 2304],
                     4 and 32 rows a group in 1,024 and 8,192 rows (half
                     of a step's pairs name experts held elsewhere and
                     sort past the last group, as in the served step).
  ragged OUT dots3   the same at the widths ``dots3-serve-longctx`` runs
                     (PR 38): 32 groups of [5120, 1536] and [1536, 5120],
                     1,024 grouped rows in 8,192 (the chunk's pairs),
                     2,048 (its bounded buffer) and 1,024, under the
                     tiles that split K or N (``DOTS3_TILES``).
  paged [OUT [PARENT_PY]]
                     the one-token paged attention kernels alone (PR 34):
                     ``paged_decode_attn`` by NAME at 16 to 256 rows of
                     6-entry tables over a [12 * 385, 128, H*D] pool, live
                     context 128 / 256 / 768, the backlog cell's lognormal
                     mix and the chat cell's (a fifth of the rows alive),
                     12 x 64 and 6 x 128 heads, bf16 and int8 pools, under
                     ``paged_schedule``'s choice and each other number of
                     table entries a grid step; the live rows' bytes over
                     819 GB/s beside each; ``max_abs_diff`` against the
                     XLA gather. PARENT_PY: a copy of an older
                     ``decode_attention.py`` to time at the same inputs
                     (``impl: parent``). Also ``paged_block_attn`` and
                     ``paged_latent_attn`` at their cells' shapes, for the
                     issues that take them up (this sweep changed neither).
                     ``paged_schedule`` is what the rows chose from.

The measured columns are TPU columns: off-TPU the kernels run in Pallas
interpret mode (orders of magnitude slow, numbers meaningless), so
--all/--cell refuse to print a table row off-TPU unless FLASH_SWEEP_CPU=1
(CI smoke only). --roofline is platform-independent.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

MODELS = ("bert", "gpt")
SEQS = (512, 1024, 2048, 4096)
BLOCKS = (128, 256, 512)
VARIANTS = ("split", "fused")
BATCH = 4                      # the long-context gate batch
PEAK_FLOPS = 197e12            # v5e bf16
HBM_BPS = 819e9                # v5e
#: Mosaic per-grid-step overhead bracket (µs) for the predicted-floor
#: column: TPU kernel-dispatch folklore puts sequential-grid step cost
#: at a few hundred ns to ~1 µs; the sweep MEASURES where reality sits.
OVERHEAD_US = (0.3, 1.0)


# ---------------------------------------------------------------------------
# analytic model — every formula mirrors the kernel/model structure
# ---------------------------------------------------------------------------

def model_shapes(model: str) -> dict:
    # bert-base / gpt-small bodies are the same trunk shape
    return dict(hidden=768, layers=12, heads=12, head_dim=64,
                intermediate=3072, vocab=30522,
                max_predictions=20 if model == "bert" else None)


def dense_train_flops(model: str, b: int, s: int) -> float:
    """Exact matmul FLOPs of the non-attention trunk for one train step
    (fwd x3: backward costs 2x forward for matmuls). Embedding gathers
    and elementwise chains are excluded — they are byte-, not
    FLOP-bound."""
    m = model_shapes(model)
    h, i, L, v = m["hidden"], m["intermediate"], m["layers"], m["vocab"]
    per_layer_fwd = (4 * 2 * b * s * h * h        # QKV + O projections
                     + 2 * 2 * b * s * h * i)     # FFN in/out
    trunk = 3.0 * per_layer_fwd * L
    if model == "bert":
        t = b * m["max_predictions"]              # masked positions only
        head = 3.0 * (2 * t * h * h + 2 * t * h * v)
    else:
        # full-vocab logits chain; the gate config chunks the loss at
        # S=4096 (lm_loss_chunk=512): logits fwd + checkpoint recompute
        # + bwd = 4x one pass
        head = 4.0 * 2 * b * s * h * v
    return trunk + head


def attn_stream_bytes(b: int, s: int, heads: int, d: int, blk_q: int,
                      blk_k: int, bwd_block: int, variant: str,
                      *, op_bytes: int = 2) -> float:
    """HBM bytes the flash kernels move per train step PER LAYER — the
    block-size-controlled term. K/V do not fit VMEM at long S, so the
    fwd grid re-fetches them once per Q block (nq times); the split
    backward re-streams K/V again (dq kernel) AND Q/dO nk times (dkv
    kernel); the fused backward drops the K/V re-stream. Row/output
    traffic (Q, O, lse, dq/dk/dv writes) is streamed once and included.
    """
    bq, bk = (bwd_block or blk_q), (bwd_block or blk_k)
    bh = b * heads
    sd = bh * s * d * op_bytes                    # one full Q/K/V/O pass
    nq, nk = s // blk_q, s // blk_k
    nq_b, nk_b = s // bq, s // bk
    fwd = sd * (1 + 1) + sd * 2 * nq + bh * s * 4          # Q,O + K,V + lse
    if variant == "split":
        dq = sd * (2 + 1) + sd * 2 * nq_b                  # Q,dO,dq + K,V
        dkv = sd * (2 + 2) + sd * 2 * nk_b                 # K,V,dk,dv + Q,dO
        bwd = dq + dkv
    else:
        bwd = sd * (2 + 3) + sd * 2 * nk_b     # K,V once; dq,dk,dv; Q,dO
    return fwd + bwd


def grid_steps(b: int, s: int, heads: int, blk_q: int, blk_k: int,
               bwd_block: int, variant: str) -> int:
    """Grid steps per train step per layer. NOTE: causal saves ~half the
    FLOPs but none of these steps — dead blocks still pay the per-step
    cost (the @pl.when guard skips compute, not the step)."""
    bq, bk = (bwd_block or blk_q), (bwd_block or blk_k)
    bh = b * heads
    fwd = bh * (s // blk_q) * (s // blk_k)
    bwd = bh * (s // bq) * (s // bk)
    return fwd + bwd * (2 if variant == "split" else 1)


def roofline_row(model: str, b: int, s: int, blk: int, variant: str,
                 bwd_block: int = 0) -> dict:
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import attention_train_flops

    m = model_shapes(model)
    causal = model == "gpt"
    dense = dense_train_flops(model, b, s)
    attn = attention_train_flops(b, s, m["hidden"], m["layers"],
                                 causal=causal, bwd_variant=variant)
    stream = m["layers"] * attn_stream_bytes(
        b, s, m["heads"], m["head_dim"], blk, blk, bwd_block, variant)
    steps = m["layers"] * grid_steps(b, s, m["heads"], blk, blk,
                                     bwd_block, variant)
    mxu_ms = (dense + attn) / PEAK_FLOPS * 1e3
    hbm_ms = stream / HBM_BPS * 1e3
    ovh_ms = tuple(round(steps * us / 1e3, 1) for us in OVERHEAD_US)
    return {
        "model": model, "seq": s, "batch": b, "block": blk,
        "bwd_variant": variant,
        "dense_TF": round(dense / 1e12, 2),
        "attn_TF": round(attn / 1e12, 2),
        "attn_stream_GB": round(stream / 1e9, 1),
        "grid_steps_k": round(steps / 1e3),
        "mxu_floor_ms": round(mxu_ms, 1),
        "attn_hbm_floor_ms": round(hbm_ms, 1),
        "overhead_ms_at_0.3_1.0us": ovh_ms,
    }


def roofline() -> None:
    print("# Analytic long-context roofline (v5e: 197 TFLOP/s bf16, "
          "819 GB/s HBM)")
    print("# dense/attn TF = executed TFLOP per train step; "
          "attn_stream_GB = kernel HBM bytes (block-controlled); "
          "grid_steps_k = Pallas grid steps (overhead-controlled)")
    for model in MODELS:
        for s in SEQS:
            for blk in BLOCKS:
                for variant in VARIANTS:
                    print(json.dumps(roofline_row(model, BATCH, s, blk,
                                                  variant)))


# ---------------------------------------------------------------------------
# measured cells
# ---------------------------------------------------------------------------

def measure(model_name: str, seq: int, impl: str, block: int,
            variant: str, bwd_block: int, *, batch=BATCH, steps=6,
            warmup=2) -> dict:
    import time

    import jax
    import numpy as np

    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           OptimizerConfig,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import (attention_train_flops, effective_bwd_variant,
                kernel_engages)
    from distributed_tensorflow_example_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_example_tpu.parallel.sync_replicas import (
        SyncReplicas)
    from distributed_tensorflow_example_tpu.train.optimizers import (
        make_optimizer)

    # shared with the gate: ONE batch-builder and ONE timing
    # implementation (decode_roofline.py:88 principle) — sweep cells
    # must measure exactly what the bench rows measure
    from bench import _gpt_batch_at, _long_batch, robust_time

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not os.environ.get("FLASH_SWEEP_CPU"):
        raise SystemExit("measured cells are TPU cells (interpret-mode "
                         "Pallas timings are meaningless); set "
                         "FLASH_SWEEP_CPU=1 for a CI smoke run")
    cfg = TrainConfig(model=model_name, dtype="bfloat16",
                      data=DataConfig(batch_size=batch, seq_len=seq),
                      optimizer=OptimizerConfig(name="adamw",
                                                learning_rate=1e-4),
                      attention_impl=impl, remat="none",
                      attention_block_q=block if impl == "flash" else 0,
                      attention_block_k=block if impl == "flash" else 0,
                      attention_bwd_block=bwd_block,
                      attention_bwd=variant if impl == "flash" else "auto",
                      lm_loss_chunk=512 if model_name == "gpt" else None)
    model = get_model(model_name, cfg)
    mesh = build_mesh()
    sync = SyncReplicas(model.loss, make_optimizer(cfg.optimizer), mesh)
    state = sync.init(model.init, seed=0,
                      prng_impl="rbg" if on_tpu else None)
    make_batch = _gpt_batch_at(seq) if model_name == "gpt" else _long_batch
    placed = sync.shard_batch(make_batch(model, batch, 0))
    compiled = sync.step.lower(state, placed).compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    basis = "cost_analysis"
    ms = model_shapes(model_name)
    # add the in-kernel FLOPs only when the kernel ENGAGES — on the XLA
    # fallback (non-tileable shape) cost_analysis already counts the
    # attention einsums and adding the analytic number would double-count
    # (and over-raise robust_time's impossibility floor)
    ran = None
    if impl == "flash" and kernel_engages(
            seq, ms["head_dim"], block_q=block, block_k=block,
            bwd_block=bwd_block):
        # count what EXECUTES: auto is the schedule's choice, and fused
        # degrades to split past the VMEM slab limit
        ran = effective_bwd_variant(
            seq, ms["head_dim"], None if variant == "auto" else variant,
            cfg.dtype)
        flops += attention_train_flops(
            batch, seq, ms["hidden"], ms["layers"],
            causal=model_name == "gpt", bwd_variant=ran)
        basis = "analytic"

    # one untimed priming step binds the metrics for loss_finite even at
    # warmup=0 (the --trace window), then the remaining warmup
    state, m_ = compiled(state, placed)
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

    # bench.robust_time rejects faster-than-roofline readings via the
    # analytic-FLOP impossibility check and flags what it cannot fix
    # — a suspect cell must never pick the winning block for the gate
    # re-base (BASELINE.md "Long-context envelope" decision rule)
    dt, suspect = robust_time(timed, steps=steps, flops=flops or None,
                              peak=PEAK_FLOPS if on_tpu else None)
    step_ms = dt / steps * 1e3
    return {
        "model": model_name, "seq": seq, "impl": impl,
        "block": block if impl == "flash" else None,
        "bwd_variant": ran,
        "bwd_block": bwd_block or None,
        "step_ms": round(step_ms, 1),
        "eps_chip": round(batch / (dt / steps), 2),
        # CPU jax builds lack the peak stat; 0 = unavailable, not "fits"
        "temp_MiB": round(getattr(ma, "temp_size_in_bytes", 0) / 2**20),
        "peak_MiB": round(getattr(ma, "peak_memory_in_bytes", 0) / 2**20),
        "mfu": round(flops / (dt / steps) / PEAK_FLOPS, 4) if flops
        else None,
        "mfu_basis": basis,
        "loss_finite": bool(np.isfinite(float(jax.device_get(
            m_["loss"])))),
        "suspect": bool(suspect),
    }


# ---------------------------------------------------------------------------
# the kernels alone (PR 25): device ms by kernel name from a capture
# ---------------------------------------------------------------------------

CELL_SHAPE = (16, 1024, 12, 64)         # gpt2s-train: B, S, H, D
TILES = (256, 512, 1024)


def kernel_rows() -> list[dict]:
    """What ``kernels`` measures, in order. A row: ``shape`` (B, S, H, D),
    ``dtype``, ``impl``, forward tiles = backward tiles ``blk_q`` x
    ``blk_k`` (None = the schedule's), ``variant``, ``masked``, ``grad``,
    ``ablate`` (names of module functions replaced for the row)."""
    base = dict(shape=CELL_SHAPE, dtype="bfloat16", impl="flash",
                causal=True, masked=False, grad=True, ablate=())
    rows = [dict(base, blk_q=None, blk_k=None, variant=None),
            dict(base, impl="xla", blk_q=None, blk_k=None, variant=None)]
    for dtype in ("bfloat16", "float32"):
        for bq in TILES:
            for bk in TILES:
                rows += [dict(base, dtype=dtype, blk_q=bq, blk_k=bk,
                              variant=v) for v in VARIANTS]
    rows += [dict(base, blk_q=128, blk_k=128, variant=v) for v in VARIANTS]
    # dead causal steps fetching a new block again, as before PR 25
    rows += [dict(base, blk_q=512, blk_k=512, variant="fused",
                  ablate=("no_clamp",)),
             dict(base, blk_q=128, blk_k=128, variant="split",
                  ablate=("no_clamp",))]
    # D=128 at the same hidden size
    for blk in (512, 1024):
        rows += [dict(base, shape=(16, 1024, 6, 128), blk_q=blk, blk_k=blk,
                      variant=v) for v in VARIANTS]
    # the serving prefill: one prompt, S=512, key mask + causal, no grad
    for blk in (128, 256, 512):
        rows.append(dict(base, shape=(1, 512, 12, 64), masked=True,
                         grad=False, blk_q=blk, blk_k=blk, variant="split"))
    # ROADMAP S5's flip rule: GPT-small at S=512, batch 32, against XLA
    s512 = dict(base, shape=(32, 512, 12, 64), blk_q=None, blk_k=None,
                variant=None)
    rows += [s512, dict(s512, impl="xla"),
             dict(s512, blk_q=128, blk_k=128, variant="split")]
    # longer sequences at the long-context batch
    for seq in (2048, 4096):
        for blk in (512, 1024):
            rows += [dict(base, shape=(BATCH, seq, 12, 64), blk_q=blk,
                          blk_k=blk, variant=v) for v in VARIANTS]
    return rows


def _capture(call, args, iters: int) -> dict:
    """Warm ``call`` up, capture ``iters`` calls of it and reduce the
    capture (``benchmark.trace_reduce``)."""
    import shutil
    import tempfile

    import jax

    from benchmark import trace_reduce

    for _ in range(2):
        jax.block_until_ready(call(*args))
    tmp = tempfile.mkdtemp(prefix="flash_sweep_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for _ in range(iters):
                out = call(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        return trace_reduce.reduce(trace_reduce.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure_kernels(row: dict, *, iters: int = 5) -> dict:
    """One row: compile, warm up, capture ``iters`` calls, and read the
    device ms per call of each kernel by name (``flash_fwd``,
    ``flash_bwd_*``), of everything else in the program, and of the
    whole program."""
    import importlib
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce
    from distributed_tensorflow_example_tpu.ops.attention import (
        multi_head_attention)
    fm = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.pallas.flash_attention")

    b, s, h, d = row["shape"]
    dtype = jnp.dtype(row["dtype"])
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(b, s, h, d) * 0.5, dtype)
               for _ in range(3))
    mask = None
    if row["masked"]:
        m = np.ones((b, s), np.int32)
        m[:, s - s // 4:] = 0
        mask = jnp.asarray(m)
    saved = {n: getattr(fm, n) for n in ("_k_stream", "_q_stream")}
    if "no_clamp" in row["ablate"]:
        fm._k_stream = fm._q_stream = lambda *a: (lambda i, j: j)
    fm._make_flash.cache_clear()
    sch = fm.resolve_schedule(s, d, dtype)
    if row["blk_q"]:
        sch = fm.FlashSchedule(row["blk_q"], row["blk_k"], row["blk_q"],
                               row["blk_k"], row["variant"])
    try:
        if row["impl"] == "xla":
            def attend(q, k, v):
                m4 = None if mask is None else mask[:, None, None, :]
                return multi_head_attention(q, k, v, mask=m4,
                                            causal=row["causal"])
        else:
            fn = fm._make_flash(h, *sch, row["causal"], mask is not None)
            mask2 = mask if mask is not None else jnp.ones((b, s),
                                                           jnp.int32)

            def fold(x):
                return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

            def attend(q, k, v):
                return fn(fold(q), fold(k), fold(v), mask2)

        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

        call = jax.jit(jax.grad(loss, argnums=(0, 1, 2)) if row["grad"]
                       else attend)
        red = _capture(call, (q, k, v), iters)
    finally:
        for n, f in saved.items():
            setattr(fm, n, f)
        fm._make_flash.cache_clear()

    def ms(pattern):
        return round(trace_reduce.op_seconds(red, pattern=pattern)
                     / iters * 1e3, 4)

    kernels = {n: round(o["seconds"] / iters * 1e3, 4)
               for n, o in sorted(red["all_ops"].items())
               if re.search("flash_", n)}
    busy = red["busy_s"] / iters * 1e3
    out = {k_: row[k_] for k_ in ("shape", "dtype", "impl", "masked",
                                  "grad")}
    out["ablate"] = list(row["ablate"])
    if row["impl"] == "flash":
        out.update(fwd=f"{sch.blk_q}x{sch.blk_k}",
                   bwd=f"{sch.bwd_variant} {sch.bwd_q}x{sch.bwd_k}",
                   chosen=row["blk_q"] is None,
                   flash_fwd_ms=ms("flash_fwd"), flash_bwd_ms=ms("flash_bwd"),
                   kernels_ms=kernels)
    out.update(program_ms=round(busy, 4),
               other_ms=round(busy - sum(kernels.values()), 4))
    return out


# ---------------------------------------------------------------------------
# the expert layer's grouped matmul alone (PR 28): device ms by tile
# ---------------------------------------------------------------------------

#: ``ragged OUT dots3``: the tiles compiled for a described v5e before
#: the call (K or N split: the whole [5120, 1536] matrix is 36 MB)
DOTS3_TILES = {
    (5120, 1536): ("128,5120,512", "128,5120,384", "128,5120,256",
                   "128,2560,768", "128,2560,512", "128,1280,1536",
                   "128,1024,1536"),
    (1536, 5120): ("128,1536,1280", "128,1536,1024", "128,1536,640",
                   "128,1536,512", "128,768,2560", "256,1536,1024"),
}


def ragged_rows(wide: str | None = None) -> list[dict]:
    """What ``ragged`` measures, in order: ``m`` pairs over 128 groups of
    ``[k, n]`` weights, ``skew`` (group sizes from a Dirichlet(0.3) draw
    in place of a uniform one), ``tiling`` (None = XLA's own, first for
    each shape: the others are compared with its result). ``wide``: the
    other configurations' widths, ``m`` rows of which ``grouped`` lie in
    a group (``"wide"`` Kimi's, ``"dots3"`` dots3-note-prev's 32 held
    experts)."""
    rows = []
    if wide == "laguna":
        # Laguna-S-2.1's 32 held experts of [3072, 1024]: a chunk's
        # 10,240 pairs and their bound of 2,560 (1,280 held under even
        # routing), a decode step's 240 (30 held; no tile divides it)
        for k, n in ((3072, 1024), (1024, 3072)):
            tiles = [f"128,{k},{n}", f"256,{k},{n}", f"128,{k},{n // 2}",
                     f"256,{k},{n // 2}", f"128,{k},{n // 4}",
                     f"64,{k},{n}", f"128,{k // 2},{n}"]
            if n % 384 == 0:
                tiles.append(f"128,{k},{n // 3}")
            for m, grouped in ((10240, 1280), (2560, 1280), (256, 32)):
                rows += [dict(m=m, k=k, n=n, groups=32, skew=False,
                              grouped=grouped, tiling=t)
                         for t in (None, *tiles)]
            rows += [dict(m=2560, k=k, n=n, groups=32, skew=True,
                          grouped=1280, tiling=t)
                     for t in (None, tiles[0], tiles[2])]
            rows += [dict(m=240, k=k, n=n, groups=32, skew=False,
                          grouped=30, tiling=None)]
        return rows
    if wide == "dots3":
        for (k, n), tiles in DOTS3_TILES.items():
            for m, grouped in ((8192, 1024), (2048, 1024), (1024, 1024)):
                rows += [dict(m=m, k=k, n=n, groups=32, skew=False,
                              grouped=grouped, tiling=t)
                         for t in (None, *tiles)]
                rows += [dict(m=m, k=k, n=n, groups=32, skew=True,
                              grouped=grouped, tiling=t)
                         for t in (None, *tiles[:2], tiles[3])]
        return rows
    if wide:
        for k, n in ((2304, 1024), (1024, 2304)):
            for m, grouped in ((1024, 512), (8192, 4096)):
                tiles = [f"{tm},{k},{tn}" for tm in (128, 256)
                         for tn in (n, 512, 256)] + [f"64,{k},{n}",
                                                     f"128,{k // 2},{n}"]
                rows += [dict(m=m, k=k, n=n, groups=128, skew=False,
                              grouped=grouped, tiling=t)
                         for t in [None] + tiles]
                rows += [dict(m=m, k=k, n=n, groups=128, skew=True,
                              grouped=grouped, tiling=t)
                         for t in (None, f"128,{k},{n}")]
        return rows
    for k, n, tns in ((2048, 768, (256, 768)),
                      (768, 2048, (256, 512, 1024, 2048))):
        for m in (1024, 2048, 4096, 8192, 16384, 32768):
            tiles = [f"{tm},{k},{tn}" for tm in (128, 256, 512)
                     for tn in tns]
            if m in (2048, 32768):      # the cell's shapes: split K, 64 rows
                tiles += [f"128,{k // 2},{n}", f"256,{k // 2},256",
                          f"64,{k},{n}"]
            rows += [dict(m=m, k=k, n=n, groups=128, skew=False, tiling=t)
                     for t in [None] + tiles]
            if m in (2048, 16384):
                rows += [dict(m=m, k=k, n=n, groups=128, skew=True, tiling=t)
                         for t in (None, f"128,{k},{n}", f"256,{k},{tns[0]}",
                                   f"256,{k},{tns[-1]}")]
    return rows


def measure_ragged(row: dict, held: dict, *, iters: int = 5) -> dict:
    """One row: device ms a call of the ``ragged-dot*`` operations, and
    the widest difference from the result under XLA's own tile. ``held``
    keeps the operands and that result for the shape in hand (made anew
    by each row whose ``tiling`` is None)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce
    from distributed_tensorflow_example_tpu.ops.moe import ragged_dot_tiled

    m, k, n, g = (row[x] for x in ("m", "k", "n", "groups"))
    call = jax.jit(functools.partial(ragged_dot_tiled, tile=row["tiling"]))
    if row["tiling"] is None:
        rs = np.random.RandomState(m + k)
        share = rs.dirichlet([0.3] * g) if row["skew"] else [1 / g] * g
        grouped = row.get("grouped", m)     # the rest lie past the groups
        ka, kw = jax.random.split(jax.random.key(m + k))
        held["args"] = (
            jax.random.normal(ka, (m, k), jnp.bfloat16) * 0.5,
            jax.random.normal(kw, (g, k, n), jnp.bfloat16) * 0.02,
            jnp.asarray(rs.multinomial(grouped, share), jnp.int32))
        held["out"] = call(*held["args"])
    red = _capture(call, held["args"], iters)
    return dict(row, tiling=row["tiling"] or "xla",
                ms=round(trace_reduce.op_seconds(red, pattern="ragged-dot")
                         / iters * 1e3, 4),
                program_ms=round(red["busy_s"] / iters * 1e3, 4),
                max_group=int(held["args"][2].max()),
                max_abs_diff=float(jnp.max(jnp.abs(
                    (call(*held["args"]) - held["out"])[
                        :row.get("grouped", m)]))))


# ---------------------------------------------------------------------------
# the one-token paged attention kernels alone (PR 34): device ms by schedule
# ---------------------------------------------------------------------------

PAGED_CELL = dict(layers=12, blocks=385, width=6, block=128)


def paged_rows() -> list[dict]:
    """What ``paged`` measures, in order. A row: ``kernel``, ``rows``,
    ``heads`` x ``head_dim``, ``ctx`` (every row's live context, or
    ``backlog`` / ``chat``: lognormal contexts of mean ~212, in ``chat``
    under a fifth of the rows alive and the others at pos 0 on the null
    block), ``dtype`` of the pool, ``entries`` a grid step (None = the
    schedule's), ``impl`` (``parent``: the older module's kernel)."""
    base = dict(kernel="paged_decode_attn", rows=64, heads=12, head_dim=64,
                ctx="backlog", dtype="bfloat16", entries=None,
                impl="change")
    rows = []
    for ctx in ("backlog", "chat", 256, 768, 128):
        rows += [dict(base, ctx=ctx, entries=e) for e in (None, 1, 2, 3)]
        rows.append(dict(base, ctx=ctx, impl="parent"))
    for n in (16, 128):
        rows += [dict(base, rows=n, ctx=ctx) for ctx in ("backlog", 768)]
    rows.append(dict(base, rows=256, ctx="backlog"))    # a verify program's
    for ctx in ("backlog", 768):
        rows += [dict(base, heads=6, head_dim=128, ctx=ctx, entries=e)
                 for e in (None, 1)]
        rows += [dict(base, dtype="int8", ctx=ctx, entries=e)
                 for e in (None, 1)]
        rows.append(dict(base, dtype="int8", ctx=ctx, impl="parent"))
    # the other two kernels at their cells' shapes, as they are
    rows += [dict(kernel="paged_block_attn", rows=64, heads=4, head_dim=128,
                  lanes=32, width=34, ctx=ctx, dtype="bfloat16",
                  entries=None, impl="change") for ctx in (1280, 4352)]
    rows += [dict(kernel="paged_latent_attn", rows=128, heads=32,
                  head_dim=640, width=136, ctx=ctx, dtype="bfloat16",
                  entries=None, impl="change") for ctx in (3200, 17408)]
    return rows


def _paged_inputs(row: dict, blocks: int, width: int, block: int):
    """Block tables, pos and what the live rows hold: every live entry
    its own pool block (scattered, as the engine's allocator leaves
    them), dead entries and dead rows on the null block 0."""
    import numpy as np

    rs = np.random.RandomState(34)
    n, ctx = row["rows"], row["ctx"]
    if isinstance(ctx, int):
        pos = np.full(n, ctx - 1)
    else:
        pos = np.clip(rs.lognormal(np.log(170.0), 0.7, n), 16,
                      width * block - 1).astype(np.int64)
        if ctx == "chat":
            pos[rs.permutation(n)[max(1, n // 5):]] = 0
    live = pos // block + 1
    ids = rs.permutation(np.arange(1, blocks))[:int(live.sum())]
    bt = np.zeros((n, width), np.int32)
    at = 0
    for r in range(n):
        if pos[r]:
            bt[r, :live[r]] = ids[at:at + live[r]]
            at += live[r]
    return bt, pos.astype(np.int32), int(live.sum())


def measure_paged(row: dict, parent, *, iters: int = 5) -> dict:
    """One row: device ms a call of the kernel by NAME, of the rest of the
    program (the fetch table's operations), and the live rows' K and V
    bytes over the chip's bandwidth."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce
    from distributed_tensorflow_example_tpu.ops import mla
    dm = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.pallas.decode_attention")

    h, d, bs = row["heads"], row["head_dim"], PAGED_CELL["block"]
    width = row.get("width", PAGED_CELL["width"])
    n = row["rows"]
    # GPT-2's pool as the cells hold it; the other kernels' hold a block
    # for every table entry
    blocks = (PAGED_CELL["layers"] * PAGED_CELL["blocks"]
              if row["kernel"] == "paged_decode_attn" else n * width + 1)
    if os.environ.get("FLASH_SWEEP_CPU"):
        blocks = min(blocks, 64)
    bt, pos, live = _paged_inputs(row, blocks, width, bs)
    key = jax.random.key(n + h)
    dtype = jnp.dtype(row["dtype"])
    out = dict(row)
    if row["kernel"] == "paged_latent_attn":
        pool = jax.random.normal(key, (blocks, bs, d), dtype)
        q = jax.random.normal(key, (n, h, d), dtype) * 0.05
        call = jax.jit(functools.partial(mla._latent_dispatch, rank=512))
        args = (q, pool, jnp.asarray(bt), jnp.asarray(pos))
        want = mla.xla_latent_attention(q, pool, block_tables=args[2],
                                        last=args[3], rank=512)
        token_bytes = d * 2
    elif row["kernel"] == "paged_block_attn":
        pool = jax.random.normal(key, (blocks, bs, h * d), dtype)
        q = jax.random.normal(key, (n, h, row["lanes"], d), dtype)
        call = jax.jit(dm._block_dispatch)
        args = (q, pool, pool, jnp.asarray(bt), jnp.asarray(pos))
        want = dm.xla_paged_block_attention(
            q, pool, pool, block_tables=args[3], last=args[4])
        token_bytes = 2 * h * d * 2
    else:
        kf = jax.random.normal(key, (blocks, bs, h * d), jnp.float32)
        q = jax.random.normal(key, (n, h, d), jnp.bfloat16)
        scales = ()
        if dtype == jnp.int8:
            sc = jnp.max(jnp.abs(kf), axis=-1) / 127.0
            pool = jnp.round(kf / sc[..., None]).astype(jnp.int8)
            scales = (sc, sc)
        else:
            pool = kf.astype(dtype)
        del kf
        mod = parent if row["impl"] == "parent" else dm
        kw = {}
        if row["entries"]:
            kw["schedule"] = dm.PagedSchedule(row["entries"], 0)
        call = jax.jit(functools.partial(mod._paged_dispatch, **kw))
        args = (q, pool, pool, jnp.asarray(bt), jnp.asarray(pos),
                jnp.zeros((n,), jnp.int32)) + scales
        want = dm.xla_paged_decode_attention(
            q, pool, pool, block_tables=args[3], pos=args[4], pad=args[5],
            **(dict(k_scale=sc, v_scale=sc) if scales else {}))
        token_bytes = 2 * h * d * dtype.itemsize
        if row["impl"] == "change":
            sch = dm.paged_schedule(n, h, d, bs, width, dtype)
            out.update(chosen=row["entries"] is None,
                       entries=row["entries"] or sch.entries)
            out["grid_steps"] = n * width // out["entries"]
        else:
            out["grid_steps"] = n * (h // max(1, 128 // d)) * width
    red = _capture(call, args, iters)
    got = call(*args)
    kv_bytes = int(np.sum(pos.astype(np.int64) + 1)) * token_bytes
    ms = trace_reduce.op_seconds(red, pattern=row["kernel"]) / iters * 1e3
    out.update(
        live_blocks=live, ms=round(ms, 4),
        program_ms=round(red["busy_s"] / iters * 1e3, 4),
        other_ms=round(red["busy_s"] / iters * 1e3 - ms, 4),
        kv_mb=round(kv_bytes / 1e6, 2),
        roofline_pct=round(kv_bytes / HBM_BPS / (ms / 1e3) * 100, 2)
        if ms else None,
        max_abs_diff=float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)))))
    return out


def _load_parent(path: str):
    """An older ``decode_attention.py`` as a sibling of the package's own
    (its relative imports resolve there)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "distributed_tensorflow_example_tpu.ops.pallas."
        "decode_attention_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernels(out_path: str | None, mode: str = "kernels",
            wide: str | None = None, parent_py: str | None = None) -> None:
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not os.environ.get("FLASH_SWEEP_CPU"):
        raise SystemExit("kernel rows are TPU rows (interpret-mode Pallas "
                         "timings are meaningless); set FLASH_SWEEP_CPU=1 "
                         "for a CI smoke run")
    if mode == "ragged":
        rows, held = ragged_rows(wide), {}
        if not on_tpu:                   # smoke: the control flow only
            rows = [dict(r, m=64, k=128, n=128, groups=4, grouped=64,
                         tiling=r["tiling"] and "128,128,128")
                    for r in rows[:3]]
    elif mode == "paged":
        parent = _load_parent(parent_py) if parent_py else None
        rows = [r for r in paged_rows()
                if parent is not None or r["impl"] != "parent"]
        if not on_tpu:                   # smoke: the control flow only
            rows = [dict(r, rows=4, heads=2, ctx=r["ctx"] if isinstance(
                r["ctx"], str) else 256) for r in rows[:7] + rows[-4:]]
    else:
        rows = kernel_rows()
        if not on_tpu:                   # smoke: the control flow only
            rows = [dict(r, shape=(1, 256, 2, 64),
                         blk_q=r["blk_q"] and 128, blk_k=r["blk_k"] and 128)
                    for r in rows[:4]]
    sink = open(out_path, "w") if out_path else None
    failed = 0
    for row in rows:
        try:
            line = (measure_ragged(row, held) if mode == "ragged"
                    else measure_paged(row, parent) if mode == "paged"
                    else measure_kernels(row))
        except Exception as e:  # noqa: BLE001 — a refused tile is a row
            failed += 1
            line = {**{k: (list(v) if isinstance(v, tuple) else v)
                       for k, v in row.items()},
                    "error": f"{type(e).__name__}: {str(e)[:300]}"}
        line["device"] = jax.devices()[0].device_kind
        text = json.dumps(line)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()
    if sink:
        sink.close()
    if failed:
        sys.exit(f"{failed} row(s) failed")


def trace(outdir: str, model_name: str) -> dict:
    """5-step xplane capture of the S=4096 b4 gate config (block/variant
    defaults) — reduce with utils.trace_summary for the PROFILE
    artifact."""
    import jax

    # warm the compilation cache with one measured pass, then capture a
    # fresh 5-step window (the second call re-uses the persistent cache)
    out = measure(model_name, 4096, "flash", 128, "split", 0,
                  steps=5, warmup=3)
    jax.profiler.start_trace(outdir)
    try:
        measure(model_name, 4096, "flash", 128, "split", 0, steps=5,
                warmup=0)
    finally:
        jax.profiler.stop_trace()   # never leave the profiler running
    return {"trace": outdir, "model": model_name, "warm_cell": out}


def main() -> None:
    if sys.argv[1:2] == ["--roofline"]:
        roofline()
        return
    if sys.argv[1:2] == ["--all"]:
        cells = []
        for mn in MODELS:
            for s in SEQS:
                cells.append(("cell", mn, s, "xla", 0, "split", 0))
                cells += [("cell", mn, s, "flash", blk, var, 0)
                          for blk in BLOCKS for var in VARIANTS]
                # the wider-block split-dkv probe at the gate shape
                if s == 4096:
                    cells.append(("cell", mn, s, "flash", 128, "split",
                                  512))
        run_cells(os.path.abspath(__file__), cells)
        return
    if sys.argv[1:2] in (["kernels"], ["ragged"], ["paged"]):
        if len(sys.argv) > 2:
            os.makedirs(os.path.dirname(os.path.abspath(sys.argv[2])),
                        exist_ok=True)
        third = sys.argv[3] if len(sys.argv) > 3 else None
        kernels(sys.argv[2] if len(sys.argv) > 2 else None, sys.argv[1],
                wide=third if third in ("wide", "dots3", "laguna") else None,
                parent_py=third if sys.argv[1] == "paged" else None)
        return
    if sys.argv[1:2] == ["--trace"]:
        outdir, mn = sys.argv[2], sys.argv[3]
        from distributed_tensorflow_example_tpu.runtime.device import (
            enable_compilation_cache)
        enable_compilation_cache()
        print(json.dumps(trace(outdir, mn)), flush=True)
        return
    if sys.argv[1:2] != ["cell"]:
        raise SystemExit(__doc__)
    mn, s, impl = sys.argv[2], int(sys.argv[3]), sys.argv[4]
    blk = int(sys.argv[5]) if len(sys.argv) > 5 else 128
    var = sys.argv[6] if len(sys.argv) > 6 else "split"
    bb = int(sys.argv[7]) if len(sys.argv) > 7 else 0
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        print(json.dumps(measure(mn, s, impl, blk, var, bb)), flush=True)
    except Exception as e:  # noqa: BLE001 — OOM at compile is a finding
        fail({"model": mn, "seq": s, "impl": impl, "block": blk,
              "bwd_variant": var}, e)


if __name__ == "__main__":
    main()
