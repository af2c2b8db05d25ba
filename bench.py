#!/usr/bin/env python
"""Benchmark suite: sync-replica training throughput on the driver metric.

The driver-defined headline metric (BASELINE.json:2) is examples/sec/chip
on MNIST + ResNet-50; this suite measures nine workloads on whatever
devices are present, and its JSON line names them (``platform``,
``device_kind``, ``device_count``) so a CPU rehearsal can never be read
as a chip row:

- ``mnist_mlp``   — the reference-parity workload (BASELINE.json:7)
- ``resnet50``    — ImageNet shapes, bf16, synthetic data (BASELINE.json:10)
- ``bert_base``   — MLM step time, seq 128 (BASELINE.json:11)
- ``moe_bert``    — expert-parallel flagship, 8 experts top-1, b64
- ``bert_large``  — the big dense model, b64
- ``bert_long``   — composed long context: S=4096 flash, b4 (remat=none
  since the round-5 sweep — BASELINE.md "Round-5 remat sweep")
- ``gpt_small``   — causal-LM train, s512 b32, fused blockwise LM loss
  (``lm_loss_impl="fused"`` since round 7 — BASELINE.md "Vocab chain")
- ``gpt_long``    — causal long context: S=4096 causal flash + fused
  LM loss, b4 (fused replaced ``lm_loss_chunk=512`` in round 7: no
  [B,S,V] logits AND no seq-chunk recompute; queued-dispatch
  methodology like bert_long)
- ``gpt_decode``  — KV-cache greedy decode, b8 prompt 128 + 128 new;
  tokens/s/chip via the one-dispatch compiled generation, riding the
  stacked-scan decode fast path (models/gpt.py decode_impl="stacked":
  lax.scan over restacked layer params, fused QKV, single-query Pallas
  cache attention on TPU); timed as the median of >=5 repeats
  (median_repeats) so the row's spread is published and < ±2%

Eight are training throughput, one is decode; a regression in ANY of
the nine moves ``vs_baseline``.

For each, an MFU estimate = step FLOPs / measured step time / chip peak
(bf16) is recorded, with its basis published per row as
``{key}_mfu_basis``: ``"cost_analysis"`` = XLA-reported FLOPs for the
compiled step; ``"analytic"`` = cost-analysis FLOPs PLUS the closed-form
flash-attention FLOPs XLA cannot see inside the Pallas custom call
(flash_attention.attention_train_flops) — so the bert_long/gpt_long MFU
rows are comparable to the seq-128 rows (VERDICT r5 weak #1). The same
augmented number feeds robust_time's physical-impossibility check. The
reference publishes no numbers (BASELINE.md), so ``bench_baseline.json``
holds this repo's own first measurements; ``vs_baseline`` is
measured/baseline of the headline metric (>1 is faster).

Every training row also publishes ``{key}_peak_mib`` (XLA memory-
analysis peak for the compiled step, when the backend reports it) so
memory levers — the fused LM loss killing the [B,S,V] logits
residency, remat, storage dtypes — are regression-visible columns, not
folklore — and ``{key}_anomaly_count`` (the on-device non-finite-step
counter carried in TrainState), so a "fast but silently skipping
steps" regression is a visible nonzero column, not a quiet throughput
win.

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "platform": ..., "device_kind": ..., "device_count": N,
     "extra": {...}}
"""

import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from distributed_tensorflow_example_tpu.config import (  # noqa: E402
    DataConfig, OptimizerConfig, TrainConfig)
from distributed_tensorflow_example_tpu.data.mnist import synthetic_mnist  # noqa: E402
from distributed_tensorflow_example_tpu.models import get_model  # noqa: E402
from distributed_tensorflow_example_tpu.parallel.mesh import build_mesh  # noqa: E402
from distributed_tensorflow_example_tpu.parallel.sync_replicas import (  # noqa: E402
    SyncReplicas)
from distributed_tensorflow_example_tpu.runtime.device import (  # noqa: E402
    chip_peak_flops as _chip_peak, enable_compilation_cache)
from distributed_tensorflow_example_tpu.train.optimizers import (  # noqa: E402
    make_optimizer)

def _peak_mib(compiled) -> float | None:
    """XLA memory-analysis peak for one compiled step, in MiB (None when
    the backend doesn't report it — CPU builds often return 0). The
    published ``{key}_peak_mib`` column is what makes memory levers
    (``--lm_loss_impl fused`` killing the [B,S,V] logits residency,
    remat, bf16 storage) regression-visible, not folklore."""
    peak = getattr(compiled.memory_analysis(), "peak_memory_in_bytes", 0)
    return peak / 2**20 if peak else None


def _step_flops(compiled) -> float | None:
    """XLA cost-analysis FLOPs for one compiled step (None if unavailable).

    Pallas custom calls are opaque to the cost analysis (their FLOPs count
    as zero) — flash workloads add ``_flash_step_flops`` on top.
    """
    f = (compiled.cost_analysis() or {}).get("flops")
    return float(f) if f and f > 0 else None


def _flash_step_flops(cfg, model, model_name: str, batch: int,
                      host_batch: dict | None) -> float | None:
    """Closed-form attention FLOPs for one train step when (and only
    when) the Pallas flash kernel actually engages — the piece XLA's
    cost analysis cannot see. None for xla-attention configs and for
    shapes where flash falls back to XLA (the fallback's einsums ARE
    counted by the cost analysis; adding the analytic number there would
    double-count)."""
    if cfg.attention_impl != "flash" or not host_batch:
        return None
    ids = host_batch.get("input_ids")
    if ids is None:
        return None
    from distributed_tensorflow_example_tpu.config import (
        flash_attention_kwargs)
    from distributed_tensorflow_example_tpu.ops.pallas.flash_attention \
        import attention_train_flops, effective_bwd_variant, kernel_engages
    fkw = flash_attention_kwargs(cfg)
    mc = model.cfg
    seq = int(ids.shape[1])
    head_dim = mc.hidden // mc.heads
    blocks = {k: fkw[k] for k in ("block_q", "block_k", "bwd_block")
              if k in fkw}
    if not kernel_engages(seq, head_dim, **blocks):
        return None
    return attention_train_flops(
        batch, seq, mc.hidden, mc.layers,
        causal=model_name.startswith("gpt"),
        # count what EXECUTES: an unset lever is the schedule's choice,
        # and fused silently degrades to split past the VMEM slab limit
        bwd_variant=effective_bwd_variant(
            seq, head_dim, fkw.get("bwd_variant"), cfg.dtype))


def robust_time(timed_pass, *, steps: int, flops=None, peak=None,
                n_dev: int = 1) -> tuple[float, bool]:
    """Artifact-resistant wall-time of ``timed_pass`` (seconds, suspect).

    A reading faster than the roofline is rejected: a timed pass that
    returned before its work ran (observed once: BERT 'completing' at
    21x MFU inside a long-lived multi-workload process) is always
    absurdly FAST, so: take the slower of two passes, and retry while
    the result is physically impossible (> 95% of peak when flops are
    known) or the two passes disagree wildly (> 3x — the fallback check
    for devices/workloads without a flops estimate). The slight upward bias of
    max-of-two is accepted: a conservative gate beats a corrupted one.
    ``suspect=True`` flags a measurement that stayed impossible after
    every retry — callers must surface it, not publish it as real.
    """
    dt = bad = 0.0
    for attempt in range(3):
        a, b = timed_pass(), timed_pass()
        dt = max(a, b)
        mfu_est = (flops / (dt / steps) / (peak * n_dev)
                   if (flops and peak) else None)
        impossible = (mfu_est is not None and mfu_est > 0.95)
        wild = min(a, b) > 0 and (max(a, b) / min(a, b)) > 3.0
        bad = impossible or wild
        if not bad:
            break
    return dt, bool(bad)


def median_repeats(timed_single, *, reps: int, floor_s: float | None = None,
                   retries: int = 3) -> tuple[float, float, bool]:
    """Median-of-repeats timing for the decode gate row (seconds).

    The decode wall-clock carried ~100 ms/call of host overhead
    (~50% of the measurement — BASELINE.md decode roofline), so a
    max-of-two estimate let its jitter move the gate row ±5%.
    ``timed_single`` times ONE generation; this takes the MEDIAN of
    ``reps`` such timings — robust to both an absurdly-fast reading (a
    corrupt low outlier cannot become the median while most repeats
    are honest) and slow dispatch hiccups. Retries the whole sample while the median sits below
    ``floor_s`` (the physically-impossible bound, e.g. half the
    weight-traffic floor); ``suspect=True`` if it never recovers.

    Returns ``(median_s, spread, suspect)`` where ``spread`` is the
    max relative deviation of any repeat from the median — the
    publishable ±noise figure the gate row's < ±2% target is judged
    by.
    """
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    med = spread = 0.0
    suspect = False
    for attempt in range(retries):
        ts = sorted(timed_single() for _ in range(reps))
        med = ts[(len(ts) - 1) // 2]
        spread = max(abs(t - med) for t in ts) / med if med > 0 else 0.0
        suspect = floor_s is not None and med < floor_s
        if not suspect:
            break
    return med, spread, suspect


def decode_device_component(short_s: float, long_s: float,
                            new_short: int, new_long: int,
                            ) -> tuple[float, float]:
    """Two-point fit splitting a generation's wall-clock into per-token
    device time and per-call overhead (both ms).

    Each decode CALL paid ~100 ms of host overhead around the device
    steady state (measured: gen_ms ≈ 99 + 0.84·new, BASELINE.md decode
    roofline) — ~50% of the b8 prompt128+new128 gate row's wall-clock,
    so its jitter could move that row ±5% with zero repo change. Timing
    the SAME program at two generation lengths cancels the per-call
    constant: the slope ``(long - short) / (new_long - new_short)`` is
    the per-token-step device component (call jitter hits both medians
    once each, not per token), and the intercept is the published
    overhead estimate.
    """
    if new_long <= new_short:
        raise ValueError(f"need new_long > new_short, got "
                         f"{new_long} <= {new_short}")
    slope_ms = (long_s - short_s) / (new_long - new_short) * 1e3
    overhead_ms = short_s * 1e3 - slope_ms * new_short
    return slope_ms, overhead_ms


def _run(model_name: str, *, batch: int, steps: int, warmup: int,
         opt: OptimizerConfig, make_batch, extra_cfg: dict | None = None,
         cfg_over: dict | None = None,
         steps_per_call: int = 1, prng_impl: str | None = None):
    """Time `steps` sync steps; returns (examples/sec/chip, step_ms, mfu,
    mfu_basis, peak_mib, suspect, anomaly_count) — ``peak_mib`` is the
    compiled step's XLA memory-analysis peak (None when unreported),
    ``suspect`` marks a measurement robust_time could not de-corrupt
    (callers surface it, never publish it as real), and
    ``anomaly_count`` is the run's cumulative non-finite-step count from
    the on-device detector.

    ``steps_per_call > 1`` uses the device-side multi-step loop
    (iterations_per_loop) — essential for latency-bound microbenchmarks
    (MNIST MLP) where per-step host dispatch would dominate the
    measurement; compute-bound workloads pipeline fine without it.
    """
    n_dev = len(jax.devices())
    mesh = build_mesh()          # all devices on the data axis
    cfg = TrainConfig(model=model_name, dtype="bfloat16",
                      data=DataConfig(batch_size=batch,
                                      **(extra_cfg or {})),
                      optimizer=opt, **(cfg_over or {}))
    model = get_model(model_name, cfg)
    tx = make_optimizer(cfg.optimizer)
    sync = SyncReplicas(model.loss, tx, mesh)
    state = sync.init(model.init, seed=0, prng_impl=prng_impl)

    k = steps_per_call
    if k > 1:
        host = [make_batch(model, batch, i) for i in range(k)]
        stacked = {key: np.stack([b[key] for b in host]) for key in host[0]}
        placed = sync.shard_stacked_batch(stacked)
        step_fn, n_calls = sync.multi_step, max(1, steps // k)
        steps = n_calls * k
    else:
        host = [make_batch(model, batch, i) for i in range(2)]
        placed2 = [sync.shard_batch(b) for b in host]
        placed = placed2[0]
        step_fn, n_calls = sync.step, steps

    # the AOT-compiled executable is reused for the run itself: lower/
    # compile does not populate the jit dispatch cache, so calling step_fn
    # afterwards would compile the same program a second time
    compiled = step_fn.lower(state, placed).compile()
    peak_mib = _peak_mib(compiled)
    flops = _step_flops(compiled)
    if flops and k > 1:
        flops /= k               # cost_analysis covers the whole K-step scan
    # flash configs: add the in-kernel attention FLOPs the cost analysis
    # cannot see, and say so in the published basis
    attn_flops = _flash_step_flops(cfg, model, model_name, batch, host[0])
    if flops and attn_flops:
        flops += attn_flops
    mfu_basis = "analytic" if (flops and attn_flops) else "cost_analysis"

    for i in range(max(1, warmup // k)):
        state, m = compiled(state, placed if k > 1 else placed2[i % 2])
    jax.block_until_ready(state.params)

    def timed_pass():
        nonlocal state
        t0 = time.perf_counter()
        for i in range(n_calls):
            state, m = compiled(state,
                                placed if k > 1 else placed2[i % 2])
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    peak = _chip_peak()
    dt, suspect = robust_time(timed_pass, steps=steps, flops=flops,
                              peak=peak, n_dev=n_dev)
    step_s = dt / steps
    eps_chip = batch / step_s / n_dev
    mfu = (flops / step_s / (peak * n_dev)) if (flops and peak) else None
    # cumulative non-finite-step count from the on-device anomaly
    # detector: a "fast but silently skipping steps" regression shows up
    # as a nonzero column in the gate, not as a quiet throughput win
    anomalies = int(jax.device_get(state.anomaly_count))
    return (eps_chip, step_s * 1e3, mfu, mfu_basis, peak_mib, suspect,
            anomalies)


def _mnist_batch(model, batch, i):
    data = synthetic_mnist(num_train=batch, num_test=16, seed=i)
    return {"x": data["train_x"], "y": data["train_y"]}


def _dummy_batch(model, batch, i):
    return model.dummy_batch(batch)


def _gpt_batch_at(seq: int):
    """Causal-LM batch maker at a fixed sequence length (dummy_batch
    caps at 128, and the model's max_len can exceed the workload's seq
    — gpt keeps max_len >= 1024)."""
    def make(model, batch, i):
        s = min(seq, model.cfg.max_len)
        rs = np.random.RandomState(i)
        return {
            "input_ids": rs.randint(0, model.cfg.vocab_size, (batch, s),
                                    dtype=np.int32),
            "attention_mask": np.ones((batch, s), np.int32),
        }
    return make


def _run_decode(*, batch: int, prompt: int, max_new: int, reps: int,
                warmup: int, tiny: bool, gen_kwargs: dict | None = None,
                amortize_new: int | None = None):
    """tokens/s/chip for the compiled KV-cache generation (the stacked
    fast path by default; ``gen_kwargs`` overrides decode_impl /
    decode_attention / tokens_per_dispatch / weight_quant for the
    lever sweep in experiments/decode_roofline.py). The whole
    generation is ONE dispatch on ONE device, each repeat synchronously
    drained via device_get (see the timing note below). The published
    number is the MEDIAN of ``reps`` per-generation timings after
    warmup (median_repeats — the de-noised gate methodology; spread is
    the row's published ±noise).

    ``amortize_new``: additionally time the same program at this longer
    generation length and publish the two-point DEVICE component
    (``decode_device_component``) — the call-overhead-free number the
    gate row regresses on once baselined. Returns a dict of row fields.
    """
    import functools

    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models.base import cast_floating
    import jax.numpy as jnp

    name = "gpt_tiny" if tiny else "gpt"
    cfg = TrainConfig(model=name, dtype="bfloat16",
                      param_dtype="bfloat16",
                      data=DataConfig(batch_size=batch))
    model = get_model(name, cfg)
    params = cast_floating(model.init(jax.random.key(0)), jnp.bfloat16)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, model.cfg.vocab_size, (batch, prompt),
                                 dtype=np.int32))
    gen = jax.jit(functools.partial(model.generate,
                                    max_new_tokens=max_new,
                                    **(gen_kwargs or {})))
    # time via device_get of the tokens: the host transfer cannot
    # complete before the computation has (round 5 measured
    # block_until_ready readings 100-1000x faster than the weight-
    # traffic bound for this program; whether that still happens is
    # ROADMAP D6's measurement). The [B, max_new] int32 transfer is
    # ~4 KB — negligible against a ~10^2 ms generation.
    np.asarray(gen(params, ids))
    for _ in range(warmup):
        np.asarray(gen(params, ids))

    # physical floor: one bf16 read of every param per token-step at
    # the v5e's 819 GB/s. Readings below half of it are corrupt.
    n_param = sum(int(p.size)
                  for p in jax.tree_util.tree_leaves(params))
    bound_ms = n_param * 2 / 819e9 * 1e3
    if (gen_kwargs or {}).get("weight_quant") == "int8":
        # int8 weights halve the per-token read, so the corruption
        # floor halves with it — a legit int8 reading near ITS bound
        # must not be flagged suspect against the bf16 one
        bound_ms /= 2
    on_tpu = jax.devices()[0].platform == "tpu"

    def timed_single():
        t0 = time.perf_counter()
        np.asarray(gen(params, ids))
        return time.perf_counter() - t0

    per_gen, spread, suspect = median_repeats(
        timed_single, reps=reps,
        # off-TPU the bf16 weight bound is meaningless (no 819 GB/s HBM)
        floor_s=(bound_ms * 0.5 * max_new / 1e3) if on_tpu else None)
    # per-chip = the whole number: the generation is a single-device
    # jit (no mesh), so dividing by the host's visible device count
    # would under-report on any multi-device host
    row = {
        "tokens_s_chip": batch * max_new / per_gen,
        "token_step_ms": per_gen / max_new * 1e3,
        "weight_bound_ms": bound_ms,
        "spread": spread,
        "suspect": suspect,
    }
    if amortize_new is not None:
        gen_long = jax.jit(functools.partial(
            model.generate, max_new_tokens=amortize_new,
            **(gen_kwargs or {})))
        np.asarray(gen_long(params, ids))          # compile
        for _ in range(warmup):
            np.asarray(gen_long(params, ids))

        def timed_long():
            t0 = time.perf_counter()
            np.asarray(gen_long(params, ids))
            return time.perf_counter() - t0

        per_long, spread_long, suspect_long = median_repeats(
            timed_long, reps=reps,
            floor_s=(bound_ms * 0.5 * amortize_new / 1e3)
            if on_tpu else None)
        dev_ms, overhead_ms = decode_device_component(
            per_gen, per_long, max_new, amortize_new)
        # a non-positive slope (longer generation measured FASTER) is
        # physically impossible — a corrupt leg slipped past the floor
        # check; flag it so the gate excludes the row
        row.update(device_token_ms=dev_ms, call_overhead_ms=overhead_ms,
                   long_spread=spread_long,
                   suspect=suspect or suspect_long or dev_ms <= 0)
    return row


def _run_serving(*, clients: int, requests: int, prompt_len: int,
                 max_new: int, slots: int, tiny: bool) -> dict:
    """The continuous-batching serving row: closed-loop clients against
    the in-process REST server with the scheduler ON (the
    experiments/serving_load.py harness). Published as
    ``{key}_serving_tps`` / ``{key}_serving_p95_ms`` so the next TPU
    window baselines the serving path, plus the dispatch counters the
    continuous-batching invariant is judged by (decode dispatches ~
    max per-request length per wave, not the per-request sum).

    Round 12 adds the fully quantized leg (int8 decode weights + int8
    paged KV pool): ``serving_int8_tps``, ``serving_int8_drift_rate``
    (token drift vs the bf16 leg on the SAME seeded matrix — the
    ROADMAP item-1 quality gate's observable), and per-dtype
    ``bytes_resident_peak`` so the ~2x-capacity-at-fixed-HBM claim is
    a baselined column, not folklore."""
    import tempfile

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "experiments"))
    import serving_load

    on_tpu = jax.devices()[0].platform == "tpu"
    platforms = ("cpu", "tpu") if on_tpu else ("cpu",)
    model_name = "gpt_tiny" if tiny else "gpt"
    # the shared-prefix workload needs sys_len (a block multiple) < the
    # prompt capacity WITH suffix room — a block of prompt_len/4 keeps
    # that true for any prompt_len >= 8 (16 at the CPU config would
    # leave no suffix room and make_requests rejects it loudly)
    block_size = 16 if prompt_len >= 32 else max(2, prompt_len // 4)
    with tempfile.TemporaryDirectory() as d:
        vocab = serving_load.build_export(
            d, prompt_len=prompt_len, max_new=max_new, slots=slots,
            model_name=model_name, platforms=platforms)
        matrix = serving_load.make_requests(
            clients, requests, prompt_len=prompt_len, max_new=max_new,
            vocab=vocab, seed=0)
        row = serving_load.run_mode(d, matrix, scheduler="on",
                                    prompt_len=prompt_len)
    # paged + shared-prefix leg (round 10): same closed-loop matrix
    # shape but every prompt opens with one seeded system prefix — the
    # prefix-cache hit rate the next TPU window baselines
    with tempfile.TemporaryDirectory() as d:
        serving_load.build_export(
            d, prompt_len=prompt_len, max_new=max_new, slots=slots,
            model_name=model_name, platforms=platforms, paged=True,
            block_size=block_size)
        shared = serving_load.make_requests(
            clients, requests, prompt_len=prompt_len, max_new=max_new,
            vocab=vocab, seed=0, prefix_mode="shared",
            block_size=block_size)
        prow = serving_load.run_mode(d, shared, scheduler="on",
                                     prompt_len=prompt_len,
                                     mode_name="paged_shared")
    # quantized leg (round 12): int8 decode weights + int8 paged KV
    # pool against the SAME shared matrix — drift is measured against
    # the bf16 paged leg's token streams (identical seeds), and the
    # per-dtype residency peaks make the capacity doubling a column
    with tempfile.TemporaryDirectory() as d:
        serving_load.build_export(
            d, prompt_len=prompt_len, max_new=max_new, slots=slots,
            model_name=model_name, platforms=platforms, paged=True,
            block_size=block_size, weight_quant="int8",
            kv_cache_dtype="int8")
        irow = serving_load.run_mode(d, shared, scheduler="on",
                                     prompt_len=prompt_len,
                                     mode_name="int8_shared")
    # speculative leg (round 16): the repetitive workload the
    # self-drafter mines, against a verify-program export —
    # `{key}_serving_spec_tps` / `{key}_serving_accept_rate` are the
    # next TPU window's baselines for the ROADMAP item-1 verdict
    # (tokens-per-dispatch uplift at the measured accept rate)
    with tempfile.TemporaryDirectory() as d:
        serving_load.build_export(
            d, prompt_len=prompt_len, max_new=max_new, slots=slots,
            model_name=model_name, platforms=platforms, paged=True,
            block_size=block_size, spec_tokens=4)
        rep = serving_load.make_repetitive_requests(
            clients, requests, prompt_len=prompt_len, max_new=max_new,
            vocab=vocab, seed=0)
        srow = serving_load.run_mode(d, rep, scheduler="on",
                                     prompt_len=prompt_len,
                                     mode_name="spec_on",
                                     spec_tokens=4)
    # fleet router leg (round 17): the same closed-loop matrix through
    # a 2-replica in-process fleet — `{key}_router_p95_ms` /
    # `{key}_router_failover_total` / `{key}_router_hedge_win_rate`
    # open the serving-fleet trajectory (BENCH had no fleet keys), all
    # sourced from the MERGED registry, not client stopwatches
    with tempfile.TemporaryDirectory() as d:
        serving_load.build_export(
            d, prompt_len=prompt_len, max_new=max_new, slots=slots,
            model_name=model_name, platforms=platforms)
        rrow = serving_load.run_router_mode(d, matrix, replicas=2,
                                            hedge_after_ms=200)
    # counters come from the registry snapshot each run_mode captured
    # (the /metrics exposition = the same atomic snapshot /stats
    # renders) — not re-derived from response bookkeeping, so the
    # bench row can never drift from what the server itself reports
    reg, preg = row["registry"], prow["registry"]
    ireg = irow["registry"]
    decode_steps = int(reg["serving_decode_steps_total"])
    slot_steps = int(reg["serving_decode_slot_steps_total"])
    admissions = int(preg["serving_admissions_total"])
    hits = int(preg.get("serving_prefix_cache_hits_total", 0))
    out = {
        "serving_tps": row["tokens_per_s"],
        "serving_p95_ms": row["latency_p95_ms"],
        "serving_decode_steps": decode_steps,
        "serving_steps_shared": round(slot_steps / decode_steps, 3)
        if decode_steps else 0.0,
        "serving_errors": len(row["errors"]),
        "serving_paged_tps": prow["tokens_per_s"],
        "serving_prefix_hit_rate": round(hits / admissions, 3)
        if admissions else 0.0,
        "serving_prefill_tokens_saved": int(
            preg["serving_prefill_tokens_saved_total"]),
        "serving_paged_errors": len(prow["errors"]),
        "serving_int8_tps": irow["tokens_per_s"],
        "serving_int8_drift_rate": round(
            1.0 - serving_load.token_agreement(irow["_gens"],
                                               prow["_gens"]), 4),
        "serving_int8_errors": len(irow["errors"]),
        # round-19 SLO columns: goodput (deadline-met tokens/s —
        # distinct from raw serving_tps; equal on this deadline-less
        # matrix, divergent the moment a deadline workload sheds or
        # expires) and attainment, both sourced from the registry's
        # serving_slo_*/goodput counters, never client bookkeeping
        "serving_goodput_tps": round(
            row["tokens_per_s"]
            * int(reg.get("serving_goodput_tokens_total", 0))
            / int(reg["serving_tokens_out_total"]), 2)
        if int(reg.get("serving_tokens_out_total", 0)) else 0.0,
        "serving_slo_attainment": round(
            int(reg.get("serving_slo_good_total", 0))
            / int(reg["serving_slo_served_total"]), 4)
        if int(reg.get("serving_slo_served_total", 0)) else 0.0,
        "serving_slo_attainment_interactive": round(
            int(reg.get("serving_slo_good_interactive_total", 0))
            / int(reg["serving_slo_served_interactive_total"]), 4)
        if int(reg.get("serving_slo_served_interactive_total", 0))
        else 0.0,
        "serving_bytes_resident_peak": int(
            preg.get("serving_bytes_resident_peak", 0)),
        "serving_int8_bytes_resident_peak": int(
            ireg.get("serving_bytes_resident_peak", 0)),
        # round-16 speculative columns: throughput on the repetitive
        # workload, the measured accept rate, and the dispatch economy
        # (emitted tokens per dispatch — > 1.0 is the whole point)
        "serving_spec_tps": srow["tokens_per_s"],
        "serving_accept_rate": float(
            srow["registry"].get("serving_spec_accept_rate", 0.0)),
        "serving_spec_errors": len(srow["errors"]),
        "serving_spec_tokens_per_dispatch": round(
            int(srow["registry"]["serving_tokens_out_total"])
            / max(1, int(srow["registry"]["serving_decode_steps_total"])
                  + int(srow["registry"]["serving_verify_steps_total"])
                  + int(srow["registry"]["serving_prefills_total"])), 3),
        # round-17 fleet columns: the router trajectory the next TPU
        # window baselines (ROADMAP items 2/3 name these as their
        # proof surface)
        "router_tps": rrow["tokens_per_s"],
        "router_p95_ms": rrow["fleet_registry_p95_ms"],
        "router_failover_total": rrow["router_failovers"],
        "router_hedge_win_rate": round(
            rrow["router_hedge_wins"] / rrow["router_hedges"], 3)
        if rrow["router_hedges"] else 0.0,
        "router_errors": len(rrow["errors"]),
    }
    # per-request latency breakdown (queue vs prefill vs decode) from
    # the request-scoped `timings` field — the p95 gate's diagnosis
    # companion: when p95 moves, this row says WHICH phase moved
    for phase, pct in row.get("breakdown_ms", {}).items():
        out[f"serving_{phase}_p95_ms"] = pct["p95"]
    return out


def _long_batch(model, batch, i):
    """BERT batch at the model's FULL configured sequence length
    (dummy_batch caps at 128 for the seq-128 workloads)."""
    c = model.cfg
    s = c.max_len
    m = c.max_predictions
    rs = np.random.RandomState(i)
    return {
        "input_ids": rs.randint(0, c.vocab_size, (batch, s),
                                dtype=np.int32),
        "token_type_ids": np.zeros((batch, s), np.int32),
        "attention_mask": np.ones((batch, s), np.int32),
        "masked_positions": np.tile(np.arange(m, dtype=np.int32),
                                    (batch, 1)),
        "masked_labels": rs.randint(0, c.vocab_size, (batch, m),
                                    dtype=np.int32),
        "masked_weights": np.ones((batch, m), np.float32),
    }


def _workloads(on_tpu: bool, scale: int) -> "list[dict]":
    """The gate workload table. ``only``: BENCH_ONLY aliases; ``key``:
    the extra/baseline prefix. Off-TPU, transformer workloads swap in
    tiny model variants (sanity only, numbers meaningless).

    Config notes that earned their place:
    - mnist: 1000 steps = 50 measured dispatches — 10 dispatches left
      the number at the mercy of dispatch-latency jitter (observed
      12.8M-15.0M swings; BASELINE.md "discrepancy" note).
    - bert @ b128: the v5e sweet spot (mfu 0.382 @ 64 -> 0.410 @ 128 ->
      0.383 @ 256 measured r3); rbg = TPU-native RNG (dropout masks
      dominate threefry's cost: 112.4 -> 89.1 ms/step measured).
    - moe_bert/bert_large @ b64: the measured sweet spots (BASELINE.md).
    - bert_long: the composed long-context capability (flash +
      remat=none @ S=4096 b4 — the regime the plain XLA path cannot
      reach); its MFU adds the closed-form flash-kernel FLOPs
      (mfu_basis="analytic") and is comparable to the seq-128 rows.
    - gpt_decode: the gate ratio moves to the two-point DEVICE
      component (device_token_ms) as soon as a baseline for it exists —
      wall-clock tokens/s keeps ~100 ms/call of host overhead in the
      denominator (~50% of the measurement) and its jitter was the gate
      row's dominant noise.
    """
    adamw = OptimizerConfig(name="adamw", learning_rate=1e-4)
    rbg = "rbg" if on_tpu else None
    return [
        dict(key="mnist_mlp", only={"mnist"}, model="mlp", batch=8192,
             steps=1000 if on_tpu else 10, warmup=100 if on_tpu else 2,
             opt=OptimizerConfig(name="sgd", learning_rate=0.5),
             make_batch=_mnist_batch,
             steps_per_call=20 if on_tpu else 5, ms_digits=3),
        dict(key="resnet50", only={"resnet50"}, model="resnet50",
             batch=max(8, 128 // scale), steps=30 if on_tpu else 3,
             warmup=5 if on_tpu else 1,
             opt=OptimizerConfig(name="momentum", learning_rate=0.1),
             make_batch=_dummy_batch),
        dict(key="bert_base", only={"bert"}, model="bert",
             batch=max(8, 128 // scale), steps=20 if on_tpu else 2,
             warmup=5 if on_tpu else 1, opt=adamw,
             make_batch=_dummy_batch, prng_impl=rbg),
        dict(key="moe_bert", only={"moe", "moe_bert"},
             model="moe_bert" if on_tpu else "moe_bert_tiny",
             batch=max(8, 64 // scale), steps=20 if on_tpu else 2,
             warmup=5 if on_tpu else 1, opt=adamw,
             make_batch=_dummy_batch, prng_impl=rbg),
        dict(key="bert_large", only={"bert_large"},
             model="bert_large" if on_tpu else "bert_tiny",
             batch=max(8, 64 // scale), steps=20 if on_tpu else 2,
             warmup=5 if on_tpu else 1, opt=adamw,
             make_batch=_dummy_batch, prng_impl=rbg),
        dict(key="bert_long", only={"bert_long"},
             model="bert" if on_tpu else "bert_tiny",
             batch=4 if on_tpu else 2, steps=8 if on_tpu else 1,
             warmup=2 if on_tpu else 1, opt=adamw,
             make_batch=_long_batch,
             extra_cfg={"seq_len": 4096 if on_tpu else 256},
             # remat=none since round 5: 36% faster at this shape and
             # fits in ~8.4 GiB of 16 (BASELINE.md "Round-5 remat
             # sweep"; baseline re-based with a methodology note).
             # lm_loss_impl=fused since round 7 (BASELINE.md "Vocab
             # chain"): the MLM head rides the blockwise core — a
             # composition row at M=80 positions, not a win
             cfg_over={"attention_impl": "flash", "remat": "none",
                       "lm_loss_impl": "fused"},
             prng_impl=rbg, eps_digits=2),
        dict(key="gpt_small", only={"gpt", "gpt_small"},
             model="gpt" if on_tpu else "gpt_tiny",
             batch=max(8, 32 // scale), steps=20 if on_tpu else 2,
             warmup=5 if on_tpu else 1, opt=adamw,
             make_batch=_gpt_batch_at(512 if on_tpu else 128),
             # fused LM loss since round 7: the ~21 ms/step vocab chain
             # (logits fwd/bwd + tied-embed grad + softmax reductions +
             # accuracy argmax — BASELINE.md "Vocab chain") collapses
             # to the blockwise scan; the full-logits path stays the
             # parity oracle, re-base rule pre-committed in BASELINE.md
             extra_cfg={"seq_len": 512 if on_tpu else 128},
             cfg_over={"lm_loss_impl": "fused"},
             prng_impl=rbg),
        dict(key="gpt_long", only={"gpt_long"},
             model="gpt" if on_tpu else "gpt_tiny",
             batch=4 if on_tpu else 2, steps=8 if on_tpu else 1,
             warmup=2 if on_tpu else 1, opt=adamw,
             make_batch=_gpt_batch_at(4096 if on_tpu else 128),
             extra_cfg={"seq_len": 4096 if on_tpu else 128},
             # fused since round 7: replaces lm_loss_chunk=512 — no
             # [B,S,V] tensor AND no seq-chunk recompute (the chunk
             # knob survives as the fallback; BASELINE.md "Vocab chain")
             cfg_over={"attention_impl": "flash", "remat": "none",
                       "lm_loss_impl": "fused"},
             prng_impl=rbg, eps_digits=2),
        # reps=7: median-of-repeats de-noising — odd count gives a true
        # middle element, 7 keeps the row under ~2 s of measurement
        # while the median shrugs off single-call jitter; decode rides
        # the stacked fast path by default
        dict(key="gpt_decode", only={"gpt_decode", "decode"},
             decode=dict(batch=8, prompt=128 if on_tpu else 16,
                         max_new=128 if on_tpu else 8,
                         reps=7 if on_tpu else 1,
                         warmup=2 if on_tpu else 0, tiny=not on_tpu,
                         # 4x-longer second leg: the two-point fit that
                         # isolates the device component from the
                         # ~100 ms/call host overhead
                         amortize_new=512 if on_tpu else 32)),
        # continuous-batching serving row (round 9): closed-loop
        # clients through the scheduler-on REST server — throughput +
        # p95 latency + the shared-dispatch counters, baselined at the
        # next TPU window (BASELINE.md "Serving")
        dict(key="gpt", only={"serving", "gpt_serving"},
             serving=dict(clients=8, requests=4 if on_tpu else 2,
                          prompt_len=128 if on_tpu else 16,
                          max_new=64 if on_tpu else 8,
                          slots=8, tiny=not on_tpu)),
    ]


def vs_baseline_geomean(extra: dict, base: dict) -> float:
    """Geomean of measured/baseline over the gate workloads.

    A workload whose measurement carries the ``*_suspect`` flag (a
    faster-than-roofline reading robust_time could not de-corrupt) is
    EXCLUDED: a corrupt reading
    must never inflate the gate. mnist prefers its dedicated baseline
    key and falls back to the legacy round-1 name — never both.

    gpt_decode regresses on the call-overhead-free DEVICE component
    (``gpt_decode_device_token_ms``, lower = faster, so the ratio
    inverts) as soon as BOTH the baseline and the measurement carry it;
    until the device-component baseline exists it stays on wall-clock
    tokens/s — re-base with a methodology note at the first on-chip
    run that records the new key.
    """
    mnist_base = (base.get("mnist_mlp_eps_chip")
                  or base.get("examples_per_sec_per_chip"))
    ratios = []
    for key, b in (("mnist_mlp_eps_chip", mnist_base),
                   ("resnet50_eps_chip", base.get("resnet50_eps_chip")),
                   ("bert_base_eps_chip", base.get("bert_base_eps_chip")),
                   ("moe_bert_eps_chip", base.get("moe_bert_eps_chip")),
                   ("bert_large_eps_chip", base.get("bert_large_eps_chip")),
                   ("bert_long_eps_chip", base.get("bert_long_eps_chip")),
                   ("gpt_small_eps_chip", base.get("gpt_small_eps_chip")),
                   ("gpt_long_eps_chip", base.get("gpt_long_eps_chip")),
                   ("gpt_decode_tokens_s_chip",
                    base.get("gpt_decode_tokens_s_chip"))):
        if extra.get(key.replace("_eps_chip", "_suspect")
                     .replace("_tokens_s_chip", "_suspect")):
            continue
        if key == "gpt_decode_tokens_s_chip":
            dev_b = base.get("gpt_decode_device_token_ms")
            dev_m = extra.get("gpt_decode_device_token_ms")
            # both must be POSITIVE: a negative slope (corrupt leg that
            # dodged the suspect flag) in a ratio would NaN the geomean
            if dev_b and dev_m and dev_b > 0 and dev_m > 0:
                ratios.append(dev_b / dev_m)   # ms: lower is faster
                continue
        if extra.get(key) and b:
            ratios.append(extra[key] / b)
    return float(np.prod(ratios) ** (1 / len(ratios))) if ratios else 1.0


def main() -> None:
    # persistent compilation cache: the gate is ~10 executables x
    # ~40-60 s of compile when cold. Turned on HERE, not at import:
    # importers of bench helpers (tests, bench_scaling) must not
    # inherit the cache.
    enable_compilation_cache()
    only = os.environ.get("BENCH_ONLY", "").split(",") if \
        os.environ.get("BENCH_ONLY") else None
    on_tpu = jax.devices()[0].platform == "tpu"
    scale = 1 if on_tpu else 16

    extra: dict[str, float | None] = {}
    for w in _workloads(on_tpu, scale):
        if only is not None and not (w["only"] & set(only)):
            continue
        key = w["key"]
        if "serving" in w:
            row = _run_serving(**w["serving"])
            for k, v in row.items():
                extra[f"{key}_{k}"] = v
            continue
        if "decode" in w:
            row = _run_decode(**w["decode"])
            extra[f"{key}_tokens_s_chip"] = round(row["tokens_s_chip"])
            extra[f"{key}_token_step_ms"] = round(row["token_step_ms"], 3)
            extra[f"{key}_weight_bound_ms"] = round(
                row["weight_bound_ms"], 3)
            extra[f"{key}_spread"] = round(row["spread"], 4)
            if "device_token_ms" in row:
                extra[f"{key}_device_token_ms"] = round(
                    row["device_token_ms"], 4)
                extra[f"{key}_call_overhead_ms"] = round(
                    row["call_overhead_ms"], 2)
                extra[f"{key}_long_spread"] = round(row["long_spread"], 4)
            if row["suspect"]:
                extra[f"{key}_suspect"] = True
            # int8 weight-quant leg (round 12): same program shape with
            # the decode weights dequantized inside the scan — the
            # promoted lever-table row, published so the next TPU
            # window verifies the ~2x tokens/s/chip target (ROADMAP
            # item 1). No second amortize leg: the int8 row regresses
            # on token_step_ms until its device-component baseline
            # exists.
            irow = _run_decode(**dict(
                w["decode"], amortize_new=None,
                gen_kwargs={"weight_quant": "int8"}))
            extra[f"{key}_int8_token_ms"] = round(
                irow["token_step_ms"], 3)
            extra[f"{key}_int8_tokens_s_chip"] = round(
                irow["tokens_s_chip"])
            if irow["suspect"]:
                extra[f"{key}_int8_suspect"] = True
            continue
        eps, ms, mfu, mfu_basis, peak_mib, suspect, anomalies = _run(
            w["model"], batch=w["batch"], steps=w["steps"],
            warmup=w["warmup"], opt=w["opt"],
            make_batch=w["make_batch"],
            extra_cfg=w.get("extra_cfg"), cfg_over=w.get("cfg_over"),
            steps_per_call=w.get("steps_per_call", 1),
            prng_impl=w.get("prng_impl"))
        extra[f"{key}_eps_chip"] = round(eps, w.get("eps_digits", 1))
        extra[f"{key}_step_ms"] = round(ms, w.get("ms_digits", 2))
        # always published, even at 0: the gate diffs rows, and a column
        # that only appears when nonzero cannot be watched for regressions
        extra[f"{key}_anomaly_count"] = anomalies
        if mfu:
            extra[f"{key}_mfu"] = round(mfu, 4)
            extra[f"{key}_mfu_basis"] = mfu_basis
        if peak_mib:
            extra[f"{key}_peak_mib"] = round(peak_mib)
        if suspect:
            extra[f"{key}_suspect"] = True

    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "bench_baseline.json")
    base = {}
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)

    # headline: MNIST MLP examples/sec/chip (the one metric with a recorded
    # round-1 baseline; ResNet-50/BERT baselines recorded from this round on).
    # A suspect-flagged mnist reading is corrupt by the code's own
    # verdict — publish 0.0 (with the flag in extra) rather than the
    # absurd number as the governing metric
    headline = (0.0 if extra.get("mnist_mlp_suspect")
                else extra.get("mnist_mlp_eps_chip", 0.0))
    vs = vs_baseline_geomean(extra, base)

    devices = jax.devices()
    print(json.dumps({
        "metric": "mnist_mlp_examples_per_sec_per_chip",
        "value": headline,
        "unit": "examples/sec/chip",
        "vs_baseline": round(vs, 3),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
