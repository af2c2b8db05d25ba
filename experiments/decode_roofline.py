#!/usr/bin/env python
"""Autoregressive decode roofline + lever table (VERDICT r4 task #3,
r5 task #3 — the per-token kernel-floor attack).

Round 5 measured KV-cache greedy decode at 0.67 ms/token-step of device
time (b8, prompt 128 + 128 new, GPT-small) — 2.5x the weight-traffic
bound (0.267 ms), root-caused to a per-op latency floor (~100 small
kernels/token — PROFILE_r05_decode). This script measures the decode
step against that bound and runs the levers:

  batch   — b in {1, 8, 16, 32, 64}: weight reads amortize over rows,
            so tokens/s/chip should scale until something else binds
  newlen  — max_new in {32, 128, 256} at b8: cache length T = prompt +
            new grows attention/DUS traffic; measures its slope
  trace   — jax.profiler capture of one generation dispatch, reduced
            with utils.trace_summary (committed as PROFILE_r05_decode)
  lever   — the round-6 fast-path lever table, one row per config:
              loop     the pre-fast-path reference (per-layer Python
                       loop, 3 QKV matmuls, XLA 1-query attention)
              stacked  lax.scan over restacked layer params + fused
                       QKV, XLA attention (isolates the scan/fusion
                       win from the kernel win)
              pallas   stacked + the single-query Pallas cache-slab
                       attention kernel (decode_attention="auto":
                       engages on TPU; off-TPU the row equals stacked)
              ktoken   pallas + tokens_per_dispatch=4 (K token steps
                       unrolled per loop body)
              int8     ktoken + int8-quantized stacked layer weights
                       (LOSSY — the weight-traffic comparison row)

Each cell is a fresh process (no cell inherits another's device
memory or compiled programs); prints one JSON line per cell. Numbers + verdicts live in BASELINE.md
("Decode fast path").

Usage: python experiments/decode_roofline.py batch 8
       python experiments/decode_roofline.py newlen 256
       python experiments/decode_roofline.py lever stacked
       python experiments/decode_roofline.py trace /tmp/decode_trace
       python experiments/decode_roofline.py --all
       python experiments/decode_roofline.py --levers
"""

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cells import fail, run_cells  # noqa: E402

PROMPT = 128

#: the lever table rows: cumulative fast-path configs (generate kwargs)
LEVERS = {
    "loop": {"decode_impl": "loop"},
    "stacked": {"decode_impl": "stacked", "decode_attention": "xla"},
    "pallas": {"decode_impl": "stacked", "decode_attention": "auto"},
    "ktoken": {"decode_impl": "stacked", "decode_attention": "auto",
               "tokens_per_dispatch": 4},
    "int8": {"decode_impl": "stacked", "decode_attention": "auto",
             "tokens_per_dispatch": 4, "weight_quant": "int8"},
}


def _build(batch: int):
    import jax
    import numpy as np

    from distributed_tensorflow_example_tpu.config import (DataConfig,
                                                           TrainConfig)
    from distributed_tensorflow_example_tpu.models import get_model
    from distributed_tensorflow_example_tpu.models.base import cast_floating
    import jax.numpy as jnp

    cfg = TrainConfig(model="gpt", dtype="bfloat16",
                      param_dtype="bfloat16",
                      data=DataConfig(batch_size=batch))
    model = get_model("gpt", cfg)
    params = cast_floating(model.init(jax.random.key(0)), jnp.bfloat16)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.data.vocab_size, (batch, PROMPT),
                                 dtype=np.int32))
    return model, params, ids


def measure(batch: int, max_new: int, *, reps=7, warmup=2,
            lever: str | None = None, tiny: bool = False) -> dict:
    # ONE decode-measurement implementation: bench.py's _run_decode
    # (device_get timing + median-of-repeats + weight-floor retry +
    # suspect flag) — the experiment and the gate must never measure
    # two different ways (that divergence is how the round-4 1.55 ms
    # and the artifacted 0.001 ms readings coexisted)
    from bench import _run_decode

    gen_kwargs = LEVERS[lever] if lever else None
    row = _run_decode(
        batch=batch, prompt=PROMPT if not tiny else 16,
        max_new=max_new, reps=reps, warmup=warmup, tiny=tiny,
        gen_kwargs=gen_kwargs)
    out = {
        "batch": batch, "prompt": PROMPT if not tiny else 16,
        "max_new": max_new,
        "gen_ms": round(row["token_step_ms"] * max_new, 1),
        "token_step_ms": round(row["token_step_ms"], 3),
        "tokens_per_s_chip": round(row["tokens_s_chip"]),
        # naive bound: every param (bf16) read once per token-step
        "weight_bound_ms": round(row["weight_bound_ms"], 3),
        "spread": round(row["spread"], 4),
        "suspect": row["suspect"],
    }
    if lever:
        import jax
        out["lever"] = lever
        out["platform"] = jax.devices()[0].platform
        out["tiny"] = tiny
    return out


def trace(outdir: str) -> dict:
    import jax

    model, params, ids = _build(8)
    gen = jax.jit(functools.partial(model.generate, max_new_tokens=128))
    jax.block_until_ready(gen(params, ids))      # compile outside trace
    jax.profiler.start_trace(outdir)
    jax.block_until_ready(gen(params, ids))
    jax.profiler.stop_trace()
    return {"trace": outdir}


def main() -> None:
    if sys.argv[1:2] == ["--all"]:
        run_cells(os.path.abspath(__file__),
                  [("batch", b) for b in (1, 8, 16, 32, 64)]
                  + [("newlen", n) for n in (32, 256)])
        return
    if sys.argv[1:2] == ["--levers"]:
        # the round-6 lever table: one fresh process per row
        run_cells(os.path.abspath(__file__),
                  [("lever", name) for name in LEVERS])
        return
    mode, arg = sys.argv[1], sys.argv[2]
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)
    enable_compilation_cache()
    try:
        if mode == "batch":
            out = measure(int(arg), 128)
        elif mode == "newlen":
            out = measure(8, int(arg))
        elif mode == "lever":
            if arg not in LEVERS:
                raise SystemExit(f"unknown lever {arg!r}; have "
                                 f"{sorted(LEVERS)}")
            # off-TPU the GPT-small decode is minutes per row: fall back
            # to the tiny model (relative ordering only, labeled)
            on_tpu = jax.devices()[0].platform == "tpu"
            out = measure(8, 128 if on_tpu else 32, lever=arg,
                          reps=7 if on_tpu else 3,
                          warmup=2 if on_tpu else 1, tiny=not on_tpu)
        elif mode == "trace":
            out = trace(arg)
        else:
            raise SystemExit(f"unknown mode {mode!r}")
        print(json.dumps(out), flush=True)
    except Exception as e:  # noqa: BLE001
        fail({"mode": mode, "arg": arg}, e)


if __name__ == "__main__":
    main()
