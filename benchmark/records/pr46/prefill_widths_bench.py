"""Each width of SDAR's whole-prompt prefill alone on the chip, at the cell's shapes and seeded weights (one
process, ~4 chip minutes): what the export and the load of the widths cost, and per width the device ms of one
``jit_prefill`` from a capture (the program, ``flash_fwd`` and the grouped matmuls by NAME), on a prompt that fills
the width and on one that fills half of it and one token (the rest padding, as the engine pads):

    chiprun -- python3 benchmark/records/pr46/prefill_widths_bench.py chiprun_out/pr46/prefill_widths.jsonl SEED

A third argument runs the rehearsal's sizes on the CPU (does it run; no time is kept)."""
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402

from benchmark import trace_reduce, weights_by_leaf         # noqa: E402
from benchmark.manifest import load_module                  # noqa: E402
from distributed_tensorflow_example_tpu import serving      # noqa: E402
from distributed_tensorflow_example_tpu.config import TrainConfig   # noqa: E402
from distributed_tensorflow_example_tpu.models import get_model     # noqa: E402
from distributed_tensorflow_example_tpu.runtime.device import (     # noqa: E402
    enable_compilation_cache)

OUT = sys.argv[1] if len(sys.argv) > 1 else None
SEED = int(sys.argv[2]) if len(sys.argv) > 2 else 4600000001
TINY = len(sys.argv) > 3
ITERS = 3
ROOT = os.getcwd()
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", "sdar-30b-a3b-chat.json")))
ENGINE = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "blockdiff-backlog.json")))
ENGINE = (ENGINE["rehearsal"] if TINY else ENGINE)["engine"]
ref = load_module(os.path.join(ROOT, "benchmark", "reference", "sdar-30b-a3b-chat.py"))


def emit(row):
    print(json.dumps(row), flush=True)
    if OUT:
        os.makedirs(os.path.dirname(OUT) or ".", exist_ok=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(row) + "\n")


def main():
    enable_compilation_cache()
    if TINY:
        model = get_model("sdar_moe_tiny", TrainConfig(model="sdar_moe_tiny", dtype="float32",
                                                       param_dtype="float32"))
        spec, hi = ref.param_spec(CONFIG["rehearsal"]["sizes"]), 500
    else:
        model = get_model("sdar_moe", TrainConfig(model="sdar_moe", num_layers=6, dtype="bfloat16",
                                                  param_dtype="bfloat16"))
        spec, hi = ref.param_spec(CONFIG), int(CONFIG["assumed"]["first_special_id"])
    model.cfg.denoising_steps = 2
    params = weights_by_leaf.make_params(spec, SEED, model.param_dtype)
    jax.block_until_ready(params)
    d = tempfile.mkdtemp(prefix="pr46_export_")
    try:
        t0 = time.perf_counter()
        serving.export_generator(model, params, d, ragged=True, stepwise=True, paged=True,
                                 slots=ENGINE["slots"], block_size=ENGINE["block_size"],
                                 prompt_len=ENGINE["prompt_len"],
                                 max_new_tokens=ENGINE["max_new_tokens"],
                                 platforms=tuple(ENGINE["platforms"]))
        export_s = time.perf_counter() - t0
        del params
        t0 = time.perf_counter()
        sw = serving.load_stepwise(d)
        load_s = time.perf_counter() - t0
        meta = sw.step_meta
        emit({"what": "export", "seed": SEED, "device": jax.devices()[0].device_kind,
              "export_s": round(export_s, 2), "load_s": round(load_s, 2), "widths": list(sw.prefill_widths),
              "moe_tiles": {k: v for k, v in meta["block"]["moe_tiles"].items() if k.startswith("prefill")},
              "files": sorted(f for f in os.listdir(d) if f.endswith(".stablehlo"))})
        bs = int(meta["block_size"])
        pool = sw.make_pool()
        rs = np.random.RandomState(SEED % (2 ** 31))
        for w in sw.prefill_widths:
            for fill, p in (("full", w), ("half", w // 2 + 1)):
                ids = np.zeros((1, w), np.int32)
                ids[0, :p] = rs.randint(110, hi, p)
                row = np.zeros((w // bs,), np.int32)
                row[:-(-p // bs)] = 1 + np.arange(-(-p // bs))

                def call(pool):
                    out = sw.prefill({"input_ids": ids, "prompt_mask": (ids > 0).astype(np.int32),
                                      "table_row": row, **pool})
                    out["cache_k"].block_until_ready()
                    return {k: v for k, v in out.items() if k.startswith("cache_")}

                t0 = time.perf_counter()
                pool = call(pool)
                first_s = time.perf_counter() - t0
                host = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    pool = call(pool)
                    host.append((time.perf_counter() - t0) * 1e3)
                rec = {"what": "prefill", "width": w, "fill": fill, "prompt_tokens": p,
                       "first_call_s": round(first_s, 2), "host_ms": [round(x, 2) for x in host]}
                if not TINY:
                    tmp = tempfile.mkdtemp(prefix="pr46_trace_")
                    try:
                        jax.profiler.start_trace(tmp)
                        try:
                            for _ in range(ITERS):
                                pool = call(pool)
                        finally:
                            jax.profiler.stop_trace()
                        red = trace_reduce.reduce(trace_reduce.find_xplane(tmp))
                    finally:
                        shutil.rmtree(tmp, ignore_errors=True)
                    runs = red["modules"].get("jit_prefill", [])
                    rec["programs"] = len(runs)
                    rec["program_ms"] = round(sum(runs) / max(len(runs), 1) * 1e3, 3)
                    for name, pattern in (("flash_fwd_ms", "flash_fwd"), ("ragged_dot_ms", "ragged-dot"),
                                          ("sort_gather_scatter_ms", "^(sort|gather|scatter)")):
                        rec[name] = round(trace_reduce.op_seconds(red, pattern=pattern) / ITERS * 1e3, 3)
                    top = sorted(red["top_ops"].items(), key=lambda kv: -kv[1])[:8]
                    rec["top_ops_ms"] = {k: round(v / ITERS * 1e3, 3) for k, v in top}
                emit(rec)
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
