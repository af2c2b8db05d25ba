"""``benchmark.run`` with the engine's ``/stats`` snapshots kept (PR 36's
``records/pr36/run_with_stats.py`` for the state decoders' cells, whose kind
calls the hook with the model first): what each snapshot says of the chunk
programs' expert layers, beside the run's record. Nothing of the run changes.

    BENCHMARK_RECORD_DIR=DIR python3 benchmark/records/pr41/run_with_stats.py \
        --workload laguna-serve-mixed --seed N --seconds 45 --trace 0
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run

KEYS = ("prefill_chunks", "moe_bounded_layers", "moe_whole_layers",
        "moe_rows", "moe_tiles", "decode_steps", "tokens_out",
        "requests_done", "sched_phase_seconds", "attn_schedule",
        "kv_pool_bytes", "window_cache_bytes", "decode_kv_bytes")


def tap(server=None, **_):
    if server is None:              # the hook's first call hands the model
        return
    real = server.engine.stats
    out_dir = os.environ.get("BENCHMARK_RECORD_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "stats_snapshots.jsonl")
    # what export.json says the programs' expert layers were traced with
    state = server.engine.sw.meta["stepwise"].get("state") or {}
    with open(path, "a") as f:
        f.write(json.dumps({"export.json": {
            k: state.get(k) for k in ("moe_tiles", "moe_rows")}}) + "\n")

    def stats(*a, **kw):
        got = real(*a, **kw)
        with open(path, "a") as f:
            f.write(json.dumps({"t": time.perf_counter(),
                                **{k: got.get(k) for k in KEYS}}) + "\n")
        return got

    server.engine.stats = stats


def hook(env):
    env.break_program = tap


if __name__ == "__main__":
    sys.exit(bench_run.main(sys.argv[1:], env_hook=hook))
