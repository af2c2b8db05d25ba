"""``benchmark.run`` with the engine's ``/stats`` snapshots kept (as
``records/pr36/run_with_stats.py`` and ``records/pr44/``'s, with PR 45's keys
added: ``steps_behind_step``, ``step_ahead_dead_rows``, ``decode_slot_steps``):
the benchmark reads
``srv.engine.stats()`` at the window's opening and close; this writes what each
snapshot says of the chunks queued behind a step and of the host's blocking
reads beside the run's record. Nothing of the run changes. On the parent the
new keys read ``null``.

    BENCHMARK_RECORD_DIR=DIR python3 benchmark/records/pr45/run_with_stats.py \
        --workload laguna-serve-mixed --seed N --seconds 45 --trace 1
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run

KEYS = ("decode_steps", "decode_slot_steps", "prefill_chunks",
        "chunks_behind_step", "steps_behind_step", "step_ahead_dead_rows",
        "host_reads", "prefill_chunk_tokens_total", "tokens_out", "requests_done",
        "moe_bounded_layers", "moe_whole_layers", "moe_rows",
        "moe_routed_rows", "latent_pool_bytes", "state_bytes",
        "sched_phase_seconds")


def tap(server=None, **_):
    if server is None:          # the hook's other call (the model)
        return
    real = server.engine.stats
    out_dir = os.environ.get("BENCHMARK_RECORD_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "stats_snapshots.jsonl")

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
