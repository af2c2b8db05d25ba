"""``benchmark.run`` with the engine's ``/stats`` snapshots kept (as
``records/pr36/run_with_stats.py`` and ``records/pr45/``'s, with PR 46's keys:
``prefills``, ``prefills_by_width``, ``prefill_pad_share``, ``block_steps``,
``jit_compiles``, ``jit_compile_s``): the benchmark reads
``srv.engine.stats()`` at the window's opening and close; this writes what each
snapshot says of the widths the prompts were admitted through beside the run's
record. Nothing of the run changes. On the parent the new keys read ``null``.

    BENCHMARK_RECORD_DIR=DIR python3 benchmark/records/pr46/run_with_stats.py \
        --workload sdar-serve-backlog --seed N --seconds 45 --trace 1
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run

KEYS = ("prefills", "prefills_by_width", "prefill_pad_share", "block_steps",
        "decode_steps", "decode_slot_steps", "denoise_forwards",
        "commit_forwards", "tokens_committed", "host_reads", "tokens_out",
        "requests_done", "jit_compiles", "jit_compile_s",
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
