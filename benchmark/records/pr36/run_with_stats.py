"""``benchmark.run`` with the engine's ``/stats`` snapshots kept (PR 36): the
benchmark reads ``srv.engine.stats()`` at the window's opening and close; this
writes what each snapshot says of the decode steps' host copies beside the
run's record, so that "every step of the window fetched ids" can be read off
a file. Nothing of the run changes: the hook wraps ``stats`` and returns what
it returned.

    BENCHMARK_RECORD_DIR=DIR python3 benchmark/records/pr36/run_with_stats.py \
        --workload gpt2s-serve-backlog --seed N --seconds 45 --trace 1
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run

KEYS = ("decode_steps", "decode_ids_steps", "decode_logits_steps",
        "decode_host_bytes", "decode_kv_bytes", "prefills", "tokens_out",
        "requests_done")


def tap(server=None, **_):
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
