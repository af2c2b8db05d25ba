"""``benchmark.run`` with what ``readers/launch_pairs.py`` reads of the
traced window kept beside the run's record (PR 37): the scheduler's
spans with their arguments, the first chip's executed programs and busy
intervals, as one gzipped JSON, so that a pairing can be looked at (and
the reader changed) off the chip; and the engine's phase counters, wall
and CPU, at the window's two edges (``phase_seconds.jsonl``: the
benchmark reads ``engine.stats()`` there). The first hook wraps
``stop_trace`` and parses the capture once more after it, INSIDE the
measured window: a run under it reads slower end to end, and is no
measurement of that (``KEEP_PAIRS=0`` leaves it out; the parent's
checkout, which has no such reader, runs under this file so). The second
returns what ``stats`` returned.

    BENCHMARK_RECORD_DIR=DIR python3 benchmark/records/pr37/run_keep_pairs.py \
        --workload gpt2s-serve-backlog --seed N --seconds 45 --trace 1
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run

KEEP = ("sched_", "admit_", "decode_step", "verify_step", "block_step",
        "cow_copy", "prefill", "retire")


def tap(server=None, **_):
    if server is None:      # a kind's earlier call, with the model alone
        return
    real = server.engine.stats
    out_dir = os.environ.get("BENCHMARK_RECORD_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)

    def stats(*a, **kw):
        got = real(*a, **kw)
        with open(os.path.join(out_dir, "phase_seconds.jsonl"), "a") as f:
            f.write(json.dumps({k: got.get(k) for k in (
                "decode_steps", "prefills", "prefill_chunks", "cow_copies",
                "sched_phase_seconds", "sched_phase_cpu_seconds")}) + "\n")
        return got

    server.engine.stats = stats


def hook(env):
    env.break_program = tap
    if not env.trace or os.environ.get("KEEP_PAIRS") == "0":
        return          # the counters alone: nothing inside the window
    from benchmark import trace_reduce
    from benchmark.readers import launch_pairs, xplane_join
    real = env.stop_trace

    def stop_trace():
        real()
        path = trace_reduce.find_xplane(env.trace_dir)
        found = xplane_join.parse(path)
        out_dir = os.environ.get("BENCHMARK_RECORD_DIR", ".")
        os.makedirs(out_dir, exist_ok=True)
        if found is None:
            return
        chip = found["chips"][0]
        doc = {"spans": {k: v for k, v in found["spans"].items()
                         if k.startswith(KEEP)},
               "busy": chip["busy"], "window": chip["window"],
               "whiles": [(a, b) for a, b, c in chip["ops"]
                          if c == "while"],
               "device_shift_s": found["device_shift_s"],
               "raw_one_while": found["raw_one_while"],
               "modules": launch_pairs.modules(path)}
        name = (f"pairs_{env.cell['name']}_seed{env.seed}.json.gz")
        with gzip.open(os.path.join(out_dir, name), "wt") as f:
            json.dump(doc, f, default=str)

    env.stop_trace = stop_trace


if __name__ == "__main__":
    sys.exit(bench_run.main(sys.argv[1:], env_hook=hook))
