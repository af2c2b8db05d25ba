"""The fp8 control of ``axk1-serve-longctx`` alone, cold (no server
runs): ``kinds/serve_state.control`` over sequences of the mix's sizes
drawn from each seed, the reference in fp8 in the program's place.

    python3 benchmark/records/pr44/control.py SEED [SEED ...] [--rehearse]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.getcwd())


def main():
    from benchmark import run as bench_run
    from benchmark.manifest import ROOT, Manifest
    rehearse = int("--rehearse" in sys.argv)
    bench_run.prepare(rehearse)
    manifest = Manifest(ROOT)
    for seed in (int(a) for a in sys.argv[1:] if a.isdigit()):
        ns = argparse.Namespace(workload="axk1-serve-longctx", seed=seed,
                                seconds=45.0, trace=0, rehearse=rehearse)
        env = bench_run.Env(manifest, ns)
        try:
            compared = manifest.kind(env.traffic).control(env)
            correct = bench_run.decide(env, compared)
            print(json.dumps({"control": "fp8", "seed": seed,
                              "correct": correct, **compared}), flush=True)
        finally:
            env.cleanup()


if __name__ == "__main__":
    main()
