"""Readings for the output check's limits, taken on the chip.

    python3 -m benchmark.readings --workload <cell> --seeds 12 \
        --control_seeds 3 --seconds 1 --out chiprun_out/readings_<cell>.json

One process: for each seed a short run of the cell (the sound program's
numbers), and for the first ``--control_seeds`` seeds the control's (the
reference computed in the precision below the configuration's). The
limits in ``benchmark/limits/<cell>.json`` are set from the largest
sound and the smallest control reading; PERF.md quotes them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control_seeds", type=int, default=3)
    ap.add_argument("--first_seed", type=int, default=2_200_000_000)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    bench_run.prepare(a.rehearse)
    manifest = Manifest(ROOT)
    rows = {"workload": a.workload, "sound": [], "control": []}
    for i in range(max(a.seeds, a.control_seeds)):
        seed = a.first_seed + 7919 * i
        ns = argparse.Namespace(workload=a.workload, seed=seed,
                                seconds=a.seconds, trace=0,
                                rehearse=a.rehearse)
        env = bench_run.Env(manifest, ns)
        try:
            kind = manifest.kind(env.traffic)
            if i < a.seeds:
                out = kind.run(env)
                rows["sound"].append({"seed": seed, **out["compared"]})
                print("sound", rows["sound"][-1], flush=True)
            if i < a.control_seeds:
                rows["control"].append({"seed": seed, **kind.control(env)})
                print("control", rows["control"][-1], flush=True)
        finally:
            env.cleanup()
    names = [k for k in rows["sound"][0] if k != "seed"] if rows["sound"] \
        else [k for k in rows["control"][0] if k != "seed"]
    rows["summary"] = {
        n: {"sound_max": max((r[n] for r in rows["sound"]), default=None),
            "control_min": min((r[n] for r in rows["control"]),
                               default=None)} for n in names}
    print(json.dumps(rows["summary"], indent=1), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
