"""How far a serving cell's end-to-end metrics move with the seed's order
of the requests alone: one set-up, then the cell's whole ramp, window and
drain once for each seed, each reduced by the run's own arithmetic
(``kinds/serve.measure``).

    python3 -m benchmark.spread --workload gpt2s-serve-chat --seeds 10 \
        --seconds 45 --out chiprun_out/spread_chat.json

A run of the cell is a process of its own with weights from its seed; this
tool keeps one server (the first seed's weights: lengths are fixed by
``max_new``, so the weights do not move a time) and so reads ten seeds for
the chip time of three runs. It leaves out what differs between processes;
the sets of whole runs in ``records/`` show that part. PERF.md section 2
quotes both where a bound was set from them.
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
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--first_seed", type=int, default=2_300_000_000)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    bench_run.prepare(a.rehearse)
    from benchmark import stats
    manifest = Manifest(ROOT)
    ns = argparse.Namespace(workload=a.workload, seed=a.first_seed,
                            seconds=a.seconds, trace=0, rehearse=a.rehearse)
    env = bench_run.Env(manifest, ns)
    serve = manifest.kind(env.traffic)
    rows = []
    try:
        srv, url, _, ref_cfg, _, spans = serve.start_server(env, env.traffic)
        print("set-up", spans, flush=True)
        try:
            for i in range(a.seeds):
                env.seed = a.first_seed + 7919 * i
                plan = serve.make_plan(env, env.traffic, url,
                                       ref_cfg["vocab_size"])
                out, _ = serve.drive(env, srv, plan)
                m = serve.measure(plan, out)
                rows.append({"seed": env.seed, "attempted": m["attempted"],
                             "failed": m["failed"], **m["values"]})
                print(json.dumps(rows[-1]), flush=True)
        finally:
            srv.stop(drain=False)
    finally:
        env.cleanup()
    summary = {}
    for name in (k for k in rows[0] if k not in ("seed", "attempted",
                                                  "failed")):
        xs = [r[name] for r in rows]
        summary[name] = {"min": min(xs), "max": max(xs),
                         "spread": stats.spread(xs) if len(xs) > 1 else None}
    print(json.dumps(summary, indent=1), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "seconds": a.seconds,
                       "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
