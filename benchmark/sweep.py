"""The knee sweep: the highest arrival rate the server sustains, found
ONCE, when the open-loop cell is defined (PERF.md keeps the table).

    python3 -m benchmark.sweep --workload gpt2s-serve-chat --seed 7 \
        --rates 6,7,8,9,10,11,12 --seconds 30 --out chiprun_out/sweep.json

One process and one set-up; then a ramp, a window and a drain at each
rate. A rate is sustained when the tokens completed per second of the
requests due in the window stay within 2 % of those offered and the
requests in the system at the window's end are no more than 10 % above
those at its start. The cell then runs at 0.8 of the knee, a number in
its traffic file; no run searches for a rate.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    bench_run.prepare(a.rehearse)
    from benchmark import stats
    manifest = Manifest(ROOT)
    ns = argparse.Namespace(workload=a.workload, seed=a.seed,
                            seconds=a.seconds, trace=0, rehearse=a.rehearse)
    env = bench_run.Env(manifest, ns)
    serve = manifest.kind(env.traffic)
    rows = []
    try:
        srv, url, _, ref_cfg, _, spans = serve.start_server(env, env.traffic)
        print("set-up", spans, flush=True)
        try:
            for i, rate in enumerate(float(r) for r in a.rates.split(",")):
                t = copy.deepcopy(env.traffic)
                (t["rehearsal"] if a.rehearse else t)["mix"]["rate_rps"] = rate
                env.seed = a.seed + i
                plan = serve.make_plan(env, t, url, ref_cfg["vocab_size"])
                out, edges = serve.drive(env, srv, plan)
                lo, hi = plan["window"]
                due = [r for r in plan["requests"] if lo <= r["due_s"] < hi]
                got = {r["idx"]: r for r in out["results"]
                       if r.get("status") == 200}
                done = [got[q["idx"]] for q in due if q["idx"] in got]
                lat = [1e3 * (r["done_s"] - r["due_s"]) for r in done]
                (_, s0), (_, s1) = edges["open"], edges["close"]
                in0 = s0["live_slots"] + s0["queue_depth"]
                in1 = s1["live_slots"] + s1["queue_depth"]
                offered = sum(q["max_new"] for q in due) / (hi - lo)
                completed = sum(
                    len(r["tokens"]) for r in out["results"]
                    if r.get("status") == 200
                    and lo <= r["done_s"] < hi) / (hi - lo)
                row = {"rate_rps": rate, "due": len(due),
                       "returned": len(done),
                       "offered_tokens_per_s": offered,
                       "completed_tokens_per_s": completed,
                       "in_system_open": in0, "in_system_close": in1,
                       "p50_ms": stats.percentile(lat, 50) if lat else None,
                       "p95_ms": stats.percentile(lat, 95) if lat else None,
                       "sustained": bool(
                           len(done) == len(due)
                           and completed >= 0.98 * offered
                           and in1 <= 1.1 * max(in0, 1))}
                rows.append(row)
                print(json.dumps(row), flush=True)
        finally:
            srv.stop(drain=False)
    finally:
        env.cleanup()
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
