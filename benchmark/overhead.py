"""What the program's tracing costs a serving cell end to end: one set-up,
then the cell's whole ramp, window and drain under three arms,

- ``off``: the span ring disarmed (what ``flight_recorder=False`` runs:
  ``span()`` returns its no-op, no annotation is entered),
- ``ring``: the ring armed (the default, and what ``--trace 0`` measures),
- ``trace``: the ring armed and the profiler capturing ``trace_seconds``
  of the window, as a ``--trace 1`` run does (the capture is reduced
  inside the window there too),

each reduced by the run's own arithmetic (``kinds/serve.measure``). The
arms of one round share a seed, rounds differ in it, and the order of
the arms alternates so that drift cancels.

    python3 -m benchmark.overhead --workload gpt2s-serve-backlog \
        --rounds 4 --seconds 20 --out chiprun_out/overhead_backlog.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import run as bench_run
from benchmark.manifest import ROOT, Manifest

ARMS = ("off", "ring", "trace")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--first_seed", type=int, default=2_400_001_000)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    bench_run.prepare(a.rehearse)
    from benchmark import stats
    from distributed_tensorflow_example_tpu.obs import trace as obs_trace
    manifest = Manifest(ROOT)
    ns = argparse.Namespace(workload=a.workload, seed=a.first_seed,
                            seconds=a.seconds, trace=0, rehearse=a.rehearse)
    env = bench_run.Env(manifest, ns)
    serve = manifest.kind(env.traffic)
    rows = []
    try:
        srv, url, _, ref_cfg, _, spans = serve.start_server(env, env.traffic)
        print("set-up", spans, flush=True)
        rec = obs_trace.recorder()
        assert rec.enabled, "the server did not arm the ring"
        try:
            for i in range(a.rounds):
                env.seed = a.first_seed + 7919 * i
                for arm in (ARMS if i % 2 == 0 else ARMS[::-1]):
                    rec.enabled = arm != "off"
                    env.trace = arm == "trace"
                    plan = serve.make_plan(env, env.traffic, url,
                                           ref_cfg["vocab_size"])
                    out, edges = serve.drive(env, srv, plan)
                    m = serve.measure(plan, out)
                    (t_a, s_a), (t_b, s_b) = edges["open"], edges["close"]
                    steps = s_b["decode_steps"] - s_a["decode_steps"]
                    rows.append({
                        "seed": env.seed, "arm": arm,
                        "attempted": m["attempted"], "failed": m["failed"],
                        "decode_step_ms": 1e3 * (t_b - t_a) / max(1, steps),
                        "spans_recorded": rec.spans_recorded,
                        **m["values"]})
                    print(json.dumps(rows[-1]), flush=True)
        finally:
            rec.enabled = True
            srv.stop(drain=False)
    finally:
        env.cleanup()
    summary = {}
    names = [k for k in rows[0] if k not in ("seed", "arm", "attempted",
                                             "failed", "spans_recorded")]
    for arm in ARMS:
        mine = [r for r in rows if r["arm"] == arm]
        summary[arm] = {n: stats.percentile([r[n] for r in mine], 50)
                        for n in names}
    # each round's arms against its own ``ring`` arm, as ratios
    by_seed: dict = {}
    for r in rows:
        by_seed.setdefault(r["seed"], {})[r["arm"]] = r
    summary["ratio_to_ring"] = {
        arm: {n: [by_seed[s][arm][n] / by_seed[s]["ring"][n]
                  for s in sorted(by_seed)] for n in names}
        for arm in ("off", "trace")}
    print(json.dumps(summary, indent=1), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "seconds": a.seconds,
                       "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
