"""The spread of ``gpt2s-serve-chat``'s end-to-end metrics over a call's runs,
a side at a time (PR 46, call 4, after the driver's refusal), and what a pair
at one seed shares and does not.

    python3 benchmark/records/pr46/spread.py benchmark/records/pr46/call4

A spread is the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``); "less the farthest" is the same over
the runs but the one farthest from their median, as the driver's refusal read
it. Per pair: the window's engine phases a decode step and a prefill (``/stats``
snapshots), and the requests' own latencies side by side (the record's
``requests``: id, due, sent, done, tokens out, queue ms).
"""
import glob
import json
import re
import statistics as st
import sys

CELL = "gpt2s-serve-chat"
METRICS = ("req_latency_p50_ms", "req_latency_p95_ms", "setup_s")


def iqr(xs):
    q = st.quantiles(xs, n=4)
    return q[2] - q[0]


def less_farthest(xs):
    mid = st.median(xs)
    kept = list(xs)
    kept.remove(max(xs, key=lambda x: abs(x - mid)))
    return iqr(kept)


def runs(directory):
    """{side: {seed: (metrics, correct, failed)}} of the call's logs."""
    out = {}
    for path in sorted(glob.glob(f"{directory}/*_{CELL}_seed*_t0.log")):
        side = path.split("/")[-1].split("_")[0]
        seed = re.search(r"seed(\d+)", path).group(1)
        line = json.loads(open(path).read().strip().splitlines()[-1])
        out.setdefault(side, {})[seed] = (
            {k: v["value"] for k, v in line["metrics"].items()},
            line["correct"], line["failed"])
    return out


def window(directory, side, seed):
    """The window's engine phases: ms a decode step, ms a prefill."""
    snaps = [json.loads(l) for l in open(
        f"{directory}/{side}_{CELL}_{seed}_t0/stats_snapshots.jsonl")]
    a, b = snaps[-2], snaps[-1]
    steps = b["decode_steps"] - a["decode_steps"]
    prefills = b["prefills"] - a["prefills"]
    phase = {k: b["sched_phase_seconds"][k] - a["sched_phase_seconds"][k]
             for k in b["sched_phase_seconds"]}
    return {"decode_steps": steps, "prefills": prefills,
            **{f"{k}_ms_a_step": round(1e3 * phase[k] / steps, 3)
               for k in ("dispatch", "wait_logits", "sample_emit")},
            **{f"{k}_ms_a_prefill": round(1e3 * phase[k] / prefills, 3)
               for k in ("admit", "admit_launch", "admit_read")}}


def latencies(directory, side, seed):
    record = json.load(open(
        f"{directory}/{side}_{CELL}_{seed}_t0/{CELL}_seed{seed}_trace0.json"))
    return {r[0]: 1e3 * (r[3] - r[2]) for r in record["record"]["requests"]}


def main(directory):
    got = runs(directory)
    for side, by_seed in got.items():
        for seed, (m, correct, failed) in by_seed.items():
            print(side, seed, correct, failed,
                  {k: round(v, 3) for k, v in m.items()})
    for name in METRICS:
        for side, by_seed in got.items():
            xs = [r[0][name] for r in by_seed.values()]
            mid = st.median(xs)
            print(f"{name} {side}: n {len(xs)} median {mid:.3f} spread "
                  f"{iqr(xs):.3f} ({100 * iqr(xs) / mid:.2f} %) less the "
                  f"farthest {less_farthest(xs):.3f}")
    for seed in got.get("parent", {}):
        if seed not in got.get("change", {}):
            continue
        p, c = got["parent"][seed][0], got["change"][seed][0]
        print(f"seed {seed}: p50 {p[METRICS[0]]:.2f} -> {c[METRICS[0]]:.2f} "
              f"({100 * (c[METRICS[0]] / p[METRICS[0]] - 1):+.2f} %), p95 "
              f"{p[METRICS[1]]:.2f} -> {c[METRICS[1]]:.2f}")
        print("  parent", window(directory, "parent", seed))
        print("  change", window(directory, "change", seed))
        lp, lc = (latencies(directory, s, seed) for s in ("parent", "change"))
        diff = [lc[i] - lp[i] for i in lp if i in lc]
        sp, sc = sorted(lp.values()), sorted(lc.values())
        half = len(sp) // 2
        print(f"  requests {len(diff)}: mean latency "
              f"{st.mean(lp.values()):.2f} -> {st.mean(lc.values()):.2f} ms; "
              f"one request's change - parent: mean {st.mean(diff):+.2f}, "
              f"sd {st.pstdev(diff):.2f} ms; the twelve about the median: "
              f"parent {[round(x, 1) for x in sp[half - 6:half + 6]]}, "
              f"change {[round(x, 1) for x in sc[half - 6:half + 6]]}")


if __name__ == "__main__":
    main(sys.argv[1])
