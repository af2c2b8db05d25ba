"""The engine's phase counters over a run's window, from the two
``/stats`` snapshots ``run_keep_pairs.py`` keeps (PR 37): ms a step of
each phase on the wall and on the CPU, and the time off the CPU over the
phases that wait for no device (``readers/launch_pairs.py``'s
``offcpu``, from counters over 45 s instead of spans over 3 s). The CPU
part reads the records of calls 3 and 4 only, whose build read the CPU
clock in every phase of every iteration; the committed build reads it
inside a profiler session alone (what that cost: ``call4/``), so its
CPU counters cover the capture's 3 s of a window and this file's
``offcpu_ms`` of such a run means nothing.

    python3 benchmark/records/pr37/read_phase_seconds.py DIR/phase_seconds.jsonl ...
"""
import json
import sys

HOST_ONLY = ("housekeeping", "secure_blocks", "build_feats", "sample_emit",
             "admit_emit")


def read(path: str) -> dict:
    with open(path) as f:
        a, b = [json.loads(line) for line in f][:2]
    steps = b["decode_steps"] - a["decode_steps"]
    out = {"steps": steps, "prefills_a_step": round(
        (b["prefills"] + b["prefill_chunks"] - a["prefills"]
         - a["prefill_chunks"]) / steps, 3)}
    wall = {k: b["sched_phase_seconds"][k] - v
            for k, v in a["sched_phase_seconds"].items()}
    out["wall_ms"] = {k: round(1e3 * v / steps, 4) for k, v in wall.items()}
    if a.get("sched_phase_cpu_seconds"):
        cpu = {k: b["sched_phase_cpu_seconds"][k] - v
               for k, v in a["sched_phase_cpu_seconds"].items()}
        out["cpu_ms"] = {k: round(1e3 * v / steps, 4)
                         for k, v in cpu.items()}

        def host(d):    # the host-only phases and admit's self time
            return (sum(d[k] for k in HOST_ONLY) + d["admit"]
                    - d["admit_launch"] - d["admit_read"]
                    - d["admit_emit"])
        out["host_only_wall_ms"] = round(1e3 * host(wall) / steps, 4)
        out["host_only_cpu_ms"] = round(1e3 * host(cpu) / steps, 4)
        out["offcpu_ms"] = round(1e3 * (host(wall) - host(cpu)) / steps, 4)
    return out


if __name__ == "__main__":
    for path in sys.argv[1:]:
        print(path, json.dumps(read(path)))
