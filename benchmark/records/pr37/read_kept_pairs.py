"""What ``readers/launch_pairs.py`` reads of a traced window kept by
``run_keep_pairs.py``, computed off the chip from the kept file alone
(PR 37): the order-pairing, the interval of causal shifts, the four
parts of ``admit`` beside ``sched_idle_ms``'s whole on the same
alignment (the time off the CPU it also printed in the first round
went with ``launch_pairs.offcpu``; ``kept_pairs_read.txt`` is that
round's output).

    python3 benchmark/records/pr37/read_kept_pairs.py benchmark/records/pr37/call*/pairs_*.json.gz
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from benchmark.readers import launch_pairs, sched_idle_ms


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    spans = {name: sorted((a, b, stats) for a, b, stats in rows)
             for name, rows in doc["spans"].items()}
    found = {"spans": spans, "device_shift_s": doc["device_shift_s"],
             "chips": [{"ops": [(a, b, "while") for a, b in doc["whiles"]],
                        "busy": [tuple(x) for x in doc["busy"]],
                        "window": tuple(doc["window"])}]}
    paired = launch_pairs.pair(spans, [tuple(m) for m in doc["modules"]])
    print(launch_pairs.describe(paired))
    at = launch_pairs.aligned(found, paired["shift"])
    steps = len(launch_pairs.inside(spans, launch_pairs.STEPS,
                                    [at["chips"][0]["window"]]))
    ms = {k: 1e3 * v / steps
          for k, v in launch_pairs.admit_idle(at).items()}
    ms["admit (sched_idle_ms, same alignment)"] = 1e3 * sched_idle_ms \
        .idle_by_phase(at)["seconds"]["admit"] / steps
    ms["shift"] = 1e3 * paired["shift"]
    ms["slack"] = 1e3 * (paired["hi"] - paired["lo"])
    return {"steps": steps, "ms_a_step": {k: round(v, 4)
                                          for k, v in ms.items()}}


if __name__ == "__main__":
    for path in sys.argv[1:]:
        print(os.path.basename(path), json.dumps(read(path)))
