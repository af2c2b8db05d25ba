"""End-to-end numbers of traced runs, which print none (PR 37): a
``--trace 1`` line holds the per-layer metrics only, but the record
beside it (``BENCHMARK_RECORD_DIR``) keeps every request's due, sent and
done times and the kind's own counts. For a chat run: latency from the
instant a request was due, over the requests due in the window (the
ramp's end to 45 s later), as ``kinds/serve.measure`` takes it (an
untraced run's reading here equals its line's). For any run: the step
time and the completed tokens a second, where the kind computed them.

    python3 benchmark/records/pr37/read_records.py DIR/*/*_trace?.json
"""
import json
import sys

import numpy as np


def read(path: str, ramp_s: float = 15.0, window_s: float = 45.0) -> dict:
    with open(path) as f:
        doc = json.load(f)
    layer = doc["record"]["per_layer"]
    out = {key: round(layer[key], 4) for key in (
        "decode_step_ms", "block_step_ms", "completed_tokens_per_s",
        "offered_tokens_per_s") if key in layer}
    for name, m in doc["line"]["metrics"].items():
        if name.startswith(("serve_tokens_per_s", "req_latency")):
            out["line_" + name] = round(m["value"], 2)
    if doc["argv"]["workload"] == "gpt2s-serve-chat":
        lat = [1e3 * (done - due) for _, due, _, done, *_ in
               doc["record"]["requests"] if ramp_s <= due < ramp_s + window_s]
        out.update(requests=len(lat),
                   p50_ms=round(float(np.percentile(lat, 50)), 2),
                   p95_ms=round(float(np.percentile(lat, 95)), 2))
    return out


if __name__ == "__main__":
    for path in sys.argv[1:]:
        print("/".join(path.split("/")[-2:]), json.dumps(read(path)))
