"""In-capture wall of every scheduler span, ms a decode step and its
median, from the captures ``run_keep_pairs.py`` kept (PR 37, call 14):
which host span is longer at the change than at the parent.

    python3 benchmark/records/pr37/span_walls.py DIR/*/pairs_*.json.gz
"""
import gzip
import json
import statistics
import sys


def walls(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    lo, hi = doc["window"]
    inside = {name: [s for s in spans if lo <= s[0] and s[1] <= hi]
              for name, spans in doc["spans"].items()}
    steps = len(inside["decode_step"])
    out = {"window_s": round(hi - lo, 4), "steps": steps}
    for name, spans in sorted(inside.items()):
        if spans:
            seconds = [s[1] - s[0] for s in spans]
            out[name] = (len(spans), round(1e3 * sum(seconds) / steps, 3),
                         round(1e3 * statistics.median(seconds), 3))
    return out


if __name__ == "__main__":
    rows = {path.split("/")[-2]: walls(path) for path in sys.argv[1:]}
    names = sorted({n for r in rows.values() for n in r})
    for name in names:
        print(name, {side: r.get(name) for side, r in rows.items()})
