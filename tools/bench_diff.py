"""bench_diff — machine-checkable comparison of two bench result files.

The driver's bench captures (and the bench gate itself) had no tool
answering "did anything regress between these two runs?" — reviewers
eyeballed JSON tails. This compares a baseline and a candidate file
key by key with a per-key relative tolerance and exits 1 on any
regression, so a TPU-window re-base (ROADMAP item 5) can gate on it:

    python tools/bench_diff.py tests/fixtures/bench_capture_old.json \
        tests/fixtures/bench_capture_new.json
    python tools/bench_diff.py old.json new.json --tolerance 0.15 \
        --key gpt_serving_tps=0.3 --json

Accepted file shapes (auto-detected):

- a driver capture: ``{"n", "cmd", "rc", "tail"}`` where
  ``tail`` holds bench.py's JSON lines (``{"metric", "value",
  "extra": {...}}``) — metrics and their ``extra`` keys are flattened
  into one ``{key: value}`` table;
- a plain JSON object of numeric keys (a bench row, a summary line,
  ``bench_baseline.json``-style files; non-numeric values are
  ignored).

Direction is inferred from the key: ``*_ms`` / ``*_s`` / ``*_seconds``
/ ``*_errors`` / ``*_failures`` / ``*_dropped`` / ``*_drift_rate`` /
``*_bytes*`` are lower-is-better, everything else (tps, mfu,
eps_chip, rates, counts of useful work) higher-is-better; override
per key with ``--lower key`` / ``--higher key``. A key present in
only one file is reported (``missing_*``) but is not a regression —
new bench keys appear every few PRs and must not break the gate. A
zero baseline cannot anchor a relative tolerance, so it is reported
as ``zero_baseline`` and skipped.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

LOWER_BETTER_MARKERS = ("_ms", "_s", "_seconds", "_errors",
                        "_failures", "_dropped", "_drift_rate")


def load_metrics(path: str) -> dict[str, float]:
    """One file -> flat ``{key: numeric value}`` (see module
    docstring for the accepted shapes)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got "
                         f"{type(doc).__name__}")
    out: dict[str, float] = {}
    if "tail" in doc and isinstance(doc["tail"], str):
        for line in doc["tail"].splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict):
                continue
            if "metric" in rec and isinstance(
                    rec.get("value"), (int, float)):
                out[str(rec["metric"])] = float(rec["value"])
            extra = rec.get("extra")
            if isinstance(extra, dict):
                for k, v in extra.items():
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        out[str(k)] = float(v)
        if not out:
            raise ValueError(
                f"{path}: a tail-style capture with no parseable "
                "metric lines — nothing to compare")
        return out
    for k, v in doc.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[str(k)] = float(v)
    if not out:
        raise ValueError(f"{path}: no numeric keys to compare")
    return out


def lower_is_better(key: str) -> bool:
    # rates named *_per_s (tokens_per_s, requests_per_s — the serving
    # row shape) are throughput: the bare "_s" marker below must not
    # claim them as latencies
    if key.endswith("_per_s"):
        return False
    if "bytes" in key:
        return True
    return any(key.endswith(m) for m in LOWER_BETTER_MARKERS)


def diff(old: dict[str, float], new: dict[str, float], *,
         tolerance: float = 0.1,
         key_tolerance: dict[str, float] | None = None,
         force_lower: set[str] | None = None,
         force_higher: set[str] | None = None
         ) -> list[dict[str, Any]]:
    """Per-key comparison rows, regressions first then by key.

    A regression is a move in the key's WORSE direction by more than
    its relative tolerance: ``(new - old) / |old|`` above tol for
    lower-is-better keys, below -tol for higher-is-better keys."""
    key_tolerance = key_tolerance or {}
    force_lower = force_lower or set()
    force_higher = force_higher or set()
    rows: list[dict[str, Any]] = []
    for key in sorted(set(old) | set(new)):
        if key not in old:
            rows.append({"key": key, "status": "missing_old",
                         "new": new[key]})
            continue
        if key not in new:
            rows.append({"key": key, "status": "missing_new",
                         "old": old[key]})
            continue
        o, n = old[key], new[key]
        tol = key_tolerance.get(key, tolerance)
        if key in force_lower:
            lower = True
        elif key in force_higher:
            lower = False
        else:
            lower = lower_is_better(key)
        row = {"key": key, "old": o, "new": n,
               "lower_is_better": lower, "tolerance": tol}
        if o == 0.0:
            row["status"] = ("ok" if n == 0.0 else "zero_baseline")
            rows.append(row)
            continue
        rel = (n - o) / abs(o)
        row["delta_rel"] = round(rel, 6)
        worse = rel > tol if lower else rel < -tol
        better = rel < -tol if lower else rel > tol
        row["status"] = ("regression" if worse
                         else "improved" if better else "ok")
        rows.append(row)
    rows.sort(key=lambda r: (r["status"] != "regression", r["key"]))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare two bench result files; exit 1 on any "
                    "regression beyond tolerance")
    ap.add_argument("old", help="baseline file (BENCH_rNN.json or a "
                    "plain numeric JSON object)")
    ap.add_argument("new", help="candidate file")
    ap.add_argument("--tolerance", type=float, default=0.1,
                    help="default relative tolerance (0.1 = 10%%)")
    ap.add_argument("--key", action="append", default=[],
                    metavar="KEY=TOL",
                    help="per-key tolerance override (repeatable), "
                    "e.g. --key gpt_serving_tps=0.3")
    ap.add_argument("--lower", action="append", default=[],
                    help="force this key lower-is-better (repeatable)")
    ap.add_argument("--higher", action="append", default=[],
                    help="force this key higher-is-better (repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full row table as JSON")
    args = ap.parse_args(argv)
    key_tol: dict[str, float] = {}
    for spec in args.key:
        k, sep, v = spec.partition("=")
        if not sep:
            ap.error(f"--key takes KEY=TOL, got {spec!r}")
        try:
            key_tol[k] = float(v)
        except ValueError:
            ap.error(f"--key {spec!r}: tolerance must be a number")
    try:
        old = load_metrics(args.old)
        new = load_metrics(args.new)
    except (OSError, ValueError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    rows = diff(old, new, tolerance=args.tolerance,
                key_tolerance=key_tol,
                force_lower=set(args.lower),
                force_higher=set(args.higher))
    regressions = [r for r in rows if r["status"] == "regression"]
    if args.json:
        print(json.dumps({"ok": not regressions,
                          "regressions": len(regressions),
                          "compared": sum(
                              1 for r in rows
                              if r["status"] not in ("missing_old",
                                                     "missing_new")),
                          "rows": rows}))
    else:
        for r in rows:
            if r["status"] in ("missing_old", "missing_new"):
                print(f"{r['status']:<13} {r['key']}")
                continue
            arrow = "v" if r["lower_is_better"] else "^"
            rel = r.get("delta_rel")
            rel_s = "     -" if rel is None else f"{100 * rel:+6.1f}%"
            print(f"{r['status']:<13} {r['key']:<44} "
                  f"{r['old']:>14g} -> {r['new']:>14g}  {rel_s} "
                  f"(better {arrow}, tol {r['tolerance']:g})")
        print(f"bench_diff: {len(regressions)} regression(s) in "
              f"{len(rows)} key(s)")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
