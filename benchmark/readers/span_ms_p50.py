"""Median duration, in milliseconds, of the program's spans of one name
inside the traced window, from the capture's host plane
(``readers/xplane_join.py``): ``decode_step`` is the decode step as the
scheduler feels it, prefills excluded. ``None`` without such spans."""

from benchmark import stats
from benchmark.readers import xplane_join


def read(ctx: dict, name: str):
    found = xplane_join.join(ctx)
    if found is None:
        return None
    lo, hi = found["chips"][0]["window"]
    ms = [1e3 * (s[1] - s[0]) for s in found["spans"].get(name, ())
          if s[0] >= lo and s[1] <= hi]
    return stats.percentile(ms, 50) if ms else None
