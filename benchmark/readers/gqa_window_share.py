"""Of the cache bytes the traced decode steps read, the share that came
from the window layers' rings: the spans' ``window_bytes`` over
``window_bytes + kv_bytes``. The rings are bounded whatever the context,
so the share falls as the contexts grow: how much of the cache traffic
the window layers bound. ``None`` without such spans."""

from benchmark.readers import gqa_steps


def read(ctx: dict):
    ring = pool = 0.0
    for args, _ in gqa_steps.totals(ctx, "decode") or ():
        ring += float(args.get("window_bytes", 0))
        pool += float(args.get("kv_bytes", 0))
    return 100.0 * ring / (ring + pool) if ring + pool else None
