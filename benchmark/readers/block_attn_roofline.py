"""Share of its roofline the block step's attention kernel reached: the
least time the chip could take to read the K and V the traced steps' live
rows' windows held (``kv_bytes``, an argument of each ``block_step``
span; memory-bound) over the device time of the kernel BY NAME
(``paged_block_attn``) inside those steps: not every ``custom-call``,
since the grouped matmuls are custom calls too. ``None`` without such
steps, the argument or the kernel."""

import re

from benchmark.readers import block_steps


def read(ctx: dict, pattern: str = "paged_block_attn"):
    found = block_steps.steps(ctx)
    if not found:
        return None
    kv_bytes = seconds = 0.0
    for step in found:
        t = sum(b - a for a, b, n in step["ops"] if re.search(pattern, n))
        if t and "kv_bytes" in step["args"]:
            kv_bytes += float(step["args"]["kv_bytes"])
            seconds += t
    if not seconds or not kv_bytes:
        return None
    return 100.0 * (kv_bytes / ctx["peak"]["hbm_bytes_per_s"]) / seconds
