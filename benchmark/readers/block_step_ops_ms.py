"""Device milliseconds a block step of the operations whose name matches
``pattern``, inside the executed block-step programs only (a prefill runs
the same kernels: it is not counted). With ``after``: of EVERYTHING that
starts after the last operation matching ``after`` in each program (what
follows the last layer's grouped matmul is its combine, the final norm,
the head, the argmax and the confidence). ``None`` without block steps
(``readers/block_steps.py``) or a matching operation."""

import re

from benchmark.readers import block_steps


def read(ctx: dict, pattern: str = "", after: str = ""):
    found = block_steps.steps(ctx)
    if not found:
        return None
    seconds, hit = 0.0, False
    for step in found:
        ops = step["ops"]
        if after:
            last = max((b for a, b, n in ops if re.search(after, n)),
                       default=None)
            if last is None:
                continue
            tail = [(a, b) for a, b, _ in ops if a >= last]
            if tail:
                hit = True
                # outermost operations only: a fusion's inner events nest
                end = 0.0
                for a, b in sorted(tail):
                    if a >= end:
                        seconds += b - a
                        end = b
            continue
        for a, b, n in ops:
            if re.search(pattern, n):
                seconds += b - a
                hit = True
    return 1e3 * seconds / len(found) if hit else None
