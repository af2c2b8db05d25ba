"""Shared plumbing of the sweep scripts: one fresh process per cell.

A sweep's launcher (``--all`` and friends) runs every cell as a child
process of the same script, so that one cell's compile-time OOM or crash
costs one row and not the sweep. Two rules keep such a sweep honest:

- a cell that fails prints its error row AND exits non-zero
  (:func:`fail`);
- the launcher runs every cell regardless, then exits non-zero itself,
  naming the failed cells, if any child did (:func:`run_cells`) — a
  sweep whose rows are half errors must never read as a clean run.
"""

from __future__ import annotations

import json
import subprocess
import sys
from typing import Iterable, NoReturn, Sequence


def fail(row: dict, exc: BaseException, *, limit: int = 250) -> NoReturn:
    """Print ``row`` with the exception as its ``error`` field (the row
    the sweep's table keeps for a failed cell) and exit 1."""
    print(json.dumps({**row,
                      "error": f"{type(exc).__name__}: "
                               f"{str(exc)[:limit]}"}), flush=True)
    sys.exit(1)


def run_cells(script: str, cells: Iterable[Sequence],
              env: dict | None = None) -> None:
    """Run ``python script *cell`` for every cell, each in a fresh
    process that inherits this one's environment (``env`` replaces it),
    then exit non-zero if any child did.

    A child that dies before its own error handler (the host's OOM
    killer at compile is the realistic case) is recorded here from its
    return code, so a row can never silently vanish from the table."""
    failed = []
    for cell in cells:
        cell = [str(a) for a in cell]
        rc = subprocess.run([sys.executable, script, *cell],
                            env=env).returncode
        if rc == 0:
            continue
        failed.append(f"{' '.join(cell)} (rc {rc})")
        if rc != 1:                  # 1 = fail() already printed the row
            print(json.dumps({
                "cell": cell,
                "error": f"cell process exited {rc} (killed before its "
                         "error handler — host OOM is the usual cause)"}),
                flush=True)
    if failed:
        sys.exit(f"{len(failed)} cell(s) failed: " + ", ".join(failed))
