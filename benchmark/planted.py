"""One run of a cell with a fault planted in its timed path, at the
cell's OWN size: the upper reading of a limit where the reference in a
lower precision is not the fault the cell exists to catch.

    python3 -m benchmark.planted --fault one_shard_batch \
        --workload gpt2s-train-dp4 --seed <n> --seconds 5 --trace 0

The run is ``benchmark.run``'s in every other respect: it prints each
compared number beside its limit and a result line that has to read
``"correct": false``. Faults (``benchmark/tests`` rehearses each on the
CPU):

- ``one_shard_batch`` (kind ``train``): every chip is fed the FIRST
  chip's rows of the global batch. The averaged gradient is then what one
  replica computes alone: the update a step applies on a chip where the
  gradient exchange was left out, and what a data-parallel step that
  reads a quarter of its batch applies on all of them.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from benchmark import run as bench_run


def one_shard_batch(parts: int, trainer=None, **_):
    real = trainer.sync.shard_batch

    def shard(batch):
        rows = len(next(iter(batch.values()))) // parts
        # chip i gets rows [i * rows, (i + 1) * rows): all of them the
        # first chip's
        return real({k: np.concatenate([v[:rows]] * parts, axis=0)
                     for k, v in batch.items()})

    trainer.sync.shard_batch = shard


FAULTS = {"one_shard_batch": one_shard_batch}


def hook_for(fault: str):
    """The ``env_hook`` that plants ``fault`` over as many parts as the
    cell has chips."""
    def hook(env):
        parts = int(env.cell["chips"])
        env.break_program = lambda **kw: FAULTS[fault](parts, **kw)
    return hook


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    a, rest = ap.parse_known_args(argv)
    return bench_run.main(rest, env_hook=hook_for(a.fault))


if __name__ == "__main__":
    sys.exit(main())
