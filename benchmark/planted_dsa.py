"""One run of a selecting decoder's cell with a fault planted in its timed
path, at the cell's OWN size (``benchmark/planted_state.py`` for the
faults of a learned sparse selection, a window layer and rotary MLA; that
file is not edited):

    python3 -m benchmark.planted_dsa --fault selection_dropped \
        --workload dots3-serve-longctx --seed <n> --seconds 45 --trace 0

The run is ``benchmark.run``'s in every other respect; its result line
has to read ``"correct": false`` for each fault the cell's limits claim to
catch (``benchmark/limits/dots3-serve-longctx.json`` says which).
Faults, each planted on the registry's model before it is exported
(``env.break_program(model=...)``), so the served programs carry it
(``benchmark/tests`` rehearses each on the CPU):

- ``selection_dropped``: the full-attention layers attend to every
  earlier row, in the chunk and in the one-token step: the indexer's
  scores choose nothing.
- ``window_not_applied``: the window layers' mask is left off: a row sees
  every row its chunk and the ring hold (up to 1,536 in a chunk, 528 in a
  step) instead of the last 513.
- ``index_keys_stale``: a chunk does not write its index keys: a
  request's selection reads what its blocks' last owner left there (zeros
  in a block never used).
- ``rope_left_off_k_pe``: the shared ``k_pe`` is cached without its
  rotary positions (``q_pe`` keeps them).

Every request is reached by each fault from its first prompt token, so
the checked sample is drawn as a sound run's is.
"""

from __future__ import annotations

import argparse
import functools
import sys

from benchmark import run as bench_run


def selection_dropped(model=None, **_):
    if model is None:
        return
    import jax.numpy as jnp

    def every_row(scores, k):
        t = scores.shape[-1]
        at = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), scores.shape)
        return at, scores > -jnp.inf

    model._select = lambda scores, k, live: scores > -jnp.inf
    model._select_rows = every_row


def window_not_applied(model=None, **_):
    if model is not None:
        model._window = lambda: 1 << 30


def index_keys_stale(model=None, **_):
    if model is not None:
        model._chunk_index = lambda index, j, blocks, keys: index


def rope_left_off_k_pe(model=None, **_):
    if model is not None:
        model._rope_k = lambda k_pe, pos, theta: k_pe


FAULTS = {"selection_dropped": selection_dropped,
          "window_not_applied": window_not_applied,
          "index_keys_stale": index_keys_stale,
          "rope_left_off_k_pe": rope_left_off_k_pe}


def hook_for(fault: str):
    def hook(env):
        env.break_program = functools.partial(FAULTS[fault], env=env)
    return hook


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    a, rest = ap.parse_known_args(argv)
    return bench_run.main(rest, env_hook=hook_for(a.fault))


if __name__ == "__main__":
    sys.exit(main())
