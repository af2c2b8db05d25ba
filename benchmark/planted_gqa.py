"""One run of a grouped-query decoder's cell with a fault planted in its
timed path, at the cell's OWN size (``benchmark/planted_dsa.py`` for the
faults of two head counts over one set of KV heads, a window over rings,
half-rotary YaRN, a head gate and a scaled softmax router; that file is
not edited):

    python3 -m benchmark.planted_gqa --fault group_map_wrong \
        --workload laguna-serve-mixed --seed <n> --seconds 45 --trace 0

The run is ``benchmark.run``'s in every other respect; its result line
has to read ``"correct": false`` for each fault the cell's limits claim to
catch (``benchmark/limits/laguna-serve-mixed.json`` says which). Faults,
each planted on the registry's model before it is exported
(``env.break_program(model=...)``), so the served programs carry it
(``benchmark/tests`` rehearses each on the CPU):

- ``group_map_wrong``: query head ``j`` reads KV head ``j % KVH`` (the
  heads dealt round the KV heads) instead of ``j // (H / KVH)``, in both
  kinds of layer, chunk and step.
- ``window_not_applied``: the window layers' mask is left off: a row sees
  every row its chunk and the ring hold instead of the last 512.
- ``rope_on_whole_head``: the full layers rotate all 128 values of a head
  at the plain frequencies of ``rope_theta`` (no half, no YaRN, no
  attention factor).
- ``head_gate_dropped``: ``W_o`` is fed the heads' outputs without
  ``sigmoid(W_g n)``.
- ``routed_scale_left_off``: the renormalised picks are not multiplied by
  ``moe_routed_scaling_factor`` (2.5).

Every request is reached by each fault from its first prompt token, so
the checked sample is drawn as a sound run's is.
"""

from __future__ import annotations

import argparse
import functools
import sys

from benchmark import run as bench_run


def group_map_wrong(model=None, **_):
    if model is None:
        return
    import numpy as np
    kvh = model.cfg.kv_heads

    def order(h):
        # position p = (j % KVH) * G + j // KVH holds query head j, so the
        # grouping p // G hands head j the KV head j % KVH
        g = h // kvh
        j = np.arange(h)
        at = np.empty(h, np.int64)
        at[(j % kvh) * g + j // kvh] = j
        return at

    model._heads_in = lambda q: q[:, order(q.shape[1])]
    model._heads_out = lambda ctx: ctx[:, np.argsort(order(ctx.shape[1]))]


def window_not_applied(model=None, **_):
    if model is not None:
        model._window = lambda: 1 << 29


def rope_on_whole_head(model=None, **_):
    if model is None:
        return
    from distributed_tensorflow_example_tpu.models import decoder
    model._full_rope = lambda x, pos: decoder._rope(
        x, pos, model.cfg.rope_theta)


def head_gate_dropped(model=None, **_):
    if model is not None:
        model._gate_heads = lambda mp, n, ctx: ctx


def routed_scale_left_off(model=None, **_):
    if model is not None:
        model._routed_scale = lambda: 1.0


FAULTS = {"group_map_wrong": group_map_wrong,
          "window_not_applied": window_not_applied,
          "rope_on_whole_head": rope_on_whole_head,
          "head_gate_dropped": head_gate_dropped,
          "routed_scale_left_off": routed_scale_left_off}


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
