"""One run of a dense-latent decoder's cell with a fault planted in its
timed path, at the cell's OWN size (``benchmark/planted_dsa.py`` and
``planted_gqa.py`` for the faults of rotary latent rows under YaRN, an
absorbed step beside an expanded chunk and a router limited by groups;
those files are not edited):

    python3 -m benchmark.planted_mla --fault rope_left_off_k \
        --workload axk1-serve-longctx --seed <n> --seconds 45 --trace 0

The run is ``benchmark.run``'s in every other respect; its result line
has to read ``"correct": false`` for each fault the cell's limits claim to
catch (``benchmark/limits/axk1-serve-longctx.json`` says which the chip's
bfloat16 cell sees). Faults, each planted on the registry's model before
it is exported (``env.break_program(model=...)``), so the served programs
carry it (``benchmark/tests`` rehearses each on the CPU):

- ``rope_left_off_k``: the one ``k_pe`` a token's heads share is cached
  without rotary positions (the queries keep theirs), chunk and step.
- ``yarn_scale_left_off``: the softmax scale is ``192^-1/2`` without
  YaRN's ``mscale(32, mscale_all_dim)^2`` (1.81 times smaller scores).
- ``group_limit_dropped``: the router picks the 8 largest biased scores
  over all 192 experts, not inside the 4 best of 8 groups.
- ``routed_scale_left_off``: the renormalised picks are not multiplied by
  ``routed_scaling_factor`` (2.5).
- ``absorbed_uses_stale_row``: a step's absorbed attention reads the
  latent pool as it lay BEFORE the step's own rows were written (what the
  row's block held, not the token's own latent); the chunk's expanded
  form is sound.

Every request is reached by each fault from its first served token, so
the checked sample is drawn as a sound run's is.
"""

from __future__ import annotations

import argparse
import functools
import sys

from benchmark import run as bench_run


def rope_left_off_k(model=None, **_):
    if model is not None:
        model._rope_k = lambda k_pe, pos, theta, g=None: k_pe


def yarn_scale_left_off(model=None, **_):
    if model is not None:
        model.cfg.rope_mscale_all_dim = 0.0


def group_limit_dropped(model=None, **_):
    if model is not None:
        model._expert_groups = lambda: 1


def routed_scale_left_off(model=None, **_):
    if model is not None:
        model._routed_scale = lambda: 1.0


def absorbed_uses_stale_row(model=None, **_):
    if model is not None:
        model._step_pool = lambda before, after: before


FAULTS = {"rope_left_off_k": rope_left_off_k,
          "yarn_scale_left_off": yarn_scale_left_off,
          "group_limit_dropped": group_limit_dropped,
          "routed_scale_left_off": routed_scale_left_off,
          "absorbed_uses_stale_row": absorbed_uses_stale_row}


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
