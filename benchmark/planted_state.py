"""One run of a per-request-state cell with a fault planted in its timed
path, at the cell's OWN size (``benchmark/planted.py`` for the faults of
a recurrent state; that file is not edited):

    python3 -m benchmark.planted_state --fault state_dropped_at_chunk \
        --workload kimi-serve-backlog --seed <n> --seconds 45 --trace 0

The run is ``benchmark.run``'s in every other respect; its result line
has to read ``"correct": false``. Faults (``benchmark/tests`` rehearses
each on the CPU):

- ``state_dropped_at_chunk``: a request's recurrent rows are zeroed
  before its SECOND prefill chunk: the state is not carried across one
  chunk boundary (every prompt longer than a chunk).
- ``state_dropped_at_seeded_chunk``: the same before ONE later chunk of
  each prompt, drawn by the seed among the chunks after its first; the
  run's last line on stderr lists ``[prompt tokens, first token of that chunk]``.
- ``slot_not_zeroed``: a request that takes a slot starts from the
  recurrent rows the slot's last request left.

Under seeded random weights a recurrent state forgets what it held within
``REACH`` tokens, so a fault further than that before a request's first
served token leaves no trace in what the request was served. The checked
sample of a planted run is therefore drawn by the seed among the finished
requests the fault lies within ``REACH`` prompt tokens of
(``env.check_pool``, read by ``kinds/serve_state.check_outputs``); a sound
run's is drawn among all of them.
"""

from __future__ import annotations

import argparse
import functools
import sys

import numpy as np

from benchmark import run as bench_run

#: prompt tokens between a fault and the first served token within which
#: the fault still reads above the limits (benchmark/limits/kimi-serve-
#: backlog.json, ``readings.served_logit_gap_request_max.reach``)
REACH = 400
#: ``[prompt tokens, first token of the chunk whose state was dropped]``
DROPPED: list = []


def _key(prompt) -> tuple:
    return len(prompt), tuple(int(x) for x in prompt[:8])


def _within_reach(env, fault_at) -> None:
    """``env.check_pool``: of the finished requests, those whose fault
    (``fault_at(prompt) -> first token it touched``, None: none) lies at
    most ``REACH`` prompt tokens before the first served token."""
    def pool(finished):
        out = []
        for r in finished:
            at = fault_at(env.requests_by_idx[r["idx"]]["prompt"])
            if at is not None and 0 < r["prompt_len"] - at <= REACH:
                out.append(r)
        return out
    env.check_pool = pool


def _drop_before(server, env, start_of) -> None:
    """Zero a slot's recurrent rows before the chunk that starts at
    ``start_of(prompt tokens)`` (None: this prompt is left whole)."""
    eng = server.engine
    sw, real = eng.sw, eng.sw.prefill_chunk
    at: dict = {}
    dropped: dict = {}
    _within_reach(env, lambda prompt: dropped.get(_key(prompt)))

    def chunk(feats):
        slot, start = int(feats["slot"]), int(feats["start"])
        if start == 0:
            prompt = next(s for s in eng._prefilling.values()
                          if s.index == slot).req.prompt
            p = len(prompt)
            at[slot] = start_of(p)
            if at[slot] is not None and at[slot] < p:
                DROPPED.append([p, at[slot]])
                dropped[_key(prompt)] = at[slot]
        if start and start == at.get(slot):
            pool = sw.zero_slot({k: v for k, v in feats.items()
                                 if k.startswith("cache_")}, slot)
            feats = {**feats, **pool}
        return real(feats)

    sw.prefill_chunk = chunk


def state_dropped_at_chunk(server=None, env=None, **_):
    if server is not None:
        width = int(server.engine.sw.prefill_chunk_tokens)
        _drop_before(server, env, lambda p: width)


def state_dropped_at_seeded_chunk(server=None, env=None, **_):
    if server is None:
        return
    width = int(server.engine.sw.prefill_chunk_tokens)
    rs = np.random.RandomState(env.seed % 2**32)

    def start_of(p):
        chunks = -(-p // width)
        return width * int(rs.randint(1, chunks)) if chunks > 1 else None

    _drop_before(server, env, start_of)


def slot_not_zeroed(server=None, env=None, **_):
    if server is not None:
        server.engine._zero_slot_state = lambda index: None
        _within_reach(env, lambda prompt: 0)    # stale from its first token


FAULTS = {"state_dropped_at_chunk": state_dropped_at_chunk,
          "state_dropped_at_seeded_chunk": state_dropped_at_seeded_chunk,
          "slot_not_zeroed": slot_not_zeroed}


def hook_for(fault: str):
    def hook(env):
        env.break_program = functools.partial(FAULTS[fault], env=env)
    return hook


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    a, rest = ap.parse_known_args(argv)
    try:
        return bench_run.main(rest, env_hook=hook_for(a.fault))
    finally:
        if DROPPED:
            print(f"planted: state dropped at {DROPPED}", file=sys.stderr,
                  flush=True)


if __name__ == "__main__":
    sys.exit(main())
