"""PR 32: a model of `kimi-serve-backlog`'s scheduler, on the CPU, to ask
what of `serve_tokens_per_s`'s 7 % spread the seed's deal of lengths makes.

    python3 benchmark/records/pr32/schedule_model.py

One iteration = one 1,024-token chunk for the oldest admitted prompt (if
any) + one decode step for every slot past its prompt + a little idle;
256 clients, 128 slots, FIFO, ramp 30 s, window 45 s; the metric credits a
request's tokens evenly over the time its client waited, as
`kinds/serve.measure` does. Times are the chip's (call 7), but the LEVEL it
reads is no measurement: only how it moves with the order of the lengths.

Read (PR 32): the seeds' own orders, 24 seeds: spread 3.2 %, max - min 11 %
(the chip: 6.9 % over ten; over the 13 seeds the chip ran, r = 0.83 between
model and chip). `same_work`'s one order: the same number at every seed by
construction; under 10 % of jitter an iteration and 0.2 s stalls, 0.8 %
(the chip: 1.10 % over call 10's six seeds, 1.54 % over ten with call 11).
"""

import math
import os
import statistics
import sys

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *[".."] * 3))
sys.path.insert(0, ROOT)

from benchmark import datagen                                   # noqa: E402
from benchmark.manifest import Manifest, load_module            # noqa: E402


def lengths(clients):
    return [[(len(r["prompt"]), r["max_new"]) for r in q] for q in clients]


def model(clients, slots=128, chunk_s=0.0285, step_s=0.0193, idle_s=0.004,
          ramp=30.0, window=45.0, jitter=None):
    lo, hi = ramp, ramp + window
    t, free = 0.0, slots
    queue = [(0.0, c) for c in range(len(clients))]
    nxt = [0] * len(clients)
    prefill, decode, done = [], {}, []
    while queue or prefill or decode:
        while free and queue:
            sent, c = queue.pop(0)
            p, k = clients[c][nxt[c]]
            prefill.append([c, math.ceil(p / 1024), sent, k])
            free -= 1
        dt = idle_s
        if prefill:
            dt += chunk_s
            prefill[0][1] -= 1
            if not prefill[0][1]:
                c, _, sent, k = prefill.pop(0)
                decode[c] = [k - 1, sent, k]
        if decode:
            dt += step_s
        if jitter is not None:
            dt = dt * math.exp(0.1 * jitter.normal()) + 0.2 * (
                jitter.rand() < 0.002)
        t += dt
        for c in [c for c, v in decode.items() if v[0] <= 0]:
            _, sent, k = decode.pop(c)
            done.append((sent, t, k))
            free += 1
            nxt[c] += 1
            if t < hi and nxt[c] < len(clients[c]):
                queue.append((t, c))
        for v in decode.values():
            v[0] -= 1
    over = [(s, d, k) for s, d, k in done if s < hi and d > lo]
    return sum(k * (min(d, hi) - max(s, lo)) / (d - s)
               for s, d, k in over) / window


def spread(v):
    q = statistics.quantiles(v, n=4)
    return (q[2] - q[0]) / statistics.median(v)


def main():
    m = Manifest(ROOT)
    t = m.traffic(m.cell("kimi-serve-backlog"))
    mix, slots = t["mix"], t["engine"]["slots"]
    # short ids (the model reads lengths only): the lengths are the mix's
    count = mix["clients"] * mix["requests_per_client"]
    same_work = load_module(os.path.join(
        ROOT, "benchmark", "kinds", "serve_state.py")).same_work
    seeded = [lengths(datagen.closed_schedule(mix, 200, seed, count))
              for seed in range(100, 124)]
    v = [model(q) for q in seeded]
    print(f"the seeds' own orders: {min(v):.0f}-{max(v):.0f}, spread "
          f"{100 * spread(v):.2f} %")
    one = lengths(same_work(datagen.closed_schedule(mix, 200, 100, count),
                            slots))
    v = [model(one, jitter=np.random.RandomState(k)) for k in range(12)]
    print(f"one order, jittered:   {min(v):.0f}-{max(v):.0f}, spread "
          f"{100 * spread(v):.2f} %")


if __name__ == "__main__":
    main()
