"""PR 36, before the engine is touched: what a one-token decode step's host
copy costs at the GPT-2 serving cells' shape (64 slots, vocabulary 50,257,
float32 logits), in a loop behind a ``jit`` that returns both the logits and
their greedy ids, as the served step will.

    chiprun -- python3 benchmark/records/pr36/fetch_loop.py chiprun_out/pr36/fetch_loop.json

Wall ms an iteration (dispatch + the read named), median and quartiles over
``ITERS`` iterations after a warm-up:

- ``fetch_logits``: ``np.asarray(out["logits"])``, 12.9 MB (what the parent's
  ``sched_wait_logits`` ends in);
- ``fetch_ids``: ``np.asarray(out["ids"])``, 256 bytes (the round trip alone);
- ``fetch_ids_3_reads``: ids and two scalars, three blocking reads in a row
  (what ``_fetch_state_step`` does for the per-request-state decoders);
- ``host_argmax_fresh`` / ``host_argmax_warm``: 64 ``np.argmax`` over a row
  of the array just fetched / of one array fetched once;
- device ms a call of the program with and without the argmax, and of the
  operations the argmax added, from a profiler capture
  (``benchmark.trace_reduce``).

Sizes can be cut for a rehearsal off the chip: ``FETCH_LOOP_VOCAB``,
``FETCH_LOOP_ITERS``.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

SLOTS, HIDDEN = 64, 768
VOCAB = int(os.environ.get("FETCH_LOOP_VOCAB", 50257))
ITERS = int(os.environ.get("FETCH_LOOP_ITERS", 200))


def head(h, wte):
    return jnp.einsum("sh,vh->sv", h, wte,
                      preferred_element_type=jnp.float32)


@jax.jit
def step_logits(h, wte):
    return {"logits": head(h, wte)}


@jax.jit
def step_both(h, wte):
    logits = head(h, wte)
    return {"logits": logits,
            "ids": jnp.argmax(logits, axis=-1).astype(jnp.int32)}


@jax.jit
def step_three(h, wte):
    out = step_both(h, wte)
    return {**out, "scalar_a": jnp.sum(out["ids"] > 0).astype(jnp.int32),
            "scalar_b": jnp.max(out["logits"][:, 1])}


def quartiles(ms: list[float]) -> dict:
    q1, q2, q3 = statistics.quantiles(ms, n=4)
    return {"p25": round(q1, 4), "p50": round(q2, 4), "p75": round(q3, 4),
            "min": round(min(ms), 4), "max": round(max(ms), 4)}


def loop(body, iters: int = ITERS) -> dict:
    for _ in range(5):
        body()
    ms = []
    for _ in range(iters):
        t0 = time.perf_counter()
        body()
        ms.append((time.perf_counter() - t0) * 1e3)
    return quartiles(ms)


def device_ms(call, args, iters: int = 20) -> dict:
    """Device ms a call and by operation, from a capture of ``iters``."""
    import shutil
    import tempfile

    from benchmark import trace_reduce
    for _ in range(2):
        jax.block_until_ready(call(*args))
    tmp = tempfile.mkdtemp(prefix="fetch_loop_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for _ in range(iters):
                out = call(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        red = trace_reduce.reduce(trace_reduce.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"program_ms": round(red["busy_s"] / iters * 1e3, 4),
            "ops_ms": {n: round(o["seconds"] / iters * 1e3, 4)
                       for n, o in sorted(red["all_ops"].items())}}


def main() -> None:
    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    dev = jax.devices()[0]
    kh, kw = jax.random.split(jax.random.key(36))
    h = jax.random.normal(kh, (SLOTS, HIDDEN), jnp.bfloat16)
    wte = jax.random.normal(kw, (VOCAB, HIDDEN), jnp.bfloat16)
    got = step_both(h, wte)
    logits = np.asarray(got["logits"])
    ids = np.asarray(got["ids"])
    assert (ids == np.argmax(logits, axis=-1)).all(), "device != host argmax"
    rows = {"device": {"platform": dev.platform, "kind": dev.device_kind},
            "shape": {"slots": SLOTS, "vocab": VOCAB, "iters": ITERS,
                      "logits_bytes": int(logits.nbytes),
                      "ids_bytes": int(ids.nbytes)}}

    def host_argmax(a):
        return [int(np.argmax(a[i])) for i in range(SLOTS)]

    fresh = []

    def fetch_then_argmax():
        a = np.asarray(step_both(h, wte)["logits"])
        t0 = time.perf_counter()
        host_argmax(a)
        fresh.append((time.perf_counter() - t0) * 1e3)

    def three_reads():
        o = step_three(h, wte)
        return np.asarray(o["ids"]), int(o["scalar_a"]), float(o["scalar_b"])

    rows["fetch_logits"] = loop(
        lambda: np.asarray(step_both(h, wte)["logits"]))
    rows["fetch_logits_of_logits_only"] = loop(
        lambda: np.asarray(step_logits(h, wte)["logits"]))
    rows["fetch_ids"] = loop(lambda: np.asarray(step_both(h, wte)["ids"]))
    rows["fetch_ids_3_reads"] = loop(three_reads)
    rows["block_only"] = loop(
        lambda: jax.block_until_ready(step_both(h, wte)["ids"]))
    loop(fetch_then_argmax)
    rows["host_argmax_fresh"] = quartiles(fresh[5:])
    rows["host_argmax_warm"] = loop(lambda: host_argmax(logits))
    rows["fetch_logits_then_argmax"] = loop(
        lambda: host_argmax(np.asarray(step_both(h, wte)["logits"])))
    if dev.platform == "tpu":
        rows["device_logits_only"] = device_ms(step_logits, (h, wte))
        rows["device_both"] = device_ms(step_both, (h, wte))
    text = json.dumps(rows, indent=1)
    print(text)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
