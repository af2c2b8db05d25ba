"""The chunk program's and the decode step's new pieces alone, at the
cell's shapes, device ms each (best of 5 after a warm call, host clock
around block_until_ready: each piece is one jitted program of several ms).

    python3 benchmark/records/pr35/pieces_bench.py [chiprun_out/pieces.jsonl]
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from distributed_tensorflow_example_tpu.ops import dsa, mla  # noqa: E402

OUT = sys.argv[1] if len(sys.argv) > 1 else None
TINY = len(sys.argv) > 2            # a rehearsal on the CPU: does it run
ROWS = []


def timed(name, fn, *args, **meta):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    row = {"piece": name, "ms": round(1e3 * best, 3), **meta}
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def main():
    bf = jnp.bfloat16
    c, bs, nbp, slots = (128, 128, 8, 2) if TINY else (1024, 128, 256, 24)
    top = 64 if TINY else 2048
    nb = 1 + slots * nbp
    k = jax.random.split(jax.random.key(0), 12)
    latent = (jax.random.normal(k[0], (nb, bs, 640)) * 0.3).astype(bf)
    index = (jax.random.normal(k[1], (nb, bs, 128))).astype(bf)
    table = jnp.arange(1, 1 + nbp, dtype=jnp.int32)
    qi = jax.random.normal(k[2], (c, 64, 128)).astype(bf)
    wi = jax.random.normal(k[3], (c, 64)) * 0.01
    q = jax.random.normal(k[4], (c, 128, 192)) * 0.3
    wkvb = (jax.random.normal(k[5], (512, 128, 256)) * 0.05).astype(bf)
    width = nbp * bs

    def scores_fn(qi, wi, index, s):
        return dsa.chunk_scores(qi, wi, index, table, s, width=width)

    def attn_fn(q, latent, wkvb, s, a, impl="xla", key_tile=512):
        return mla.mla_masked_prefill_attention(
            q, latent, table, s, wkvb, a, rank=512, nope=128, pe=64,
            v_dim=128, scale=192 ** -0.5, impl=impl, key_tile=key_tile)

    for start in ((256, 896) if TINY else (3072, 7168, 15360, 31744)):
        ctx = start + c
        scores = jax.jit(scores_fn)(qi, wi, index, start)
        timed("chunk_scores", scores_fn, qi, wi, index, start, context=ctx)
        timed("top_k_mask", lambda sc: dsa.top_k_mask(sc, top), scores,
              context=ctx)
        timed("top_k_mask_live", lambda sc, n: dsa.top_k_mask(
            sc, top, live=n), scores, ctx, context=ctx)
        allowed = jax.jit(lambda sc: dsa.top_k_mask(sc, top))(scores)
        same = jax.jit(lambda sc, n: dsa.top_k_mask(sc, top, live=n))(
            scores, ctx)
        assert bool((allowed == same).all())
        for impl in ("xla",) if TINY else ("xla", "pallas"):
            timed("masked_attention_" + impl,
                  functools.partial(attn_fn, impl=impl), q, latent, wkvb,
                  start, allowed, context=ctx)
        for key_tile in () if TINY else (256, 1024):
            timed(f"masked_attention_pallas_{key_tile}", functools.partial(
                attn_fn, impl="pallas", key_tile=key_tile), q, latent, wkvb,
                start, allowed, context=ctx)
        if not TINY:
            a, b = (jax.jit(functools.partial(attn_fn, impl=i))(
                q, latent, wkvb, start, allowed) for i in ("xla", "pallas"))
            print(json.dumps({"kernel_against_xla": float(
                jnp.abs(a - b).max()), "largest": float(jnp.abs(a).max()),
                "context": ctx}), flush=True)
    # the window layers' chunk attention (geometry 64 x (192 + 64), 1,024)
    qw = jax.random.normal(k[6], (c, 64, 256)) * 0.3
    lat_w = (jax.random.normal(k[7], (c, 1152)) * 0.3).astype(bf)
    ring = (jax.random.normal(k[8], (48 if TINY else 528, 1152)) * 0.3
            ).astype(bf)
    wkvb_w = (jax.random.normal(k[9], (1024, 64, 320)) * 0.05).astype(bf)
    timed("window_chunk_attention", lambda qw, lat_w, ring, wkvb_w, s: (
        mla.mla_window_prefill_attention(
            qw, lat_w, ring, s, wkvb_w, window=33 if TINY else 513,
            rank=1024, nope=192, pe=64, v_dim=128, scale=256 ** -0.5)),
        qw, lat_w, ring, wkvb_w, 256 if TINY else 7168)
    # one token a slot
    bt = jnp.arange(1, 1 + slots * nbp, dtype=jnp.int32).reshape(slots, nbp)
    qs = jax.random.normal(k[10], (slots, 64, 128)).astype(bf)
    ws = jax.random.normal(k[11], (slots, 64)) * 0.01
    pos = jnp.asarray(np.linspace(*((200, 1000) if TINY else (4096, 32000)),
                                  slots).astype(np.int32))
    timed("step_scores", dsa.step_scores, qs, ws, index, bt, pos)
    sc = jax.jit(dsa.step_scores)(qs, ws, index, bt, pos)
    timed("top_k_rows", lambda x: dsa.top_k_rows(x, top), sc)
    at, chosen = jax.jit(lambda x: dsa.top_k_rows(x, top))(sc)
    q_abs = (jax.random.normal(k[4], (slots, 128, 640)) * 0.1).astype(bf)
    timed("gathered_attention", lambda q, pool, bt, a, ch: (
        mla.mla_gathered_attention(q, pool, block_tables=bt, positions=a,
                                   chosen=ch, rank=512)),
        q_abs, latent, bt, at, chosen)
    if OUT:
        os.makedirs(os.path.dirname(OUT) or ".", exist_ok=True)
        with open(OUT, "w") as f:
            f.write("\n".join(json.dumps(r) for r in ROWS) + "\n")


if __name__ == "__main__":
    main()
