"""The new pieces of ``axk1-serve-longctx`` alone on the chip, device ms each from a capture (kernels and grouped
matmuls by NAME; one process, ~3 chip minutes):

    chiprun -- python3 benchmark/records/pr44/pieces_sweep.py chiprun_out/pr44/pieces.jsonl

- ``ragged``: ``lax.ragged_dot`` over 12 groups of [7168, 2048] (gate / up) and [2048, 7168] (down), the widest K
  yet: a chunk's 8,192 pair rows, their bound of 1,024 (``ops/moe.pair_bound`` at 12 of 192 held; 512 held under
  even routing) and a step's 192 (12 held), under XLA's own tile and the candidates ``ragged_tiling``'s budget
  admits (N cut, K cut), beside the weights' read over 819 GB/s;
- ``chunk``: ``mla_chunk_attn`` (64 heads, one chunk of 1,024 rows) at contexts of 1 / 4 / 8 / 16 / 28 chunks under
  key tiles of 1,024 and 512, beside its required FLOPs over 197 TFLOP/s; the XLA tile loop once, at 8;
- ``step``: ``paged_latent_attn`` (24 slots x 64 heads, a table of 232 blocks) at the cell's mix of contexts, at 2 k
  and at 28 k, under 8 and 4 blocks a grid step, beside the live rows' bytes over 819 GB/s.

A third argument runs tiny shapes on the CPU (does it run)."""
import functools
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.getcwd())

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from benchmark import trace_reduce                          # noqa: E402
from distributed_tensorflow_example_tpu.ops import mla      # noqa: E402
from distributed_tensorflow_example_tpu.ops.moe import (    # noqa: E402
    ragged_dot_tiled, ragged_tiling)

OUT = sys.argv[1] if len(sys.argv) > 1 else None
TINY = len(sys.argv) > 2
ITERS = 3


def capture(call, args):
    for _ in range(2):
        jax.block_until_ready(call(*args))
    if TINY:
        return None
    tmp = tempfile.mkdtemp(prefix="pr44_sweep_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for _ in range(ITERS):
                out = call(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        return trace_reduce.reduce(trace_reduce.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def emit(row, red, pattern):
    if red is not None:
        row["ms"] = round(trace_reduce.op_seconds(red, pattern=pattern) / ITERS * 1e3, 4)
        row["program_ms"] = round(red["busy_s"] / ITERS * 1e3, 4)
    print(json.dumps(row), flush=True)
    if OUT:
        os.makedirs(os.path.dirname(os.path.abspath(OUT)), exist_ok=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(row) + "\n")


def ragged():
    groups = 12
    shapes = ((128, 256), (256, 128)) if TINY else ((7168, 2048), (2048, 7168))
    for k, n in shapes:
        rule = ragged_tiling(8192, k, n, jnp.bfloat16)
        tiles = [None, rule]
        if not TINY:
            tiles += ([f"128,{k},128", f"128,{k // 2},512", f"128,{k // 4},1024"] if k > n else
                      [f"128,{k},512", f"128,{k},1792", f"128,{k // 2},1792"])
        for m, grouped in ((8192, 512), (1024, 512), (192, 12)):
            rs = np.random.RandomState(m + k)
            ka, kw = jax.random.split(jax.random.key(m + k))
            args = (jax.random.normal(ka, (m, k), jnp.bfloat16) * 0.5,
                    jax.random.normal(kw, (groups, k, n), jnp.bfloat16) * 0.02,
                    jnp.asarray(rs.multinomial(grouped, [1 / groups] * groups), jnp.int32))
            want = None
            for t in tiles:
                if t is not None and m % int(t.split(",")[0]):
                    continue
                call = jax.jit(functools.partial(ragged_dot_tiled, tile=t))
                try:
                    red = capture(call, args)
                except Exception as e:  # noqa: BLE001 - a tile the compiler refuses says so
                    emit(dict(piece="ragged", m=m, k=k, n=n, grouped=grouped, tiling=t or "xla",
                              refused=f"{type(e).__name__}: {str(e)[:160]}"), None, "")
                    continue
                got = call(*args)[:grouped]
                want = got if want is None else want
                emit(dict(piece="ragged", m=m, k=k, n=n, groups=groups, grouped=grouped, tiling=t or "xla",
                          rule=t == rule, read_ms=round(groups * k * n * 2 / 819e9 * 1e3, 4),
                          max_abs_diff=float(jnp.max(jnp.abs(got - want)))), red, "ragged-dot")


def chunk():
    h, rank, nope, pe, v, t, bs = (2, 128, 128, 64, 128, 256, 128) if TINY else (64, 512, 128, 64, 128, 1024, 128)
    chunks = (1, 2) if TINY else (1, 4, 8, 16, 28)
    nbp = chunks[-1] * t // bs
    k = jax.random.split(jax.random.key(0), 4)
    pool = (jax.random.normal(k[0], (1 + nbp, bs, 640)) * 0.3).astype(jnp.bfloat16)
    table = jnp.arange(1, 1 + nbp, dtype=jnp.int32)
    q = jax.random.normal(k[1], (t, h, nope + pe)) * 0.3
    w = (jax.random.normal(k[2], (rank, h, nope + v)) * 0.05).astype(jnp.bfloat16)
    kw = dict(rank=rank, nope=nope, pe=pe, v_dim=v, scale=0.13)
    for n in chunks:
        start = (n - 1) * t
        pairs = t * start + t * (t + 1) / 2
        flops = 2.0 * h * (pairs * (nope + pe + v) + (start + t) * rank * (nope + v))
        forms = [("pallas", t), ("pallas", t // 2)] + ([("xla", t)] if n == chunks[min(2, len(chunks) - 1)] else [])
        for impl, tile in forms:
            call = jax.jit(lambda q, pool, w, impl=impl, tile=tile: mla.mla_chunk_attention(
                q, pool, table, start, w, key_tile=tile, impl=impl, **kw))
            red = capture(call, (q, pool, w))
            emit(dict(piece="chunk", impl=impl, key_tile=tile, heads=h, context=start + t,
                      floor_ms=round(flops / 197e12 * 1e3, 4)), red,
                 "mla_chunk_attn" if impl == "pallas" else "while|fusion")


def step():
    slots, h, rank, bs, nbs = (2, 8, 128, 128, 8) if TINY else (24, 64, 512, 128, 232)
    rs = np.random.RandomState(0)
    nb = 1 + slots * nbs
    pool = (jax.random.normal(jax.random.key(1), (nb, bs, 640)) * 0.3).astype(jnp.bfloat16)
    bt = (1 + np.arange(slots * nbs, dtype=np.int32)).reshape(slots, nbs)
    q = (jax.random.normal(jax.random.key(2), (slots, h, 640)) * 0.3).astype(jnp.bfloat16)
    top = nbs * bs - 1
    mixes = {"mix": np.clip(rs.lognormal(np.log(9000), 0.7, slots), 2048, top).astype(np.int32),
             "2k": np.full(slots, min(2047, top), np.int32), "28k": np.full(slots, top, np.int32)}
    saved = mla._BLOCKS_A_STEP
    try:
        for blocks in (8, 4):
            mla._BLOCKS_A_STEP = blocks
            for name, last in mixes.items():
                call = jax.jit(lambda q, pool, last: mla.mla_decode_attention(
                    q, pool, block_tables=bt, last=last, rank=rank, impl="pallas"))
                red = capture(call, (q, pool, jnp.asarray(last)))
                emit(dict(piece="step", blocks_per_step=blocks, contexts=name, rows=int(last.sum() + slots),
                          read_ms=round(float(last.sum() + slots) * 1280 / 819e9 * 1e3, 4)), red,
                     "paged_latent_attn")
    finally:
        mla._BLOCKS_A_STEP = saved


if __name__ == "__main__":
    print(jax.devices()[0].device_kind, flush=True)
    for piece in (step, chunk, ragged):
        piece()
