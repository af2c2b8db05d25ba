"""The grouped-query attention forms alone, on the chip, at the
``laguna-serve-mixed`` cell's shapes (my chip runs, PR 41):

    chiprun -- python3 benchmark/records/pr41/attn_sweep.py chiprun_out/attn.jsonl

- one token a slot against the pool (24 slots, 8 KV heads x 128, 48 query
  heads, a table of 128 blocks of 128): ``paged_gqa_attn`` at 2 / 4 / 8 /
  16 table entries a grid step against ``paged_block_attn`` with one lane
  (the 6 query heads of a KV head padded to 8 rows) and the XLA gather,
  at the mix's contexts and at uniform ones; device ms by kernel NAME,
  the live rows' bytes over 819 GB/s beside each;
- one token a slot over the rings (72 heads, 512 rows);
- a chunk of 1,024 rows: ``gqa_chunk_attn`` causal over 1 / 4 / 8 / 15
  k rows of context and its band form over ring + chunk, against the XLA
  tile loop.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "experiments"))


def main(out_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flash_sweep
    from benchmark import trace_reduce
    from distributed_tensorflow_example_tpu.ops import gqa
    import importlib
    da = importlib.import_module(
        "distributed_tensorflow_example_tpu.ops.pallas.decode_attention")

    on_tpu = jax.devices()[0].platform == "tpu"
    slots, kvh, d, bs, nb = (24, 8, 128, 128, 128) if on_tpu else (
        3, 2, 128, 128, 8)
    h, hw = 6 * kvh, 9 * kvh
    blocks = 1 + slots * nb
    key = jax.random.key(41)
    kp = jax.random.normal(key, (blocks, bs, kvh * d), jnp.bfloat16) * 0.5
    vp = jax.random.normal(jax.random.fold_in(key, 1),
                           (blocks, bs, kvh * d), jnp.bfloat16) * 0.5
    bt = jnp.asarray(1 + np.arange(slots * nb).reshape(slots, nb), jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (slots, h, d),
                          jnp.bfloat16)
    rs = np.random.RandomState(0)
    mixes = {"mix": np.clip(np.exp(rs.randn(slots) * 0.8 + np.log(4500)),
                            600, nb * bs - 1).astype(np.int32),
             "2k": np.full(slots, 2047, np.int32),
             "8k": np.full(slots, 8191, np.int32),
             "16k": np.full(slots, nb * bs - 1, np.int32)}
    if not on_tpu:
        mixes = {"mix": np.array([5, 300, 1023], np.int32)}
    sink = open(out_path, "w") if out_path else None

    def emit(row):
        row["device"] = jax.devices()[0].device_kind
        print(json.dumps(row), flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()

    def timed(call, args, pattern, iters=5):
        red = flash_sweep._capture(call, args, iters)
        return (round(trace_reduce.op_seconds(red, pattern=pattern)
                      / iters * 1e3, 4) if pattern else None,
                round(red["busy_s"] / iters * 1e3, 4))

    # ---- one token a slot against the pool
    for name, pos in mixes.items():
        posj = jnp.asarray(pos)
        live_bytes = int((pos + 1).sum()) * 2 * kvh * d * 2
        floor_ms = live_bytes / 819e9 * 1e3
        want = gqa.paged_gqa_decode_attention(q, kp, vp, block_tables=bt,
                                              pos=posj, impl="xla")
        for entries in (2, 4, 8, 16):
            if nb % entries:
                continue
            call = jax.jit(lambda q, kp, vp, bt, pos, e=entries:
                           gqa.paged_gqa_decode_attention(
                               q, kp, vp, block_tables=bt, pos=pos,
                               impl="pallas", entries=e))
            try:
                got = call(q, kp, vp, bt, posj)
                ms, prog = timed(call, (q, kp, vp, bt, posj),
                                 "paged_gqa_attn")
                emit(dict(what="decode", impl="paged_gqa_attn",
                          entries=entries, ctx=name, ms=ms, program_ms=prog,
                          floor_ms=round(floor_ms, 4),
                          max_abs_diff=float(jnp.max(jnp.abs(got - want)))))
            except Exception as e:  # noqa: BLE001 — a refused tile is a row
                emit(dict(what="decode", impl="paged_gqa_attn",
                          entries=entries, ctx=name,
                          error=f"{type(e).__name__}: {str(e)[:300]}"))
        # the block kernel with one lane: 6 query rows a KV head, padded to 8
        def block(q, kp, vp, bt, pos):
            qq = q.reshape(slots, kvh, h // kvh, d)
            qq = jnp.pad(qq, ((0, 0), (0, 0), (0, 8 - h // kvh), (0, 0)))
            out = da.paged_block_attention(qq, kp, vp, block_tables=bt,
                                           last=pos, impl="pallas")
            return out[:, :, :h // kvh].reshape(slots, h, d)
        call = jax.jit(block)
        try:
            got = call(q, kp, vp, bt, posj)
            ms, prog = timed(call, (q, kp, vp, bt, posj), "paged_block_attn")
            emit(dict(what="decode", impl="paged_block_attn", entries=1,
                      ctx=name, ms=ms, program_ms=prog,
                      floor_ms=round(floor_ms, 4),
                      max_abs_diff=float(jnp.max(jnp.abs(
                          got.astype(jnp.float32) - want)))))
        except Exception as e:  # noqa: BLE001
            emit(dict(what="decode", impl="paged_block_attn", ctx=name,
                      error=f"{type(e).__name__}: {str(e)[:300]}"))
        if name == "mix":
            call = jax.jit(lambda q, kp, vp, bt, pos:
                           gqa.paged_gqa_decode_attention(
                               q, kp, vp, block_tables=bt, pos=pos,
                               impl="xla"))
            _, prog = timed(call, (q, kp, vp, bt, posj), None)
            emit(dict(what="decode", impl="xla_gather", ctx=name,
                      program_ms=prog, floor_ms=round(floor_ms, 4)))

    # ---- one token a slot over the rings
    rows = 512
    rk = jax.random.normal(key, (slots, rows, kvh * d), jnp.bfloat16) * 0.5
    rv = jax.random.normal(jax.random.fold_in(key, 3),
                           (slots, rows, kvh * d), jnp.bfloat16) * 0.5
    qw = jax.random.normal(jax.random.fold_in(key, 4), (slots, hw, d),
                           jnp.bfloat16)
    posj = jnp.asarray(mixes["mix"])
    call = jax.jit(lambda q, rk, rv, pos: gqa.gqa_window_decode_attention(
        q, rk, rv, pos, window=512))
    _, prog = timed(call, (qw, rk, rv, posj), None)
    emit(dict(what="ring_decode", impl="xla_masked_tile", program_ms=prog,
              floor_ms=round(slots * rows * 2 * kvh * d * 2 / 819e9 * 1e3,
                             4)))

    # ---- a chunk of 1,024 rows
    t = 1024 if on_tpu else 256
    prompt_blocks = (15360 // bs) if on_tpu else 8
    table = jnp.asarray(1 + np.arange(prompt_blocks), jnp.int32)
    qc = jax.random.normal(jax.random.fold_in(key, 5), (t, h, d),
                           jnp.float32)
    for start in ((0, 3072, 7168, 14336) if on_tpu else (0, 256)):
        pairs = t * start + t * (t + 1) / 2
        floor_ms = 4 * pairs * h * d / 197e12 * 1e3
        for impl in ("pallas", "xla"):
            call = jax.jit(lambda q, kp, vp, table, start, impl=impl:
                           gqa.gqa_prefill_attention(
                               q, kp, vp, table, start, key_tile=t,
                               impl=impl))
            args = (qc, kp, vp, table, jnp.int32(start))
            out = call(*args)
            if impl == "pallas":
                got = out
                ms, prog = timed(call, args, "gqa_chunk_attn")
            else:
                ms, prog = timed(call, args, None)
                diff = float(jnp.max(jnp.abs(got - out)))
            emit(dict(what="chunk_full", impl=impl, start=start, ms=ms,
                      program_ms=prog, floor_ms=round(floor_ms, 4),
                      **({"max_abs_diff": diff} if impl == "xla" else {})))
    k = jax.random.normal(key, (t, kvh * d), jnp.bfloat16) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 6), (t, kvh * d),
                          jnp.bfloat16) * 0.5
    qcw = jax.random.normal(jax.random.fold_in(key, 7), (t, hw, d),
                            jnp.float32)
    floor_ms = 4 * t * 512 * hw * d / 197e12 * 1e3
    for tile in ((512, 256) if on_tpu else (128,)):
        for impl in ("pallas", "xla"):
            call = jax.jit(lambda q, k, v, rk, rv, start, impl=impl,
                           tile=tile: gqa.gqa_window_prefill_attention(
                               q, k, v, rk, rv, start, window=512,
                               tile=tile, impl=impl))
            args = (qcw, k, v, rk[0], rv[0], jnp.int32(4096))
            out = call(*args)
            if impl == "pallas":
                got = out
                ms, prog = timed(call, args, "gqa_chunk_attn")
            else:
                ms, prog = timed(call, args, None)
                diff = float(jnp.max(jnp.abs(got - out)))
            emit(dict(what="chunk_window", impl=impl, tile=tile, ms=ms,
                      program_ms=prog, floor_ms=round(floor_ms, 4),
                      **({"max_abs_diff": diff} if impl == "xla" else {})))
    if sink:
        sink.close()


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
