"""The programs of the three expert configurations at their CELLS' shapes (SDAR's prefill and block
step, Kimi's and dots3's chunk program and decode step), traced from shapes alone and lowered for
the TPU here on the CPU (kernels lowered by Mosaic; nothing runs), from the tree in argv[1]: sha256[:16]
of each program's StableHLO text with source locations stripped, and the tile of each
``chlo.ragged_dot`` in it. Beside PR 36's script (``records/pr36/program_hashes.py``: the tiny
decoders' EXPORTED programs), which never reaches the shapes ``ragged_tiling`` and ``pair_bound``
decide on. Run each tree from ONE path: a Mosaic kernel's serialized body embeds its source file's
path."""
import hashlib
import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, sys.argv[1])

import jax
import numpy as np

import distributed_tensorflow_example_tpu as dtx
from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model

print(dtx.__file__)
jax.default_backend = lambda: "tpu"   # "auto" attention takes its TPU branch, lowered for the chip
spec, i32 = jax.ShapeDtypeStruct, np.int32


def model_of(name, layers, **share):
    m = get_model(name, TrainConfig(model=name, num_layers=layers, dtype="bfloat16",
                                    param_dtype="bfloat16"))
    for k, v in share.items():
        setattr(m.cfg, k, v)
    return m, jax.eval_shape(m.init, jax.random.key(0))


def state_programs(name, layers, share, slots, prompt, new, chunk=1024, bs=128):
    m, params = model_of(name, layers, **share)
    nb = (prompt + new) // bs
    state = {k: spec(tuple(v["shape"]), np.dtype(v["dtype"])) for k, v in m.state_specs(
        slots=slots, num_blocks=1 + slots * nb, block_size=bs).items()}
    yield "prefill_chunk", m.prefill_chunk, (
        params, state, spec((1, chunk), i32), spec((), i32), spec((), i32), spec((), i32),
        spec((-(-prompt // chunk) * chunk // bs,), i32), spec((chunk // bs,), i32))
    yield "decode", m.decode_step, (
        params, state, spec((slots, nb), i32), spec((slots,), i32), spec((slots,), i32),
        spec((slots,), i32))


def block_programs(slots=64, width=4096, nb=34):
    m, params = model_of("sdar_moe", 6)
    c = m.cfg
    pool = spec((6, 1 + slots * nb, 128, c.kv_heads * c.head_dim), m.dtype)
    yield "prefill", m.paged_prefill, (
        params, spec((1, width), i32), spec((1, width), i32), pool, pool, spec((width // 128,), i32))
    yield "block_step", m.block_step, (
        params, pool, pool, spec((slots, nb), i32), spec((slots, c.block_length), i32),
        spec((slots,), i32), spec((slots,), i32), spec((slots,), i32))


CASES = (("sdar_moe", block_programs()),
         ("kimi_linear", state_programs("kimi_linear", 5, dict(experts_held=128, vocab_held=81920),
                                        128, 16384, 1024)),
         ("dots3_note", state_programs("dots3_note", 5, dict(experts_held=32, vocab_held=19008),
                                       24, 32256, 512)))
for name, programs in CASES:
    for program, fn, args in programs:
        txt = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        tiles = [(re.search(r'ragged_dot_tiling = "([^"]*)"', ln) or [0, "xla"])[1]
                 for ln in txt.splitlines() if "chlo.ragged_dot" in ln]
        txt = re.sub(r"(?m)^#loc.*$", "", txt)
        txt = re.sub(r" loc\(.*?\)$", "", txt, flags=re.M)
        txt = re.sub(r"loc\(#loc\d*\)|loc\(unknown\)", "", txt)
        print(name, program, len(txt), hashlib.sha256(txt.encode()).hexdigest()[:16],
              " ".join(f"{t}x{tiles.count(t)}" for t in sorted(set(tiles))))
