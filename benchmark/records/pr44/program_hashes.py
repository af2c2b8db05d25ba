"""Export the tiny GPT-2, SDAR, Kimi, dots3, Laguna and A.X-K1 decoders for the TPU (kernels lowered by Mosaic,
here on the CPU; nothing runs) from the tree in argv[1]; print sha256[:16] of each program's MLIR text
with source locations stripped. PR 36's script (records/pr36/program_hashes.py) with laguna_tiny
(PR 43) and axk1_tiny (PR 44, which the parent cannot export) added: all six served configurations. Run each tree from ONE path: a Mosaic kernel's serialized body
embeds its source file's path."""
import glob
import hashlib
import os
import re
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, sys.argv[1])

import jax
from jax import export as je

import distributed_tensorflow_example_tpu as dtx
from distributed_tensorflow_example_tpu import serving
from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model

print(dtx.__file__)
jax.default_backend = lambda: "tpu"   # the exports take their TPU branch, lowered for the chip
CASES = (("gpt_tiny", dict(prompt_len=64, max_new_tokens=64, spec_tokens=2)),
         ("sdar_moe_tiny", {}),
         ("kimi_linear_tiny", dict(prefill_chunk=128)),
         ("dots3_note_tiny", dict(prefill_chunk=128)),
         ("laguna_tiny", dict(prefill_chunk=128)),
         ("axk1_tiny", dict(prefill_chunk=128)))
for name, kw in CASES:
    m = get_model(name, TrainConfig(model=name))
    params = m.init(jax.random.key(0))
    d = tempfile.mkdtemp()
    args = dict(ragged=True, stepwise=True, paged=True, slots=8, block_size=128, prompt_len=128,
                max_new_tokens=128, platforms=("tpu",))
    try:
        serving.export_generator(m, params, d, **{**args, **kw})
    except Exception as e:  # noqa: BLE001 - a tree that cannot export a model says so
        print(name, "export failed:", type(e).__name__, str(e)[:300])
        continue
    for f in sorted(glob.glob(d + "/*.stablehlo")):
        with open(f, "rb") as fh:
            txt = je.deserialize(fh.read()).mlir_module()
        txt = re.sub(r"(?m)^#loc.*$", "", txt)
        txt = re.sub(r" loc\(.*?\)$", "", txt, flags=re.M)
        txt = re.sub(r"loc\(#loc\d*\)|loc\(unknown\)", "", txt)
        print(name, os.path.basename(f), len(txt), hashlib.sha256(txt.encode()).hexdigest()[:16])
