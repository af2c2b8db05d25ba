"""Part 3 of PR 46's hashes: the tiny SDAR decoder EXPORTED for the TPU (kernels lowered by Mosaic, here on the CPU;
nothing runs) at shapes where this PR's exporter writes three widths (prompt_len 128 over blocks of 32), from the
tree in argv[1]: sha256[:16] of each program's MLIR text with source locations stripped, as
``records/pr44/program_hashes.py`` (whose SDAR case, prompt_len 128 over blocks of 128, has one width). The parent
writes ``prefill.stablehlo`` alone; the change's ``prefill.stablehlo`` must be it, byte for byte. Run each tree
from ONE path: a Mosaic kernel's serialized body embeds its source file's path."""
import glob
import hashlib
import json
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
jax.default_backend = lambda: "tpu"   # the export takes its TPU branch, lowered for the chip
m = get_model("sdar_moe_tiny", TrainConfig(model="sdar_moe_tiny"))
d = tempfile.mkdtemp()
serving.export_generator(m, m.init(jax.random.key(0)), d, ragged=True, stepwise=True, paged=True, slots=8,
                         block_size=32, prompt_len=128, max_new_tokens=64, platforms=("tpu",))
print("prefill_widths", json.load(open(d + "/export.json"))["stepwise"].get("prefill_widths"))
for f in sorted(glob.glob(d + "/*.stablehlo")):
    with open(f, "rb") as fh:
        raw = fh.read()
    txt = je.deserialize(raw).mlir_module()
    txt = re.sub(r"(?m)^#loc.*$", "", txt)
    txt = re.sub(r" loc\(.*?\)$", "", txt, flags=re.M)
    txt = re.sub(r"loc\(#loc\d*\)|loc\(unknown\)", "", txt)
    print("sdar_moe_tiny", os.path.basename(f), len(txt), hashlib.sha256(txt.encode()).hexdigest()[:16])
