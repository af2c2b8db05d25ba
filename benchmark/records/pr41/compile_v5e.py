"""Both served programs of ``laguna`` at the ``laguna-serve-mixed`` cell's shape, compiled for a described
v5e here on the CPU (nothing runs): compile time, temporaries, the copies as large as a sixtieth of a layer's
rings, which state arrays are aliased, how often each kernel's name occurs (PR 41; what
``tests/test_tpu_compile.py::test_grouped_query_programs_keep_pool_and_rings_where_they_lie`` asserts)."""
import os, sys, re, functools, importlib, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ['JAX_PLATFORMS']='cpu'
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import SingleDeviceSharding
from jax.experimental import topologies
from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = list(topo.devices)[0]
gqa_mod = importlib.import_module("distributed_tensorflow_example_tpu.ops.gqa")
gqa_mod._interpret = lambda: False
def on(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(dev))
model = get_model("laguna", TrainConfig(model="laguna", dtype="bfloat16", param_dtype="bfloat16", num_layers=9))
model.cfg.experts_held, model.cfg.vocab_held = 32, 12544
slots, bs, chunk, prompt, new = 24, 128, 1024, 15360, 1024
nb = (prompt + new) // bs
params = jax.tree_util.tree_map(lambda x: on(x.shape, x.dtype), jax.eval_shape(model.init, jax.random.key(0)))
weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
print("weights GB", weights/1e9)
specs = model.state_specs(slots=slots, num_blocks=1 + slots * nb, block_size=bs)
state = {k: on(tuple(v["shape"]), jnp.dtype(v["dtype"])) for k, v in specs.items()}
for k, v in specs.items(): print(k, v["shape"], np.prod(v["shape"])*2/1e9)
i32 = functools.partial(on, dtype=jnp.int32)
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1, "f16": 2}
which = sys.argv[1:] or ["decode", "prefill_chunk"]
for name in which:
    if name == "decode":
        fn = lambda st, p, bt, tok, pos, alive: model.decode_step(p, st, bt, tok, pos, alive, attention="pallas")
        args = (i32((slots, nb)), i32((slots,)), i32((slots,)), i32((slots,)))
    else:
        fn = lambda st, p, ids, n, start, slot, row, cb: model.prefill_chunk(p, st, ids, n, start, slot, row, cb, attention="pallas")
        args = (i32((1, chunk)), i32(()), i32(()), i32(()), i32((-(-prompt // chunk) * chunk // bs,)), i32((chunk // bs,)))
    t0 = time.time()
    compiled = jax.jit(fn, donate_argnums=0).lower(state, params, *args).compile()
    text = compiled.as_text()
    if os.environ.get("OUT"):           # the compiled text, to read by hand
        open(os.path.join(os.environ["OUT"], name + "_v5e.txt"), "w").write(text)
    print(name, "compiled in", time.time() - t0, "s; temp", compiled.memory_analysis().temp_size_in_bytes/1e6, "MB")
    pool = int(np.prod(specs["cache_k"]["shape"])) * 2
    ring = int(np.prod(specs["cache_window_k"]["shape"])) * 2
    copies = [(m.group(0), _ITEM.get(m.group(1), 4) * int(np.prod([int(x) for x in m.group(2).split(",")]))) for m in re.finditer(r"(\w+)\[([\d,]+)\]\S* copy\(", text)]
    big = [c for c in copies if c[1] >= ring // 60]
    print(" copies >= a slot's ring of a layer:", big[:20], len(big))
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    print(" alias:", re.findall(r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", m.group(1)) if m else None)
    for kname in ("paged_gqa_attn", "gqa_chunk_attn", "tpu_custom_call"):
        print(" ", kname, text.count(kname))
