"""Both served programs of ``axk1`` at the ``axk1-serve-longctx`` cell's shape, compiled for a described v5e here
on the CPU (nothing runs): compile time, temporaries, every copy as large as a 200th of the latent pool, whether
the pool is aliased, how often each kernel's name occurs, and the shapes the issue asked to look for: a
``[64, T, ctx]`` score tensor, a copy of the pool, a transposed latent block (PR 44; what
``tests/test_tpu_compile.py::test_dense_latent_programs_keep_the_pool_where_it_lies`` asserts). With ``ref`` as
an argument: the plain reference's forward at 30,720 rows (the widest the check compiles), its temporaries."""
import os, sys, re, functools, importlib, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ['JAX_PLATFORMS'] = 'cpu'
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import SingleDeviceSharding
from jax.experimental import topologies
from distributed_tensorflow_example_tpu.config import TrainConfig
from distributed_tensorflow_example_tpu.models import get_model
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = list(topo.devices)[0]
mla_mod = importlib.import_module("distributed_tensorflow_example_tpu.ops.mla")
mla_mod._interpret = lambda: False
def on(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(dev))
model = get_model("axk1", TrainConfig(model="axk1", dtype="bfloat16", param_dtype="bfloat16", num_layers=5))
model.cfg.experts_held, model.cfg.vocab_held = 12, 20480
slots, bs, chunk, prompt, new = 24, 128, 1024, 28672, 1024
nb = (prompt + new) // bs
params = jax.tree_util.tree_map(lambda x: on(x.shape, x.dtype), jax.eval_shape(model.init, jax.random.key(0)))
weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
print("weights GB", weights / 1e9)
specs = model.state_specs(slots=slots, num_blocks=1 + slots * nb, block_size=bs)
state = {k: on(tuple(v["shape"]), jnp.dtype(v["dtype"])) for k, v in specs.items()}
for k, v in specs.items(): print(k, v["shape"], np.prod(v["shape"]) * 2 / 1e9, "GB")
i32 = functools.partial(on, dtype=jnp.int32)
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1, "f16": 2}
which = sys.argv[1:] or ["decode", "prefill_chunk"]
for name in which:
    if name == "ref":
        import json
        from benchmark.manifest import load_module
        ref = load_module(os.path.join(ROOT, "benchmark", "reference", "a.x-k1.py"))
        cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs", "a.x-k1.json")))
        width, kmax = int(os.environ.get("WIDTH", 30720)), 1024
        def rows(p, x, first):
            hid = ref.hidden(cfg, p, x, "f32")
            return ref.head(cfg, p, jax.lax.dynamic_slice_in_dim(hid, first, kmax, axis=0), "f32")
        t0 = time.time()
        c = jax.jit(rows).lower(params, i32((width,)), i32(())).compile()
        print("reference at", width, "rows compiled in", time.time() - t0, "s; temp",
              c.memory_analysis().temp_size_in_bytes / 1e9, "GB beside", weights / 1e9, "GB of weights")
        continue
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
    print(name, "compiled in", time.time() - t0, "s; temp", compiled.memory_analysis().temp_size_in_bytes / 1e6, "MB")
    pool = int(np.prod(specs["cache_latent"]["shape"])) * 2
    copies = [(m.group(0), _ITEM.get(m.group(1), 4) * int(np.prod([int(x) for x in m.group(2).split(",")])))
              for m in re.finditer(r"(\w+)\[([\d,]+)\]\S* copy\(", text)]
    print(" copies >= pool / 200:", [c for c in copies if c[1] >= pool // 200][:20])
    print(" largest copies:", sorted(copies, key=lambda c: -c[1])[:6])
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    print(" alias:", re.findall(r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", m.group(1)) if m else None)
    for kname in ("paged_latent_attn", "mla_chunk_attn", "tpu_custom_call", "ragged-dot", "conditional("):
        print(" ", kname, text.count(kname))
    print("  score tensors f32[64,1024,N]:", sorted(set(re.findall(r"f32\[64,1024,(?:\d{3,})\]", text)))[:8])
    print("  transposed latent block [.., 640, 128]:", sorted(set(re.findall(r"\w+\[(?:\d+,)*640,128\]", text)))[:8])
    print("  ragged_dot_tiling:", sorted(set(re.findall(r'ragged_dot_tiling="?([\d,]+)', text))))
