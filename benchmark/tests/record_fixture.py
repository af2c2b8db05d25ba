"""Record the small TPU trace the trace_reduce test reads (run once on the
chip: ``chiprun -- python3 benchmark/tests/record_fixture.py``). Two
executions of one small jitted program with a loop in it."""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

from benchmark import trace_reduce


@jax.jit
def small_step(x, w):
    def body(c, _):
        return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(body, x, None, length=4)
    return y.sum()


x = jnp.ones((256, 256), jnp.bfloat16)
w = jnp.ones((256, 256), jnp.bfloat16) * 0.01
small_step(x, w).block_until_ready()
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
for _ in range(2):
    small_step(x, w).block_until_ready()
jax.profiler.stop_trace()
out = os.path.join("chiprun_out", "fixture")
os.makedirs(out, exist_ok=True)
path = trace_reduce.find_xplane(d)
shutil.copy(path, os.path.join(out, "tiny_tpu.xplane.pb"))
print(os.path.getsize(path), "bytes")
print("\n".join(trace_reduce.describe(path))[:3000])
print(trace_reduce.reduce(path))
