"""Plain float32 ``jax.numpy`` pieces shared by the references.

Nothing here imports the program. ``precision`` selects how every matrix
multiplication is computed:

- ``"f32"``: float32 operands at ``highest`` matmul precision, the
  reference proper;
- ``"fp8"``: each operand rounded to float8 (e4m3, per-tensor amax
  scaling, the usual fp8 matmul recipe) and multiplied in float32: the
  control, the nearest precision below the bf16 the configurations
  state. It has to come out as NOT correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_E4M3_MAX = 448.0


def _fp8(x):
    """Round to e4m3 under per-tensor amax scaling. Straight-through: the
    backward pass sees the rounded operands but is not itself rounded (a
    bare cast would flush every small cotangent to zero), so this is the
    mildest fp8 a later PR could try."""
    s = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def einsum(spec: str, a, b, precision: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "f32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def dense(p, x, precision):
    return einsum("...i,io->...o", x, p["kernel"], precision) + p["bias"]


def layernorm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(ap, x, key_mask, heads: int, causal: bool, precision):
    """Multi-head self-attention over ``x`` [B, S, H]; ``key_mask``
    [B, S] (1 = a real token)."""
    b, s, h = x.shape
    d = h // heads

    def split(t):
        return t.reshape(b, s, heads, d)

    q = split(dense(ap["q"], x, precision))
    k = split(dense(ap["k"], x, precision))
    v = split(dense(ap["v"], x, precision))
    scores = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(d)
    allowed = key_mask.astype(bool)[:, None, None, :]
    if causal:
        pos = jnp.arange(s)
        allowed = allowed & (pos[:, None] >= pos[None, :])[None, None]
    scores = jnp.where(allowed, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(allowed, axis=-1, keepdims=True), probs, 0.0)
    ctx = einsum("bhqk,bkhd->bqhd", probs, v, precision)
    return dense(ap["o"], ctx.reshape(b, s, h), precision)


def weighted_nll(logits, labels, weights):
    """Sum of ``weights * nll`` over every position, and the weights' sum."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights), jnp.sum(weights)


# --------------------------------------------------------------------------
# the optimizer the cells train with (AdamW, decay on matrices only)
# --------------------------------------------------------------------------

def adamw_init(params):
    def zeros():
        return jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.int32)}


def adamw_step(params, grads, state, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               weight_decay=0.0):
    t = state["t"] + 1
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state["v"], grads)

    def upd(p, m_, v_):
        u = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            u = u + weight_decay * p
        return p - lr * u

    return (jax.tree_util.tree_map(upd, params, m, v),
            {"m": m, "v": v, "t": t})


def train_reference(loss_sums, params, batches, *, block_rows: int,
                    optimizer: dict, precision: str = "f32"):
    """Follow ``len(batches)`` optimizer steps of the plain model.

    ``loss_sums(params, block, precision) -> (sum of weighted nll, sum of
    weights)`` for a block of rows. Gradients accumulate over blocks of
    ``block_rows`` rows so that the float32 activations fit beside
    nothing else. Returns the losses, the first gradient's per-leaf
    norms and the per-leaf norms of the parameters' change."""
    def block_grad(p, block, denom):
        def f(p_):
            s, _ = loss_sums(p_, block, precision)
            return s / denom
        return jax.value_and_grad(f)(p)

    block_grad = jax.jit(block_grad)
    weights_sum = jax.jit(lambda p, blk: loss_sums(p, blk, precision)[1])
    leaf_norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
    step = jax.jit(lambda p, g, s: adamw_step(p, g, s, **optimizer),
                   donate_argnums=(0, 2))
    p0 = params
    params = jax.tree_util.tree_map(jnp.copy, params)
    state = adamw_init(params)
    losses, grad_norms = [], None
    for batch in batches:
        rows = len(next(iter(batch.values())))
        blocks = [{k: v[i:i + block_rows] for k, v in batch.items()}
                  for i in range(0, rows, block_rows)]
        denom = jnp.maximum(sum(weights_sum(params, b) for b in blocks), 1.0)
        loss, grads = 0.0, None
        for b in blocks:
            l, g = block_grad(params, b, denom)
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = jax.device_get(leaf_norms(grads))
        params, state = step(params, grads, state)
    delta = jax.device_get(leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, params, p0)))
    return {"losses": losses, "grad_norms": flatten(grad_norms),
            "delta_norms": flatten(delta)}


def flatten(tree, prefix: str = "") -> dict:
    """Nested dict -> {"a/b/c": leaf}."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return out
