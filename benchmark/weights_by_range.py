"""Random weights from the seed, a leaf at a time, for a configuration
some of whose leaves are drawn from a RANGE the family initialises them
in, not from a normal.

``weights.py`` knows four inits; drawn with its N(0, 0.02) a recurrent
decay's parameters would make every state forget in ten tokens, and a
state lost at a chunk boundary would never reach a served logit. Two
more inits, the rest are ``weights_by_leaf``'s, from the same keys (leaf
``i`` of the sorted paths is drawn from ``fold_in(seed_key(seed), i)``):

- ``a_log``: ``log(U[1, 16])``, so ``exp(A_log)`` lies in [1, 16];
- ``dt_bias``: the inverse softplus of ``exp(U[log 0.001, log 0.1])``,
  so ``softplus(dt_bias)`` is log-uniform in [0.001, 0.1].

Both are kept in float32 whatever the storage dtype: they are a few
thousand values that enter two exponentials.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import refmath
from .weights import seed_key
from .weights_by_leaf import _leaf

RANGES = {"a_log": (1.0, 16.0), "dt_bias": (1e-3, 0.1)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _ranged(key, shape, init):
    lo, hi = RANGES[init]
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(lo),
                                    math.log(hi)))
    return dt + jnp.log(-jnp.expm1(-dt))


def make_params(spec: dict, seed: int, dtype) -> dict:
    """The seeded tree for ``spec``: ``dtype`` leaves, float32 where the
    init is a range."""
    key = seed_key(seed)
    dtype = jnp.dtype(dtype)
    out = {}
    for i, path in enumerate(sorted(spec)):
        shape, init = tuple(spec[path][0]), spec[path][1]
        k = jax.random.fold_in(key, i)
        out[path] = (_ranged(k, shape, init) if init in RANGES
                     else _leaf(k, shape, init, dtype))
    return refmath.unflatten(out)
