"""Random weights from the seed, made on the device in one jitted call.

A reference states its parameters as ``{"path/to/leaf": (shape, init)}``.
The same tree goes to the program (which keeps the same paths) and to the
reference, so neither takes anything the other has made. Inits:

- ``normal``: N(0, 0.02), embeddings;
- ``glorot``: N(0, 2 / (fan_in + fan_out)), dense kernels;
- ``bias``: N(0, 0.02) — not zeros, so a dropped bias shows;
- ``scale``: 1 + N(0, 0.02), norm scales.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import refmath


def seed_key(seed: int):
    """A key from any whole-number seed up to and beyond 2**31. The ``rbg``
    generator: the chip's own, quick to compile and to run; its bits
    differ from one backend to another, which harms nothing, since the
    program and the reference of a run get the very same arrays."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                              seed >> 31)


def _scale_shift(shape, init):
    if init in ("normal", "bias"):
        return 0.02, 0.0
    if init == "glorot":        # Glorot-normal: std sqrt(2 / (in + out))
        return math.sqrt(2.0 / (shape[0] + shape[1])), 0.0
    if init == "scale":
        return 0.02, 1.0
    raise ValueError(f"unknown init {init!r}")


def build(spec: dict, key) -> dict:
    """The nested float32 tree for ``spec`` from ``key``; traceable. One
    normal draw for all leaves, sliced: one random op to compile, not one
    a leaf."""
    paths = sorted(spec)
    sizes = [math.prod(spec[p][0]) for p in paths]
    z = jax.random.normal(key, (sum(sizes),), jnp.float32)
    flat, off = {}, 0
    for p, n in zip(paths, sizes):
        shape, init = tuple(spec[p][0]), spec[p][1]
        scale, shift = _scale_shift(shape, init)
        flat[p] = z[off:off + n].reshape(shape) * scale + shift
        off += n
    return refmath.unflatten(flat)


def make_params(spec: dict, seed: int) -> dict:
    """The seeded parameter tree, made on the device in one jitted call."""
    return jax.jit(lambda key: build(spec, key))(seed_key(seed))
