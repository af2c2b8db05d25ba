"""Random weights from the seed, a leaf at a time.

``weights.make_params`` draws one float32 vector for all leaves: 17 GB
for a 4.4 B-parameter configuration. Here each leaf of a reference's
``param_spec`` is drawn from ``fold_in(seed_key(seed), i)``, ``i`` the
index of its path among the sorted paths, with ``weights``' inits, and
cast to the configuration's storage dtype; at most one leaf is ever held
in float32. A leaf with more than two axes (stacked experts) takes its
Glorot fans from its last two. The program and the reference read the
same arrays."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import refmath
from .weights import _scale_shift, seed_key


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape, init, dtype):
    scale, shift = _scale_shift(shape[-2:] if len(shape) > 2 else shape,
                                init)
    z = jax.random.normal(key, shape, jnp.float32)
    return (z * scale + shift).astype(dtype)


def make_params(spec: dict, seed: int, dtype) -> dict:
    """The seeded tree for ``spec`` in ``dtype``, made on the device."""
    key = seed_key(seed)
    dtype = jnp.dtype(dtype)
    return refmath.unflatten({
        path: _leaf(jax.random.fold_in(key, i), tuple(spec[path][0]),
                    spec[path][1], dtype)
        for i, path in enumerate(sorted(spec))})
