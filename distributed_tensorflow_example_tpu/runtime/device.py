"""Device discovery helpers.

The reference enumerated devices implicitly through ClusterSpec task lists;
here devices come from the JAX runtime. These helpers centralize backend
selection so tests can force the virtual-CPU path (8 XLA host devices via
``--xla_force_host_platform_device_count``) while production uses TPU.
"""

from __future__ import annotations

import os
from typing import Sequence

import jax


#: the checkout that holds this package — where the compile cache lives
#: when the environment names no other place
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: chip peak bf16 FLOP/s by device_kind substring (Google Cloud TPU
#: documentation, per-chip figures)
PEAK_BF16_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Every entry point (the CLI, the servers, the benches, the experiment
    scripts, ``chip_smoke.py``) calls this once at start. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and nothing
    is set in code. Otherwise the cache is ``<checkout>/.jax_cache`` — a
    fixed, git-ignored path, because the path is part of what a cached
    program is found by: a directory that moves between runs never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def chip_peak_flops(device: jax.Device | None = None) -> float | None:
    """bf16 peak FLOP/s of one chip: None off-TPU (no utilization is
    claimed there), and an error — never a default — for a TPU whose
    ``device_kind`` the table does not know."""
    d = device if device is not None else jax.devices()[0]
    if d.platform != "tpu":
        return None
    kind = d.device_kind.lower()
    for key, peak in PEAK_BF16_FLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s recorded for TPU device_kind {d.device_kind!r} "
        f"(known: {sorted(PEAK_BF16_FLOPS)}) — add it to "
        "runtime/device.py PEAK_BF16_FLOPS with its source")


def available_devices(backend: str | None = None) -> list[jax.Device]:
    """All addressable devices of ``backend`` (``None``: the default
    backend's — TPU when present). A backend that is not there raises
    JAX's ``RuntimeError``: code that asked for a TPU must never be
    handed CPUs instead."""
    return list(jax.devices(backend))


def cpu_devices(min_count: int = 1) -> list[jax.Device]:
    """CPU devices for simulated-mesh tests (SURVEY.md §4 item 2).

    Raises with a actionable message when too few virtual devices exist.
    """
    devs = jax.devices("cpu")
    if len(devs) < min_count:
        raise RuntimeError(
            f"need >= {min_count} CPU devices but found {len(devs)}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{min_count} before importing jax")
    return list(devs)


def default_device_kind() -> str:
    return jax.devices()[0].device_kind


def local_device_count(backend: str | None = None) -> int:
    return len(available_devices(backend))
